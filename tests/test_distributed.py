"""Multi-host (jax.distributed) backend tests.

"Multi-node without a cluster" at the process level: N real OS
processes, each a jax.distributed participant with its own virtual CPU
devices, joined through a loopback coordinator — the DCN-scale analogue
of the socket tests' master+slaves shape."""

import socket
import subprocess
import sys

import pytest

from ytk_mp4j_tpu.comm.distributed import DistributedComm

from helpers import REPO_ROOT


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_single_process_fallback():
    """Without jax.distributed, the comm degrades to 1 rank and every
    collective is an in-place no-op."""
    import numpy as np

    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    comm = DistributedComm()
    assert comm.slave_num >= 1
    if comm.slave_num == 1:
        arr = np.arange(5, dtype=np.float32)
        comm.allreduce_array(arr, Operands.FLOAT, Operators.SUM)
        np.testing.assert_array_equal(arr, np.arange(5, dtype=np.float32))
        d = {"a": 1.0}
        comm.allreduce_map(d)
        assert d == {"a": 1.0}


def test_device_reduce_rejects_shadowing_custom_operator():
    """SUM / MAX / MIN ride the device collective, PROD has no device
    reducer, and a custom operator NAMED "MAX"/"SUM" must never take the
    device path: the gate is operator identity, not operator.name (a
    name-keyed gate once handed a custom "MAX" lax.pmax)."""
    import numpy as np

    from ytk_mp4j_tpu.operators import Operator, Operators

    ok = DistributedComm._device_reduce_ok
    assert ok(Operators.SUM) and ok(Operators.MAX) and ok(Operators.MIN)
    assert ok(Operators.PROD) is False
    absmax = Operator.custom(
        "MAX", lambda a, b: np.where(np.abs(a) >= np.abs(b), a, b), 0.0)
    assert ok(absmax) is False
    fake_sum = Operator.custom("SUM", lambda a, b: a, 0.0)
    assert ok(fake_sum) is False


def test_reduce_scatter_shadowing_custom_sum_goes_host_path():
    """reduce_scatter_array routed custom operators named "SUM" onto
    psum_scatter (name equality); the gate is now object identity and
    the custom's own fn must decide the result."""
    import numpy as np

    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operator, Operators

    comm = DistributedComm.__new__(DistributedComm)
    comm._rank, comm._n, comm._closed = 0, 2, False
    comm._djits = {}

    device_calls = []
    comm._device_rows_collective = (
        lambda kind, block, lax_name:
        device_calls.append((kind, lax_name)) or block)
    # two ranks: ours and a peer row of all 10s
    comm._allgather_rows = lambda row: np.stack(
        [row, np.full_like(row, 10.0)])

    first = Operator.custom("SUM", lambda a, b: a, 0.0)  # keeps first
    arr = np.arange(4, dtype=np.float32)
    out = comm.reduce_scatter_array(arr.copy(), Operands.FLOAT, first)
    assert device_calls == []           # builtin psum_scatter NOT taken
    np.testing.assert_array_equal(out[:2], arr[:2])  # fn: keep ours

    # and the real builtin still rides the device plane
    comm.reduce_scatter_array(arr.copy(), Operands.FLOAT, Operators.SUM)
    assert device_calls == [("reduce_scatter", "psum")]


@pytest.mark.slow
@pytest.mark.parametrize("procs", [2, 3])
def test_checkdist_multiprocess(procs):
    port = _free_port()
    workers = [
        subprocess.Popen(
            [sys.executable, "-m", "ytk_mp4j_tpu.check.checkdist",
             "--coordinator", f"127.0.0.1:{port}",
             "--num-processes", str(procs), "--process-id", str(i),
             "--local-devices", "2", "--length", "53"],
            cwd=REPO_ROOT,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in range(procs)
    ]
    for p in workers:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"checkdist failed:\n{out}\n{err}"
