"""Differential checks of the TPU (device) collective path against numpy.

Mirrors the reference's check-suite pattern (SURVEY.md section 4): every
collective x element type x operator on generated data, compared against
locally computed expected values. Runs on the 8-virtual-device CPU mesh.
"""

import numpy as np
import pytest

from ytk_mp4j_tpu import meta
from ytk_mp4j_tpu.comm.tpu_comm import TpuCommCluster
from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.operands import Operands
from ytk_mp4j_tpu.operators import Operator, Operators

from helpers import expected_reduce, make_inputs


def assert_close(got, want, operand):
    if operand.dtype.kind == "f":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    else:
        np.testing.assert_array_equal(got, want)


@pytest.fixture(scope="module")
def cluster():
    return TpuCommCluster()


@pytest.fixture(scope="module")
def cluster5():
    # non-power-of-2 rank count (reference supports these, SURVEY.md 3b)
    return TpuCommCluster(5)


@pytest.mark.parametrize("op", ["SUM", "PROD", "MAX", "MIN"])
@pytest.mark.parametrize("operand", Operands.NUMERIC, ids=lambda o: o.name)
def test_allreduce_all_types(cluster, operand, op, rng):
    arrs = make_inputs(cluster.n, 100, operand, rng)
    want = expected_reduce(arrs, op)
    cluster.allreduce_array(arrs, operand, Operators.by_name(op))
    for a in arrs:
        assert_close(a, want, operand)


def test_allreduce_subrange(cluster, rng):
    operand = Operands.DOUBLE
    arrs = make_inputs(cluster.n, 50, operand, rng)
    orig = [a.copy() for a in arrs]
    want = expected_reduce(arrs, "SUM")
    cluster.allreduce_array(arrs, operand, Operators.SUM, from_=10, to=30)
    for a, o in zip(arrs, orig):
        np.testing.assert_allclose(a[10:30], want[10:30])
        np.testing.assert_array_equal(a[:10], o[:10])
        np.testing.assert_array_equal(a[30:], o[30:])


def test_allreduce_empty_range(cluster, rng):
    arrs = make_inputs(cluster.n, 10, Operands.FLOAT, rng)
    orig = [a.copy() for a in arrs]
    cluster.allreduce_array(arrs, Operands.FLOAT, Operators.SUM,
                            from_=4, to=4)
    for a, o in zip(arrs, orig):
        np.testing.assert_array_equal(a, o)


def test_allreduce_nonpow2(cluster5, rng):
    operand = Operands.DOUBLE
    arrs = make_inputs(5, 33, operand, rng)
    want = expected_reduce(arrs, "SUM")
    cluster5.allreduce_array(arrs, operand, Operators.SUM)
    for a in arrs:
        np.testing.assert_allclose(a, want)


@pytest.mark.parametrize("root", [0, 3])
def test_reduce(cluster, root, rng):
    operand = Operands.DOUBLE
    arrs = make_inputs(cluster.n, 40, operand, rng)
    orig = [a.copy() for a in arrs]
    want = expected_reduce(arrs, "SUM")
    cluster.reduce_array(arrs, operand, Operators.SUM, root=root)
    np.testing.assert_allclose(arrs[root], want)
    for r, (a, o) in enumerate(zip(arrs, orig)):
        if r != root:
            np.testing.assert_array_equal(a, o)


@pytest.mark.parametrize("root", [0, 2])
def test_broadcast(cluster, root, rng):
    operand = Operands.FLOAT
    arrs = make_inputs(cluster.n, 31, operand, rng)
    src = arrs[root].copy()
    cluster.broadcast_array(arrs, operand, root=root)
    for a in arrs:
        np.testing.assert_array_equal(a, src)


def test_broadcast_subrange(cluster, rng):
    operand = Operands.INT
    arrs = make_inputs(cluster.n, 20, operand, rng)
    orig = [a.copy() for a in arrs]
    src = arrs[1].copy()
    cluster.broadcast_array(arrs, operand, root=1, from_=5, to=15)
    for r, (a, o) in enumerate(zip(arrs, orig)):
        np.testing.assert_array_equal(a[5:15], src[5:15])
        np.testing.assert_array_equal(a[:5], o[:5])
        np.testing.assert_array_equal(a[15:], o[15:])


def test_allgather(cluster, rng):
    operand = Operands.DOUBLE
    L = 45  # uneven over 8 ranks
    ranges = meta.partition_range(0, L, cluster.n)
    arrs = make_inputs(cluster.n, L, operand, rng)
    want = np.zeros(L, dtype=operand.dtype)
    for r, (s, e) in enumerate(ranges):
        want[s:e] = arrs[r][s:e]
    cluster.allgather_array(arrs, operand)
    for a in arrs:
        np.testing.assert_array_equal(a, want)


def test_gather(cluster, rng):
    operand = Operands.LONG
    L = 37
    ranges = meta.partition_range(0, L, cluster.n)
    arrs = make_inputs(cluster.n, L, operand, rng)
    orig = [a.copy() for a in arrs]
    want = np.zeros(L, dtype=operand.dtype)
    for r, (s, e) in enumerate(ranges):
        want[s:e] = arrs[r][s:e]
    root = 2
    cluster.gather_array(arrs, operand, root=root)
    np.testing.assert_array_equal(arrs[root], want)
    for r, (a, o) in enumerate(zip(arrs, orig)):
        if r != root:
            np.testing.assert_array_equal(a, o)


def test_scatter(cluster, rng):
    operand = Operands.FLOAT
    L = 43
    ranges = meta.partition_range(0, L, cluster.n)
    arrs = make_inputs(cluster.n, L, operand, rng)
    root = 1
    src = arrs[root].copy()
    orig = [a.copy() for a in arrs]
    cluster.scatter_array(arrs, operand, root=root)
    for r, (s, e) in enumerate(ranges):
        np.testing.assert_array_equal(arrs[r][s:e], src[s:e])
        # outside own segment unchanged (except root keeps its own array)
        if r != root:
            mask = np.ones(L, bool)
            mask[s:e] = False
            np.testing.assert_array_equal(arrs[r][mask], orig[r][mask])


@pytest.mark.parametrize("op", ["SUM", "MAX", "PROD"])
def test_reduce_scatter(cluster, op, rng):
    operand = Operands.DOUBLE
    L = 53  # uneven
    ranges = meta.partition_range(0, L, cluster.n)
    arrs = make_inputs(cluster.n, L, operand, rng)
    orig = [a.copy() for a in arrs]
    want = expected_reduce(orig, op)
    cluster.reduce_scatter_array(arrs, operand, Operators.by_name(op))
    for r, (s, e) in enumerate(ranges):
        assert_close(arrs[r][s:e], want[s:e], operand)
        mask = np.ones(L, bool)
        mask[s:e] = False
        np.testing.assert_array_equal(arrs[r][mask], orig[r][mask])


def test_custom_operator_allreduce(cluster, rng):
    import jax.numpy as jnp
    absmax = Operator.custom(
        "ABSMAX",
        lambda x, y: jnp.where(jnp.abs(x) >= jnp.abs(y), x, y),
        0.0,
    )
    operand = Operands.DOUBLE
    arrs = make_inputs(cluster.n, 64, operand, rng)
    stacked = np.stack(arrs)
    idx = np.abs(stacked).argmax(axis=0)
    want = stacked[idx, np.arange(stacked.shape[1])]
    cluster.allreduce_array(arrs, operand, absmax)
    for a in arrs:
        np.testing.assert_allclose(a, want)


def test_string_operand_rejected(cluster):
    with pytest.raises(Mp4jError):
        cluster.allreduce_array([None] * cluster.n, Operands.STRING,
                                Operators.SUM)


def test_barrier(cluster):
    cluster.barrier()  # must simply complete


def test_wrong_rank_count(cluster):
    with pytest.raises(Mp4jError):
        cluster.allreduce_array([np.zeros(3, np.float32)] * (cluster.n - 1),
                                Operands.FLOAT, Operators.SUM)


@pytest.mark.parametrize("bad_root", [-1, 99])
def test_bad_root_rejected(cluster, bad_root, rng):
    arrs = make_inputs(cluster.n, 5, Operands.FLOAT, rng)
    orig = [a.copy() for a in arrs]
    for call in (
        lambda: cluster.broadcast_array(arrs, Operands.FLOAT, root=bad_root),
        lambda: cluster.reduce_array(arrs, Operands.FLOAT, Operators.SUM,
                                     root=bad_root),
        lambda: cluster.gather_array(arrs, Operands.FLOAT, root=bad_root),
        lambda: cluster.scatter_array(arrs, Operands.FLOAT, root=bad_root),
    ):
        with pytest.raises(Mp4jError):
            call()
    for a, o in zip(arrs, orig):
        np.testing.assert_array_equal(a, o)


def test_noncontiguous_2d_allreduce(cluster, rng):
    # Fortran-ordered 2-D inputs must still receive results (copyto path).
    arrs = [np.asfortranarray(rng.standard_normal((4, 3)))
            for _ in range(cluster.n)]
    want = expected_reduce(arrs, "SUM")
    cluster.allreduce_array(arrs, Operands.DOUBLE, Operators.SUM)
    for a in arrs:
        np.testing.assert_allclose(a, want)


@pytest.mark.parametrize("op_name", ["MAX", "MIN"])
def test_max_min_lower_to_native_all_reduce(cluster, op_name):
    """MAX and MIN emit pmax / pmin unconditionally, like SUM: the
    lowered program holds an all-reduce and no all-gather (the gathered
    tree reduction is PROD's and the custom operators' path only)."""
    from functools import partial

    import jax
    from jax.sharding import PartitionSpec as P

    from ytk_mp4j_tpu.ops import collectives as coll

    axis = cluster.axis_name
    op = Operators.by_name(op_name)

    def lowered(operator):
        f = jax.jit(partial(
            jax.shard_map, mesh=cluster.mesh, in_specs=P(axis),
            out_specs=P(axis))(
                lambda x: coll.allreduce(x, operator, axis)))
        return f.lower(
            jax.ShapeDtypeStruct((cluster.n, 8), np.float32)).as_text()

    text = lowered(op)
    assert "all_reduce" in text and "all_gather" not in text, text
    # the discriminator discriminates: PROD does gather
    assert "all_gather" in lowered(Operators.PROD)


# ----------------------------------------------------------------------
# algorithm selection (reference parity: ProcessCommSlave's algo arg):
# "xla" / "ring" (ppermute) / "rdma" (Pallas kernel, interpreted on CPU
# meshes) must be result-identical through the driver API
# ----------------------------------------------------------------------
@pytest.mark.parametrize("algo", ["ring", "rdma"])
def test_allreduce_algo_equivalence(cluster, algo, rng):
    operand = Operands.FLOAT
    for op_name in ("SUM", "MAX"):
        arrs = make_inputs(cluster.n, 37, operand, rng)   # 37: pads
        want = [a.copy() for a in arrs]
        cluster.allreduce_array(want, operand, Operators.by_name(op_name))
        got = [a.copy() for a in arrs]
        cluster.allreduce_array(got, operand, Operators.by_name(op_name),
                                algo=algo)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-6)


@pytest.mark.parametrize("algo", ["ring", "rdma"])
def test_reduce_scatter_algo_equivalence(cluster, algo, rng):
    operand = Operands.FLOAT
    arrs = make_inputs(cluster.n, 41, operand, rng)
    want = [a.copy() for a in arrs]
    cluster.reduce_scatter_array(want, operand, Operators.SUM)
    got = [a.copy() for a in arrs]
    cluster.reduce_scatter_array(got, operand, Operators.SUM, algo=algo)
    for a, b in zip(got, want):
        # ring merges sequentially; XLA's reduction order differs
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("algo", ["ring", "rdma"])
def test_allgather_algo_equivalence(cluster, algo, rng):
    operand = Operands.FLOAT
    arrs = make_inputs(cluster.n, 29, operand, rng)
    want = [a.copy() for a in arrs]
    cluster.allgather_array(want, operand)
    got = [a.copy() for a in arrs]
    cluster.allgather_array(got, operand, algo=algo)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_algo_validation(cluster, rng):
    arrs = make_inputs(cluster.n, 8, Operands.FLOAT, rng)
    with pytest.raises(Mp4jError):
        cluster.allreduce_array(arrs, Operands.FLOAT, Operators.SUM,
                                algo="bogus")


def test_algo_rejects_hierarchical_mesh(rng):
    from ytk_mp4j_tpu.parallel import make_hier_mesh
    cl = TpuCommCluster(mesh=make_hier_mesh(2, 2))
    arrs = make_inputs(4, 8, Operands.FLOAT, rng)
    with pytest.raises(Mp4jError):
        cl.allreduce_array(arrs, Operands.FLOAT, Operators.SUM,
                           algo="rdma")
