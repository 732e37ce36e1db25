"""ISSUE 17 (mp4j-overlap) engine conformance: the dense small-array
coalesced plane must match the sequential ``i*`` stream bit-exactly,
shm-paired async jobs must route ring-eligible chunks through the SPSC
rings, and a fault in the middle of an epoch of outstanding
``iallreduce`` calls must recover bit-exact or fail cleanly on every
rank — never hang."""

import numpy as np
import pytest

from helpers import run_slaves
from ytk_mp4j_tpu.operands import Operands
from ytk_mp4j_tpu.operators import Operators

JOIN = 60.0


# ----------------------------------------------------------------------
# coalesced array plane == sequential i*, bit-exact
# ----------------------------------------------------------------------
def _array_stream(slave, r, arrays=12, size=32):
    bufs = [np.full(size, float(r + 1) * (i + 1), np.float64)
            for i in range(arrays)]
    for b in bufs:
        slave.iallreduce(b, Operands.DOUBLE, Operators.SUM)
    slave.wait_all()
    return bufs, slave.stats()


@pytest.mark.parametrize("n", [3, 5])
def test_coalesced_array_matches_sequential_grid(n, monkeypatch):
    """The dense small-array fused plane (consecutive same-signature
    iallreduce submissions -> ONE count-negotiated multi-exchange)
    against the same stream submitted sequentially with the window
    off: bit-exact, and the window leg really fused (coalesced_elems
    booked)."""
    monkeypatch.setenv("MP4J_COALESCE_USECS", "0")
    want = run_slaves(n, _array_stream, timeout=JOIN)
    monkeypatch.setenv("MP4J_COALESCE_USECS", "500")
    got = run_slaves(n, _array_stream, timeout=JOIN)
    for (wb, _), (gb, gst) in zip(want, got):
        for a, b in zip(wb, gb):
            np.testing.assert_array_equal(a, b)
    assert sum(st.get("allreduce_array_multi", {})
               .get("coalesced_elems", 0)
               for _, st in got) > 0


def test_array_multi_ragged_offer_negotiates_min():
    """Direct allreduce_array_multi with ragged offers: the fused
    count is the min over ranks; un-merged arrays stay untouched and
    a follow-up call drains them — matching the blocking twin."""
    def mk(r, i, size=16):
        return np.full(size, float(r + 1) * (i + 1), np.float64)

    def blocking(slave, r):
        outs = [mk(r, i) for i in range(3)]
        for a in outs:
            slave.allreduce_array(a, Operands.DOUBLE, Operators.SUM)
        return outs

    def fused(slave, r):
        arrs = [mk(r, i) for i in range(3)]
        if r == 0:
            assert slave.allreduce_array_multi(
                [arrs[0]], Operands.DOUBLE, Operators.SUM) == 1
            assert slave.allreduce_array_multi(
                arrs[1:], Operands.DOUBLE, Operators.SUM) == 2
        else:
            m1 = slave.allreduce_array_multi(
                list(arrs), Operands.DOUBLE, Operators.SUM)
            assert m1 == 1          # min over offers (rank 0 offered 1)
            np.testing.assert_array_equal(arrs[1], mk(r, 1))
            assert slave.allreduce_array_multi(
                arrs[1:], Operands.DOUBLE, Operators.SUM) == 2
        return arrs

    want = run_slaves(3, blocking, timeout=JOIN)
    got = run_slaves(3, fused, timeout=JOIN)
    for w, g in zip(want, got):
        for a, b in zip(w, g):
            np.testing.assert_array_equal(a, b)


def test_engine_tiny_odd_payload_stream_ordering():
    """Regression: k outstanding 1-element iallreduces — rhd hands some
    rank an EMPTY segment, i.e. zero-length legs — must pair
    collective k with collective k on every rank. The full-batch
    leg-graph driver once let zero-length legs anchor its per-
    (peer, dir) FIFO gate chain; born "complete", they unblocked
    successors ahead of the chain behind them and the fd slot scan
    paired the stream's bytes with the wrong collective."""
    def fn(slave, r):
        bufs = [np.array([float((r + 1) * 10 + k)]) for k in range(6)]
        for b in bufs:
            slave.iallreduce(b, Operands.DOUBLE, Operators.SUM)
        slave.wait_all()
        return [float(b[0]) for b in bufs]

    for n in (2, 3):
        want = [float(sum((rr + 1) * 10 + k for rr in range(n)))
                for k in range(6)]
        for out in run_slaves(n, fn, timeout=JOIN, shm=False,
                              async_collectives=True):
            assert out == want


# ----------------------------------------------------------------------
# shm ring routing on the engine path
# ----------------------------------------------------------------------
def test_engine_shm_legs_ride_rings():
    """A shm-paired async job's ring-eligible chunks go through the
    SPSC rings (the engine's nonblocking pumps), not the carrier
    socket: the ring share of the shm plane's wire bytes dominates for
    ring-sized payloads."""
    def fn(slave, r):
        a = np.full(600_000, float(r + 1), np.float64)   # 4.8 MB
        fut = slave.iallreduce(a, Operands.DOUBLE, Operators.SUM)
        fut.wait()
        return a, slave.stats()

    out = run_slaves(2, fn, timeout=JOIN)
    want = np.full(600_000, 3.0, np.float64)
    ring = shm = 0
    for a, st in out:
        np.testing.assert_array_equal(a, want)
        for entry in st.values():
            ring += entry.get("wire_bytes_shm_ring", 0)
            shm += entry.get("wire_bytes_shm", 0)
    assert ring > 0, "async shm job booked no ring bytes"
    assert ring >= 0.5 * shm, \
        f"ring share too low: {ring}/{shm} — chunks fell back to the " \
        f"carrier socket"


# ----------------------------------------------------------------------
# chaos mid-overlapped-epoch: recover bit-exact or fail clean — no hangs
# ----------------------------------------------------------------------
def _overlapped_epoch(slave, r):
    """Four steps' statistics posted nonblocking, drained once at the
    epoch boundary; returns their job-wide means, [4, 64]."""
    bufs = [np.full(64, float((r + 1) * (k + 1)), np.float64)
            for k in range(4)]
    for b in bufs:
        slave.iallreduce(b, Operands.DOUBLE, Operators.SUM)
    slave.wait_all()
    return np.stack(bufs) / float(slave.slave_num)


def test_chaos_reset_mid_overlapped_epoch():
    """A connection reset mid-overlapped-epoch: either the engine's
    epoch-fenced recovery completes the drain bit-exact against an
    unfaulted run, or EVERY rank raises the same clean fatal — and
    nobody hangs (run_chaos's hard join deadline)."""
    from test_resilience import run_chaos
    from ytk_mp4j_tpu.exceptions import Mp4jFatalError

    kw = {"async_collectives": True}
    want, werr, _, _ = run_chaos(4, _overlapped_epoch,
                                 fault_plan=None, **kw)
    assert all(e is None for e in werr), werr
    got, errors, stats, log = run_chaos(
        4, _overlapped_epoch, fault_plan="reset:rank=1:nth=2", **kw)
    if any(errors):
        assert all(isinstance(e, Mp4jFatalError) for e in errors), \
            f"{errors}\n{log}"
    else:
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        tot = sum(int(e.get("retries", 0)) for snap in stats
                  for e in (snap or {}).values())
        assert tot >= 1, "reset fault never fired"


def test_chaos_kill_mid_overlapped_epoch_fails_clean():
    """A rank killed mid-overlapped-epoch: the killed rank dies with
    its injected fault, every survivor surfaces a clean Mp4jFatalError
    at (or before) the drain — never a hang, never a silent partial
    history."""
    from test_resilience import run_chaos
    from ytk_mp4j_tpu.resilience.faults import FaultKill
    from ytk_mp4j_tpu.exceptions import Mp4jFatalError

    _, errors, _, log = run_chaos(
        4, _overlapped_epoch, fault_plan="kill:rank=2:nth=2",
        async_collectives=True)
    assert isinstance(errors[2], FaultKill), f"{errors}\n{log}"
    survivors = [errors[r] for r in range(4) if r != 2]
    assert all(isinstance(e, Mp4jFatalError) for e in survivors), \
        f"{errors}\n{log}"
