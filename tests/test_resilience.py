"""mp4j-resilience (ISSUE 5): the chaos grid and the recovery engine.

The grid crosses {connection reset mid-allreduce, slave killed at
collective N, slow rank} with {raw, framed, columnar-map} data planes
and asserts the acceptance contract: bit-exact recovery within
``MP4J_MAX_RETRIES`` (the faulted run's outputs equal an unfaulted
run's, byte for byte), or — when a rank is permanently gone — a clean
SAME-MESSAGE error on every surviving rank within the bounded join.
Zero hangs anywhere: every scenario runs under a hard thread-join
deadline.

Plus unit coverage for the fault-plan grammar, the resilience knobs,
the new ``comm.stats()`` counters (retries / reconnects / aborts_seen),
the recovery spans in the mp4j-scope ring, fail-stop mode
(``MP4J_MAX_RETRIES=0``), retry exhaustion, and the master watchdog's
escalation from log-only diagnosis to the terminal abort fan-out.
"""

import io
import json
import threading
import time

import numpy as np
import pytest

from ytk_mp4j_tpu.comm.master import Master
from ytk_mp4j_tpu.comm.process_comm import ProcessCommSlave
from ytk_mp4j_tpu.exceptions import (
    Mp4jError, Mp4jFatalError, Mp4jTransportError)
from ytk_mp4j_tpu.obs import spans
from ytk_mp4j_tpu.operands import Operands
from ytk_mp4j_tpu.operators import Operator, Operators
from ytk_mp4j_tpu.resilience.faults import FaultInjector, FaultKill, FaultPlan
from ytk_mp4j_tpu.transport.tcp import connect
from ytk_mp4j_tpu.utils import trace, tuning

N = 4
JOIN = 45.0


def run_chaos(n, fn, fault_plan=None, join=JOIN, master_kwargs=None,
              **slave_kwargs):
    """Master + n slave threads under a HARD join deadline. Returns
    (results, errors, stats, log): per-rank fn results, per-rank
    exceptions (None when clean), per-rank comm.stats() snapshots, and
    the master's log. Asserts no thread outlives the deadline — the
    no-hang half of every acceptance criterion."""
    log = io.StringIO()
    master = Master(n, timeout=join, log_stream=log,
                    **(master_kwargs or {})).serve_in_thread()
    results = [None] * n
    errors: list = [None] * n
    stats: list = [None] * n

    def worker(i):
        slave = None
        try:
            slave = ProcessCommSlave(
                "127.0.0.1", master.port, timeout=join,
                fault_plan=fault_plan, dead_rank_secs=20.0,
                **slave_kwargs)
            results[slave.rank] = fn(slave, slave.rank)
            stats[slave.rank] = slave.stats()
            slave.close(0)
        except Exception as e:
            r = slave.rank if slave is not None else i
            errors[r] = e
            if slave is not None:
                stats[r] = slave.stats()
                try:
                    slave.close(1)
                except Exception:
                    pass

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + join
    for t in threads:
        t.join(max(0.1, deadline - time.monotonic()))
    hung = [i for i, t in enumerate(threads) if t.is_alive()]
    assert not hung, f"ranks {hung} hung past the join deadline:\n" \
                     + log.getvalue()
    master.join(10.0)
    return results, errors, stats, log.getvalue()


# ----------------------------------------------------------------------
# the chaos grid
# ----------------------------------------------------------------------
def _body(path):
    """Two collectives on the given data plane; the fault plans target
    the SECOND (ordinal 2), so the first proves the healthy path and
    establishes peer channels."""
    if path == "map":
        def fn(slave, r):
            d = {int(k): np.float64((r + 1) * k) for k in range(800)}
            slave.allreduce_map(d, Operands.DOUBLE, Operators.SUM)
            slave.barrier()   # lockstep: recovery is per-collective
            slave.allreduce_map(d, Operands.DOUBLE, Operators.SUM)
            return d
        return fn, {}

    # raw and framed planes: 120k f64 = 960 KB -> the rhd regime, whose
    # in-place halving merges make retry idempotence non-trivial
    rng = np.random.default_rng(11)
    alls = [rng.standard_normal(120_000) for _ in range(N)]

    def fn(slave, r):
        arr = alls[r].copy()
        slave.allreduce_array(arr, Operands.DOUBLE, Operators.SUM)
        # lockstep before the faulted call: recovery is per-collective
        # (an unsynchronized schedule can put ranks a whole collective
        # apart at fault time, which aborts terminally by design)
        slave.barrier()
        slave.allreduce_array(arr, Operands.DOUBLE, Operators.SUM)
        return arr
    return fn, {"native_transport": path == "raw"}


# transport dimension (ISSUE 7): the thread harness co-locates every
# rank, so the default plane is the shm rings — "reset" faults become
# the ring-poison analogue (the injector's invalidate() poisons the
# shared header) and recovery must drain/re-negotiate SEGMENTS, not
# sockets. shm=False pins the original all-TCP grid.
def _transport_kw(transport):
    return {} if transport == "shm" else {"shm": False}


def _totals(stats, keys=("retries", "reconnects", "aborts_seen")):
    tot = dict.fromkeys(keys, 0)
    for snap in stats:
        for entry in (snap or {}).values():
            for k in keys:
                tot[k] += int(entry.get(k, 0))
    return tot


@pytest.mark.parametrize("transport", ["shm", "tcp"])
@pytest.mark.parametrize("path", ["raw", "framed", "map"])
def test_chaos_reset_recovers_bit_exactly(path, transport):
    """A connection reset mid-collective recovers without operator
    intervention, bit-exact against an unfaulted run."""
    fn, kw = _body(path)
    kw.update(_transport_kw(transport))
    want, werr, _, _ = run_chaos(N, fn, fault_plan=None, **kw)
    assert all(e is None for e in werr)
    got, errors, stats, log = run_chaos(
        N, fn, fault_plan="reset:rank=1:nth=2", **kw)
    assert all(e is None for e in errors), \
        f"recovery failed: {errors}\n{log}"
    for w, g in enumerate(got):
        if path == "map":
            assert set(g) == set(want[w])
            for k in g:
                assert g[k] == want[w][k]     # bit-exact, no tolerance
        else:
            np.testing.assert_array_equal(g, want[w])
    tot = _totals(stats)
    # every rank observed exactly one abort round; at least the faulted
    # exchange pair retried; torn channels were re-dialed
    assert tot["aborts_seen"] == N
    assert tot["retries"] >= 1
    assert tot["reconnects"] >= 2
    assert "abort round -> epoch 1" in log


def test_chaos_reset_object_map_inplace_operator_recovers():
    """Regression: the retry snapshot must DEEP-copy mutable values.
    The pickled dict plane runs ``op(acc, src)`` directly on the
    caller's value objects; with a user operator that mutates its left
    argument in place, a shallow ``dict()`` snapshot would restore the
    same already-merged objects and the retry would double-apply peer
    contributions — silently wrong 'recovered' results."""
    iadd = Operator.custom(
        "IADD", lambda a, b: (a.__setitem__(0, a[0] + b[0]), a)[1],
        [0.0])

    def fn(slave, r):
        d = {k: [float((r + 1) * k)] for k in range(50)}
        slave.allreduce_map(d, Operands.OBJECT_OPERAND(), iadd)
        slave.barrier()   # lockstep: recovery is per-collective
        slave.allreduce_map(d, Operands.OBJECT_OPERAND(), iadd)
        return d

    want, werr, _, _ = run_chaos(N, fn, fault_plan=None)
    assert all(e is None for e in werr)
    got, errors, stats, log = run_chaos(
        N, fn, fault_plan="reset:rank=1:nth=2")
    assert all(e is None for e in errors), \
        f"recovery failed: {errors}\n{log}"
    for w, g in enumerate(got):
        assert g == want[w], f"rank {w}: {g} != {want[w]}"
    assert _totals(stats)["retries"] >= 1


def test_reduce_plane_inplace_operator_values_isolated():
    """Regression: the pickled reduce planes (_reduce_map_obj /
    non-numeric reduce_array) must copy VALUES, not just the
    container. An in-place-mutating operator otherwise merges into the
    caller's value objects mid-protocol — corrupting non-root inputs
    even on a healthy run, and double-applying contributions when the
    epoch-fenced retry re-runs from the (supposedly untouched)
    input. These collectives are _SNAPSHOT_FREE on the strength of
    that copy."""
    iadd = Operator.custom(
        "IADD", lambda a, b: (a.__setitem__(0, a[0] + b[0]), a)[1],
        [0.0])

    def fn(slave, r):
        d = {k: [float((r + 1) * k)] for k in range(30)}
        orig = {k: list(v) for k, v in d.items()}
        slave.reduce_map(d, Operands.OBJECT_OPERAND(), iadd, root=0)
        slave.barrier()   # lockstep: recovery is per-collective
        slave.reduce_map(d, Operands.OBJECT_OPERAND(), iadd, root=0)
        if slave.rank != 0:
            assert d == orig, "non-root input mutated by reduce_map"
        slave.barrier()
        xs = [[float(slave.rank + 1)] for _ in range(8)]
        xs_orig = [list(v) for v in xs]
        slave.reduce_array(xs, Operands.OBJECT_OPERAND(), iadd, root=0)
        if slave.rank != 0:
            assert xs == xs_orig, "non-root input mutated by reduce_array"
        return d

    want, werr, _, _ = run_chaos(N, fn)
    assert all(e is None for e in werr), werr
    got, errors, _, log = run_chaos(
        N, fn, fault_plan="reset:rank=1:nth=2")
    if any(errors):
        # reduce-to-root completes its sender ranks early, so this
        # fault window usually spans a collective boundary — the
        # documented terminal outcome, which must then be the SAME
        # clean error on every rank (never a hang, never a silently
        # wrong root result)
        assert all(isinstance(e, Mp4jFatalError) for e in errors), \
            f"{errors}\n{log}"
        assert len({str(e) for e in errors}) == 1, errors
    else:
        assert got[0] == want[0], f"root diverged after recovery"


@pytest.mark.parametrize("transport", ["shm", "tcp"])
@pytest.mark.parametrize("path", ["raw", "framed", "map"])
def test_chaos_kill_gives_clean_identical_error(path, transport):
    """A slave killed at collective N: the killed rank raises
    FaultKill, every SURVIVOR raises the same Mp4jFatalError naming
    the dead rank, within the bounded join — never a hang, never a
    partial result."""
    fn, kw = _body(path)
    kw.update(_transport_kw(transport))
    _, errors, _, log = run_chaos(
        N, fn, fault_plan="kill:rank=2:nth=2", **kw)
    assert isinstance(errors[2], FaultKill)
    survivors = [errors[r] for r in range(N) if r != 2]
    assert all(isinstance(e, Mp4jFatalError) for e in survivors), \
        f"{errors}\n{log}"
    msgs = {str(e) for e in survivors}
    assert len(msgs) == 1, f"survivors disagree: {msgs}"
    assert "rank 2" in msgs.pop()
    assert "terminal abort" in log


@pytest.mark.parametrize("transport", ["shm", "tcp"])
@pytest.mark.parametrize("path", ["raw", "framed", "map"])
def test_chaos_slow_rank_completes_bit_exactly(path, transport):
    """A persistently slow rank is a performance event, not a fault:
    no retries, no aborts, bit-exact output."""
    fn, kw = _body(path)
    kw.update(_transport_kw(transport))
    want, werr, _, _ = run_chaos(N, fn, fault_plan=None, **kw)
    assert all(e is None for e in werr)
    got, errors, stats, _ = run_chaos(
        N, fn, fault_plan="slow:rank=3:secs=0.002", **kw)
    assert all(e is None for e in errors), errors
    for w, g in enumerate(got):
        if path == "map":
            assert g == want[w]
        else:
            np.testing.assert_array_equal(g, want[w])
    tot = _totals(stats)
    assert tot == {"retries": 0, "reconnects": 0, "aborts_seen": 0}


def test_chaos_reset_with_growing_vocabulary_stays_consistent():
    """A reset during a map collective whose keys are NOVEL exercises
    the codec rollback: a torn sync round can leave the vocabulary
    grown on some ranks only, so the retry must first truncate back to
    the pre-attempt size or code tables desync job-wide. Three calls
    with disjoint fresh keys, the middle one faulted; a final call
    proves the vocabulary still agrees everywhere."""
    def fn(slave, r):
        out = []
        for step in range(3):
            base = 10_000 * step
            d = {base + int(k): np.float64((r + 1) * (k + 1))
                 for k in range(400)}
            slave.barrier()   # lockstep (recovery is per-collective)
            slave.allreduce_map(d, Operands.DOUBLE, Operators.SUM)
            out.append(d)
        return out

    want, werr, _, _ = run_chaos(N, fn)
    assert all(e is None for e in werr)
    got, errors, stats, log = run_chaos(
        N, fn, fault_plan="reset:rank=1:nth=2")
    assert all(e is None for e in errors), f"{errors}\n{log}"
    for w, g in zip(want, got):
        assert g == w          # all three steps bit-exact, dict ==
    assert _totals(stats)["aborts_seen"] == N


def test_codec_truncate_rolls_back_a_half_grown_vocabulary():
    """Unit half of the rollback: truncate drops codes, keys AND the
    cached partition placements, so a re-grown code slot can hold a
    different key with a correct placement."""
    from ytk_mp4j_tpu.comm import keycodec

    for codec, keys_a, keys_b in (
            (keycodec.IntKeyCodec(), [5, 9, 1], [77, 42]),
            (keycodec.ObjKeyCodec(), ["a", "c", "b"], ["zz", "q"])):
        codec.encode(keys_a, len(keys_a))
        base = codec.size
        decode_before = codec.decode(np.arange(base, dtype=np.int32))
        part_before = codec.partition(
            np.arange(base, dtype=np.int32), 4).tolist()
        codec.encode(keys_b, len(keys_b))
        assert codec.size == base + len(keys_b)
        codec.truncate(base)
        assert codec.size == base
        assert codec.novel(keys_b, len(keys_b)) == keys_b   # forgotten
        # re-grow DIFFERENT keys into the same code slots
        other = [k * 2 for k in keys_b] if codec.size and \
            isinstance(keys_b[0], int) else [k + "!" for k in keys_b]
        codes = codec.encode(other, len(other))
        assert codec.decode(codes) == other
        # surviving codes keep their original keys and placements
        assert codec.decode(
            np.arange(base, dtype=np.int32)) == decode_before
        assert codec.partition(
            np.arange(base, dtype=np.int32), 4).tolist() == part_before
        # truncating to a larger-or-equal size is a no-op
        codec.truncate(codec.size + 10)
        assert codec.decode(codes) == other


# ----------------------------------------------------------------------
# recovery engine edges
# ----------------------------------------------------------------------
def test_retry_exhaustion_is_terminal_and_identical():
    """A fault that outlives the retry budget: N resets armed at the
    same ordinal cut one attempt per recovery round, so max_retries=1
    exhausts and the master fans out ONE terminal message that every
    rank raises."""
    fn, kw = _body("raw")
    _, errors, _, log = run_chaos(
        N, fn, fault_plan="reset:rank=1:nth=2;reset:rank=1:nth=2;"
                          "reset:rank=1:nth=2;reset:rank=1:nth=2",
        max_retries=1, **kw)
    assert all(isinstance(e, Mp4jFatalError) for e in errors), \
        f"{errors}\n{log}"
    msgs = {str(e) for e in errors}
    assert len(msgs) == 1, msgs
    assert "failed after 1 recovery round" in msgs.pop()


def test_failstop_mode_is_reference_behavior():
    """MP4J_MAX_RETRIES=0 restores PR-1 semantics: the first transport
    error is final, no abort round runs, peers surface their own
    bounded-timeout errors."""
    fn, kw = _body("raw")
    _, errors, stats, log = run_chaos(
        N, fn, fault_plan="reset:rank=1:nth=2", max_retries=0,
        peer_timeout=1.5, **kw)
    assert any(isinstance(e, Mp4jError) for e in errors)
    tot = _totals(stats)
    assert tot["retries"] == 0 and tot["aborts_seen"] == 0
    assert "abort round" not in log


def test_recovery_spans_land_in_scope_ring(tmp_path):
    """Abort/retry events are visible in the mp4j-scope Chrome trace
    (zero-duration 'recovery' instants)."""
    spans.configure(16384)
    spans.clear()
    try:
        fn, kw = _body("framed")
        _, errors, _, log = run_chaos(
            N, fn, fault_plan="reset:rank=1:nth=2", **kw)
        assert all(e is None for e in errors), \
            f"recovery failed: {errors}\n{log}"
        cats = {s[0] for s in spans.snapshot() if s[1] == "recovery"}
        assert "abort" in cats and "retry" in cats
        out = tmp_path / "trace.json"
        trace.export_chrome_trace(str(out))
        doc = json.loads(out.read_text())
        rec = [ev for ev in doc["traceEvents"]
               if ev.get("cat") == "recovery"]
        assert rec and all(ev["dur"] == 0 for ev in rec)
    finally:
        spans.configure(tuning.span_ring_capacity())


def test_watchdog_escalates_stalled_barrier_to_terminal_abort():
    """The PR-3 watchdog acted on nothing; now a barrier stalled past
    dead_rank_secs terminates the whole job cluster-wide instead of
    relying on each rank's local timeout."""
    def fn(slave, r):
        if r == 1:
            time.sleep(6.0)   # rank 0 waits at the barrier alone
        slave.barrier()
        return None

    _, errors, _, log = run_chaos(
        2, fn, master_kwargs={"stall_timeout": 0.3,
                              "dead_rank_secs": 1.0})
    assert all(isinstance(e, Mp4jFatalError) for e in errors), errors
    msgs = {str(e) for e in errors}
    assert len(msgs) == 1
    assert "barrier gen 0 stalled" in msgs.pop()
    assert "terminal abort" in log


def test_mixed_progress_rule():
    """The master releases an abort round only when every in-flight
    rank retries the SAME collective and idle ranks sit exactly one
    behind; anything else (a fault spanning a collective boundary) is
    terminal — a completed rank cannot re-serve its contribution."""
    ok = Master._mixed_progress
    # consistent: all retrying #5, one idle rank about to enter #5
    assert ok({0: (5, True), 1: (5, True), 2: (4, False)}) is None
    # nobody in flight: nothing to align
    assert ok({0: (3, False), 1: (3, False)}) is None
    # a rank already COMPLETED the collective others must retry
    msg = ok({0: (5, True), 1: (5, False)})
    assert msg is not None and "collective boundary" in msg
    # in-flight ranks at different collectives
    msg = ok({0: (5, True), 1: (4, True)})
    assert msg is not None and "rank 1 at collective #4" in msg
    # an idle rank two behind can never reach the retried collective
    assert ok({0: (5, True), 1: (3, False)}) is not None


def test_watchdog_escalation_works_without_stall_timeout():
    """dead_rank_secs must bound the job even when the diagnosis-only
    stall_timeout is disabled — the escalation is not allowed to ride
    on the diagnosis being armed."""
    def fn(slave, r):
        if r == 1:
            time.sleep(6.0)
        slave.barrier()
        return None

    _, errors, _, log = run_chaos(
        2, fn, master_kwargs={"stall_timeout": None,
                              "dead_rank_secs": 1.0})
    assert all(isinstance(e, Mp4jFatalError) for e in errors), errors
    assert "barrier gen 0 stalled" in str(errors[0])


def test_dead_peer_default_recovery_goes_terminal_quickly():
    """A rank that defects (clean close, nonzero code) mid-job: with
    recovery ON by default the survivors converge on one clean
    terminal error naming the departed rank — no local peer_timeout
    needed, no hang."""
    def fn(slave, r):
        if r == 1:
            raise RuntimeError("defect before the collective")
        arr = np.ones(64)
        slave.allreduce_array(arr, Operands.DOUBLE, Operators.SUM)
        return arr

    _, errors, _, log = run_chaos(2, fn)
    assert isinstance(errors[0], Mp4jFatalError)
    assert "rank 1" in str(errors[0])


def test_stray_dial_ins_rejected_at_handshake():
    """Regression: a stray connection to a slave's peer listen socket
    carrying a coercible-but-wrong-typed handshake (('1',0), (2.7,0),
    (True,0)) must be rejected at the handshake — never claim a
    healthy rank's peer slot, never launder through a recovery
    round."""
    def fn(slave, r):
        if r == 0:
            port = slave._server.getsockname()[1]
            for bad in [("1", 0), (2.7, 0), (True, 0), "junk", (7,)]:
                ch = connect("127.0.0.1", port, timeout=5.0)
                try:
                    ch.send_obj(bad)
                finally:
                    ch.close()
        else:
            time.sleep(0.8)   # strays land before the real dials
        x = np.arange(16, dtype=np.float64) + r
        slave.allreduce_array(x, Operands.DOUBLE, Operators.SUM)
        return x

    res, errors, stats, log = run_chaos(N, fn)
    assert errors == [None] * N, f"{errors}\n{log}"
    want = sum(np.arange(16, dtype=np.float64) + r for r in range(N))
    for g in res:
        np.testing.assert_array_equal(g, want)
    assert _totals(stats)["retries"] == 0   # rejected, not recovered


def test_malformed_control_frame_is_fatal_not_a_hang():
    """Regression: a malformed-but-tuple control frame (('abort',))
    used to raise out of the ctl loop's dispatch, killing the sole
    master-channel reader without setting fatal — an untimed barrier
    wait would then hang forever. It must surface as a clean terminal
    error on every rank within the bounded join."""
    log = io.StringIO()
    master = Master(2, timeout=15.0, log_stream=log).serve_in_thread()
    errors: list = [None, None]

    def worker(i):
        slave = None
        try:
            slave = ProcessCommSlave("127.0.0.1", master.port,
                                     timeout=15.0, dead_rank_secs=8.0)
            for _ in range(60):
                slave.barrier()
                time.sleep(0.05)
            slave.close(0)
        except Exception as e:
            errors[slave.rank if slave is not None else i] = e
            if slave is not None:
                try:
                    slave.close(1)
                except Exception:
                    pass

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.5)
    master._send_to(0, ("abort",))    # torn frame: no epoch field
    deadline = time.monotonic() + 20.0
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    assert not any(t.is_alive() for t in threads), \
        f"HANG after malformed control frame\n{log.getvalue()}"
    master.join(5.0)
    assert all(isinstance(e, Mp4jFatalError) for e in errors), errors
    assert "protocol violation" in str(errors[0])


# ----------------------------------------------------------------------
# fault-plan grammar + knobs
# ----------------------------------------------------------------------
def test_fault_plan_parse_grammar():
    plan = FaultPlan.parse(
        "seed=42; reset:rank=1:nth=3:peer=2;"
        "delay:rank=0:nth=2:secs=0.2; slow:rank=3:secs=0.01;"
        "kill:rank=2:nth=5")
    assert plan.seed == 42 and len(plan.faults) == 4
    r = plan.faults[0]
    assert (r.action, r.rank, r.nth, r.peer) == ("reset", 1, 3, 2)
    assert plan.for_rank(3)[0].action == "slow"
    assert plan.for_rank(9) == []


@pytest.mark.parametrize("bad", [
    "explode:rank=1",            # unknown action
    "reset",                     # missing rank
    "reset:rank=x",              # non-int rank
    "delay:rank=0",              # delay without secs
    "reset:rank=1:color=red",    # unknown field
    "seed=abc",                  # bad seed
    "reset:rank=1:prob=2.0",     # prob outside [0, 1]
])
def test_fault_plan_rejects_garbage(bad):
    with pytest.raises(Mp4jError):
        FaultPlan.parse(bad)


def test_fault_plan_prob_is_seed_deterministic():
    plan = FaultPlan.parse("seed=7; reset:rank=0:prob=0.5;"
                           "reset:rank=0:prob=0.5")
    picks = [not FaultInjector(plan, 0).empty for _ in range(3)]
    assert picks[0] == picks[1] == picks[2]  # same seed, same outcome
    none = FaultPlan.parse("reset:rank=0:prob=0.0")
    assert FaultInjector(none, 0).empty


def test_resilience_knobs_env_validated(monkeypatch):
    monkeypatch.setenv("MP4J_MAX_RETRIES", "3")
    assert tuning.max_retries() == 3
    monkeypatch.setenv("MP4J_MAX_RETRIES", "-1")
    with pytest.raises(Mp4jError):
        tuning.max_retries()
    monkeypatch.setenv("MP4J_RECONNECT_BACKOFF", "nope")
    with pytest.raises(Mp4jError):
        tuning.reconnect_backoff()
    monkeypatch.setenv("MP4J_DEAD_RANK_SECS", "0")
    with pytest.raises(Mp4jError):
        tuning.dead_rank_secs()
    monkeypatch.setenv("MP4J_FAULT_PLAN", " reset:rank=0 ")
    assert tuning.fault_plan_spec() == "reset:rank=0"


def test_dead_rank_secs_constructor_validated():
    """The explicit constructor arg must get the same positivity check
    as the env path: dead_rank_secs=0 would arm a watchdog that
    terminal-aborts healthy jobs (master) / instantly expire every
    recovery deadline (slave) — reject it at construction, on both.
    inf (the documented disable idiom) stays accepted."""
    with pytest.raises(Mp4jError, match="dead_rank_secs"):
        Master(1, dead_rank_secs=0.0)
    with pytest.raises(Mp4jError, match="dead_rank_secs"):
        Master(1, dead_rank_secs=-1.0)
    m = Master(1, timeout=10.0, dead_rank_secs=float("inf"),
               log_stream=io.StringIO()).serve_in_thread()
    try:
        with pytest.raises(Mp4jError, match="dead_rank_secs"):
            ProcessCommSlave("127.0.0.1", m.port, timeout=10.0,
                             dead_rank_secs=0.0)
        slave = ProcessCommSlave("127.0.0.1", m.port, timeout=10.0)
        slave.barrier()
        slave.close(0)
    finally:
        m.join(10.0)


def test_error_hierarchy():
    """Recovery retries transport errors only; fatal is never
    transport (nothing may retry it)."""
    from ytk_mp4j_tpu.exceptions import Mp4jAbortError
    assert issubclass(Mp4jTransportError, Mp4jError)
    assert issubclass(Mp4jAbortError, Mp4jTransportError)
    assert issubclass(Mp4jFatalError, Mp4jError)
    assert not issubclass(Mp4jFatalError, Mp4jTransportError)
    assert issubclass(FaultKill, Mp4jError)
    assert not issubclass(FaultKill, Mp4jTransportError)


# ----------------------------------------------------------------------
# mp4j-async chaos (ISSUE 11): {reset, kill, slow} x {2, 8 outstanding}
# x {tcp, shm} over nonblocking futures
# ----------------------------------------------------------------------
def _async_body(k):
    """One healthy blocking allreduce (establishes channels + ordinal
    1), a barrier (lockstep: recovery is per-collective), then k
    OUTSTANDING iallreduces drained by wait_all; the fault plans
    target ordinal 2 = the first batch member, so the fault lands
    inside the engine batch on every rank."""
    rng = np.random.default_rng(23)
    alls = [rng.standard_normal(120_000) for _ in range(N)]

    def fn(slave, r):
        warm = alls[r].copy()
        slave.allreduce_array(warm, Operands.DOUBLE, Operators.SUM)
        slave.barrier()
        arrs = [alls[r].copy() * (i + 1) for i in range(k)]
        futs = [slave.iallreduce(a, Operands.DOUBLE, Operators.SUM)
                for a in arrs]
        slave.wait_all()
        assert all(f.done() for f in futs)
        return arrs
    return fn


@pytest.mark.parametrize("transport", ["tcp", "shm"])
@pytest.mark.parametrize("k", [2, 8])
def test_async_reset_recovers_bit_exact(k, transport):
    """A connection reset inside an engine batch of k outstanding
    futures: the whole batch restores and re-drives at the new epoch,
    bit-exact against an unfaulted run, zero errors, zero hangs."""
    kw = {} if transport == "shm" else {"shm": False}
    fn = _async_body(k)
    want, werr, _, _ = run_chaos(N, fn, fault_plan=None, **kw)
    assert all(e is None for e in werr), werr
    got, errors, stats, log = run_chaos(
        N, fn, fault_plan="reset:rank=1:nth=2", **kw)
    assert all(e is None for e in errors), f"{errors}\n{log}"
    for r in range(N):
        for i in range(k):
            np.testing.assert_array_equal(got[r][i], want[r][i])
    # the reset forced an epoch-fenced retry somewhere (which rank
    # books it can race with the round's completion on this 1-core
    # host; the bit-exact outputs above are the real contract)
    assert any(stats[r].get("allreduce_array", {}).get("retries", 0)
               >= 1 for r in range(N)), stats


@pytest.mark.parametrize("transport", ["tcp", "shm"])
@pytest.mark.parametrize("k", [2, 8])
def test_async_kill_same_message_everywhere(k, transport):
    """A rank killed inside an engine batch: the killed rank's waiter
    raises FaultKill, every survivor raises the SAME Mp4jFatalError,
    nobody hangs."""
    kw = {} if transport == "shm" else {"shm": False}
    fn = _async_body(k)
    got, errors, _, log = run_chaos(
        N, fn, fault_plan="kill:rank=2:nth=2", **kw)
    assert isinstance(errors[2], FaultKill), f"{errors}\n{log}"
    survivor_msgs = {str(errors[r]) for r in range(N) if r != 2}
    assert all(isinstance(errors[r], Mp4jFatalError)
               for r in range(N) if r != 2), f"{errors}\n{log}"
    assert len(survivor_msgs) == 1, survivor_msgs


@pytest.mark.parametrize("transport", ["tcp", "shm"])
@pytest.mark.parametrize("k", [2, 8])
def test_async_slow_rank_still_bit_exact(k, transport):
    """An injected-slow rank inside the batch: no retry needed, just
    latency — results bit-exact, zero errors."""
    kw = {} if transport == "shm" else {"shm": False}
    fn = _async_body(k)
    want, werr, _, _ = run_chaos(N, fn, fault_plan=None, **kw)
    assert all(e is None for e in werr), werr
    got, errors, _, log = run_chaos(
        N, fn, fault_plan="slow:rank=3:nth=2:secs=0.02", **kw)
    assert all(e is None for e in errors), f"{errors}\n{log}"
    for r in range(N):
        for i in range(k):
            np.testing.assert_array_equal(got[r][i], want[r][i])


# ----------------------------------------------------------------------
# RecoveryManager alone, scripted: the "master" is the send_ctl hook,
# which answers on the caller's own thread — no sockets, no sleeps
# ----------------------------------------------------------------------
def _scripted_recovery(on_ctl, seq=3, **kw):
    from ytk_mp4j_tpu.resilience.recovery import RecoveryManager
    from ytk_mp4j_tpu.utils.stats import CommStats
    sent: list = []
    pos = {"seq": seq}

    def send_ctl(kind, payload):
        sent.append((kind, dict(payload)))
        on_ctl(rm, kind, payload)

    rm = RecoveryManager(rank=1, max_retries=3, dead_rank_secs=5.0,
                         send_ctl=send_ctl, teardown=lambda: None,
                         stats=CommStats(),
                         progress=lambda: (pos["seq"], False), **kw)
    return rm, sent, pos


def _run_once(rm):
    calls = []
    out = rm.run("allreduce_array", lambda: calls.append(1) or "done",
                 lambda: None, None)
    return out, len(calls)


def test_terminal_message_is_one_class_for_every_waiter():
    fired = []
    rm, sent, _ = _scripted_recovery(lambda *a: None,
                                     terminal_hook=fired.append)
    rm.on_fatal("rank 2 is dead; aborting the job")
    rm.on_fatal("a later, different message")      # first one wins
    assert fired == ["rank 2 is dead; aborting the job"]
    for raiser in (rm.poll, lambda: _run_once(rm)):
        with pytest.raises(Mp4jFatalError) as ei:
            raiser()
        assert type(ei.value) is Mp4jFatalError
        assert str(ei.value) == "rank 2 is dead; aborting the job"
    assert str(rm.fatal_exc("local")) == "local"
    assert [k for _, k, _ in rm.events()] == ["fatal"]
    assert sent == []


def test_fence_parks_at_the_boundary_until_released():
    def master(rm, kind, payload):
        if kind == "fence_ack":
            assert payload == {"token": 7, "seq": 3}
            rm.on_fence_release(7)          # applied; resume free
    rm, sent, _ = _scripted_recovery(master)
    rm.on_fence_release(7)                  # nothing armed: a no-op
    rm.on_fence(7)
    assert _run_once(rm) == ("done", 1)
    assert [k for k, _ in sent] == ["fence_ack"]
    assert _run_once(rm) == ("done", 1)     # released: no second park
    assert [k for k, _ in sent] == ["fence_ack"]
    # (the scripted master answers inside the ack, before the park)
    assert [k for _, k, _ in rm.events()] == [
        "fence_release", "fence", "fence_release", "fence_park"]


def test_fence_advance_runs_the_laggard_on_then_reparks():
    script = iter(["advance", "release"])

    def master(rm, kind, payload):
        if kind == "fence_ack" and next(script) == "advance":
            rm.on_fence_advance(8, 5)       # a stale token: ignored
            rm.on_fence_advance(7, 5)
        elif kind == "fence_ack":
            rm.on_fence_release(7)
    rm, sent, pos = _scripted_recovery(master)
    rm.on_fence(7)
    assert _run_once(rm) == ("done", 1)     # seq 3 < goal 5: runs on
    pos["seq"] = 4
    assert _run_once(rm) == ("done", 1)     # still behind: no ack
    assert [p["seq"] for _, p in sent] == [3]
    pos["seq"] = 5
    assert _run_once(rm) == ("done", 1)     # at the goal: park + ack
    assert [p["seq"] for _, p in sent] == [3, 5]


def test_abort_round_supersedes_an_armed_fence():
    def master(rm, kind, payload):
        if kind == "fence_ack":
            rm.on_abort(1)                  # a death opened a round
        elif kind == "abort_ack":
            assert payload == {"epoch": 1, "seq": 3, "inflight": False}
            rm.on_go(1)
    rm, sent, _ = _scripted_recovery(master)
    rm.on_fence(7)
    assert _run_once(rm) == ("done", 1)
    assert [k for k, _ in sent] == ["fence_ack", "abort_ack"]
    assert rm.epoch == 1 and not rm.abort_pending()
    rm.on_fence_release(7)                  # the late cancel: harmless
    assert _run_once(rm) == ("done", 1)
    assert [k for k, _ in sent] == ["fence_ack", "abort_ack"]
