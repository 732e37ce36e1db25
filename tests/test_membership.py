"""mp4j-elastic (ISSUE 10): rank replacement from warm spares and
degraded shrink mode.

The chaos grid crosses ``kill`` with {replace, shrink} membership
modes, {raw, framed, columnar-map} data planes and {tcp, shm-carrier}
transports, asserting the acceptance contract:

- **replace**: a warm spare is adopted into the dead rank's id at the
  next epoch, the fenced retry restores inputs and re-runs, and the
  job completes with results BIT-IDENTICAL to an unfaulted run — zero
  surviving-rank errors, the joiner seeded with the roster, the
  columnar keycodec vocabularies and the resume ordinal.
- **shrink**: survivors renumber contiguously, rebuild topology at
  n-1 and continue; results equal the correct n-1 reduction of the
  survivors' restored inputs.
- **off** (default): today's single clean ``Mp4jFatalError`` on every
  survivor — the pre-elastic contract, bit-for-bit.

Plus negative cases (no spare available under ``replace``; a spare
dying mid-adoption falls through to the next spare), knob-conflict
validation (``MP4J_MAX_RETRIES=0`` hard-disables both elastic modes),
membership observability (live view badges, Prometheus counters,
recovery-log events) and vocabulary continuity across an adoption.
Every scenario runs under a hard thread-join deadline — zero hangs.
"""

import io
import threading
import time

import numpy as np
import pytest

from ytk_mp4j_tpu.comm import keycodec
from ytk_mp4j_tpu.comm.master import Master, REGISTER
from ytk_mp4j_tpu.comm.process_comm import ProcessCommSlave
from ytk_mp4j_tpu.exceptions import (
    Mp4jError, Mp4jFatalError, Mp4jSpareReleased)
from ytk_mp4j_tpu.obs import telemetry
from ytk_mp4j_tpu.operands import Operands
from ytk_mp4j_tpu.operators import Operators
from ytk_mp4j_tpu.resilience import membership
from ytk_mp4j_tpu.resilience.faults import FaultKill
from ytk_mp4j_tpu.transport.tcp import connect
from ytk_mp4j_tpu.utils import tuning

N = 4
JOIN = 45.0


def run_elastic(n, fn, spare_fns=(), fault_plan=None, join=JOIN,
                master_kwargs=None, **slave_kwargs):
    """Master + ``n`` slave threads + one thread per entry of
    ``spare_fns`` (each a continuation body run AFTER adoption), all
    under a HARD join deadline. Returns ``(results, errors, spares,
    master, log)`` where ``spares`` is a list of per-spare dicts
    ({"adopted_rank", "resume_seq", "result" | "released" |
    "error"}). Replace-mode results index by rank: an adopted spare's
    result lands at its adopted rank."""
    log = io.StringIO()
    mk = dict(master_kwargs or {})
    mk.setdefault("spares", len(spare_fns))
    master = Master(n, timeout=join, log_stream=log,
                    **mk).serve_in_thread()
    results = [None] * n
    errors: list = [None] * n

    def worker(i):
        slave = None
        try:
            slave = ProcessCommSlave(
                "127.0.0.1", master.port, timeout=join,
                fault_plan=fault_plan, dead_rank_secs=20.0,
                **slave_kwargs)
            r = slave.rank
            out = fn(slave, r)
            # shrink renumbers mid-run: report under the FINAL rank
            results[slave.rank] = out
            slave.close(0)
        except Exception as e:
            r = slave.rank if slave is not None else i
            errors[r] = e
            if slave is not None:
                try:
                    slave.close(1)
                except Exception:
                    pass

    spares: list[dict] = [{} for _ in spare_fns]

    def spare_worker(k):
        sp = None
        try:
            sp = ProcessCommSlave(
                "127.0.0.1", master.port, timeout=join, spare=True,
                dead_rank_secs=20.0, **slave_kwargs)
            spares[k]["adopted_rank"] = sp.rank
            spares[k]["resume_seq"] = sp.resume_seq
            out = spare_fns[k](sp)
            spares[k]["result"] = out
            results[sp.rank] = out
            sp.close(0)
        except Mp4jSpareReleased as e:
            spares[k]["released"] = str(e)
        except Exception as e:
            spares[k]["error"] = e
            if sp is not None:
                try:
                    sp.close(1)
                except Exception:
                    pass

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(n)]
    threads += [threading.Thread(target=spare_worker, args=(k,),
                                 daemon=True)
                for k in range(len(spare_fns))]
    for t in threads:
        t.start()
    deadline = time.monotonic() + join
    for t in threads:
        t.join(max(0.1, deadline - time.monotonic()))
    hung = [i for i, t in enumerate(threads) if t.is_alive()]
    assert not hung, f"threads {hung} hung past the join deadline:\n" \
                     + log.getvalue()
    master.join(10.0)
    return results, errors, spares, master, log.getvalue()


# ----------------------------------------------------------------------
# deterministic two-collective bodies (fault plans target ordinal 2)
# ----------------------------------------------------------------------
_RNG = np.random.default_rng(11)
_ALLS = [_RNG.standard_normal(60_000) for _ in range(N)]
_SUM1 = sum(_ALLS)                      # every rank's state after coll 1


def _map_init(r):
    return {int(k): np.float64((r + 1) * (k + 1)) for k in range(600)}


_MAP_SUM1 = {}
for _r in range(N):
    for _k, _v in _map_init(_r).items():
        _MAP_SUM1[_k] = _MAP_SUM1.get(_k, 0.0) + _v


def _body(path, after1=None):
    """coll 1 (allreduce) -> barrier -> coll 2 (allreduce), the same
    shape as the PR 5 chaos grid; plus the matching SPARE continuation
    which reconstructs the dead rank's pre-coll-2 state (after an
    allreduce every rank holds the IDENTICAL value, recorded into
    ``after1`` — the joiner re-derives the dead rank's state without
    communication, the application-level half of the elastic
    contract; a real job would load a checkpoint here)."""
    after1 = after1 if after1 is not None else {}
    if path == "map":
        def fn(slave, r):
            d = _map_init(r)
            slave.allreduce_map(d, Operands.DOUBLE, Operators.SUM)
            after1["v"] = dict(d)     # identical on every rank
            slave.barrier()
            slave.allreduce_map(d, Operands.DOUBLE, Operators.SUM)
            return d

        def spare_fn(sp):
            assert sp.resume_seq == 1, sp.resume_seq
            d = dict(after1["v"])
            sp.allreduce_map(d, Operands.DOUBLE, Operators.SUM)
            return d
        return fn, spare_fn, after1, {}

    def fn(slave, r):
        arr = _ALLS[r].copy()
        slave.allreduce_array(arr, Operands.DOUBLE, Operators.SUM)
        after1["v"] = arr.copy()      # identical on every rank
        slave.barrier()
        slave.allreduce_array(arr, Operands.DOUBLE, Operators.SUM)
        return arr

    def spare_fn(sp):
        assert sp.resume_seq == 1, sp.resume_seq
        arr = after1["v"].copy()
        sp.allreduce_array(arr, Operands.DOUBLE, Operators.SUM)
        return arr
    return fn, spare_fn, after1, {"native_transport": path == "raw"}


def _transport_kw(transport):
    # the thread harness co-locates every rank, so the default plane is
    # the shm rings ("shm-carrier": peer re-dials renegotiate SEGMENTS
    # with the joiner); shm=False pins the all-TCP grid
    return {} if transport == "shm" else {"shm": False}


# ----------------------------------------------------------------------
# the chaos grid: kill × {replace, shrink} × planes × transports
# ----------------------------------------------------------------------
@pytest.mark.parametrize("transport", ["shm", "tcp"])
@pytest.mark.parametrize("path", ["raw", "framed", "map"])
def test_replace_kill_bit_exact_continuation(path, transport):
    """A killed rank is replaced from a warm spare: the job completes
    with results bit-identical to an unfaulted run, zero survivor
    errors, the spare adopted into the dead rank's id."""
    fn, spare_fn, _, kw = _body(path)
    kw.update(_transport_kw(transport))
    want, werr, _, _, _ = run_elastic(N, fn, **kw)
    assert all(e is None for e in werr), werr
    got, errors, spares, master, log = run_elastic(
        N, fn, spare_fns=[spare_fn],
        fault_plan="kill:rank=2:nth=2",
        master_kwargs={"elastic": "replace"}, elastic="replace", **kw)
    assert isinstance(errors[2], FaultKill)
    survivors = [errors[r] for r in range(N) if r != 2]
    assert all(e is None for e in survivors), \
        f"survivor errors: {errors}\n{log}"
    assert spares[0].get("adopted_rank") == 2, f"{spares}\n{log}"
    assert "error" not in spares[0], f"{spares[0].get('error')}\n{log}"
    for r in range(N):
        if path == "map":
            assert set(got[r]) == set(want[r])
            for k in got[r]:
                assert got[r][k] == want[r][k]   # bit-exact
        else:
            np.testing.assert_array_equal(got[r], want[r])
    assert master.final_code == 0, log
    assert "adopted as rank 2" in log


@pytest.mark.parametrize("transport", ["shm", "tcp"])
@pytest.mark.parametrize("path", ["raw", "framed", "map"])
def test_shrink_kill_continues_at_n_minus_1(path, transport):
    """A killed rank under shrink: survivors renumber contiguously and
    produce the correct n-1 reduction of their restored inputs."""
    fn, _, after1, kw = _body(path)
    kw.update(_transport_kw(transport))

    final = {}

    def fn2(slave, r):
        out = fn(slave, r)
        final[r] = (slave.rank, slave.slave_num)
        return out

    got, errors, _, master, log = run_elastic(
        N, fn2, fault_plan="kill:rank=2:nth=2",
        master_kwargs={"elastic": "shrink"}, elastic="shrink", **kw)
    assert isinstance(errors[2], FaultKill)
    survivors = [r for r in range(N) if r != 2]
    assert all(errors[r] is None for r in survivors), \
        f"survivor errors: {errors}\n{log}"
    # renumbering: old ranks 0,1,3 -> 0,1,2 at slave_num 3
    assert {final[r] for r in survivors} == {(0, 3), (1, 3), (2, 3)}, \
        f"{final}\n{log}"
    # every survivor's coll-2 input was its (identical) post-coll-1
    # state, restored by the fenced retry — the n-1 result is three
    # copies summed, bitwise 3x (x+x is exact, so either reduction
    # shape is one rounding of the exact 3x)
    for new_r in range(3):     # results index by the FINAL rank
        if path == "map":
            for k, v in got[new_r].items():
                assert v == 3.0 * after1["v"][k]
        else:
            np.testing.assert_array_equal(got[new_r],
                                          3.0 * after1["v"])
    assert master.final_code == 0, log
    assert master.slave_num == 3
    assert "shrunk to 3 rank(s)" in log


def test_replace_with_novel_vocabulary_stays_consistent():
    """Vocabulary continuity across an adoption: the joiner's imported
    codec tables must match the survivors' exactly, including codes
    grown over MULTIPLE pre-kill map collectives — a post-adoption map
    collective mixing old and new keys is bit-exact against an
    unfaulted run."""
    def fn(slave, r):
        out = []
        for step in range(3):
            base = 10_000 * step
            d = {base + int(k): np.float64((r + 1) * (k + 1))
                 for k in range(300)}
            slave.barrier()
            slave.allreduce_map(d, Operands.DOUBLE, Operators.SUM)
            out.append(d)
        return out

    def spare_fn(sp):
        # adopted at the third map collective (ordinal 3): steps 0-1
        # completed job-wide; rebuild rank 2's inputs for step 2
        assert sp.resume_seq == 2, sp.resume_seq
        base = 10_000 * 2
        d = {base + int(k): np.float64(3 * (k + 1))
             for k in range(300)}
        sp.allreduce_map(d, Operands.DOUBLE, Operators.SUM)
        return [None, None, d]

    want, werr, _, _, _ = run_elastic(N, fn)
    assert all(e is None for e in werr), werr
    got, errors, spares, _, log = run_elastic(
        N, fn, spare_fns=[spare_fn],
        fault_plan="kill:rank=2:nth=3",
        master_kwargs={"elastic": "replace"}, elastic="replace")
    assert all(errors[r] is None for r in range(N) if r != 2), \
        f"{errors}\n{log}"
    assert spares[0].get("adopted_rank") == 2, f"{spares}\n{log}"
    for r in range(N):
        if r == 2:
            assert got[2][2] == want[2][2]   # the joiner's step
        else:
            assert got[r] == want[r]         # all three steps bit-==


# ----------------------------------------------------------------------
# negative cases + the off contract
# ----------------------------------------------------------------------
def test_replace_without_spare_is_clean_fatal():
    """MP4J_ELASTIC=replace with an empty pool: today's clean
    Mp4jFatalError on every survivor — same message everywhere, within
    the bounded join, naming the missing spare."""
    fn, _, _, kw = _body("raw")
    _, errors, _, _, log = run_elastic(
        N, fn, fault_plan="kill:rank=2:nth=2",
        master_kwargs={"elastic": "replace"}, elastic="replace", **kw)
    assert isinstance(errors[2], FaultKill)
    survivors = [errors[r] for r in range(N) if r != 2]
    assert all(isinstance(e, Mp4jFatalError) for e in survivors), \
        f"{errors}\n{log}"
    msgs = {str(e) for e in survivors}
    assert len(msgs) == 1, msgs
    msg = msgs.pop()
    assert "rank 2" in msg and "no warm spare available" in msg


def test_spare_dies_mid_adoption_next_spare_adopted():
    """The first spare (registration order) dies the moment it is
    adopted: the master falls through to the NEXT spare and the job
    still completes bit-exactly."""
    fn, spare_fn, _, kw = _body("framed")
    want, werr, _, _, _ = run_elastic(N, fn, **kw)
    assert all(e is None for e in werr), werr

    log = io.StringIO()
    master = Master(N, timeout=JOIN, log_stream=log, elastic="replace",
                    spares=2, adopt_secs=4.0).serve_in_thread()

    # fake spare: registers FIRST (adopted first), reads its adopt
    # message, then drops dead without acking
    fake_ready = threading.Event()

    def fake_spare():
        ch = connect("127.0.0.1", master.port, timeout=JOIN)
        ch.send_obj((REGISTER, {"listen_port": 1, "host": "127.0.0.1",
                                "fp": "", "spare": True}))
        ch.recv()                      # registration ack
        fake_ready.set()
        try:
            ch.set_timeout(JOIN)
            ch.recv()                  # the adopt message
        except Exception:
            pass
        ch.close()                     # die without acking

    fk = threading.Thread(target=fake_spare, daemon=True)
    fk.start()
    fake_ready.wait(10.0)

    results = [None] * N
    errors: list = [None] * N

    def worker(i):
        slave = None
        try:
            slave = ProcessCommSlave(
                "127.0.0.1", master.port, timeout=JOIN,
                fault_plan="kill:rank=2:nth=2", dead_rank_secs=20.0,
                elastic="replace", **kw)
            results[slave.rank] = fn(slave, slave.rank)
            slave.close(0)
        except Exception as e:
            errors[slave.rank if slave is not None else i] = e

    spare_out: dict = {}

    def real_spare():
        try:
            sp = ProcessCommSlave("127.0.0.1", master.port,
                                  timeout=JOIN, spare=True,
                                  dead_rank_secs=20.0,
                                  elastic="replace", **kw)
            spare_out["rank"] = sp.rank
            results[sp.rank] = spare_fn(sp)
            sp.close(0)
        except Exception as e:
            spare_out["error"] = e

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(N)]
    threads.append(threading.Thread(target=real_spare, daemon=True))
    for t in threads:
        t.start()
    deadline = time.monotonic() + JOIN
    for t in threads:
        t.join(max(0.1, deadline - time.monotonic()))
    assert not any(t.is_alive() for t in threads), \
        f"HANG\n{log.getvalue()}"
    master.join(10.0)
    out = log.getvalue()
    assert isinstance(errors[2], FaultKill)
    assert all(errors[r] is None for r in range(N) if r != 2), \
        f"{errors}\n{out}"
    assert spare_out.get("rank") == 2, f"{spare_out}\n{out}"
    for r in range(N):
        np.testing.assert_array_equal(results[r], want[r])
    assert "spare #0 lost" in out
    assert "spare #1 adopted as rank 2" in out


def test_elastic_off_preserves_fatal_contract():
    """The default (off) keeps the pre-elastic behavior bit-for-bit:
    one clean identical Mp4jFatalError naming the dead rank on every
    survivor."""
    fn, _, _, kw = _body("framed")
    _, errors, _, _, log = run_elastic(
        N, fn, fault_plan="kill:rank=2:nth=2", **kw)
    assert isinstance(errors[2], FaultKill)
    survivors = [errors[r] for r in range(N) if r != 2]
    assert all(isinstance(e, Mp4jFatalError) for e in survivors), \
        f"{errors}\n{log}"
    assert len({str(e) for e in survivors}) == 1
    assert "membership" not in log


def test_surplus_nonspare_registration_rejected_during_spare_wait():
    """Regression: with spares configured, rendezvous stays open past
    slave_num — a surplus NON-spare dial-in in that window must be
    rejected (closed), never assigned an out-of-range rank (it would
    hang at its first barrier while the real job released without
    it)."""
    log = io.StringIO()
    master = Master(2, timeout=JOIN, log_stream=log, elastic="replace",
                    spares=1).serve_in_thread()
    results = [None, None]
    errors: list = [None, None]

    def worker(i):
        try:
            s = ProcessCommSlave("127.0.0.1", master.port,
                                 timeout=JOIN, dead_rank_secs=20.0,
                                 elastic="replace")
            arr = np.ones(32) * (s.rank + 1)
            s.allreduce_array(arr, Operands.DOUBLE, Operators.SUM)
            results[s.rank] = arr
            s.close(0)
        except Exception as e:
            errors[i] = e

    ts = [threading.Thread(target=worker, args=(i,), daemon=True)
          for i in range(2)]
    for t in ts:
        t.start()
    time.sleep(0.8)   # both ranks registered; rendezvous now waits
    # only on the spare — the surplus window under test
    stray = connect("127.0.0.1", master.port, timeout=JOIN)
    stray.send_obj((REGISTER, {"listen_port": 1,
                               "host": "127.0.0.1", "fp": ""}))
    stray.set_timeout(10.0)
    with pytest.raises(Exception):
        stray.recv()             # surplus: master closes -> EOF/error
    stray.close()
    # the real job proceeds once the spare registers
    spare_out: dict = {}

    def spare():
        try:
            ProcessCommSlave("127.0.0.1", master.port, timeout=JOIN,
                             spare=True, elastic="replace",
                             dead_rank_secs=20.0)
        except Mp4jSpareReleased:
            spare_out["released"] = True

    sp = threading.Thread(target=spare, daemon=True)
    sp.start()
    deadline = time.monotonic() + JOIN
    for t in ts + [sp]:
        t.join(max(0.1, deadline - time.monotonic()))
    assert not any(t.is_alive() for t in ts + [sp]), \
        f"HANG\n{log.getvalue()}"
    master.join(10.0)
    assert errors == [None, None], f"{errors}\n{log.getvalue()}"
    for r in range(2):
        np.testing.assert_array_equal(results[r], np.ones(32) * 3.0)
    assert spare_out.get("released")
    assert master.final_code == 0


def test_spare_released_when_job_completes():
    """A never-needed spare is the success case: the job completes,
    the master releases the pool, and the spare constructor raises
    Mp4jSpareReleased instead of hanging."""
    def fn(slave, r):
        arr = np.ones(64)
        slave.allreduce_array(arr, Operands.DOUBLE, Operators.SUM)
        return arr

    results, errors, spares, master, log = run_elastic(
        2, fn, spare_fns=[lambda sp: None],
        master_kwargs={"elastic": "replace"}, elastic="replace")
    assert all(e is None for e in errors), f"{errors}\n{log}"
    assert "released" in spares[0], f"{spares}\n{log}"
    assert master.final_code == 0


# ----------------------------------------------------------------------
# knob validation: fail-stop conflict (the ISSUE 10 bugfix guard)
# ----------------------------------------------------------------------
def test_failstop_conflicts_with_elastic_modes(monkeypatch):
    """MP4J_MAX_RETRIES=0 (exact fail-stop reference semantics) must
    hard-disable both elastic modes with a validated-knob conflict
    error — never a silent precedence."""
    monkeypatch.setenv("MP4J_MAX_RETRIES", "0")
    for mode in ("replace", "shrink"):
        with pytest.raises(Mp4jError, match="conflicts"):
            tuning.elastic_mode(mode)
    monkeypatch.delenv("MP4J_MAX_RETRIES")
    # the master hits the SAME validator (env MP4J_MAX_RETRIES path)
    monkeypatch.setenv("MP4J_MAX_RETRIES", "0")
    with pytest.raises(Mp4jError, match="conflicts"):
        Master(2, elastic="shrink")
    monkeypatch.delenv("MP4J_MAX_RETRIES")
    # slave-side: explicit max_retries=0 + explicit elastic
    m = Master(1, timeout=10.0, log_stream=io.StringIO())
    m.serve_in_thread()
    try:
        with pytest.raises(Mp4jError, match="conflicts"):
            ProcessCommSlave("127.0.0.1", m.port, timeout=10.0,
                             max_retries=0, elastic="replace")
        slave = ProcessCommSlave("127.0.0.1", m.port, timeout=10.0)
        slave.close(0)
    finally:
        m.join(10.0)
    # off + fail-stop remains legal (the reference contract)
    monkeypatch.setenv("MP4J_MAX_RETRIES", "0")
    assert tuning.elastic_mode() == "off"
    monkeypatch.delenv("MP4J_MAX_RETRIES")
    with pytest.raises(Mp4jError):
        tuning.elastic_mode("sideways")
    assert tuning.spares(3) == 3
    with pytest.raises(Mp4jError):
        tuning.spares(-1)
    with pytest.raises(Mp4jError):
        tuning.adopt_secs(0)


# ----------------------------------------------------------------------
# observability: badges, counters, events
# ----------------------------------------------------------------------
def test_membership_observability_after_replace():
    """After a replacement: the membership doc counts it, the live
    view renders the REPLACED badge + spares line, Prometheus exports
    the counters, and the joiner's recovery log records the
    adoption."""
    from ytk_mp4j_tpu.obs import metrics as metrics_mod

    fn, spare_fn, _, kw = _body("framed")
    events: dict = {}

    def spare_fn2(sp):
        out = spare_fn(sp)
        events["recovery"] = sp._recovery.events()
        return out

    _, errors, spares, master, log = run_elastic(
        N, fn, spare_fns=[spare_fn2],
        fault_plan="kill:rank=2:nth=2",
        master_kwargs={"elastic": "replace"}, elastic="replace", **kw)
    assert all(errors[r] is None for r in range(N) if r != 2)
    ms = master.membership_status()
    assert ms["mode"] == "replace"
    assert ms["replacements"] == 1 and ms["shrinks"] == 0
    assert ms["badges"].get("2", "").startswith("REPLACED@e")
    assert ms["events"] and ms["events"][-1]["kind"] == "replace"
    doc = master.metrics_doc()
    assert doc["cluster"]["membership"]["replacements"] == 1
    text = metrics_mod.to_prometheus(doc)
    assert "mp4j_replacements_total 1" in text
    assert "mp4j_shrinks_total 0" in text
    assert "mp4j_spares_available 0" in text
    live = telemetry.format_live(doc)
    assert "membership: mode=replace" in live
    assert "1 replacement(s)" in live
    # joiner-side recovery log carries the adoption event
    kinds = [k for _, k, _ in events.get("recovery", [])]
    assert "adopted" in kinds


def test_membership_observability_after_shrink():
    from ytk_mp4j_tpu.obs import metrics as metrics_mod

    fn, _, _, kw = _body("framed")
    _, errors, _, master, log = run_elastic(
        N, fn, fault_plan="kill:rank=2:nth=2",
        master_kwargs={"elastic": "shrink"}, elastic="shrink", **kw)
    assert all(errors[r] is None for r in range(N) if r != 2), \
        f"{errors}\n{log}"
    ms = master.membership_status()
    assert ms["shrinks"] == 1
    assert ms["events"][-1]["kind"] == "shrink"
    assert ms["events"][-1]["dead"] == [2]
    text = metrics_mod.to_prometheus(master.metrics_doc())
    assert "mp4j_shrinks_total 1" in text
    live = telemetry.format_live(master.metrics_doc())
    assert "1 shrink(s)" in live


# ----------------------------------------------------------------------
# pure-function units
# ----------------------------------------------------------------------
def test_joiner_seq_rule():
    # in-flight survivors retry #5; the joiner enters #5 fresh
    assert membership.joiner_seq({0: (5, True), 1: (4, False)}) == 4
    # nobody in flight: match the idle position
    assert membership.joiner_seq({0: (3, False), 1: (3, False)}) == 3
    assert membership.joiner_seq({}) == 0


def test_shrink_mapping_and_rosters():
    m = membership.shrink_mapping(5, {1, 3})
    assert m == {0: 0, 2: 1, 4: 2}
    roster = [("h", p, "") for p in range(5)]
    assert membership.shrink_roster(roster, m) == [
        ("h", 0, ""), ("h", 2, ""), ("h", 4, "")]
    swapped = membership.swap_roster(roster, {2: ("x", 99, "fp")})
    assert swapped[2] == ("x", 99, "fp") and swapped[0] == roster[0]


def test_vocab_export_import_roundtrip():
    codecs: dict = {}
    ic = keycodec.IntKeyCodec()
    # grown over multiple calls with per-call sorted batches — code
    # order is NOT globally sorted
    ic.encode([50, 10], 2)
    ic.encode([5, 99], 2)
    oc = keycodec.ObjKeyCodec()
    oc.encode(["z", "a"], 2)
    oc.encode(["m"], 1)
    src = {"int": ic, "obj": oc}
    vocab = membership.export_vocab(src, None)
    membership.import_vocab(codecs, vocab)
    for kind in ("int", "obj"):
        assert codecs[kind].size == src[kind].size
        codes = np.arange(src[kind].size, dtype=np.int32)
        assert codecs[kind].decode(codes) == src[kind].decode(codes)
    # pin truncates the export to pre-attempt sizes
    # (IntKeyCodec orders each novel BATCH by sorted key: 10<50 -> 0,1)
    vocab2 = membership.export_vocab(src, {"int": 2, "obj": 2})
    assert vocab2["int"] == [10, 50] and vocab2["obj"] == ["z", "a"]
    # import into an occupied table is refused
    with pytest.raises(Mp4jError):
        membership.import_vocab(codecs, {"int": [1]})
    # import_keys preserves exact code order (not sorted order)
    ic2 = keycodec.IntKeyCodec()
    ic2.import_keys([50, 10, 5, 99])
    assert ic2.encode([10, 99, 50, 5], 4).tolist() == [1, 3, 0, 2]
    with pytest.raises(Mp4jError):
        ic2.import_keys([1, 2])


# ----------------------------------------------------------------------
# mid-map-sync vocabulary replay (the PR 10 follow-up, closed in
# ISSUE 11)
# ----------------------------------------------------------------------
def test_replace_mid_map_sync_vocab_replay():
    """A rank killed BETWEEN the novelty-up and decision-down legs of
    the job's FIRST map collective: the codec kind was created by the
    in-flight attempt, so it is absent from the donor's pre-attempt
    pin — the manifest must export that kind EMPTY (every survivor's
    retry truncates it to zero), never the attempt's tentative growth.
    Shipping the tentative table instead seeds the joiner with keys no
    survivor re-offers after the rollback: its novelty exchange skips
    them (already encoded locally), the canonical growth never assigns
    them on the survivors, and the job's code tables diverge for good.
    The regression: adoption converges bit-exactly in ONE retry round,
    and a SECOND map mixing old and novel keys — the call diverged
    tables corrupt even when the first looks right — stays bit-exact
    too."""
    def mk(r):
        # per-rank-unique keys: the dead rank's keys exist nowhere
        # else, so a stale joiner vocabulary cannot hide
        return {int(r * 1000 + k): np.float64((r + 1) * (k + 1))
                for k in range(40)}

    def mk2(r, d):
        d2 = {int(5000 + k): np.float64(r + 1) for k in range(20)}
        for kk in list(d)[:5]:
            d2[kk] = np.float64(1.0)
        return d2

    def body(slave, r, sabotage=False):
        d = mk(r)
        if sabotage:
            orig = slave._grow_map_codec
            state = {"fired": False}

            def grow(decision):
                if not state["fired"]:
                    state["fired"] = True
                    # die mid-sync: the novelty went up and the
                    # decision came down (so the DONOR survivor's
                    # codec holds the attempt's full tentative
                    # growth), but no column moved — the worst case
                    # for the manifest export
                    slave._fault_kill(None)
                    raise FaultKill(
                        "fault injection: rank 2 killed mid-map-sync")
                return orig(decision)

            slave._grow_map_codec = grow
        slave.allreduce_map(d, Operands.DOUBLE, Operators.SUM)
        d2 = mk2(r, mk(r))
        slave.allreduce_map(d2, Operands.DOUBLE, Operators.SUM)
        return d, d2

    def fn_clean(slave, r):
        return body(slave, r)

    def fn_faulted(slave, r):
        return body(slave, r, sabotage=(r == 2))

    def spare_fn(sp):
        assert sp.resume_seq == 0, sp.resume_seq
        return body(sp, 2)

    want, werr, _, _, _ = run_elastic(N, fn_clean, shm=False)
    assert all(e is None for e in werr), werr
    got, errors, spares, master, log = run_elastic(
        N, fn_faulted, spare_fns=[spare_fn], shm=False,
        master_kwargs={"elastic": "replace"}, elastic="replace")
    assert isinstance(errors[2], FaultKill), f"{errors}\n{log}"
    survivors = [errors[r] for r in range(N) if r != 2]
    assert all(e is None for e in survivors), \
        f"survivor errors: {errors}\n{log}"
    assert spares[0].get("adopted_rank") == 2, f"{spares}\n{log}"
    assert "error" not in spares[0], f"{spares[0].get('error')}\n{log}"
    for r in range(N):
        for i in range(2):
            assert set(got[r][i]) == set(want[r][i]), (r, i)
            for k in got[r][i]:
                assert got[r][i][k] == want[r][i][k], (r, i, k)
    assert master.final_code == 0, log


# ----------------------------------------------------------------------
# what left with ISSUE 41: one way to change a roster, a rank's death.
# The names that went are spelled in pieces here so that a grep for
# them over the tree stays empty.
# ----------------------------------------------------------------------
_AS = "auto" + "scale"
GONE_MASTER_ARGS = (_AS, _AS + "_cooldown", _AS + "_budget", _AS + "_tick",
                    "provision" + "_hook", "provision" + "_cmd")


def test_elastic_modes_are_off_replace_shrink(monkeypatch):
    assert tuning.ELASTIC_MODES == ("off", "replace", "shrink")
    monkeypatch.setenv("MP4J_ELASTIC", "grow")
    with pytest.raises(Mp4jError) as ei:
        tuning.elastic_mode()
    assert all(m in str(ei.value) for m in ("off", "replace", "shrink"))
    # the slave validates the same value, before it dials anything
    with pytest.raises(Mp4jError, match="MP4J_ELASTIC='grow'"):
        ProcessCommSlave("127.0.0.1", 1, timeout=0.1)


def test_master_rejects_elastic_grow_before_binding():
    with pytest.raises(Mp4jError) as ei:
        Master(2, elastic="grow")
    assert "['off', 'replace', 'shrink']" in str(ei.value)


def test_master_constructor_lost_the_controller_seams():
    import inspect
    params = inspect.signature(Master.__init__).parameters
    assert not set(GONE_MASTER_ARGS) & set(params)
    # what drives a roster change now: the mode, the pool, the deadline
    assert {"elastic", "spares", "adopt_secs"} <= set(params)
    for name in GONE_MASTER_ARGS:
        with pytest.raises(TypeError):
            Master(2, **{name: None})


def test_slave_has_no_roster_growth_boundary():
    assert not hasattr(ProcessCommSlave, "resize" + "_point")
    # the boundaries a schedule can name are the collectives and this
    assert callable(ProcessCommSlave.barrier)
    from ytk_mp4j_tpu.comm import master as master_mod
    assert not hasattr(master_mod, "RESIZE")
    assert not hasattr(Master, "request_" + "planned" + "_evict")


# ----------------------------------------------------------------------
# the replace and shrink rounds, branch by branch, on a scripted
# master: no sockets, no threads of ranks, no sleeps. Each wire records
# what the master pushed; the test plays the ranks' control messages.
# ----------------------------------------------------------------------
class _Wire:
    """A control channel that records every push; ``recv`` blocks
    until ``close`` (a parked spare's serve thread waits there)."""

    def __init__(self):
        self.sent: list = []
        self.closed = False
        self._gate = threading.Event()

    def send_obj(self, obj):
        if self.closed:
            raise OSError("wire closed")
        self.sent.append(obj)

    def recv(self):
        self._gate.wait(JOIN)
        raise OSError("wire closed")

    def close(self, *args, **kwargs):
        self.closed = True
        self._gate.set()

    def pushes(self):
        """The pushes in order, less the health-alert pipe (a DEAD
        verdict or a tuner event rides to the lowest live rank)."""
        return [m for m in self.sent
                if isinstance(m, tuple) and m[0] != "health_alert"]

    def kinds(self):
        return [m[0] for m in self.pushes()]

    def last(self, kind):
        return [m for m in self.sent
                if isinstance(m, tuple) and m[0] == kind][-1]


@pytest.fixture
def scripted():
    from ytk_mp4j_tpu.comm.master import _Slot
    made = []

    def make(n, elastic, spares=0, **kw):
        m = Master(n, elastic=elastic, log_stream=io.StringIO(), **kw)
        made.append(m)
        wires = [_Wire() for _ in range(n)]
        m._slots = [_Slot(r, w) for r, w in enumerate(wires)]
        m._roster = [("h", 9000 + r, "") for r in range(n)]
        pool = []
        for k in range(spares):
            rec = membership.SpareRecord(k, _Wire(), ("s", 9100 + k, ""))
            m._spare_pool.append(rec)
            m._spare_seq += 1
            pool.append(rec)
        return m, wires, pool

    yield make
    for m in made:
        m._stop.set()
        m._release_spares("test over")
        m._server.close()


def _ack(m, ranks, epoch, seq, inflight=True):
    for r in ranks:
        m._handle_abort_ack(r, {"epoch": epoch, "seq": seq,
                                "inflight": inflight})


def _manifest(m, rank, epoch, **extra):
    m._handle_manifest(rank, {"epoch": epoch, "vocab": {"i": [7, 9]},
                              "seq": 5, "inflight": True,
                              "stats_seq": 11, "barrier_gen": 2,
                              **extra})


def test_replace_round_adopts_then_releases_one_epoch(scripted):
    m, wires, (spare,) = scripted(3, "replace", spares=1)
    m._on_rank_dead(1, "connection lost", "rank 1 is dead; aborting")
    # one round, one epoch: survivors torn down, lowest donates
    assert wires[0].kinds() == ["abort", "manifest_req"]
    assert wires[2].kinds() == ["abort"]
    assert wires[1].kinds() == ["abort_fatal"]     # the declared-dead
    assert spare.ch.sent == []                     # nothing before acks
    _ack(m, (0, 2), 1, 5)
    assert spare.ch.sent == []                     # ... nor the manifest
    _manifest(m, 0, 1)
    kind, info = spare.ch.sent[-1]
    assert kind == "adopt"
    assert set(info) == {"rank", "epoch", "roster", "job", "seq",
                         "stats_seq", "barrier_gen", "vocab",
                         "watermark", "why"}
    assert (info["rank"], info["epoch"], info["seq"]) == (1, 1, 4)
    assert (info["stats_seq"], info["barrier_gen"]) == (11, 2)
    assert info["roster"][1] == spare.entry
    assert info["why"] == "connection lost"
    assert "abort_go" not in wires[0].kinds()      # held for the ack
    slot = m._finish_adoption(spare)
    assert slot.rank == 1 and m._slots[1] is slot
    go = wires[0].last("abort_go")
    assert go[1] == 1 and go[2]["replaced"] == [1]
    assert go[2]["roster"][1] == spare.entry
    assert wires[2].last("abort_go") == go
    # the joiner needs no go: its adoption seeded it AT the epoch
    assert spare.ch.kinds() == ["adopt"]
    # the round closed whole
    assert m._abort_since is None and m._round_kind is None
    assert m._round_dead == {} and m._round_adopted == {}
    assert m._departed == {} and m._spare_pool == []
    ms = m.membership_status()
    assert set(ms) == {"mode", "replacements", "shrinks",
                       "spares_available", "spares_total", "badges",
                       "events"}
    assert ms["replacements"] == 1 and ms["badges"] == {
        "1": "REPLACED@e1"}
    assert [e["kind"] for e in ms["events"]] == ["replace"]


def test_death_during_an_open_abort_round_upgrades_it(scripted):
    """A rank dies while a plain abort round is collecting acks: the
    SAME epoch becomes the membership round (no second fan-out), and
    the dead rank's missing ack no longer gates it."""
    m, wires, (spare,) = scripted(3, "replace", spares=1)
    m._handle_abort_req(0, {"epoch": 0, "collective": "allreduce",
                            "error": "reset"})
    assert [w.kinds() for w in wires] == [["abort"]] * 3
    _ack(m, (0,), 1, 5)
    m._mark_departed(2, "unreachable on push")
    assert m._abort_epoch == 1 and m._round_kind == "replace"
    assert wires[0].kinds() == ["abort", "manifest_req"]
    assert wires[1].kinds() == ["abort"]            # not fanned twice
    _ack(m, (1,), 1, 5)
    _manifest(m, 0, 1)
    assert spare.ch.last("adopt")[1]["rank"] == 2
    m._finish_adoption(spare)
    assert wires[1].last("abort_go")[2]["replaced"] == [2]


def test_second_death_mid_round_takes_a_second_spare(scripted):
    m, wires, (s0, s1) = scripted(4, "replace", spares=2)
    m._on_rank_dead(3, "connection lost", "rank 3 is dead")
    _ack(m, (0, 1, 2), 1, 5)
    _manifest(m, 0, 1)
    assert s0.ch.last("adopt")[1]["rank"] == 3
    # rank 2 dies while spare #0 is still seeding rank 3
    m._on_rank_dead(2, "connection lost", "rank 2 is dead")
    assert m._abort_epoch == 1                      # same round
    assert s1.ch.last("adopt")[1]["rank"] == 2
    # each joiner's roster names every adoption assigned so far
    assert s1.ch.last("adopt")[1]["roster"][3] == s0.entry
    m._finish_adoption(s0)
    assert "abort_go" not in wires[0].kinds()       # one still pending
    m._finish_adoption(s1)
    go = wires[0].last("abort_go")
    assert go[2]["replaced"] == [2, 3]
    assert m.membership_status()["replacements"] == 2


def test_donor_death_moves_the_manifest_request(scripted):
    m, wires, _ = scripted(4, "replace", spares=2)
    m._on_rank_dead(3, "connection lost", "rank 3 is dead")
    assert wires[0].kinds() == ["abort", "manifest_req"]
    m._on_rank_dead(0, "connection lost", "rank 0 is dead")
    assert wires[1].kinds() == ["abort", "manifest_req"]
    assert m._round_manifest_from == 1
    # the dead donor's manifest, had it been in flight, is refused
    m._slots[0].dead = True
    _ack(m, (1, 2), 1, 5)
    _manifest(m, 1, 1)
    assert sorted(rec.adopting_rank for rec in m._spare_pool) == [0, 3]


def test_spare_lost_mid_adoption_retries_then_goes_terminal(scripted):
    m, wires, (s0, s1) = scripted(3, "replace", spares=2)
    m._on_rank_dead(1, "connection lost", "rank 1 is dead; aborting")
    _ack(m, (0, 2), 1, 5)
    _manifest(m, 0, 1)
    assert s0.ch.kinds() == ["adopt"] and s1.ch.sent == []
    m._spare_gone(s0, "adoption not acked within 1.0s")
    assert s0.ch.closed and not s0.alive
    assert s1.ch.last("adopt")[1]["rank"] == 1      # next one tried
    assert m._fatal_msg is None
    m._spare_gone(s1, "connection lost")
    # the pool is dry: the same clean fatal as never having had one
    assert m._fatal_msg.startswith("rank 1 is dead; aborting; no warm "
                                   "spare available")
    for w in (wires[0], wires[2]):
        assert w.last("abort_fatal")[1] == m._fatal_msg
    # a late ack of the lost adoption is stale, not a resurrection
    assert m._finish_adoption(s0) is None


def test_replace_with_dry_pool_is_fatal_at_once(scripted):
    m, wires, _ = scripted(3, "replace", spares=0)
    m._on_rank_dead(2, "connection lost", "rank 2 is dead; aborting")
    assert m._fatal_msg == ("rank 2 is dead; aborting; no warm spare "
                            "available to replace rank(s) [2]")
    assert "abort" not in wires[0].kinds()          # no round fanned
    assert wires[0].last("abort_fatal")[1] == m._fatal_msg


def test_spare_registered_mid_round_waits_in_the_pool(scripted):
    """A spare that registers while a round is open is pooled and
    acked, and changes nothing about the round in flight."""
    m, wires, (s0,) = scripted(3, "replace", spares=1)
    m._on_rank_dead(1, "connection lost", "rank 1 is dead")
    _ack(m, (0,), 1, 5)                             # rank 2 still out
    late = _Wire()
    m._register_spare(late, ("s", 9200, ""))
    assert late.sent == [{"spare": 1, "job": m.job_id}]
    assert m.membership_status()["spares_available"] == 2
    assert s0.ch.sent == [] and m._round_adoptions == {}
    _ack(m, (2,), 1, 5)
    _manifest(m, 0, 1)
    assert s0.ch.kinds() == ["adopt"]               # oldest first
    assert late.kinds() == []
    m._finish_adoption(s0)
    assert m.membership_status()["spares_available"] == 1


def test_manifest_is_refused_outside_its_replace_round(scripted):
    m, wires, (spare,) = scripted(3, "replace", spares=1)
    _manifest(m, 0, 0)                              # no round at all
    assert m._round_manifest is None
    m._handle_abort_req(0, {"epoch": 0, "collective": "x", "error": "e"})
    _manifest(m, 0, 1)                              # a plain abort round
    assert m._round_manifest is None
    _ack(m, (0, 1, 2), 1, 5)
    assert wires[1].last("abort_go") == ("abort_go", 1)
    m._on_rank_dead(2, "connection lost", "rank 2 is dead")
    _manifest(m, 0, 1)                              # the LAST epoch's
    assert m._round_manifest is None
    _manifest(m, 0, 2)
    assert m._round_manifest_from == 0 and m._round_manifest["seq"] == 5
    # and a shrink master never takes one
    ms, _, _ = scripted(3, "shrink")
    ms._on_rank_dead(2, "connection lost", "rank 2 is dead")
    _manifest(ms, 0, 1)
    assert ms._round_manifest is None


def test_replace_round_spanning_a_collective_boundary_is_fatal(scripted):
    m, wires, (spare,) = scripted(3, "replace", spares=1)
    m._on_rank_dead(1, "connection lost", "rank 1 is dead")
    m._handle_abort_ack(0, {"epoch": 1, "seq": 5, "inflight": True})
    m._handle_abort_ack(2, {"epoch": 1, "seq": 7, "inflight": True})
    assert "spans a collective boundary" in m._fatal_msg
    assert spare.ch.kinds() == ["release"]          # never adopted
    assert spare.ch.closed


def test_shrink_renumbers_and_releases_a_filled_barrier(scripted):
    m, wires, _ = scripted(4, "shrink")
    for r in (0, 2, 3):                             # rank 1 never came
        m._barrier(m._slots[r], 0)
    assert "barrier_release" not in wires[0].kinds()
    m._on_rank_dead(1, "connection lost", "rank 1 is dead")
    _ack(m, (0, 2), 1, 5, inflight=False)
    assert m.slave_num == 4                         # one ack missing
    _ack(m, (3,), 1, 5, inflight=False)
    assert m.slave_num == 3 and len(m._slots) == 3
    assert [s.rank for s in m._slots] == [0, 1, 2]
    assert m._slots[1].ch is wires[2] and m._slots[2].ch is wires[3]
    go = wires[3].last("abort_go")
    assert go[2]["shrink"]["ranks"] == {0: 0, 2: 1, 3: 2}
    assert go[2]["shrink"]["departed"] == [1]
    assert len(go[2]["shrink"]["roster"]) == 3
    # the generation only the dead rank was missing from goes with it
    for w in (wires[0], wires[2], wires[3]):
        assert w.kinds()[-2:] == ["abort_go", "barrier_release"]
    assert m._barrier_waiting == {} and m._barrier_max_released == 0
    assert m._departed == {} and m._round_kind is None
    assert m.membership_status()["badges"] == {
        "1": "SHRUNK 2->1@e1", "2": "SHRUNK 3->2@e1"}


def test_shrink_down_to_the_last_rank_and_past_it(scripted):
    m, wires, _ = scripted(2, "shrink")
    m._on_rank_dead(0, "connection lost", "rank 0 is dead")
    _ack(m, (1,), 1, 3, inflight=False)
    assert m.slave_num == 1 and m._slots[0].ch is wires[1]
    assert wires[1].last("abort_go")[2]["shrink"]["ranks"] == {1: 0}
    # the survivor goes too: nothing is left to continue
    m._on_rank_dead(0, "connection lost", "rank 0 is dead; aborting")
    assert m._fatal_msg == ("rank 0 is dead; aborting; no surviving "
                            "rank left")


def test_declared_dead_ranks_channel_error_kills_nobody(scripted):
    """After a shrink the dead rank's old slot may carry a survivor's
    number; its serve thread's late error must not declare THAT rank."""
    m, wires, _ = scripted(3, "shrink")
    zombie = m._slots[1]
    m._on_rank_dead(1, "barrier gen 0 stalled", "rank 1 is dead")
    _ack(m, (0, 2), 1, 5, inflight=False)
    assert m.slave_num == 2 and zombie.dead and zombie.rank == 1
    zombie.ch.close()
    m._serve_slave(zombie)          # recv raises at once; returns
    assert m._departed == {} and m._abort_since is None
    assert m._fatal_msg is None and m.slave_num == 2
    assert "declared-dead rank's channel closed" in (
        m.log_stream.getvalue())


def test_tuner_fence_takes_acks_only_and_yields_to_a_death(scripted):
    m, wires, _ = scripted(3, "shrink")
    assert m.request_tuner_leaders({0: 1})
    token = wires[0].last("fence")[1]
    assert [w.kinds() for w in wires] == [["fence"]] * 3
    assert not m.request_tuner_leaders({0: 2})      # one at a time
    m._handle_fence_ack(0, {"token": token, "seq": 4})
    m._handle_fence_ack(1, {"token": token + 1, "seq": 4})   # stale
    m._barrier(m._slots[2], 0)      # idling in a barrier is no ack
    assert m._tuner_fence is not None
    assert m._tuner_fence["acks"] == {0: 4}
    m._on_rank_dead(2, "connection lost", "rank 2 is dead")
    assert m._tuner_fence is None
    assert wires[0].kinds() == ["fence", "abort", "fence_release"]
    assert not m.request_tuner_leaders({0: 1})      # a round is open
    _ack(m, (0, 1), 1, 4, inflight=False)
    # the round over, the update goes through: equal acks complete it
    assert m.request_tuner_leaders({0: 1})
    token = wires[0].last("fence")[1]
    for r in (0, 1):
        m._handle_fence_ack(r, {"token": token, "seq": 4})
    for w in (wires[0], wires[1]):
        assert w.pushes()[-2:] == [("tuner_leaders", {0: 1}),
                                   ("fence_release", token)]
    assert m.tuner_status()["overrides"] == {0: 1}
