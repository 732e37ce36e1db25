"""mp4j-serve (ISSUE 19): hot-key cache accounting, micro-batcher
deadline semantics, request framing, and the bit-exact sharded-serve
grid — 4 model families x {tcp, shm} x n in {2, 4} — plus the
slow-rank deadline story and the serve observability surfaces."""

import threading
import time

import numpy as np
import pytest

from helpers import run_slaves
from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.models import fm as fm_mod
from ytk_mp4j_tpu.models import gbdt as gbdt_mod
from ytk_mp4j_tpu.models import linear as linear_mod
from ytk_mp4j_tpu.models.fm import FMConfig
from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer
from ytk_mp4j_tpu.models.linear import LinearConfig
from ytk_mp4j_tpu.serve import framing
from ytk_mp4j_tpu.serve.batcher import MicroBatcher, ServeFuture
from ytk_mp4j_tpu.serve.cache import HotKeyCache, validate_version
from ytk_mp4j_tpu.serve.dispatcher import ServeFrontend, serve_worker
from ytk_mp4j_tpu.utils import tuning


# ----------------------------------------------------------------------
# hot-key cache: analytic accounting
# ----------------------------------------------------------------------
def test_cache_hit_miss_eviction_accounting():
    c = HotKeyCache(capacity_rows=2, stale_versions=0)
    r = np.ones(3)
    assert c.lookup(1, 0) is None            # miss
    c.insert(1, r, 0)
    assert c.lookup(1, 0) is r               # hit
    c.insert(2, r, 0)
    c.insert(3, r, 0)                        # evicts LRU id=1
    assert c.evictions == 1
    assert c.lookup(1, 0) is None            # miss (evicted)
    assert c.lookup(3, 0) is r
    s = c.stats()
    assert (s["hits"], s["misses"], s["evictions"]) == (2, 2, 1)
    assert s["rows"] == 2
    assert s["hit_rate"] == pytest.approx(0.5)


def test_cache_lru_order_follows_lookups():
    c = HotKeyCache(capacity_rows=2, stale_versions=0)
    r = np.ones(1)
    c.insert(1, r, 0)
    c.insert(2, r, 0)
    c.lookup(1, 0)                           # 1 becomes most recent
    c.insert(3, r, 0)                        # evicts 2, not 1
    assert c.lookup(1, 0) is not None
    assert c.lookup(2, 0) is None


def test_cache_staleness_bound_counts_stale_and_miss():
    c = HotKeyCache(capacity_rows=8, stale_versions=1)
    r = np.ones(1)
    c.insert(5, r, 0)
    assert c.lookup(5, 1) is r               # within the bound
    assert c.lookup(5, 2) is None            # 2 bumps behind: stale
    s = c.stats()
    assert s["stale"] == 1
    # the stale drop is ALSO a miss: staleness explains the miss, it
    # does not replace it
    assert s["misses"] == 1 and s["hits"] == 1
    assert len(c) == 0                       # stale row was dropped


def test_cache_capacity_zero_disables():
    c = HotKeyCache(capacity_rows=0)
    c.insert(1, np.ones(1), 0)
    assert len(c) == 0 and c.lookup(1, 0) is None


def test_version_validation():
    assert validate_version(3) == 3
    with pytest.raises(Mp4jError):
        validate_version(-1)


def test_serve_knob_validation():
    with pytest.raises(Mp4jError):
        tuning.serve_deadline_ms(0.0)
    with pytest.raises(Mp4jError):
        tuning.serve_max_batch(0)
    with pytest.raises(Mp4jError):
        tuning.serve_cache_rows(-1)


# ----------------------------------------------------------------------
# micro-batcher: deadline / full / drain semantics
# ----------------------------------------------------------------------
def test_batcher_full_batch_dispatches_immediately():
    seen = []
    b = MicroBatcher(lambda reqs: [r * 10 for r in seen.append(list(reqs))
                                   or reqs],
                     deadline_ms=10_000.0, max_batch=4)
    try:
        futs = [b.submit(i) for i in range(4)]
        t0 = time.monotonic()
        out = [f.wait(5.0) for f in futs]
        # a FULL batch must not wait the 10s deadline out
        assert time.monotonic() - t0 < 5.0
        assert out == [0, 10, 20, 30]
        assert seen == [[0, 1, 2, 3]]
        assert b.batch_full == 1 and b.batch_deadline == 0
    finally:
        b.close()


def test_batcher_deadline_bounds_oldest_wait():
    waits = []
    b = MicroBatcher(lambda reqs: reqs, deadline_ms=20.0, max_batch=64,
                     on_batch=lambda n, reason, w: waits.append(
                         (reason, w)))
    try:
        fut = b.submit("only")
        assert fut.wait(5.0) == "only"
        (reason, wait_secs), = waits
        assert reason == "deadline"
        # the oldest request's accumulation wait honored the deadline
        # (generous slack: shared CI hosts wake late, never early)
        assert 0.015 <= wait_secs < 1.0
        assert b.batch_deadline == 1
    finally:
        b.close()


def test_batcher_close_drains_and_rejects():
    b = MicroBatcher(lambda reqs: reqs, deadline_ms=60_000.0,
                     max_batch=64)
    futs = [b.submit(i) for i in range(3)]
    b.close()                                # drain, not discard
    assert [f.wait(1.0) for f in futs] == [0, 1, 2]
    with pytest.raises(Mp4jError):
        b.submit("late")
    b.close()                                # idempotent


def test_batcher_dispatch_failure_fans_out_and_plane_survives():
    state = {"boom": True}

    def dispatch(reqs):
        if state["boom"]:
            raise RuntimeError("poisoned batch")
        return reqs

    b = MicroBatcher(dispatch, deadline_ms=5.0, max_batch=64)
    try:
        bad = b.submit("a")
        with pytest.raises(RuntimeError):
            bad.wait(5.0)
        state["boom"] = False
        assert b.submit("b").wait(5.0) == "b"   # plane still serving
    finally:
        b.close()


def test_batcher_result_count_mismatch_fails_futures():
    b = MicroBatcher(lambda reqs: [], deadline_ms=5.0, max_batch=64)
    try:
        with pytest.raises(Mp4jError, match="0 results"):
            b.submit("x").wait(5.0)
    finally:
        b.close()


def test_future_timeout_does_not_consume():
    fut = ServeFuture()
    with pytest.raises(Mp4jError):
        fut.wait(0.01)
    fut._resolve(7)
    assert fut.wait(0.01) == 7


# ----------------------------------------------------------------------
# framing round-trips
# ----------------------------------------------------------------------
def test_frame_request_roundtrip_pull_family():
    ids = np.asarray([3, 1, 4], np.int64)
    fields = np.asarray([0, 1, 0], np.int32)
    vals = np.asarray([1.0, 0.5, 0.0], np.float32)
    buf = framing.encode_request("ffm", 42, ids, fields, vals)
    family, req_id, i2, f2, v2 = framing.decode_request(buf)
    assert (family, req_id) == ("ffm", 42)
    np.testing.assert_array_equal(i2, ids)
    np.testing.assert_array_equal(f2, fields)
    np.testing.assert_array_equal(v2, vals)


def test_frame_request_roundtrip_gbdt_bins_only():
    bins = np.asarray([7, 0, 255, 3], np.int64)
    buf = framing.encode_request("gbdt", 1, bins)
    family, req_id, i2, f2, v2 = framing.decode_request(buf)
    assert family == "gbdt" and req_id == 1
    np.testing.assert_array_equal(i2, bins)
    assert not f2.any() and not v2.any()     # unused lanes ride zero


def test_frame_response_roundtrip_and_status():
    preds = np.asarray([0.25, 0.75], np.float64)
    buf = framing.encode_response(9, preds,
                                  status=framing.STATUS_DEGRADED)
    req_id, p2, status = framing.decode_response(buf)
    assert req_id == 9 and status == framing.STATUS_DEGRADED
    np.testing.assert_array_equal(p2, preds)


def test_frame_rejects_garbage():
    with pytest.raises(Mp4jError):
        framing.decode_request(b"not a frame at all....")
    with pytest.raises(Mp4jError):
        framing.encode_request("nope", 1, np.zeros(1, np.int64))


# ----------------------------------------------------------------------
# the bit-exact sharded grid: 4 families x {tcp, shm} x n in {2, 4}
# ----------------------------------------------------------------------
_RNG = np.random.default_rng(7)


def _linear_servable():
    cfg = LinearConfig(n_features=24, loss="logistic")
    w = _RNG.standard_normal(24).astype(np.float32)
    b = np.float32(0.3)
    return linear_mod.servable((w, b), cfg)


def _fm_servable():
    cfg = FMConfig(n_features=24, k=4, max_nnz=6, model="fm",
                   loss="logistic")
    w0 = np.float32(0.1)
    w = _RNG.standard_normal(24).astype(np.float32)
    V = (0.1 * _RNG.standard_normal((24, 4))).astype(np.float32)
    return fm_mod.servable((w0, w, V), cfg)


def _ffm_servable():
    cfg = FMConfig(n_features=24, n_fields=3, k=4, max_nnz=6,
                   model="ffm", loss="logistic")
    w0 = np.float32(-0.2)
    w = _RNG.standard_normal(24).astype(np.float32)
    V = (0.1 * _RNG.standard_normal((24 * 3, 4))).astype(np.float32)
    return fm_mod.servable((w0, w, V), cfg)


_GBDT = {}


def _gbdt_servable():
    # train ONCE per session (jit compile dominates); tiny ensemble
    if "s" not in _GBDT:
        from ytk_mp4j_tpu.parallel import make_mesh
        cfg = GBDTConfig(n_features=5, n_bins=8, depth=2, n_trees=4,
                         loss="logistic", hist_mode="flat")
        rng = np.random.default_rng(3)
        bins = rng.integers(0, 8, (64, 5)).astype(np.int8)
        y = (bins[:, 0] > 3).astype(np.float32)
        trees, _ = GBDTTrainer(cfg, mesh=make_mesh(1)).train(bins, y)
        _GBDT["s"] = gbdt_mod.servable(trees, cfg)
    return _GBDT["s"]


_FAMILIES = {
    "linear": _linear_servable,
    "fm": _fm_servable,
    "ffm": _ffm_servable,
    "gbdt": _gbdt_servable,
}


def _requests(servable, n_reqs=10):
    """Deterministic request set; every family sees repeated hot ids
    (cache hits) plus tail ids."""
    rng = np.random.default_rng(11)
    reqs = []
    if servable.kind == "reduce":
        for _ in range(n_reqs):
            reqs.append(rng.integers(
                0, servable.cfg.n_bins,
                servable.req_width).astype(np.int64))
        return reqs
    nnz = servable.cfg.max_nnz if hasattr(servable.cfg, "max_nnz") \
        else 6
    nf = getattr(servable.cfg, "n_fields", 1)
    for _ in range(n_reqs):
        ids = rng.choice(servable.n_rows, size=nnz, replace=False)
        fields = (np.arange(nnz) % nf).astype(np.int32)
        vals = rng.standard_normal(nnz).astype(np.float32)
        vals[rng.random(nnz) < 0.2] = 0.0    # padded slots
        reqs.append((ids.astype(np.int64), fields, vals))
    return reqs


def _reference(servable, reqs):
    """Single-process per-example scoring — the sequential oracle the
    batched sharded path must match BITWISE."""
    if servable.kind == "pull":
        all_ids = np.arange(servable.n_rows, dtype=np.int64)
        mat = servable.rows(all_ids)
        rowmap = {int(i): mat[j] for j, i in enumerate(all_ids)}
        return servable.predict_sharded(reqs, rowmap)
    bins = np.stack(reqs)
    return servable.link(servable.partial_margins(bins, 0, 1))


@pytest.mark.parametrize("family", sorted(_FAMILIES))
@pytest.mark.parametrize("shm", [False, True],
                         ids=["tcp", "shm"])
@pytest.mark.parametrize("n", [2, 4])
def test_sharded_serve_bit_exact_grid(family, shm, n):
    servable = _FAMILIES[family]()
    reqs = _requests(servable)
    want = _reference(servable, reqs)

    def fn(slave, rank):
        if rank != 0:
            return serve_worker(slave, servable, max_batch=8)
        fe = ServeFrontend(slave, servable, deadline_ms=50.0,
                           max_batch=8)
        try:
            futs = [fe.submit(r) for r in reqs]
            return [f.wait(30.0) for f in futs]
        finally:
            fe.close()

    results = run_slaves(n, fn, shm=shm)
    got = results[0]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        # bitwise, not allclose: per-example scoring in fixed op order
        # makes batched == sequential exact by construction
        np.testing.assert_array_equal(g, w)
    for r in range(1, n):
        assert results[r]["rounds"] >= 1


def test_sequential_equals_batched_single_rank():
    """max_batch=1 (pure sequential) and max_batch=8 (batched) produce
    bitwise-identical predictions — the ISSUE's headline contract."""
    servable = _FAMILIES["fm"]()
    reqs = _requests(servable)

    def serve_all(max_batch):
        def fn(slave, rank):
            fe = ServeFrontend(slave, servable, deadline_ms=5.0,
                               max_batch=max_batch)
            try:
                return [fe.predict(r, timeout=30.0) for r in reqs]
            finally:
                fe.close()
        return run_slaves(1, fn)[0]

    seq = serve_all(1)
    bat = serve_all(8)
    for a, b in zip(seq, bat):
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------------------
# cache accounting over the live pull plane
# ----------------------------------------------------------------------
def test_warm_cache_serves_with_zero_collectives():
    servable = _FAMILIES["ffm"]()
    reqs = _requests(servable, n_reqs=6)

    def fn(slave, rank):
        if rank != 0:
            return serve_worker(slave, servable)
        fe = ServeFrontend(slave, servable, deadline_ms=5.0,
                           max_batch=4)
        try:
            cold = [fe.predict(r, timeout=30.0) for r in reqs]
            stats_cold = dict(fe.cache_stats())
            warm = [fe.predict(r, timeout=30.0) for r in reqs]
            stats_warm = dict(fe.cache_stats())
            return cold, warm, stats_cold, stats_warm
        finally:
            fe.close()

    results = run_slaves(2, fn)
    cold, warm, stats_cold, stats_warm = results[0]
    worker = results[1]
    for a, b in zip(cold, warm):
        np.testing.assert_array_equal(a, b)
    # the warm pass touched only cached rows: zero new misses, so the
    # worker saw no pull rounds beyond the cold pass
    assert stats_warm["misses"] == stats_cold["misses"]
    assert stats_warm["hits"] > stats_cold["hits"]
    assert worker["pull_ids"] == stats_cold["misses"]


def test_version_bump_invalidates_cache():
    servable = _FAMILIES["linear"]()
    req = _requests(servable, n_reqs=1)[0]

    def fn(slave, rank):
        fe = ServeFrontend(slave, servable, deadline_ms=5.0,
                           max_batch=4, stale_versions=0)
        try:
            fe.predict(req, timeout=30.0)
            fe.predict(req, timeout=30.0)          # warm hit
            fe.bump_version()
            fe.predict(req, timeout=30.0)          # stale -> re-pull
            return dict(fe.cache_stats())
        finally:
            fe.close()

    stats = run_slaves(1, fn)[0]
    assert stats["stale"] >= 1
    assert stats["hits"] >= 1


# ----------------------------------------------------------------------
# deadline honored under a slow-rank fault
# ----------------------------------------------------------------------
def test_deadline_honored_under_slow_rank():
    """A persistently slow worker cannot stretch the batcher's
    accumulation wait: batches keep dispatching at the deadline and
    every request completes (the slow collective costs latency
    DOWNSTREAM of the batcher, never an unbounded queue)."""
    servable = _FAMILIES["gbdt"]()
    reqs = _requests(servable, n_reqs=6)
    waits = []

    def fn(slave, rank):
        if rank != 0:
            return serve_worker(slave, servable, max_batch=4)
        fe = ServeFrontend(slave, servable, deadline_ms=2.0,
                           max_batch=4)
        fe._batcher._on_batch = lambda n, reason, w: (
            waits.append(w), fe._note_batch(n, reason, w))
        try:
            return [fe.predict(r, timeout=30.0) for r in reqs]
        finally:
            fe.close()

    results = run_slaves(
        2, fn, fault_plan="slow:rank=1:secs=0.01")
    want = _reference(servable, reqs)
    for g, w in zip(results[0], want):
        np.testing.assert_array_equal(g, w)
    # accumulation waits stayed near the 2ms deadline even though each
    # dispatch round was an order of magnitude slower than that
    assert waits and max(waits) < 1.0


# ----------------------------------------------------------------------
# serve metrics + observability surfaces
# ----------------------------------------------------------------------
def test_serve_metrics_and_master_serve_status():
    servable = _FAMILIES["fm"]()
    reqs = _requests(servable, n_reqs=8)

    def fn(slave, rank):
        if rank != 0:
            return serve_worker(slave, servable)
        fe = ServeFrontend(slave, servable, deadline_ms=5.0,
                           max_batch=4)
        try:
            futs = [fe.submit(r) for r in reqs]
            [f.wait(30.0) for f in futs]
        finally:
            fe.close()
        return slave.metrics_registry().snapshot()

    snap = run_slaves(2, fn)[0]
    counters = snap["counters"]
    assert counters["serve/requests"] == len(reqs)
    assert counters["serve/batches"] >= 2
    assert counters["serve/cache_misses"] >= 1
    h = snap["histograms"]["latency/serve_request"]
    assert h["count"] == len(reqs)
    assert snap["gauges"]["serve/qps"] > 0.0


def test_serve_section_and_live_headline_render():
    from ytk_mp4j_tpu.comm.master import _serve_section
    from ytk_mp4j_tpu.obs.telemetry import format_fleet, format_live

    ranks = {"0": {"counters": {
        "serve/requests": 100, "serve/batches": 20,
        "serve/batch_deadline": 5, "serve/batch_full": 15,
        "serve/cache_hits": 80, "serve/cache_misses": 20,
        "serve/cache_stale": 2, "serve/degraded_batches": 1,
    }, "gauges": {"serve/qps": 42.5}}}
    sec = _serve_section(ranks, {})
    assert sec["active"] and sec["qps"] == pytest.approx(42.5)
    assert sec["requests"] == 100
    assert sec["hit_rate"] == pytest.approx(0.8)
    assert sec["degraded_batches"] == 1

    doc = {"job_id": "j", "slave_num": 1, "window_secs": 5.0,
           "ranks": {}, "cluster": {"rates": {}, "stats": {},
                                    "serve": sec}}
    live = format_live(doc)
    assert "serve: 42.5 QPS" in live
    assert "80% hit" in live and "1 DEGRADED" in live

    # a training job's doc (no serve section) renders no serve line
    doc2 = {"job_id": "j", "slave_num": 1, "window_secs": 5.0,
            "ranks": {}, "cluster": {"rates": {}, "stats": {}}}
    assert "serve:" not in format_live(doc2)

    # fleet: a serve job carries a QPS cell, a batch job shows "-"
    summary = {"job_id": "sj", "slave_num": 2, "ranks_reporting": 2,
               "bytes_per_sec": 0.0, "collectives_per_sec": 0.0,
               "keys_per_sec": 0.0, "wire_bytes": 0, "retries": 0,
               "hosts": {}, "health": {"states": {}}, "roster_gen": 0,
               "serve": sec}
    batch = dict(summary, job_id="bj", serve=None)
    model = {"aggregate": {"jobs": 2, "live": 2, "ranks": 4},
             "jobs": {"a": {"state": "LIVE", "age": 0.0, "url": "u1",
                            "summary": summary},
                      "b": {"state": "LIVE", "age": 0.0, "url": "u2",
                            "summary": batch}},
             "hosts": {}, "shared_hosts": [], "contention": []}
    out = format_fleet(model)
    line_serve = next(ln for ln in out.splitlines() if "sj" in ln)
    line_batch = next(ln for ln in out.splitlines() if "bj" in ln)
    assert "42.5" in line_serve
    assert "42.5" not in line_batch


def test_job_summary_carries_serve_section():
    from ytk_mp4j_tpu.obs.fleet import job_summary
    doc = {"job_id": "x", "slave_num": 1, "roster_gen": 0,
           "ranks": {}, "cluster": {
               "rates": {}, "serve": {"active": True, "qps": 7.0}}}
    s = job_summary(doc)
    assert s["serve"]["qps"] == pytest.approx(7.0)
    doc["cluster"].pop("serve")
    assert job_summary(doc)["serve"] is None


def test_frontend_requires_rank_zero():
    servable = _FAMILIES["linear"]()

    def fn(slave, rank):
        if rank == 0:
            fe = ServeFrontend(slave, servable, deadline_ms=5.0)
            try:
                fe.predict(_requests(servable, 1)[0], timeout=30.0)
            finally:
                fe.close()
            return "frontend"
        with pytest.raises(Mp4jError, match="rank 0"):
            ServeFrontend(slave, servable)
        return serve_worker(slave, servable)

    run_slaves(2, fn)
