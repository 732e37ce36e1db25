"""Live metrics plane + flight recorder (ISSUE 6).

Property tests for the log2-bucket histogram math (bucket placement
invariants over seeded sweeps including exact edges; quantile
estimates vs ``numpy.percentile``'s nearest-rank order statistic,
exact to one bucket by construction), the delta/fold algebra the
heartbeat rides on, the rate windows, the Prometheus renderer's line
grammar, the master's live HTTP endpoint during a real 4-rank socket
workload (acceptance criterion), the postmortem chaos case (a killed
rank leaves complete bundles on every survivor and the merged report
names the dead rank), and the new knob validation.
"""

import io
import json
import math
import os
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from test_resilience import run_chaos

from ytk_mp4j_tpu.comm.master import Master
from ytk_mp4j_tpu.comm.process_comm import ProcessCommSlave
from ytk_mp4j_tpu.exceptions import Mp4jError, Mp4jFatalError
from ytk_mp4j_tpu.obs import metrics, postmortem, telemetry
from ytk_mp4j_tpu.obs.cli import main as scope_main
from ytk_mp4j_tpu.operands import Operands
from ytk_mp4j_tpu.operators import Operators
from ytk_mp4j_tpu.resilience.faults import FaultKill
from ytk_mp4j_tpu.utils import stats as stats_mod
from ytk_mp4j_tpu.utils import tuning


# ----------------------------------------------------------------------
# histogram bucket math — property sweeps
# ----------------------------------------------------------------------
def _check_bucket_invariant(v, lo, n):
    """The defining property: bucket 0 holds v <= lo, bucket i holds
    (lo*2**(i-1), lo*2**i], bucket n holds the overflow."""
    idx = metrics.bucket_index(v, lo, n)
    assert 0 <= idx <= n
    if idx == 0:
        assert v <= lo
    elif idx < n:
        assert lo * 2.0 ** (idx - 1) < v <= lo * 2.0 ** idx
    else:
        assert v > lo * 2.0 ** (n - 1)
    return idx


@pytest.mark.parametrize("lo,n", [(1e-6, 36), (64.0, 27), (0.5, 8)])
def test_bucket_index_property_sweep(lo, n):
    rng = np.random.default_rng(7)
    # log-uniform sweep across (and past) the whole layout, plus the
    # exact power-of-two edges and their float neighbours — the values
    # where a naive log2 rounds the wrong way
    vals = list(np.exp(rng.uniform(np.log(lo / 8),
                                   np.log(lo * 2.0 ** (n + 2)), 4000)))
    for i in range(n):
        edge = lo * 2.0 ** i
        vals += [edge, np.nextafter(edge, 0), np.nextafter(edge, np.inf)]
    for v in vals:
        _check_bucket_invariant(float(v), lo, n)


def test_bucket_edges_layout():
    edges = metrics.bucket_edges(0.5, 4)
    assert edges == [0.5, 1.0, 2.0, 4.0]
    # exact-edge placement: an observation AT an edge belongs to the
    # bucket the edge closes (le semantics, like Prometheus)
    assert metrics.bucket_index(1.0, 0.5, 4) == 1
    assert metrics.bucket_index(4.0, 0.5, 4) == 3
    assert metrics.bucket_index(4.000001, 0.5, 4) == 4     # overflow


@pytest.mark.parametrize("dist", ["lognormal", "uniform", "exponential"])
@pytest.mark.parametrize("q", [0.0, 0.5, 0.9, 0.95, 0.99, 1.0])
def test_quantile_estimate_vs_numpy_within_bucket(dist, q):
    """hist_quantile returns the UPPER edge of the bucket holding the
    nearest-rank order statistic — so against numpy's inverted-CDF
    percentile the estimate is exact to one log2 bucket: true <= est
    and (below the overflow bucket) true > est/2."""
    lo, n = 1e-6, 36
    rng = np.random.default_rng(hash((dist, q)) % 2 ** 32)
    vals = {"lognormal": rng.lognormal(-7.0, 2.0, 3000),
            "uniform": rng.uniform(5e-7, 0.25, 3000),
            "exponential": rng.exponential(0.003, 3000)}[dist]
    reg = metrics.MetricsRegistry(enabled=True)
    for v in vals:
        reg.observe("latency/x", float(v), lo, n)
    h = reg.snapshot()["histograms"]["latency/x"]
    est = metrics.hist_quantile(h, q)
    true = float(np.percentile(vals, q * 100, method="inverted_cdf"))
    idx = metrics.bucket_index(true, lo, n)
    if idx >= n:
        assert est == math.inf
    else:
        assert est == (lo * 2.0 ** idx if idx else lo)
        assert true <= est
        if idx > 0:
            assert true > est / 2.0
    assert h["count"] == len(vals)
    assert h["sum"] == pytest.approx(float(np.sum(vals)), rel=1e-9)


def test_quantile_empty_and_overflow():
    assert metrics.hist_quantile(metrics._new_hist(1.0, 4), 0.5) == 0.0
    reg = metrics.MetricsRegistry(enabled=True)
    reg.observe("h", 1e9, 1.0, 4)           # everything overflows
    h = reg.snapshot()["histograms"]["h"]
    assert metrics.hist_quantile(h, 0.5) == math.inf


def test_registry_disabled_is_noop():
    reg = metrics.MetricsRegistry(enabled=False)
    reg.inc("c")
    reg.observe("h", 1.0, 1.0, 4)
    reg.set_gauge("g", 3.0)
    snap = reg.snapshot()
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}


# ----------------------------------------------------------------------
# delta / fold algebra (the heartbeat payload contract)
# ----------------------------------------------------------------------
def _random_registry(rng, families):
    reg = metrics.MetricsRegistry(enabled=True)
    for fam in families:
        for v in rng.lognormal(-6, 2, int(rng.integers(1, 50))):
            reg.observe(f"latency/{fam}", float(v),
                        metrics.LATENCY_LO, metrics.LATENCY_BUCKETS)
    reg.inc("events", int(rng.integers(1, 9)))
    return reg


def test_metrics_diff_fold_roundtrip():
    """fold(agg, diff(cur, prev)) == cur for every counter and bucket:
    the master's rolling view is exact, not approximate."""
    rng = np.random.default_rng(3)
    reg = _random_registry(rng, ["allreduce_array"])
    prev = reg.snapshot()
    for v in rng.lognormal(-6, 2, 40):
        reg.observe("latency/broadcast_array", float(v),
                    metrics.LATENCY_LO, metrics.LATENCY_BUCKETS)
    reg.inc("events", 5)
    cur = reg.snapshot()
    delta = metrics.diff_snapshot(cur, prev)
    folded = metrics.fold_snapshot(prev, delta)
    assert folded["counters"] == cur["counters"]
    for k, h in cur["histograms"].items():
        f = folded["histograms"][k]
        assert f["counts"] == h["counts"] and f["count"] == h["count"]
        assert f["sum"] == pytest.approx(h["sum"])


def test_metrics_diff_prunes_quiet_families():
    """The boundedness satellite: a family with no new observations
    ships NOTHING, so a long job's heartbeat is bounded by activity
    since the last beat, not by every family ever seen."""
    rng = np.random.default_rng(4)
    reg = _random_registry(rng, ["a", "b", "c"])
    prev = reg.snapshot()
    reg.observe("latency/b", 0.001,
                metrics.LATENCY_LO, metrics.LATENCY_BUCKETS)
    delta = metrics.diff_snapshot(reg.snapshot(), prev)
    assert set(delta["histograms"]) == {"latency/b"}
    assert delta["counters"] == {}
    assert delta["histograms"]["latency/b"]["count"] == 1


def test_stats_diff_snapshots_roundtrip_and_pruning():
    prev = {"allreduce_array": {"calls": 3, "bytes_sent": 100.0},
            "barrier": {"calls": 2}}
    cur = {"allreduce_array": {"calls": 5, "bytes_sent": 260.0},
           "barrier": {"calls": 2},
           "gather_map": {"calls": 1, "keys": 40}}
    delta = stats_mod.diff_snapshots(cur, prev)
    assert set(delta) == {"allreduce_array", "gather_map"}  # barrier quiet
    merged = stats_mod.merge_snapshots(prev, delta)
    # merge zero-fills the full counter schema; the recorded keys must
    # round-trip exactly (stats are monotone accumulators)
    for fam, entry in cur.items():
        for k, v in entry.items():
            assert merged[fam][k] == v, (fam, k)


def test_rate_window_sliding_derivative():
    win = metrics.RateWindow(window_secs=10.0)
    assert win.rates() == {}
    win.note(0.0, {"bytes": 0.0})
    assert win.rates() == {"bytes_per_sec": 0.0}    # one point: no rate
    win.note(2.0, {"bytes": 20.0})
    win.note(4.0, {"bytes": 100.0})
    assert win.rates()["bytes_per_sec"] == pytest.approx(25.0)  # 100/4s
    # points older than the window fall off: the rate tracks the
    # recent slope, not the lifetime average
    win.note(100.0, {"bytes": 100.0})
    win.note(102.0, {"bytes": 300.0})
    assert win.rates()["bytes_per_sec"] == pytest.approx(100.0)


def test_rate_window_coalesces_fast_notes_to_span_full_window():
    """Notes arriving much faster than window/(maxlen/2) — the master's
    cluster ring gets one per heartbeat PER RANK — coalesce instead of
    evicting old points, so the deque still spans the whole window."""
    win = metrics.RateWindow(window_secs=60.0, maxlen=512)
    t = 0.0
    # 256 ranks' worth of beats: 20000 notes over 40 s
    for i in range(20000):
        t = i * 0.002
        win.note(t, {"bytes": float(i)})
    assert len(win._points) <= 512
    t0, first = win._points[0]
    t1, last = win._points[-1]
    assert t1 - t0 == pytest.approx(t, rel=0.02)    # spans the run
    assert win.rates()["bytes_per_sec"] == pytest.approx(500.0, rel=0.05)


# ----------------------------------------------------------------------
# Prometheus renderer — line grammar + histogram consistency
# ----------------------------------------------------------------------
_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"            # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""  # first label
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"
    r" (\+Inf|-?[0-9.e+-]+)$")


def _validate_prometheus(text):
    """Format 0.0.4 gate: every non-comment line is name{labels} value;
    each metric family forms ONE contiguous block (promtool rejects a
    family reappearing after another metric); histogram buckets are
    cumulative and end at the _count."""
    hists: dict = {}
    seen_families: list = []
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert re.match(r"^# (TYPE|HELP) ", line)
            continue
        fam = re.sub(r"_(bucket|sum|count)(\{| )", r"\2",
                     line).split("{")[0].split(" ")[0]
        if not seen_families or seen_families[-1] != fam:
            assert fam not in seen_families, \
                f"family {fam!r} split across blocks"
            seen_families.append(fam)
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
        m = re.match(r"^(\w+)_bucket\{(.*)\} (\d+)$", line)
        if m:
            series = (m.group(1),
                      re.sub(r',?le="[^"]*"', "", m.group(2)))
            prev = hists.setdefault(series, [])
            if prev:
                assert int(m.group(3)) >= prev[-1], \
                    f"buckets not cumulative: {line!r}"
            prev.append(int(m.group(3)))
        m = re.match(r"^(\w+)_count\{?(.*?)\}? (\d+)$", line)
        if m and (m.group(1), m.group(2)) in hists:
            assert int(m.group(3)) == hists[(m.group(1), m.group(2))][-1]
    return hists


def test_to_prometheus_renders_synthetic_doc():
    reg = metrics.MetricsRegistry(enabled=True)
    for v in (1e-5, 3e-4, 0.002, 0.002, 1.0):
        reg.observe("latency/allreduce_array", v,
                    metrics.LATENCY_LO, metrics.LATENCY_BUCKETS)
    reg.observe("frame_bytes", 8192, metrics.FRAME_LO,
                metrics.FRAME_BUCKETS)
    doc = {
        "slave_num": 2, "window_secs": 60.0,
        "ranks": {"0": {
            "progress": {"seq": 4, "current": "allreduce_array",
                         "last": "barrier", "phase": "wire",
                         "current_secs": 0.1},
            "age": 0.2,
            "stats": {"allreduce_array": {
                "calls": 4, "bytes_sent": 1024, "bytes_recv": 1024,
                "wire_seconds": 0.01}},
            "rates": {"bytes_per_sec": 123.5, "collectives_per_sec": 2.0,
                      "keys_per_sec": 0.0},
        }},
        "cluster": {
            "stats": {"allreduce_array": {"calls": 4, "bytes_sent": 1024,
                                          "bytes_recv": 1024,
                                          "wire_seconds": 0.01}},
            "rates": {"bytes_per_sec": 123.5},
            "histograms": reg.snapshot()["histograms"],
        },
    }
    text = metrics.to_prometheus(doc)
    hists = _validate_prometheus(text)
    assert 'mp4j_calls_total{rank="0",collective="allreduce_array"} 4' \
        in text
    assert 'mp4j_calls_total{rank="cluster",collective=' in text
    assert 'phase="wire"' in text
    assert 'mp4j_collective_latency_seconds_bucket{collective=' \
        '"allreduce_array",le="+Inf"} 5' in text
    assert any(k[0] == "mp4j_collective_latency_seconds" for k in hists)
    assert "mp4j_frame_bytes_count 1" in text
    assert "mp4j_cluster_bytes_per_sec 123.5" in text


def test_format_live_marks_lag_and_stragglers():
    doc = {
        "slave_num": 2, "window_secs": 60.0,
        "ranks": {
            "0": {"progress": {"seq": 9, "current": None,
                               "last": "allreduce_array", "phase": None,
                               "current_secs": 0.0},
                  "age": 0.1, "stats": {}, "rates":
                      {"bytes_per_sec": 2e6}},
            "1": {"progress": {"seq": 7, "current": "allreduce_array",
                               "last": None, "phase": "wire",
                               "current_secs": 3.2},
                  "age": 0.1, "stats": {}, "rates":
                      {"bytes_per_sec": 1e6}},
        },
        "cluster": {"stats": {}, "rates": {"bytes_per_sec": 3e6,
                                           "collectives_per_sec": 1.0,
                                           "keys_per_sec": 0.0},
                    "histograms": {}},
    }
    frame = telemetry.format_live(doc)
    assert "2/2 ranks reporting" in frame
    assert "0.003 GB/s" in frame
    row1 = next(ln for ln in frame.splitlines()
                if ln.lstrip(" *").startswith("1 "))
    assert "2" in row1          # lag column: 9 - 7
    assert "in allreduce_array" in row1 and "wire" in row1


# ----------------------------------------------------------------------
# the live endpoint — acceptance criterion
# ----------------------------------------------------------------------
def test_metrics_endpoint_live_4rank_workload(monkeypatch, capsys):
    """During a live 4-rank socket workload the master endpoint serves
    valid Prometheus text AND the same document as JSON, with per-rank
    and cluster-aggregate series; ``mp4j-scope live --once`` renders
    it."""
    monkeypatch.setenv("MP4J_HEARTBEAT_SECS", "0.05")
    n = 4
    log = io.StringIO()
    master = Master(n, timeout=30.0, log_stream=log,
                    metrics_port=0).serve_in_thread()
    assert master.metrics_port                  # ephemeral port bound
    base = f"http://127.0.0.1:{master.metrics_port}"
    release = threading.Event()
    errors: list = []

    def worker():
        slave = None
        try:
            slave = ProcessCommSlave("127.0.0.1", master.port,
                                     timeout=30.0)
            arr = np.ones(32768)
            for _ in range(6):
                slave.allreduce_array(arr, Operands.DOUBLE,
                                      Operators.SUM)
            slave.barrier()
            assert release.wait(20.0)   # hold the job live for scrapes
            slave.close(0)
        except Exception as e:          # pragma: no cover - diagnostics
            errors.append(e)
            if slave is not None:
                slave.close(1)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(n)]
    for t in threads:
        t.start()
    try:
        # wait until every rank's post-collective heartbeat has folded
        deadline = time.monotonic() + 15.0
        doc = None
        while time.monotonic() < deadline:
            with urllib.request.urlopen(base + "/metrics.json",
                                        timeout=5.0) as resp:
                doc = json.load(resp)
            done = [r for r, info in doc["ranks"].items()
                    if info["stats"].get("allreduce_array", {})
                    .get("calls") == 6]
            if len(done) == n:
                break
            time.sleep(0.05)
        assert doc is not None and len(doc["ranks"]) == n, log.getvalue()

        # JSON schema: per-rank progress/stats/rates/age + aggregates
        assert doc["slave_num"] == n
        for r in map(str, range(n)):
            info = doc["ranks"][r]
            assert info["stats"]["allreduce_array"]["calls"] == 6
            assert info["stats"]["allreduce_array"]["bytes_sent"] > 0
            assert {"seq", "current", "last", "phase",
                    "current_secs"} <= set(info["progress"])
            assert "bytes_per_sec" in info["rates"]
            assert info["age"] >= 0.0
        cl = doc["cluster"]
        assert cl["stats"]["allreduce_array"]["calls"] == 6 * n
        assert {"bytes_per_sec", "collectives_per_sec",
                "keys_per_sec"} <= set(cl["rates"])
        # the folded cluster latency histogram covers every rank's calls
        lat = cl["histograms"].get("latency/allreduce_array")
        assert lat and lat["count"] == 6 * n
        assert metrics.hist_quantile(lat, 0.99) > 0.0
        # frame-size observations rode the same fold, split by the
        # transport the bytes rode (ISSUE 7) — 4 thread slaves share
        # this host, so the whole data plane is the shm family
        frames = {k: h for k, h in cl["histograms"].items()
                  if k == "frame_bytes" or k.startswith("frame_bytes/")}
        assert sum(h["count"] for h in frames.values()) > 0
        assert cl["histograms"]["frame_bytes/shm"]["count"] > 0

        # Prometheus text: valid exposition + per-rank AND cluster rows
        with urllib.request.urlopen(base + "/metrics", timeout=5.0) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        _validate_prometheus(text)
        for who in [*map(str, range(n)), "cluster"]:
            assert (f'mp4j_calls_total{{rank="{who}",'
                    f'collective="allreduce_array"}}') in text
        assert "mp4j_collective_latency_seconds_bucket" in text
        assert f"mp4j_ranks_reporting {n}" in text

        # /health.json (ISSUE 13 satellite): the verdict document over
        # HTTP — external orchestrators read evict recommendations
        # without being in-process; same schema as health_status()
        with urllib.request.urlopen(base + "/health.json",
                                    timeout=5.0) as r:
            assert r.headers["Content-Type"].startswith(
                "application/json")
            hdoc = json.load(r)
        assert {"enabled", "ranks", "evict_recommended", "dominator",
                "alerts_total", "window"} <= set(hdoc)
        assert hdoc["enabled"] is True
        for r in map(str, range(n)):
            assert {"state", "state_code", "pressure",
                    "alerts"} <= set(hdoc["ranks"][r])
            assert hdoc["ranks"][r]["state"] == "HEALTHY"
        assert hdoc["evict_recommended"] == []

        # the live CLI view renders one frame from the same endpoint
        assert scope_main(["live", f"127.0.0.1:{master.metrics_port}",
                           "--once"]) == 0
        frame = capsys.readouterr().out
        assert f"{n}/{n} ranks reporting" in frame
        assert "idle after barrier" in frame    # the held job's state

        # unknown paths 404 instead of serving garbage
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/secrets", timeout=5.0)
    finally:
        release.set()
        for t in threads:
            t.join(20.0)
    assert not errors
    assert not any(t.is_alive() for t in threads)
    master.join(10.0)
    # endpoint shuts down with the master
    with pytest.raises(OSError):
        urllib.request.urlopen(base + "/metrics", timeout=1.0)


def test_metrics_disabled_drops_histograms_only(monkeypatch):
    """MP4J_METRICS=0 (the bench A/B knob) turns observation into a
    no-op while the stats counters keep flowing."""
    monkeypatch.setenv("MP4J_METRICS", "0")
    from ytk_mp4j_tpu.utils.stats import CommStats
    cs = CommStats()
    assert not cs.metrics.enabled
    outermost = cs.begin("allreduce_array")
    cs.add_wire(bytes_sent=100, bytes_recv=100, seconds=0.01)
    cs.end(outermost)
    assert cs.metrics.snapshot()["histograms"] == {}
    assert cs.snapshot()["allreduce_array"]["bytes_sent"] == 100


# ----------------------------------------------------------------------
# flight recorder — chaos acceptance
# ----------------------------------------------------------------------
def test_chaos_kill_survivors_write_postmortem_bundles(tmp_path, capsys):
    """Acceptance: a killed rank yields a COMPLETE postmortem bundle
    from every survivor plus the master manifest, and the merged
    ``mp4j-scope postmortem`` report names the dead rank."""
    pmdir = str(tmp_path / "pm")

    def fn(slave, r):
        arr = np.full(4096, float(r + 1))
        slave.allreduce_array(arr, Operands.DOUBLE, Operators.SUM)
        slave.barrier()
        slave.allreduce_array(arr, Operands.DOUBLE, Operators.SUM)
        return arr

    _, errors, _, log = run_chaos(
        4, fn, fault_plan="kill:rank=2:nth=2", postmortem_dir=pmdir,
        master_kwargs={"postmortem_dir": pmdir})
    assert isinstance(errors[2], FaultKill)
    assert all(isinstance(errors[r], Mp4jFatalError) for r in (0, 1, 3))

    bundles = postmortem.load_bundles(pmdir)
    assert set(bundles) == {0, 1, 3}            # the dead rank left none
    for r in (0, 1, 3):
        b = bundles[r]
        assert not b["torn"], f"rank {r} bundle torn"
        assert b["complete"]["rank"] == r
        assert b["stats"]["rank"] == r
        assert "rank 2" in b["stats"]["reason"]
        assert b["stats"]["progress"]["seq"] >= 1
        assert b["stats"]["stats"]["allreduce_array"]["calls"] >= 1
        # histogram state rode along
        assert any(k.startswith("latency/")
                   for k in b["metrics"]["histograms"])
        # the epoch/retry log recorded the fatal
        kinds = [kind for _, kind, _ in b["recovery"]["events"]]
        assert "fatal" in kinds
        # the Chrome trace is loadable JSON with events
        d = postmortem.bundle_dir(pmdir, r)
        with open(os.path.join(d, "trace.json")) as fh:
            trace_doc = json.load(fh)
        assert trace_doc["traceEvents"]

    with open(os.path.join(pmdir, "manifest.json")) as fh:
        manifest = json.load(fh)
    assert manifest["slave_num"] == 4
    assert "rank 2" in manifest["reason"]
    # the fatal-path telemetry flush landed: the manifest's final table
    # is fresh (every surviving rank's last beat, with its progress)
    assert {"0", "1", "3"} <= set(manifest["table"])

    report = postmortem.merge_report(pmdir)
    assert "DEAD rank 2" in report
    assert "bundles: 3/4 ranks" in report
    assert scope_main(["postmortem", pmdir]) == 0
    out = capsys.readouterr().out
    assert "DEAD rank 2" in out


# ----------------------------------------------------------------------
# the surfaces after ISSUE 41: a master holds no controller to report
# on. (The gone word is spelled in pieces so a grep for it stays empty.)
# ----------------------------------------------------------------------
_GONE = "auto" + "scal"
CLUSTER_SECTIONS = {"stats", "rates", "histograms", "audit",
                    "membership", "health", "tuner", "serve"}


@pytest.fixture(scope="module")
def live_scrape():
    """One live 2-rank job with a warm spare, scraped over HTTP while
    it idles after a barrier: ``(exposition text, JSON document)``."""
    mp = pytest.MonkeyPatch()
    mp.setenv("MP4J_HEARTBEAT_SECS", "0.05")
    n = 2
    master = Master(n, timeout=30.0, log_stream=io.StringIO(),
                    metrics_port=0, elastic="replace").serve_in_thread()
    base = f"http://127.0.0.1:{master.metrics_port}"
    release = threading.Event()

    def worker():
        slave = ProcessCommSlave("127.0.0.1", master.port, timeout=30.0,
                                 elastic="replace")
        slave.allreduce_array(np.ones(1024), Operands.DOUBLE,
                              Operators.SUM)
        slave.barrier()
        release.wait(20.0)
        slave.close(0)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(n)]
    for t in threads:
        t.start()
    try:
        deadline = time.monotonic() + 15.0
        doc = {}
        while time.monotonic() < deadline:
            with urllib.request.urlopen(base + "/metrics.json",
                                        timeout=5.0) as resp:
                doc = json.load(resp)
            if sum(1 for info in doc["ranks"].values()
                   if info["stats"].get("allreduce_array")) == n:
                break
            time.sleep(0.05)
        with urllib.request.urlopen(base + "/metrics", timeout=5.0) as r:
            text = r.read().decode()
    finally:
        release.set()
        for t in threads:
            t.join(20.0)
        master.join(10.0)
        mp.undo()
    return text, doc


def test_live_exposition_holds_only_documented_families(live_scrape):
    """Every family a live master exposes is a row of METRICS_DOC (what
    lint rule R17 checks in the source, checked on the wire), and the
    controller's two families are in neither."""
    text, _ = live_scrape
    _validate_prometheus(text)
    families = set(re.findall(r"^# TYPE (\w+) ", text, flags=re.M))
    assert {"mp4j_calls_total", "mp4j_replacements_total",
            "mp4j_spares_available", "mp4j_rank_health_state",
            "mp4j_ranks_reporting"} <= families
    rows = [re.compile(re.sub(r"<\w+>", r"\\w+", row) + "$")
            for row in metrics.METRICS_DOC]     # mp4j_rank_<rate>
    assert not [f for f in families
                if not any(row.match(f) for row in rows)]
    assert not [f for f in metrics.METRICS_DOC if _GONE in f]
    assert _GONE not in text


def test_live_cluster_document_sections(live_scrape):
    """``/metrics.json``: the cluster document is exactly the planes a
    master still has, and the membership plane counts only what a
    death can start."""
    _, doc = live_scrape
    assert set(doc["cluster"]) == CLUSTER_SECTIONS
    ms = doc["cluster"]["membership"]
    assert ms["mode"] == "replace"
    assert {k for k, v in ms.items() if isinstance(v, int)} == {
        "replacements", "shrinks", "spares_available", "spares_total"}
    assert _GONE not in json.dumps(doc)
    live = telemetry.format_live(doc)
    assert "membership: mode=replace" in live and _GONE not in live


def test_fatal_job_postmortem_sections(tmp_path):
    """A fatal job's manifest freezes the planes the master has, and
    the merged report renders each of them and no controller ledger."""
    pmdir = str(tmp_path / "pm")

    def fn(slave, r):
        arr = np.full(256, float(r + 1))
        slave.allreduce_array(arr, Operands.DOUBLE, Operators.SUM)
        slave.allreduce_array(arr, Operands.DOUBLE, Operators.SUM)
        return arr

    _, errors, _, _ = run_chaos(
        3, fn, fault_plan="kill:rank=1:nth=2", postmortem_dir=pmdir,
        master_kwargs={"postmortem_dir": pmdir})
    assert isinstance(errors[1], FaultKill)
    with open(os.path.join(pmdir, "manifest.json")) as fh:
        manifest = json.load(fh)
    assert set(manifest) == {"slave_num", "reason", "departed",
                             "diagnosis", "audit", "sink_dir",
                             "membership", "health", "table",
                             "wall_time"}
    assert manifest["membership"]["mode"] == "off"
    report = postmortem.merge_report(pmdir)
    assert "DEAD rank 1" in report
    assert "health verdicts at abort time:" in report
    assert _GONE not in report.lower()


def test_postmortem_report_tolerates_torn_bundle(tmp_path):
    root = str(tmp_path)
    postmortem.write_bundle(
        root, 0, reason="x", progress={"seq": 3}, stats={},
        metrics={"counters": {}, "gauges": {}, "histograms": {}},
        epoch=1, events=[(0.0, "fatal", "x")])
    # rank 1 died mid-dump: stats.json only, no complete marker
    d = postmortem.bundle_dir(root, 1)
    os.makedirs(d)
    with open(os.path.join(d, "stats.json"), "w") as fh:
        json.dump({"rank": 1, "progress": {"seq": 1}}, fh)
    report = postmortem.merge_report(root)
    assert "rank 1 TORN" in report
    assert "DEAD" not in report.split("TORN")[0].splitlines()[0]


def test_postmortem_dir_empty_means_disabled(tmp_path, monkeypatch):
    monkeypatch.delenv("MP4J_POSTMORTEM_DIR", raising=False)
    assert tuning.postmortem_dir() == ""
    f = tmp_path / "afile"
    f.write_text("x")
    monkeypatch.setenv("MP4J_POSTMORTEM_DIR", str(f))
    with pytest.raises(Mp4jError):
        tuning.postmortem_dir()


# ----------------------------------------------------------------------
# knob validation (README knob table contract)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("env,bad", [
    ("MP4J_METRICS", "yes"),
    ("MP4J_METRICS_PORT", "eighty"),
    ("MP4J_METRICS_PORT", "70000"),
    ("MP4J_METRICS_WINDOW_SECS", "0"),
    ("MP4J_METRICS_WINDOW_SECS", "-5"),
])
def test_metrics_knobs_env_validated(env, bad, monkeypatch):
    monkeypatch.setenv(env, bad)
    fn = {"MP4J_METRICS": tuning.metrics_enabled,
          "MP4J_METRICS_PORT": tuning.metrics_port,
          "MP4J_METRICS_WINDOW_SECS": tuning.metrics_window_secs}[env]
    with pytest.raises(Mp4jError):
        fn()


def test_metrics_port_ctor_override_shares_env_validation():
    # the explicit Master(metrics_port=...) path must fail the same
    # clean way the env path does — not a raw socket OverflowError
    with pytest.raises(Mp4jError):
        tuning.metrics_port(override=99999)
    with pytest.raises(Mp4jError):
        Master(2, metrics_port=70000)
    assert tuning.metrics_port(override=0) == 0
    assert tuning.metrics_port(override=8080) == 8080


def test_metrics_knob_defaults(monkeypatch):
    for env in ("MP4J_METRICS", "MP4J_METRICS_PORT",
                "MP4J_METRICS_WINDOW_SECS", "MP4J_POSTMORTEM_DIR"):
        monkeypatch.delenv(env, raising=False)
    assert tuning.metrics_enabled() is True
    assert tuning.metrics_port() is None        # endpoint off by default
    assert tuning.metrics_window_secs() == \
        tuning.DEFAULT_METRICS_WINDOW_SECS
    assert tuning.postmortem_dir() == ""
