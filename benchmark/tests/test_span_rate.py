"""``readers/trace_span_rate.py`` (``stage_gbps``, ``score_stage_gbps``):
on a hand-made ``.xplane.pb`` whose numbers are known, on a trace that the
program's own staging leaves on the CPU, and on the traces recorded on the
chip before the staging spans existed (nothing to read, nothing raised).
By hand, like the other ``benchmark/tests``."""

import glob
import json
import os

import numpy as np
import pytest

from benchmark import cells, xplane

from conftest import ROOT
from test_scopes import _msg, _run

rate = cells.load_module(ROOT, "readers", "trace_span_rate")
host_span = cells.load_module(ROOT, "readers", "trace_host_span")
MS = 1_000_000                                   # ns


def _spec(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           f"{metric}.json")) as f:
        return json.load(f)


SPEC = _spec("stage_gbps")


def _space(path, events):
    """A file with one host plane and one ``python`` line holding
    ``events``: (name, start ns, duration ns, {argument: int})."""
    names = sorted({e[0] for e in events})
    args = sorted({k for e in events for k in e[3]})
    line = _msg((1, 1), (2, "python"), (3, 0), *(
        (4, _msg((1, names.index(name) + 1), (2, start * 1000),
                 (3, dur * 1000),
                 *((4, _msg((1, args.index(k) + 1), (4, v)))
                   for k, v in stats.items())))
        for name, start, dur, stats in events))
    plane = _msg(
        (1, 1), (2, xplane.HOST_PLANE), (3, line),
        *((4, _msg((1, i + 1), (2, _msg((1, i + 1), (2, n)))))
          for i, n in enumerate(names)),
        *((5, _msg((1, i + 1), (2, _msg((1, i + 1), (2, k)))))
          for i, k in enumerate(args)))
    path.write_bytes(_msg((1, plane)))
    trace = xplane.load(str(path))
    return {"trace": trace, "trace_path": str(path), "counters": {"jobs": 1},
            "window_ns": xplane.window_of(trace, "bench.slice"), "spans": {}}


CHUNKED = [
    ("bench.slice", 0, 1000 * MS, {}),
    # a table that crossed in chunks: 4e9 bytes in 400 ms
    ("mp4j.put_sharded", 100 * MS, 400 * MS, {"bytes": 4_000_000_000}),
    ("mp4j.stage.send", 101 * MS, 20 * MS, {"chunk": 0, "bytes": 2 ** 28}),
    ("mp4j.stage.place", 121 * MS, 1 * MS, {"chunk": 0}),
    ("mp4j.stage.device_wait", 130 * MS, 300 * MS, {"chunk": 0}),
    # labels in one transfer: handed over in 1 ms, no wait inside
    ("mp4j.put_sharded", 510 * MS, 1 * MS, {"bytes": 44_000_000}),
    ("mp4j.stage.send", 510 * MS, 1 * MS, {"chunk": 0, "bytes": 44_000_000}),
]


def test_rate_counts_only_the_puts_that_hold_a_wait(tmp_path):
    run = _space(tmp_path / "chunked.xplane.pb", CHUNKED)
    assert rate.read(SPEC, run) == pytest.approx(10.0)
    # a second chunked put, by a link wait: bytes add, time is the union
    more = CHUNKED + [
        ("mp4j.put_sharded", 600 * MS, 100 * MS, {"bytes": 2_000_000_000}),
        ("mp4j.stage.link_wait", 610 * MS, 80 * MS, {"chunk": 3})]
    run = _space(tmp_path / "two.xplane.pb", more)
    assert rate.read(SPEC, run) == pytest.approx(6.0 / 0.5)
    # the scoring cell's file names the same reader and the same spans
    assert rate.read(_spec("score_stage_gbps"), run) \
        == pytest.approx(6.0 / 0.5)
    # cut to the window: a put that ends after it is not counted
    run["window_ns"] = (0.0, 650.0 * MS)
    assert rate.read(SPEC, run) == pytest.approx(10.0)


def test_rate_has_nothing_to_read(tmp_path):
    # every put in one transfer (the Higgs table): spans, but no wait
    run = _space(tmp_path / "one.xplane.pb",
                 [e for e in CHUNKED if "_wait" not in e[0]])
    assert rate.read(SPEC, run) is None
    assert host_span.read(_spec("stage_send_ms_per_job"), run) \
        == pytest.approx(21.0)
    assert host_span.read(_spec("stage_device_wait_ms_per_job"), run) is None
    # a put that says no bytes
    run = _space(tmp_path / "bare.xplane.pb", [
        ("bench.slice", 0, 1000 * MS, {}),
        ("mp4j.put_sharded", 100 * MS, 400 * MS, {}),
        ("mp4j.stage.device_wait", 130 * MS, 300 * MS, {"chunk": 0})])
    assert rate.read(SPEC, run) is None
    # no trace; no file; another run's file
    run = _space(tmp_path / "chunked.xplane.pb", CHUNKED)
    assert rate.read(SPEC, {**run, "trace": None}) is None
    assert rate.read(SPEC, {**run, "trace_path": str(
        tmp_path / "absent.xplane.pb")}) is None
    shifted = [(n, s + 5 * MS if n == "mp4j.put_sharded" else s, d, a)
               for n, s, d, a in CHUNKED]
    other = _space(tmp_path / "other.xplane.pb", shifted)
    assert rate.read(SPEC, {**run, "trace_path": other["trace_path"]}) is None


NEW = ["stage_prep_ms_per_job", "stage_send_ms_per_job",
       "stage_device_wait_ms_per_job", "stage_gbps",
       "score_stage_send_ms_per_job", "score_stage_link_wait_ms_per_job",
       "score_stage_device_wait_ms_per_job", "score_stage_gbps",
       "stream_next_ms_per_chunk"]


@pytest.mark.parametrize("metric", NEW)
@pytest.mark.parametrize("recorded", ["gbdt_1m_2trees_scoped",
                                      "gbdt_1m_2trees",
                                      "ffm_small_4chunks"])
def test_new_metrics_read_nothing_on_the_parents_traces(metric, recorded):
    """The traces recorded on the chip by the program as it was: with its
    spans (PR 24: four ``mp4j.put_sharded``, no ``mp4j.stage.*``) and
    before it had any."""
    spec = _spec(metric)
    reader = cells.load_module(ROOT, "readers", spec["reader"])
    run = _run(recorded, trees=2, jobs=1, chunks=4)
    assert reader.read(spec, run) is None


def test_new_metrics_are_declared_alike_in_both_places():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(declared)
    for name in NEW:
        spec, entry = _spec(name), declared[name]
        assert spec["name"] == name and "adapters" not in spec
        for key in ("layer", "moves", "source"):
            assert spec[key] == entry[key], (name, key)
        assert entry["layer"] == "trainers_host"
        assert entry["better"] == ("higher" if name.endswith("gbps")
                                   else "lower")
        for cell in entry["workloads"]:
            assert name in {m["name"] for m in
                            cells.load_cell(ROOT, cell).per_layer}


# ------------------------------------------- the program's own staging
def test_rate_on_a_trace_of_the_programs_staging(tmp_path):
    """``_put_sharded`` on the CPU under the benchmark's profiler options:
    one table in row chunks and one small array in one transfer. The rate
    is the chunked table's bytes over its span's time, whatever the small
    one did, and the children lie inside their parent."""
    import jax

    from ytk_mp4j_tpu.models._base import DataParallelTrainer

    t = DataParallelTrainer(n_devices=4)
    a = np.arange(4 * 4096 * 32, dtype=np.int32).reshape(4 * 4096, 32)
    t._ONE_TRANSFER_BYTES = a.nbytes // 4
    t._CHUNK_BYTES = t._EACH_CHUNK_BYTES = 2 ** 15      # 16 chunks
    t._put_sharded(a, 4096)                             # builds the placer
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench.slice"):
            jax.block_until_ready(t._put_sharded(a, 4096))
            jax.block_until_ready(t._put_sharded(a[:64, 0], 16))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    trace = xplane.load(path)
    run = {"trace": trace, "trace_path": path, "counters": {"jobs": 1},
           "window_ns": xplane.window_of(trace, "bench.slice"), "spans": {}}
    puts = trace.host.matching(r"^mp4j\.put_sharded$")
    assert len(puts) == 2
    seconds = float(puts.end[0] - puts.start[0]) / 1e9
    assert rate.read(SPEC, run) == pytest.approx(a.nbytes / seconds / 1e9)
    ms = {name: host_span.read(_spec(name), run)
          for name in NEW if not name.endswith("gbps")}
    assert ms["stage_send_ms_per_job"] == ms["score_stage_send_ms_per_job"] > 0
    assert ms["stage_device_wait_ms_per_job"] > 0
    # one pace for every caller since PR 46 (``each=`` went with PR 52:
    # a scoring call takes its pieces from ``_pieces``, not from here)
    assert ms["score_stage_link_wait_ms_per_job"] > 0
    assert ms["score_stage_link_wait_ms_per_job"] == host_span.read(
        _spec("stage_link_wait_ms_per_job"), run)
    assert ms["stage_prep_ms_per_job"] is None          # no _pad_rows here
    children = trace.host.matching(r"^mp4j\.stage\.")
    inside = children.take(np.nonzero(children.start < puts.end[0])[0])
    assert len(inside) == 16 * 2 + 15 + 4
    assert xplane.union_ns(inside) <= seconds * 1e9
