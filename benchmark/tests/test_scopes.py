"""The program's names in the device trace: ``scopes.py``'s wire-format
decoder against the protobuf classes (where the sandbox has them) and on
hand-made messages, the two readers that use it on the traces recorded
on the chip, and every per-layer metric this PR added on the trace
recorded with the scopes and spans in the program."""

import glob
import json
import os
import time

import pytest

from benchmark import cells, scopes, xplane

from conftest import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OLD = ["gbdt_1m_2trees", "ffm_small_4chunks", "allreduce_4chip_small"]
SCOPED = "gbdt_1m_2trees_scoped"
KERNEL = 'custom_call_target="tpu_custom_call"'
CUSTOM_FUSION = r" fusion\(.*kind=kCustom"
scope_time = cells.load_module(ROOT, "readers", "trace_scope_time")
host_span = cells.load_module(ROOT, "readers", "trace_host_span")
op_time = cells.load_module(ROOT, "readers", "trace_op_time")


def _path(name):
    return os.path.join(DATA, f"{name}.xplane.pb")


def _run(name, **counters):
    trace = xplane.load(_path(name))
    return {"trace": trace, "trace_path": _path(name), "counters": counters,
            "window_ns": xplane.window_of(trace, "bench.slice"),
            "spans": {}}


def _spec(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           f"{metric}.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ the decoder
@pytest.mark.parametrize("name", OLD + [SCOPED])
def test_decoder_agrees_with_the_protobuf_classes(name):
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError as e:
        pytest.skip(f"no xplane_pb2 here: {e}")
    space = xplane_pb2.XSpace()
    with open(_path(name), "rb") as f:
        space.ParseFromString(f.read())
    want = {}
    for plane in space.planes:
        m = scopes.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        names = want.setdefault(int(m.group(1)), {})
        for meta in plane.event_metadata.values():
            tf_op = ""
            for stat in meta.stats:
                if stat_names[stat.metadata_id] == "tf_op":
                    tf_op = (stat.str_value
                             if stat.WhichOneof("value") == "str_value"
                             else stat_names[stat.ref_value])
            assert names.setdefault(meta.name, tf_op) == tf_op
    got = scopes.load(_path(name))
    assert got == want and got
    # and every XLA Ops event the reducer reads has an entry
    trace = xplane.load(_path(name))
    for chip, events in trace.ops.items():
        assert set(events.names) <= got[chip].keys()


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """Encode (number, value) pairs: int -> varint, bytes/str ->
    length-delimited, float -> fixed64."""
    import struct

    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        elif isinstance(value, float):
            out += _varint(number << 3 | 1) + struct.pack("<d", value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def test_decoder_on_a_hand_made_space(tmp_path):
    """str_value and ref_value, a stat that is not tf_op, an event with
    none, one name printed by two programs, fields the decoder skips
    (fixed64, a line), a host plane, and a second chip."""
    stat_meta = {1: "tf_op", 2: "flops", 3: "jit(step)/gbdt.route/eq:"}

    def event(key, name, *stats):
        meta = _msg((1, key), (2, name), *((5, s) for s in stats))
        return 4, _msg((1, key), (2, meta))

    def plane(name, *events):
        return 1, _msg(
            (1, 7), (2, name), (3, _msg((1, 1), (2, "XLA Ops"))), *events,
            *((5, _msg((1, k), (2, _msg((1, k), (2, v)))))
              for k, v in stat_meta.items()))

    space = _msg(
        plane("/device:TPU:0",
              event(1, "%a = f32[] add()", _msg((1, 2), (4, 9)),
                    _msg((1, 1), (5, "jit(step)/gbdt.hist/add:"))),
              event(2, "%b = s32[] eq()", _msg((1, 2), (2, 1.5)),
                    _msg((1, 1), (7, 3))),
              event(3, "%c = f32[] copy()"),
              event(4, "%d = f32[] mul()", _msg((1, 1), (5, "jit(f)/mul:"))),
              event(5, "%d = f32[] mul()", _msg((1, 1), (5, "jit(g)/mul:"))),
              event(6, "%d = f32[] mul()", _msg((1, 1), (5, "jit(f)/mul:")))),
        plane("/host:CPU", event(1, "mp4j.gbdt.stage")),
        plane("/device:TPU:3",
              event(1, "%a = f32[] add()", _msg((1, 1), (5, "x/y:")))))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(space)
    assert scopes.load(str(path)) == {
        0: {"%a = f32[] add()": "jit(step)/gbdt.hist/add:",
            "%b = s32[] eq()": "jit(step)/gbdt.route/eq:",
            "%c = f32[] copy()": "",
            "%d = f32[] mul()": "jit(f)/mul: | jit(g)/mul:"},
        3: {"%a = f32[] add()": "x/y:"}}
    only_host = tmp_path / "cpu.xplane.pb"
    only_host.write_bytes(_msg(plane("/host:CPU", event(1, "python"))))
    assert scopes.load(str(only_host)) == {}
    group = tmp_path / "group.pb"
    group.write_bytes(b"\x0b")                  # a group: not an xplane.pb
    with pytest.raises(ValueError, match="wire type"):
        scopes.load(str(group))


def test_recorded_traces_name_the_kernel_and_the_custom_fusions():
    trace = xplane.load(_path("gbdt_1m_2trees"))
    names = scopes.load(_path("gbdt_1m_2trees"))[0]
    kernels = [n for n in trace.ops[0].names if KERNEL in n]
    assert len(kernels) == 12
    assert {names[n] for n in kernels} == {"jit(step)/pallas_call:"}
    # _route_samples, found so far by reading fusion names by eye
    assert sum(names[n] == "jit(step)/reduce_sum:"
               for n in trace.ops[0].names) == 24

    trace = xplane.load(_path("ffm_small_4chunks"))
    names = scopes.load(_path("ffm_small_4chunks"))[0]
    custom = [n for n in trace.ops[0].names if "kind=kCustom" in n]
    assert sorted({names[n] for n in custom}) == [
        "jit(step)/gather:", "jit(step)/jvp()/gather:",
        "jit(step)/scatter-add:", "jit(step)/transpose(jvp())/scatter-add:"]
    assert len(custom) == 16


# ------------------------------------------------- which file is the run's
def test_for_run_takes_only_the_runs_own_file(tmp_path, monkeypatch):
    run = _run("gbdt_1m_2trees", trees=2)
    assert scopes.for_run(run) == scopes.load(_path("gbdt_1m_2trees"))
    # another run's file lacks this run's operations
    assert scopes.for_run({**run, "trace_path": _path(
        "ffm_small_4chunks")}) is None
    assert scopes.for_run({**run, "trace_path": str(
        tmp_path / "absent.xplane.pb")}) is None
    # no path in ``run`` (as run.py leaves it): the newest file under the
    # benchmark's trace directory
    pattern = str(tmp_path / "*" / "plugins" / "profile" / "*"
                  / "*.xplane.pb")
    newest = scopes.newest_trace
    monkeypatch.setattr(scopes, "newest_trace", lambda: newest(pattern))
    del run["trace_path"]
    assert scopes.for_run(run) is None          # nothing there yet
    for i, name in enumerate(["ffm_small_4chunks", "gbdt_1m_2trees"]):
        d = tmp_path / f"cell{i}" / "plugins" / "profile" / f"t{i}"
        d.mkdir(parents=True)
        target = d / "host.xplane.pb"
        target.write_bytes(open(_path(name), "rb").read())
        os.utime(target, (time.time() + i, time.time() + i))
    assert scopes.for_run(run) == scopes.load(_path("gbdt_1m_2trees"))


# ------------------------------------------------------------ the readers
def test_scope_time_equals_op_time_on_the_ffm_trace():
    run = _run("ffm_small_4chunks", chunks=4)
    per = {"per": "chunks", "scale": 1e3}
    by_text = op_time.read({"pattern": CUSTOM_FUSION, **per}, run)
    assert by_text == pytest.approx(35.541604)
    scatter = scope_time.read({"scope": "scatter-add",
                               "pattern": "kind=kCustom", **per}, run)
    gather = scope_time.read({"scope": "/gather:",
                              "pattern": "kind=kCustom", **per}, run)
    assert scatter + gather == pytest.approx(by_text)
    assert scatter == pytest.approx(31.39195625)
    # without the pattern the scope also holds what XLA put around the
    # gather (the clamp of the indices, a copy of the gathered rows)
    whole = scope_time.read({"scope": "/gather:", **per}, run)
    assert gather < whole < 1.04 * gather
    assert scope_time.read({"scope": r"transpose\(jvp\(\)\)/scatter-add",
                            **per}, run) == pytest.approx(0.1333235)


def test_scope_time_modules_chips_and_nothing_to_read():
    run = _run("allreduce_4chip_small", hist_trees=4)
    pat = r" all-reduce(-start|-done)?\("
    for module in (r"^jit_hist_tree_allreduces\(", r"^jit_bulk_allreduce\("):
        spec = {"module": module, "per": "hist_trees", "scale": 1e6}
        got = scope_time.read({"scope": "psum_invariant", **spec}, run)
        assert got == pytest.approx(op_time.read({"pattern": pat, **spec},
                                                 run))
        assert got > 0
    assert scope_time.read({"scope": "psum_invariant", "per": "hist_trees",
                            "module": r"^jit_nothing\("}, run) == 0.0
    # the program has no such scope: nothing to read, not 0
    assert scope_time.read({"scope": r"mp4j\.allreduce"}, run) is None
    # no counter, no trace, or not this run's file
    assert scope_time.read({"scope": "psum", "per": "absent"}, run) is None
    assert scope_time.read({"scope": "psum"},
                           {**run, "trace": None}) is None
    assert scope_time.read({"scope": "psum"}, {**run, "trace_path": _path(
        "ffm_small_4chunks")}) is None


def test_host_span_statistics_on_the_gbdt_trace():
    run = _run("gbdt_1m_2trees", trees=2)
    span = {"span": "bench.job"}
    assert host_span.read({**span, "stat": "sum", "scale": 1e3}, run) \
        == pytest.approx(145.901209)
    assert host_span.read({**span, "stat": "count"}, run) == 1
    assert host_span.read({**span, "stat": "median", "per": "trees"}, run) \
        == pytest.approx(0.145901209 / 2)
    # the runtime's own event for a jitted call, two a tree
    assert host_span.read({"span": "PjitFunction(step)", "stat": "count"},
                          run) == 4
    longest = host_span.read({"span": "PjitFunction(step)", "stat": "max"},
                             run)
    assert 0 < longest < host_span.read(
        {"span": "PjitFunction(step)", "stat": "sum"}, run)
    # an exact name, not a prefix
    assert host_span.read({"span": "bench", "stat": "count"}, run) == 0
    assert host_span.read({"span": "bench", "stat": "sum"}, run) is None
    # a program that records no span of its own has nothing to count
    assert host_span.read({**span, "stat": "count",
                           "instrumented": r"^mp4j\."}, run) is None
    assert host_span.read({**span, "stat": "sum", "per": "absent"},
                          run) is None
    # cut to the window
    t0, t1 = run["window_ns"]
    half = {**run, "window_ns": (t0, (t0 + t1) / 2)}
    assert host_span.read({**span, "stat": "sum"}, half) < 0.08


# ------------------------------------- this PR's metrics, on a scoped trace
NEW_GBDT = {
    "gbdt_stage_ms_per_job": 6.81499,
    "gbdt_dispatch_ms_per_tree": 13.0848045,
    "gbdt_fetch_wait_ms_per_job": 111.970616,
    "gbdt_hist_ms_per_tree": 52.0775255,
    "gbdt_bins_relayout_ms_per_tree": 1.563928,
    "gbdt_route_ms_per_tree": 5.21464,
    "gbdt_split_leaf_ms_per_tree": 0.358518,
    "step_builds_in_window": 0.0,
}


@pytest.mark.parametrize("metric", sorted(NEW_GBDT))
def test_new_gbdt_metric_on_the_scoped_trace(metric):
    spec = _spec(metric)
    reader = cells.load_module(ROOT, "readers", spec["reader"])
    run = _run(SCOPED, trees=2, jobs=1)
    assert reader.read(spec, run) == pytest.approx(NEW_GBDT[metric])
    # the same files on the parent's program (the trace recorded before
    # the scopes and spans): nothing to read, and no exception
    assert reader.read(spec, _run("gbdt_1m_2trees", trees=2, jobs=1)) is None


def test_the_scoped_trace_agrees_with_the_metrics_from_outside():
    run = _run(SCOPED, trees=2, jobs=1)
    outside = op_time.read(_spec("hist_kernel_ms_per_tree"), run)
    inside = scope_time.read(_spec("gbdt_hist_ms_per_tree"), run)
    assert inside == outside == pytest.approx(52.0775255)
    names = scopes.for_run(run)[0]
    kernels = [n for n in run["trace"].ops[0].names if KERNEL in n]
    assert {names[n] for n in kernels} == {
        "jit(step)/gbdt.hist/mp4j_hist/pallas_call:"}
    assert len(kernels) == 12 and all(
        n.startswith("%mp4j_hist") for n in kernels)
    # XLA's own copy of the parameter is not under the scope
    assert {names[n] for n in run["trace"].ops[0].names
            if n.startswith("%copy.") and "s32[1,1000000,28]" in n} \
        == {"bins:"}
    # the parts of a tree, by the program's names, are most of its time
    t0, t1 = run["window_ns"]
    parts = sum(scope_time.read(_spec(m), run) for m in (
        "gbdt_hist_ms_per_tree", "gbdt_bins_relayout_ms_per_tree",
        "gbdt_route_ms_per_tree", "gbdt_split_leaf_ms_per_tree"))
    busy_per_tree = xplane.mean_busy_ns(run["trace"], t0, t1) / 1e6 / 2
    assert 0.95 * busy_per_tree < parts < busy_per_tree
    # the job's spans lie inside bench.job and account for nearly all of it
    job = host_span.read({"span": "bench.job", "stat": "sum"}, run)
    spans_s = sum(host_span.read({"span": s, "stat": "sum"}, run) for s in (
        "mp4j.gbdt.stage", "mp4j.gbdt.dispatch", "mp4j.gbdt.fetch"))
    assert 0.97 * job < spans_s < job
    assert host_span.read({"span": "mp4j.put_sharded", "stat": "count"},
                          run) == 4


def test_idle_gaps_are_named_by_the_programs_spans():
    """``xplane.idle_gaps`` names a gap by the innermost host event that
    covers it: with the trainer's spans in the trace no gap of 1 ms or
    more is put down to the benchmark's outer spans."""
    run = _run(SCOPED)
    gaps = dict(xplane.breakdown(run["trace"], *run["window_ns"])
                ["idle_gaps"])
    assert gaps["mp4j.gbdt.stage"] == pytest.approx(0.006891272)
    for outer in ("bench.slice", "bench.job"):
        assert gaps.get(outer, 0.0) < 1e-3
    before = dict(xplane.breakdown(
        xplane.load(_path("gbdt_1m_2trees")),
        *_run("gbdt_1m_2trees")["window_ns"])["idle_gaps"])
    assert before["bench.job"] > 5e-3


# ----------------------------------------------- the files this PR added
NEW = sorted(NEW_GBDT) + [
    "ffm_stage_ms_per_chunk", "ffm_dispatch_ms_per_chunk",
    "ffm_throttle_wait_ms_per_chunk", "ffm_table_update_ms_per_chunk",
    "ffm_table_gather_ms_per_chunk", "collective_scope_us_per_tree"]


def test_new_metrics_are_declared_alike_in_both_places():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    # declared, wherever later PRs have put them in the list; no file
    # filters on adapters since PR 49: the entry's cells say who reads it
    assert set(NEW) <= set(declared)
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        spec, entry = _spec(name), declared[name]
        assert spec["name"] == name
        for key in ("layer", "moves", "source"):
            assert spec[key] == entry[key], (name, key)
        assert entry["layer"] in layers and entry["better"] == "lower"
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "readers", f"{spec['reader']}.py"))
        assert "adapters" not in spec and entry["workloads"]
        for cell in entry["workloads"]:
            assert name in {m["name"] for m in
                            cells.load_cell(ROOT, cell).per_layer}


@pytest.mark.parametrize("workload,want", [
    ("gbdt-higgs-11m.train", ["gbdt_stage_ms_per_job",
                              "gbdt_dispatch_ms_per_tree",
                              "gbdt_fetch_wait_ms_per_job"]),
    # the toy slice is 3 chunks with 2 in flight: the host never has to
    # wait for the queue, so the throttle wait reads 0 (a reading, since
    # PR 49 merged the metric under ``trace_host_span_total``)
    ("ffm-criteo.stream-zipf", ["ffm_stage_ms_per_chunk",
                                "ffm_dispatch_ms_per_chunk"]),
])
def test_traced_rehearsal_reports_the_programs_spans(capsys, tiny_root,
                                                     workload, want):
    """The CPU's trace has a host plane: the span metrics are read there
    as on the chip; the scope metrics have no device plane to read and
    are left out."""
    from benchmark import run

    rc = run.main(["--workload", workload, "--seed", "5", "--seconds",
                   "0.5", "--trace", "1"], root=tiny_root)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "compared"}
    for name in want:
        assert line["metrics"][name]["value"] > 0
        assert line["metrics"][name]["unit"] == "ms"
    assert line["metrics"]["step_builds_in_window"] == {
        "value": 0.0, "unit": "builds"}
    assert not [m for m in line["metrics"] if m.startswith("ffm_table_")
                or m in ("gbdt_hist_ms_per_tree", "gbdt_route_ms_per_tree")]
    if "ffm_throttle_wait_ms_per_chunk" in line["metrics"]:
        assert line["metrics"]["ffm_throttle_wait_ms_per_chunk"]["value"] >= 0
    assert glob.glob(os.path.join(tiny_root, "benchmark", "out", "trace",
                                  workload, "plugins", "profile", "*",
                                  "*.xplane.pb"))
