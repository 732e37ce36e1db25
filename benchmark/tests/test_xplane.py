"""The trace reduction: known busy, idle and per-kernel numbers on the
recorded traces, each recomputed here by a cruder method, and the corner
cases on hand-made traces."""

import os

import numpy as np
import pytest

import jax

from benchmark import cells, xplane

from conftest import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
trace_idle_share = cells.load_module(ROOT, "readers", "trace_idle_share")
trace_op_time = cells.load_module(ROOT, "readers", "trace_op_time")
KERNEL = 'custom_call_target="tpu_custom_call"'


def _raw_ops(path):
    """(text, start, end) of every XLA Ops event, read with no help from
    the reducer."""
    data = jax.profiler.ProfileData.from_file(path)
    plane = next(p for p in data.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _raster_busy(intervals, t0, t1, step=1000.0):
    """Busy time by rastering the window into ``step``-ns cells."""
    cells = np.zeros(int(np.ceil((t1 - t0) / step)), bool)
    for s, e in intervals:
        a, b = max(s, t0), min(e, t1)
        if b > a:
            cells[int((a - t0) // step): int(np.ceil((b - t0) / step))] = True
    return cells.sum() * step


def test_gbdt_trace_known_numbers():
    path = os.path.join(DATA, "gbdt_1m_2trees.xplane.pb")
    trace = xplane.load(path)
    assert sorted(trace.ops) == [0] and len(trace.ops[0]) == 747
    t0, t1 = xplane.window_of(trace, "bench.slice")
    assert t1 - t0 == pytest.approx(145_908_250.0)
    raw = _raw_ops(path)
    busy = xplane.mean_busy_ns(trace, t0, t1)
    assert busy == pytest.approx(121_203_902.0)
    # events of this line do not nest, so the union is the plain sum
    assert busy == pytest.approx(sum(e - s for _, s, e in raw))
    assert busy == pytest.approx(
        _raster_busy([(s, e) for _, s, e in raw], t0, t1), rel=0.02)
    kernels = [(s, e) for n, s, e in raw if KERNEL in n]
    assert len(kernels) == 12                   # 6 levels x 2 trees
    kernel_s = xplane.op_seconds(trace, KERNEL, t0, t1)
    assert kernel_s == pytest.approx(0.104155698)
    assert kernel_s == pytest.approx(sum(e - s for s, e in kernels) / 1e9)
    assert xplane.op_seconds(trace, KERNEL, t0, t1,
                             module=r"^jit_step\(") == kernel_s
    assert xplane.op_seconds(trace, KERNEL, t0, t1,
                             module=r"^jit_nothing\(") == 0.0
    assert xplane.op_seconds(trace, r" all-reduce\(", t0, t1) == 0.0

    run = {"trace": trace, "window_ns": (t0, t1), "counters": {"trees": 2}}
    idle = trace_idle_share.read({}, run)
    assert idle == pytest.approx(100 * (1 - 121_203_902.0 / 145_908_250.0))
    per_tree = trace_op_time.read(
        {"pattern": KERNEL, "per": "trees", "scale": 1e3}, run)
    assert per_tree == pytest.approx(52.077849)

    b = xplane.breakdown(trace, t0, t1)
    assert b["device_ops"][0][0] == "step (custom-call tpu_custom_call)"
    assert b["device_ops"][0][1] == pytest.approx(kernel_s)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    gaps = dict(b["idle_gaps"])
    assert "PjitFunction(step)" in gaps     # the host was dispatching
    assert sum(gaps.values()) == pytest.approx((t1 - t0 - busy) / 1e9)


def test_ffm_trace_known_numbers():
    path = os.path.join(DATA, "ffm_small_4chunks.xplane.pb")
    trace = xplane.load(path)
    t0, t1 = xplane.window_of(trace, "bench.slice")
    raw = _raw_ops(path)
    custom = [(s, e) for n, s, e in raw
              if " fusion(" in n and "kind=kCustom" in n]
    assert len(custom) == 16                    # 4 a chunk x 4 chunks
    got = xplane.op_seconds(trace, r" fusion\(.*kind=kCustom", t0, t1)
    assert got == pytest.approx(0.142166416)
    assert got == pytest.approx(sum(e - s for s, e in custom) / 1e9)
    assert xplane.mean_busy_ns(trace, t0, t1) == pytest.approx(146_071_679.0)
    assert xplane.op_seconds(trace, KERNEL, t0, t1) == 0.0
    assert len(trace.modules[0].matching(r"^jit_step\(")) == 4


def test_four_chip_trace_known_numbers():
    """Two runs of the hist program (2 trees' worth each) and two of the
    bulk program (2 allreduces each, 1Mi elements) on four chips."""
    path = os.path.join(DATA, "allreduce_4chip_small.xplane.pb")
    trace = xplane.load(path)
    assert sorted(trace.ops) == [0, 1, 2, 3]
    t0, t1 = xplane.window_of(trace, "bench.slice")
    pat = r" all-reduce(-start|-done)?\("
    per_chip_busy, per_chip_collective = [], []
    for chip in range(4):
        ev = trace.ops[chip].clip(t0, t1)
        hits = ev.matching(pat)
        assert len(hits) == 2 * 2 * 6 + 2 * 2
        # a while holds its body: own times add up to the union
        assert xplane.self_ns(ev).sum() == pytest.approx(xplane.union_ns(ev))
        assert (ev.end - ev.start).sum() > 1.5 * xplane.union_ns(ev)
        per_chip_busy.append(xplane.union_ns(ev))
        per_chip_collective.append((hits.end - hits.start).sum())
        runs = [n.split("(")[0] for n in trace.modules[chip].names]
        assert runs == ["jit_hist_tree_allreduces"] * 2 \
            + ["jit_bulk_allreduce"] * 2
    assert xplane.mean_busy_ns(trace, t0, t1) == pytest.approx(
        np.mean(per_chip_busy)) == pytest.approx(601_130.25)
    everything = xplane.op_seconds(trace, pat, t0, t1)
    assert everything == pytest.approx(np.mean(per_chip_collective) / 1e9)
    hist = xplane.op_seconds(trace, pat, t0, t1,
                             module=r"^jit_hist_tree_allreduces\(")
    bulk = xplane.op_seconds(trace, pat, t0, t1,
                             module=r"^jit_bulk_allreduce\(")
    assert hist == pytest.approx(0.00024595675)
    assert bulk == pytest.approx(0.00029148525)
    assert hist + bulk == pytest.approx(everything)
    top = xplane.top_ops(trace, t0, t1)
    assert top[0][0] == "psum_invariant (all-reduce)"
    assert top[0][1] == pytest.approx(everything)
    assert dict(top)["while (while)"] < 1e-6    # its body is not its own


def _events(rows):
    rows = sorted(rows, key=lambda r: (r[1], r[1] - r[2]))  # as load() sorts
    return xplane.Events(tuple(r[0] for r in rows),
                         np.array([r[1] for r in rows], float),
                         np.array([r[2] for r in rows], float))


def test_union_counts_nested_and_overlapping_once():
    ev = _events([("a", 0, 10), ("b", 2, 5), ("c", 8, 14), ("d", 20, 21)])
    assert xplane.union_ns(ev) == 15
    assert xplane.union_ns(ev.clip(4, 20.5)) == 10.5
    assert xplane.union_ns(_events([])) == 0


def test_self_time_takes_the_body_out_of_a_while():
    loop = "%while.2 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b"
    ar = "%psum.1 = f32[8]{0} all-reduce(f32[8]{0} %p), replica_groups={}"
    ev = _events([(ar, 10, 30), (loop, 10, 100), (ar, 40, 70), ("%x = f32[] add(f32[] %a)", 120, 130)])
    assert ev.names[0] == loop                  # the parent sorts first
    assert list(xplane.self_ns(ev)) == [40.0, 20.0, 30.0, 10.0]
    trace = xplane.Trace({0: ev}, {}, _events([]))
    assert xplane.mean_busy_ns(trace, 0, 200) == 100
    assert xplane.top_ops(trace, 0, 200) == [
        ["psum (all-reduce)", pytest.approx(50e-9)],
        ["while (while)", pytest.approx(40e-9)],
        ["x (add)", pytest.approx(10e-9)]]


def test_modules_chips_and_gaps_on_a_hand_made_trace():
    ar = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %p), replica_groups={}"
    mul = "%multiply_fusion.2 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop"
    ops = {
        0: _events([(ar, 100, 140), (mul, 140, 150), (ar, 300, 360)]),
        1: _events([(ar, 100, 120), (mul, 120, 150), (ar, 300, 320)]),
        2: _events([]),                         # a chip that ran nothing
    }
    modules = {c: _events([("jit_hist(1)", 100, 150), ("jit_bulk(2)", 300, 360)])
               for c in (0, 1)}
    host = _events([("bench.slice", 0, 400), ("bench.hist", 90, 160),
                    ("dispatch", 150, 300), ("tiny", 200, 201)])
    trace = xplane.Trace(ops, modules, host)
    assert xplane.window_of(trace, "bench.slice") == (0.0, 400.0)
    assert xplane.window_of(trace, "absent") == (100.0, 360.0)
    assert xplane.mean_busy_ns(trace, 0, 400) == (110 + 70) / 2
    pat = r" all-reduce(-start|-done)?\("
    approx = pytest.approx
    assert xplane.op_seconds(trace, pat, 0, 400) == approx(70e-9)
    assert xplane.op_seconds(trace, pat, 0, 400,
                             module=r"^jit_hist\(") == approx(30e-9)
    assert xplane.op_seconds(trace, pat, 0, 400,
                             module=r"^jit_bulk\(") == approx(40e-9)
    assert xplane.op_seconds(xplane.Trace({}, {}, host), pat, 0, 400) is None
    top = xplane.top_ops(trace, 0, 400)
    assert top[0] == ["all-reduce (all-reduce)", approx(70e-9)]
    assert top[1] == ["multiply_fusion (fusion)", approx(20e-9)]
    gaps = dict(xplane.idle_gaps(trace, 0, 400))
    # the innermost host event that covers most of each gap names it
    assert gaps == {"dispatch": approx(150e-9), "bench.slice": approx(140e-9)}


def test_label_of_instruction_text():
    assert xplane.label(
        '%step.6 = f32[4,7168]{1,0:T(4,128)S(1)} custom-call(s32[8,28]{1,0} '
        '%pad.0), custom_call_target="tpu_custom_call", x={}'
    ) == "step (custom-call tpu_custom_call)"
    assert xplane.label("%fusion = f32[2]{0} fusion(f32[2]{0} %a), kind=kLoop") \
        == "fusion (fusion)"
    assert xplane.label("%copy-done.12 = f32[2]{0} copy-done((f32[2]) %c)") \
        == "copy-done (copy-done)"
    assert xplane.label("not an instruction") == "not an instruction"
