"""``benchmark/readers/trace_unscoped_time.py`` on the traces recorded on
the chip (``benchmark/tests/data/``, no chip here): with one ``module``
and one list of scopes, the union of the listed scopes
(``trace_scope_time``) and the residual add up to the busy time of the
matched runs, to the nanosecond; a spec that lists nothing reads the busy
time; a trace without ``tf_op`` reads nothing; and the ``unscoped:`` line
a run prints names instructions that add up to the residual (ISSUE 50)."""

import json
import os
import re

import numpy as np
import pytest

from benchmark import cells, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(ROOT, "benchmark", "tests", "data")
unscoped = cells.load_module(ROOT, "readers", "trace_unscoped_time")
scope_time = cells.load_module(ROOT, "readers", "trace_scope_time")

STEP = r"^jit_step\("
GBDT = r"gbdt\.(hist|route|best_splits|leaf)"
# (trace, counter, its count, a module, lists to try). The GBDT trace was
# recorded with PR 24's scopes in the program; the other two before any,
# so their ``tf_op`` holds the primitives' names alone and a list of those
# stands in for scopes (a scope is searched for anywhere in ``tf_op``)
CASES = [
    ("gbdt_1m_2trees_scoped", "trees", 2, STEP,
     [GBDT, r"gbdt\.hist", r"gbdt\.route|gbdt\.leaf", r"no\.such\.scope"]),
    ("ffm_small_4chunks", "chunks", 4, STEP,
     [r"ffm\.(select|pairs|table_gather)", r"transpose\(jvp\(",
      r"scatter-add|/gather"]),
    ("allreduce_4chip_small", "programs", 4, r"^jit_bulk_allreduce\(",
     [r"psum_invariant", r"while/body", r"mp4j\.allreduce"]),
]


def _run(name, **counters):
    path = os.path.join(DATA, f"{name}.xplane.pb")
    trace = xplane.load(path)
    return {"trace": trace, "trace_path": path, "counters": counters,
            "window_ns": xplane.window_of(trace, "bench.slice"),
            "spans": {}}


def _busy_ns(run, module=None):
    """Busy nanoseconds inside the window, inside the runs of programs
    matching ``module`` where given, a chip's mean: computed here, event
    by event, not through the reader."""
    per_chip = []
    for chip, ev in run["trace"].ops.items():
        ev = ev.clip(*run["window_ns"])
        if module is not None:
            runs = run["trace"].modules[chip].matching(module)
            ev = ev.take([
                i for i, s in enumerate(ev.start)
                if any(a <= s < b for a, b in zip(runs.start, runs.end))])
        per_chip.append(xplane.union_ns(ev))
    return float(np.mean(per_chip))


@pytest.mark.parametrize("cut", [False, True], ids=["job", "module"])
@pytest.mark.parametrize("name,per,count,module,lists", CASES,
                         ids=[c[0] for c in CASES])
def test_listed_union_and_residual_are_the_busy_time(name, per, count,
                                                     module, lists, cut,
                                                     capsys):
    run = _run(name, **{per: count})
    module = module if cut else None
    busy = _busy_ns(run, module)
    assert busy > 0
    seen = set()
    for listed in lists:
        spec = {"scope": listed, "per": per, "scale": 1e9}
        if module is not None:
            spec["module"] = module
        left = unscoped.read(spec, run) * count
        named = scope_time.read(spec, run)
        # a program with no such scope: nothing is named, all is left
        named = 0.0 if named is None else named * count
        assert abs(left + named - busy) < 0.5, listed      # of a nanosecond
        seen.add(named > 0)
    assert seen == {False, True}        # a list that names, one that does not
    assert "unscoped: " in capsys.readouterr().out


def test_the_step_alone_is_less_than_the_job():
    """``module`` cuts the job's other programs (key folding, the
    conversions of the margins) out of the residual."""
    run = _run("gbdt_1m_2trees_scoped", trees=2)
    spec = {"scope": GBDT, "per": "trees", "scale": 1000.0}
    whole = unscoped.read(spec, run)
    step = unscoped.read({**spec, "module": STEP}, run)
    assert 0 < step < whole
    assert unscoped.read({**spec, "module": r"^jit_no_such\("}, run) is None


def test_a_spec_that_lists_nothing_reads_the_busy_time():
    run = _run("gbdt_1m_2trees_scoped", trees=2)
    busy = xplane.mean_busy_ns(run["trace"], *run["window_ns"])
    assert round(busy) == round(_busy_ns(run))
    for spec in ({"per": "trees"}, {"per": "trees", "scope": ""}):
        got = unscoped.read({**spec, "scale": 1e9}, run) * 2
        assert round(got) == round(busy)


def test_a_trace_without_tf_op_reads_nothing(monkeypatch):
    """A profile that drops the stat (a jax that stops writing it, a
    CPU's trace) gives nothing to tell named from nameless by: no number,
    and no exception."""
    run = _run("gbdt_1m_2trees_scoped", trees=2)
    names = unscoped.scopes.load(run["trace_path"])
    monkeypatch.setattr(unscoped.scopes, "load", lambda path: {
        chip: dict.fromkeys(events, "") for chip, events in names.items()})
    for spec in ({"scope": GBDT, "per": "trees"}, {"per": "trees"},
                 {"scope": GBDT, "module": STEP}):
        assert unscoped.read(spec, run) is None
    assert unscoped.read({"scope": GBDT}, {"counters": {}}) is None


def test_a_missing_counter_reads_nothing():
    run = _run("gbdt_1m_2trees_scoped", trees=0)
    assert unscoped.read({"scope": GBDT, "per": "trees"}, run) is None
    assert unscoped.read({"scope": GBDT, "per": "jobs"}, run) is None


def test_the_line_names_what_the_residual_holds(capsys):
    """The instructions ``longest`` names are the residual split by
    instruction: a parent ``while`` under no scope counts only where
    nothing named ran, an operation inside a named loop not at all."""
    run = _run("gbdt_1m_2trees_scoped", trees=2)
    spec = {"name": "gbdt_unscoped_ms_per_tree", "scope": GBDT,
            "per": "trees", "scale": 1000.0}
    value = unscoped.read(spec, run)
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("unscoped: ")]
    said = json.loads(line[0][len("unscoped: "):])
    assert said["metric"] == "gbdt_unscoped_ms_per_tree"
    assert said["busy"] - said["named"] == pytest.approx(value, rel=1e-12)
    (ev, named, stacks), = unscoped._split(spec, run)
    every = unscoped.longest(ev, named, stacks, limit=10 ** 6)
    assert sum(ns for *_, ns in every) == pytest.approx(
        value * 2 * 1e6, rel=1e-9)
    assert [e[:2] for e in every[:5]] == [e[:2] for e in said["longest"]]
    assert all(ns >= 0 for *_, ns in every)
    # an instruction comes with its name stack, which no listed scope is in
    assert all(not re.search(GBDT, stack) for _, stack, _ in every)
    assert any(stack.startswith("jit(step)/") for _, stack, _ in every)


def test_longest_on_hand_made_events():
    """A named loop hides its nameless child; a nameless loop keeps its
    own counter's time and its nameless child's, not its named child's."""
    names = ("%w1 = s32[] while(%p)", "%a = f32[] add(%x)",
             "%c = f32[] copy(%x)", "%w2 = s32[] while(%p)",
             "%b = f32[] multiply(%x)", "%c = f32[] copy(%x)")
    start = np.array([0.0, 1.0, 4.0, 10.0, 11.0, 15.0])
    end = np.array([8.0, 3.0, 6.0, 20.0, 14.0, 18.0])
    ev = xplane.Events(names, start, end)
    stacks = {names[3]: "jit(step)/while:", names[2]: ""}
    got = unscoped.longest(ev, {names[0], names[4]}, stacks)
    # w2's own 10 - 3 - 3 = 4 and its copy's 3; w1's copy is under a name
    assert got == [["w2 (while)", "jit(step)/while:", 4.0],
                   ["c (copy)", "", 3.0]]
