"""ISSUE 14 — whole-program concurrency analysis tests.

Covers the interprocedural substrate (callgraph index, lock model),
the three whole-program rules (R19 lock-order cycles, R20
blocking-under-lock, R21 callback/dispatch-under-lock) with
firing/non-firing/suppression grids — including a known-deadlock toy
module and the outbox-pattern negative case — plus the stale-baseline
strictness, the graph/explain/json CLI surfaces, and the regression
for the one true positive the pass found on the tree (the native
g++ build under the progression scheduler's condition variable).
"""

import textwrap

import pytest

from ytk_mp4j_tpu.analysis import baseline as baseline_mod
from ytk_mp4j_tpu.analysis import cli as cli_mod
from ytk_mp4j_tpu.analysis.engine import Engine, Program
from ytk_mp4j_tpu.analysis.rules import ALL_RULES, get_rules

COMM_PATH = "ytk_mp4j_tpu/comm/snippet.py"


def run_rule(rule_id, src, path=COMM_PATH, baseline=None):
    engine = Engine(rules=get_rules([rule_id]), baseline=baseline)
    result = engine.lint_source(textwrap.dedent(src), path)
    assert not [f for f in result.findings if f.rule == "E001"], \
        f"snippet failed to parse: {result.findings}"
    return result


def program_of(src, path=COMM_PATH):
    eng = Engine(rules=[])
    ctx, errs = eng._parse(textwrap.dedent(src), path)
    assert ctx is not None, errs
    return Program([ctx])


# ----------------------------------------------------------------------
# callgraph: index + conservative resolution
# ----------------------------------------------------------------------
def test_callgraph_resolves_self_methods_and_bases():
    idx = program_of("""
        class Base:
            def shared(self):
                return 1

        class C(Base):
            def run(self):
                self.helper()
                self.shared()

            def helper(self):
                pass
    """).index
    [mod] = idx.modules.values()
    c = mod.classes["C"]
    run = c.methods["run"]
    import ast
    calls = [n for n in ast.walk(run.node) if isinstance(n, ast.Call)]
    got = {idx.resolve_call(call, run)[0].display for call in calls}
    assert got == {"C.helper", "Base.shared"}


def test_callgraph_types_ctor_param_and_list_attrs():
    idx = program_of("""
        import threading

        class _Slot:
            def __init__(self):
                self.lock = threading.Lock()

        class Master:
            def __init__(self):
                self._slots: list[_Slot] = []
                self._lock = threading.Lock()

        class Controller:
            def __init__(self, master):
                self._master = master      # param-name heuristic
    """).index
    [mod] = idx.modules.values()
    master = mod.classes["Master"]
    assert idx.attr_type(master, "_slots").endswith(":_Slot") \
        and idx.attr_type(master, "_slots").startswith("list:")
    assert idx.attr_type(master, "_lock") == "threading.Lock"
    ctl = mod.classes["Controller"]
    assert idx.attr_type(ctl, "_master").endswith(":Master")


def test_callgraph_class_attr_method_binding():
    idx = program_of("""
        class V:
            def visit_A(self, n):
                return n
            visit_B = visit_A
    """).index
    [mod] = idx.modules.values()
    v = mod.classes["V"]
    assert v.methods["visit_B"] is v.methods["visit_A"]


def test_callgraph_unresolvable_contributes_no_edge():
    idx = program_of("""
        def f(x):
            x.mystery()         # unknown receiver
            unknown_fn()        # unknown function
    """).index
    [mod] = idx.modules.values()
    f = mod.functions["f"]
    import ast
    calls = [n for n in ast.walk(f.node) if isinstance(n, ast.Call)]
    assert all(idx.resolve_call(c, f) == [] for c in calls)


# ----------------------------------------------------------------------
# lock model: discovery, held sets, edges, witnesses
# ----------------------------------------------------------------------
def test_lockmodel_discovers_attr_module_and_local_locks():
    model = program_of("""
        import threading

        _mod_lock = threading.Lock()

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition()

            def local(self):
                lk = threading.Lock()
                with lk:
                    pass
    """).locks
    kinds = {d.display: d.kind for d in model.locks.values()}
    assert kinds["C._lock"] == "Lock"
    assert kinds["C._cv"] == "Condition"
    assert kinds["snippet._mod_lock"] == "Lock"
    assert any("<local:lk>" in k or "local" in d.attr
               for k, d in model.locks.items())


def test_lockmodel_with_nesting_builds_order_edge():
    model = program_of("""
        import threading

        class C:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def f(self):
                with self._a:
                    with self._b:
                        pass
    """).locks
    [edge] = model.edges.values()
    assert model.locks[edge.src].display == "C._a"
    assert model.locks[edge.dst].display == "C._b"
    assert edge.chain == ("C.f",)


def test_lockmodel_interprocedural_edge_with_witness_chain():
    model = program_of("""
        import threading

        class C:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def top(self):
                with self._a:
                    self.mid()

            def mid(self):
                self.bottom()

            def bottom(self):
                with self._b:
                    pass
    """).locks
    [edge] = model.edges.values()
    assert model.locks[edge.src].display == "C._a"
    assert model.locks[edge.dst].display == "C._b"
    assert edge.chain == ("C.top", "C.mid", "C.bottom")


def test_lockmodel_acquire_release_linear_tracking():
    model = program_of("""
        import threading

        class C:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def f(self):
                self._a.acquire()
                with self._b:       # edge a -> b
                    pass
                self._a.release()
                with self._b:       # NOT under a anymore
                    pass
    """).locks
    assert len(model.edges) == 1


def test_lockmodel_closure_bodies_get_empty_held_set():
    # a thread-body closure defined inside a `with` does NOT inherit
    # the definition site's held locks
    model = program_of("""
        import threading

        class C:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def f(self):
                with self._a:
                    def worker():
                        with self._b:
                            pass
    """).locks
    assert len(model.edges) == 0


def test_lockmodel_subscripted_receiver_resolves():
    model = program_of("""
        import threading

        class _Slot:
            def __init__(self):
                self.lock = threading.Lock()

        class M:
            def __init__(self):
                self._slots: list[_Slot] = []
                self._lock = threading.Lock()

            def push(self, r):
                with self._lock:
                    with self._slots[r].lock:
                        pass
    """).locks
    [edge] = model.edges.values()
    assert model.locks[edge.src].display == "M._lock"
    assert model.locks[edge.dst].display == "_Slot.lock"


TOY_DEADLOCK = """
    import threading

    class Master:
        def __init__(self):
            self._lock = threading.Lock()
            self._ctl = Controller(self)

        def status(self):
            with self._lock:
                return self._ctl.snapshot()

    class Controller:
        def __init__(self, master):
            self._lock = threading.Lock()
            self._master = master

        def snapshot(self):
            with self._lock:
                return 1

        def dispatch(self, ev):
            with self._lock:
                self._master.status()
"""


def test_lockmodel_cycle_detection_on_toy_deadlock():
    model = program_of(TOY_DEADLOCK).locks
    [scc] = model.cycles()
    names = {model.locks[k].display for k in scc}
    assert names == {"Master._lock", "Controller._lock"}


# ----------------------------------------------------------------------
# R19 — lock-order cycles
# ----------------------------------------------------------------------
def test_r19_fires_on_toy_deadlock_module():
    r = run_rule("R19", TOY_DEADLOCK)
    [f] = [f for f in r.findings if f.rule == "R19"]
    assert "Master._lock" in f.message
    assert "Controller._lock" in f.message
    assert "via" in f.message          # witness chains present


def test_r19_quiet_on_consistent_order():
    r = run_rule("R19", """
        import threading

        class C:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def f(self):
                with self._a:
                    with self._b:
                        pass

            def g(self):
                with self._a:
                    with self._b:
                        pass
    """)
    assert not r.findings


def test_r19_cross_module_cycle(tmp_path):
    pkg = tmp_path / "ytk_mp4j_tpu" / "comm"
    pkg.mkdir(parents=True)
    (pkg / "a.py").write_text(textwrap.dedent("""
        import threading
        from ytk_mp4j_tpu.comm.b import B

        class A:
            def __init__(self):
                self._lock = threading.Lock()
                self._b = B(self)

            def fold(self):
                with self._lock:
                    self._b.peek()
    """))
    (pkg / "b.py").write_text(textwrap.dedent("""
        import threading

        class B:
            def __init__(self, a):
                self._lock = threading.Lock()
                self._a = a

            def peek(self):
                with self._lock:
                    return 1

            def push(self):
                with self._lock:
                    self._a.fold()
    """))
    eng = Engine(rules=get_rules(["R19"]))
    result = eng.lint_paths([str(tmp_path)])
    assert [f.rule for f in result.findings] == ["R19"]


def test_r19_inline_suppression():
    # the cycle is charged at the first witness edge's frame
    # (Controller.dispatch's call into the master); a directive on
    # that line accepts it
    src = TOY_DEADLOCK.replace(
        "self._master.status()",
        "self._master.status()  # mp4j-lint: disable=R19 (toy)")
    r = run_rule("R19", src)
    assert not [f for f in r.findings if f.rule == "R19"]
    assert any(f.rule == "R19" for f in r.suppressed)


def test_r19_baseline_suppression():
    bl = baseline_mod.parse(textwrap.dedent("""
        [[suppression]]
        rule = "R19"
        file = "ytk_mp4j_tpu/comm/snippet.py"
        reason = "toy"
    """))
    r = run_rule("R19", TOY_DEADLOCK, baseline=bl)
    assert not r.findings
    assert any(f.rule == "R19" for f in r.suppressed)


# ----------------------------------------------------------------------
# R20 — blocking under a held lock
# ----------------------------------------------------------------------
def test_r20_fires_on_direct_send_under_lock():
    r = run_rule("R20", """
        import threading

        class S:
            def __init__(self, chan):
                self._lock = threading.Lock()
                self._chan = chan

            def flush(self, obj):
                with self._lock:
                    self._chan.send_obj(obj)
    """)
    [f] = r.findings
    assert f.rule == "R20" and "send_obj" in f.message
    assert "S._lock" in f.message


def test_r20_fires_interprocedurally_with_chain():
    r = run_rule("R20", """
        import threading

        class S:
            def __init__(self, chan):
                self._lock = threading.Lock()
                self._chan = chan

            def flush(self, obj):
                with self._lock:
                    self._ship(obj)

            def _ship(self, obj):
                self._relay(obj)

            def _relay(self, obj):
                self._chan.send_obj(obj)
    """)
    [f] = r.findings
    assert "S.flush -> S._ship -> S._relay" in f.message
    assert f.context == "S.flush"      # charged at the held frame


def test_r20_fires_on_wait_on_other_object():
    r = run_rule("R20", """
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._done = threading.Event()

            def stall(self):
                with self._lock:
                    self._done.wait()
    """)
    [f] = r.findings
    assert "wait" in f.message


def test_r20_quiet_on_wait_on_held_condition():
    # the house barrier pattern: cv.wait releases the cv
    r = run_rule("R20", """
        import threading

        class S:
            def __init__(self):
                self._cv = threading.Condition()

            def park(self):
                with self._cv:
                    self._cv.wait_for(lambda: True)
    """)
    assert not r.findings


def test_r20_fires_on_thread_join_and_subprocess():
    r = run_rule("R20", """
        import subprocess
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._thread = threading.Thread(target=print)

            def a(self):
                with self._lock:
                    self._thread.join()

            def b(self):
                with self._lock:
                    subprocess.run(["true"])
    """)
    assert len(r.findings) == 2


def test_r20_quiet_on_str_and_path_join():
    r = run_rule("R20", """
        import os
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()

            def fmt(self, parts):
                with self._lock:
                    return ", ".join(parts) + os.path.join("a", "b")
    """)
    assert not r.findings


def test_r20_quiet_outside_lock():
    r = run_rule("R20", """
        import threading

        class S:
            def __init__(self, chan):
                self._lock = threading.Lock()
                self._chan = chan

            def flush(self, obj):
                with self._lock:
                    out = obj
                self._chan.send_obj(out)
    """)
    assert not r.findings


def test_r20_inline_suppression():
    r = run_rule("R20", """
        import threading

        class S:
            def __init__(self, chan):
                self._lock = threading.Lock()
                self._chan = chan

            def flush(self, obj):
                with self._lock:
                    # mp4j-lint: disable=R20 (send serialization lock)
                    self._chan.send_obj(obj)
    """)
    assert not r.findings
    assert any(f.rule == "R20" for f in r.suppressed)


def test_r20_quiet_outside_covered_dirs():
    r = run_rule("R20", """
        import threading

        class S:
            def __init__(self, chan):
                self._lock = threading.Lock()
                self._chan = chan

            def flush(self, obj):
                with self._lock:
                    self._chan.send_obj(obj)
    """, path="ytk_mp4j_tpu/models/snippet.py")
    assert not r.findings


# ----------------------------------------------------------------------
# R21 — callback/dispatch under the minting lock
# ----------------------------------------------------------------------
def test_r21_fires_on_hook_under_lock():
    r = run_rule("R21", """
        import threading

        class C:
            def __init__(self, on_alert):
                self._lock = threading.Lock()
                self._terminal_hook = on_alert

            def settle(self, ev):
                with self._lock:
                    self._terminal_hook(ev)
    """)
    [f] = r.findings
    assert "_terminal_hook" in f.message and "C._lock" in f.message


def test_r21_fires_on_hook_via_chain():
    r = run_rule("R21", """
        import threading

        class C:
            def __init__(self, cb):
                self._lock = threading.Lock()
                self._cb = cb

            def settle(self, ev):
                with self._lock:
                    self._fan(ev)

            def _fan(self, ev):
                self._cb(ev)
    """)
    [f] = r.findings
    assert "C.settle -> C._fan" in f.message


def test_r21_fires_on_reentrant_dispatch():
    r = run_rule("R21", """
        import threading

        class Ctl:
            def __init__(self, master):
                self._lock = threading.Lock()
                self._master = master

            def dispatch(self, ev):
                with self._lock:
                    self._master.push(ev)

            def status(self):
                with self._lock:
                    return 1

        class Master:
            def __init__(self):
                self._ctl = Ctl(self)

            def push(self, ev):
                self._ctl.status()
    """)
    assert any("re-acquires" in f.message and "Ctl._lock" in f.message
               for f in r.findings)


def test_r21_quiet_on_rlock_reentry():
    r = run_rule("R21", """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.RLock()

            def outer(self):
                with self._lock:
                    self.inner()

            def inner(self):
                with self._lock:
                    return 1
    """)
    assert not [f for f in r.findings if "re-acquires" in f.message]


def test_r21_quiet_on_outbox_pattern():
    # the PR 13 negative case: mint under the lock, dispatch outside
    r = run_rule("R21", """
        import threading

        class Ctl:
            def __init__(self, hook):
                self._lock = threading.Lock()
                self._hook = hook
                self._outbox = []

            def settle(self, ev):
                with self._lock:
                    self._outbox.append(ev)
                self._flush()

            def _flush(self):
                with self._lock:
                    out, self._outbox = self._outbox, []
                for ev in out:
                    self._hook(ev)
    """)
    assert not r.findings


def test_r21_inline_suppression():
    r = run_rule("R21", """
        import threading

        class C:
            def __init__(self, cb):
                self._lock = threading.Lock()
                self._cb = cb

            def settle(self, ev):
                with self._lock:
                    # mp4j-lint: disable=R21 (hook is a pure counter)
                    self._cb(ev)
    """)
    assert not r.findings
    assert any(f.rule == "R21" for f in r.suppressed)


# ----------------------------------------------------------------------
# stale-baseline strictness + prune
# ----------------------------------------------------------------------
STALE_BL = """
    [[suppression]]
    rule = "R1"
    file = "ytk_mp4j_tpu/comm/gone.py"
    context = "Gone.f"
    reason = "site was deleted two PRs ago"
"""


def _pkg_tree(tmp_path):
    """A throwaway tree whose linted paths cover the ytk_mp4j_tpu
    package segment (staleness is only judged for covered entries)."""
    pkg = tmp_path / "ytk_mp4j_tpu" / "comm"
    pkg.mkdir(parents=True)
    (pkg / "ok.py").write_text("def f():\n    return 1\n")
    return tmp_path


def test_stale_baseline_entry_is_finding_in_strict_mode(tmp_path):
    bl = baseline_mod.parse(textwrap.dedent(STALE_BL))
    eng = Engine(rules=get_rules(["R1"]), baseline=bl,
                 strict_baseline=True, baseline_path="bl.toml")
    result = eng.lint_paths([str(_pkg_tree(tmp_path))])
    [f] = result.findings
    assert f.rule == "B001" and "stale baseline entry" in f.message
    assert f.path == "bl.toml" and f.line == 2   # the entry's own line


def test_stale_baseline_quiet_without_strict(tmp_path):
    bl = baseline_mod.parse(textwrap.dedent(STALE_BL))
    eng = Engine(rules=get_rules(["R1"]), baseline=bl)
    assert eng.lint_paths([str(_pkg_tree(tmp_path))]).ok


def test_strict_partial_runs_cannot_condemn_out_of_scope_entries(
        tmp_path):
    """Code-review regression: a --select run (the entry's rule never
    ran) or a single-file run (the entry's file out of scope) must
    not flag entries it could not judge."""
    tree = _pkg_tree(tmp_path)
    bl = baseline_mod.parse(textwrap.dedent(STALE_BL))   # an R1 entry
    eng = Engine(rules=get_rules(["R2"]), baseline=bl,
                 strict_baseline=True, baseline_path="bl.toml")
    assert eng.lint_paths([str(tree)]).ok     # R1 never ran
    other = tmp_path / "standalone.py"
    other.write_text("def f():\n    return 1\n")
    eng = Engine(rules=get_rules(["R1"]), baseline=bl,
                 strict_baseline=True, baseline_path="bl.toml")
    assert eng.lint_paths([str(other)]).ok    # file out of scope


def test_prune_baseline_select_keeps_unjudged_entries(tmp_path):
    """Code-review regression: `--select R18 --prune-baseline` used to
    delete every entry whose rule did not run."""
    target = tmp_path / "bl.toml"
    tree = _pkg_tree(tmp_path)
    bad = tmp_path / "ytk_mp4j_tpu" / "comm" / "bad.py"
    bad.write_text("def f(c):\n    if c.rank:\n        c.barrier()\n")
    target.write_text(textwrap.dedent("""
        [[suppression]]
        rule = "R1"
        file = "ytk_mp4j_tpu/comm/bad.py"
        context = "f"
        reason = "live, but R1 will not run"
    """))
    rc = cli_mod.main([str(tree), "--baseline", str(target),
                       "--select", "R2", "--prune-baseline"])
    assert rc == 0
    assert 'reason = "live, but R1 will not run"' in target.read_text()


def test_prune_baseline_rewrites_keeping_reasons(tmp_path):
    target = tmp_path / "bl.toml"
    bad = tmp_path / "ytk_mp4j_tpu" / "comm" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("def f(c):\n    if c.rank:\n        c.barrier()\n")
    target.write_text(textwrap.dedent("""
        # header comment survives the rewrite

        [[suppression]]
        rule = "R1"
        file = "ytk_mp4j_tpu/comm/bad.py"
        context = "f"
        reason = "the live entry"
    """) + textwrap.dedent(STALE_BL))
    rc = cli_mod.main([str(tmp_path), "--baseline", str(target),
                       "--prune-baseline"])
    assert rc == 0
    text = target.read_text()
    assert "header comment survives" in text
    assert 'reason = "the live entry"' in text
    assert "gone.py" not in text
    # and the pruned baseline still suppresses the live finding
    rc = cli_mod.main([str(tmp_path), "--baseline", str(target),
                       "--strict"])
    assert rc == 0


# ----------------------------------------------------------------------
# CLI surfaces: --json, --explain, graph --dot
# ----------------------------------------------------------------------
def test_cli_json_flag(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("def f(c):\n    if c.rank:\n        c.barrier()\n")
    assert cli_mod.main([str(bad), "--json"]) == 1
    out = capsys.readouterr().out
    import json
    doc = json.loads(out)
    assert doc["findings"][0]["rule"] == "R1"


@pytest.mark.parametrize("cls", ALL_RULES,
                         ids=[c.rule_id for c in ALL_RULES])
def test_every_rule_example_fires(cls):
    """--explain's catalogue stays honest: each rule's example is a
    real firing case (program rules included, proving single-file
    mode runs them)."""
    assert cls.example, f"{cls.rule_id} has no example"
    eng = Engine(rules=[cls()])
    r = eng.lint_source(cls.example, cls.example_path)
    assert not [f for f in r.findings if f.rule == "E001"]
    assert any(f.rule == cls.rule_id for f in r.findings)


def test_cli_explain(capsys):
    assert cli_mod.main(["--explain", "R20"]) == 0
    out = capsys.readouterr().out
    assert "R20" in out and "firing example" in out and "fires:" in out
    assert cli_mod.main(["--explain", "R99"]) == 2


def test_cli_graph_dot(tmp_path, capsys):
    pkg = tmp_path / "ytk_mp4j_tpu" / "comm"
    pkg.mkdir(parents=True)
    (pkg / "mod.py").write_text(textwrap.dedent("""
        import threading

        class C:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def f(self):
                with self._a:
                    with self._b:
                        pass
    """))
    assert cli_mod.main(["graph", str(tmp_path), "--dot"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("digraph mp4j_lock_order")
    assert "C._a" in out and "C._b" in out and "C.f" in out


def test_cli_graph_text_reports_cycles(tmp_path, capsys):
    pkg = tmp_path / "ytk_mp4j_tpu" / "comm"
    pkg.mkdir(parents=True)
    (pkg / "mod.py").write_text(textwrap.dedent(TOY_DEADLOCK))
    assert cli_mod.main(["graph", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "1 cycle(s)" in out and "CYCLE:" in out


# ----------------------------------------------------------------------
# regression: the true positive R20 found on the tree
# ----------------------------------------------------------------------
def test_reduce_opcode_never_builds(monkeypatch):
    """PR 14's R20 true positive: `reduce_opcode` used to trigger the
    lazy native load — whose first call shells out to g++ — from
    under the progression scheduler's condition variable. It must now
    read the cached verdict only; the scheduler forces the one-time
    attempt at construction, outside any lock."""
    from ytk_mp4j_tpu.utils import native
    from ytk_mp4j_tpu.operators import Operators

    def boom():
        raise AssertionError("reduce_opcode must not trigger _load")

    monkeypatch.setattr(native, "_load", boom)
    # unattempted verdict: no native kernels, NO build attempt
    monkeypatch.setattr(native, "HAVE_NATIVE", None)
    monkeypatch.setattr(native, "_lib", None)
    assert native.reduce_opcode(Operators.SUM, "float32") is None
    # negative cached verdict: same
    monkeypatch.setattr(native, "HAVE_NATIVE", False)
    assert native.reduce_opcode(Operators.SUM, "float32") is None


def test_analysis_package_is_self_clean():
    """ISSUE 14 satellite: analysis/ itself is in the linted path set
    and passes every rule — the linter polices the linter."""
    import os

    import ytk_mp4j_tpu
    from ytk_mp4j_tpu.analysis import lint_paths

    pkg = os.path.join(os.path.dirname(ytk_mp4j_tpu.__file__),
                       "analysis")
    result = lint_paths([pkg])
    assert result.ok, "\n".join(f.format() for f in result.findings)
    # and the tier-1 gate really collects it (no skip list hides it)
    files = Engine.collect_files(
        [os.path.dirname(ytk_mp4j_tpu.__file__)])
    assert any(f.replace(os.sep, "/").endswith("analysis/locks.py")
               for f in files)


def test_ensure_loaded_matches_have_native():
    from ytk_mp4j_tpu.utils import native
    from ytk_mp4j_tpu.operators import Operators

    ok = native.ensure_loaded()
    assert ok is bool(native.HAVE_NATIVE)
    if ok:
        # with the verdict cached, reduce_opcode serves codes again
        assert native.reduce_opcode(Operators.SUM, "float32") \
            is not None
