"""Tier-1 gate: the comm stack must lint clean forever.

Runs mp4j-lint (all rules, committed baseline, STRICT baseline mode
since ISSUE 14) over the installed ``ytk_mp4j_tpu`` package and fails
on any unsuppressed finding — the static analogue of the differential
tests: every future PR to comm/, ops/, models/ inherits the protocol
checks by construction. The whole-program rules R19-R21 run here too
(the package is the program), and the discovered lock-order graph
must stay cycle-free: the concurrency disciplines the PR texts state
in prose are a checked invariant from this gate on.

Also proves the gate has teeth: a scratch file seeded with a deliberate
rank-conditional collective must be reported by R1 at the right
file:line.
"""

import os
import textwrap

import ytk_mp4j_tpu
from ytk_mp4j_tpu.analysis import lint_paths
from ytk_mp4j_tpu.analysis.cli import DEFAULT_BASELINE, main

PKG_DIR = os.path.dirname(ytk_mp4j_tpu.__file__)


def test_repo_lints_clean():
    result = lint_paths([PKG_DIR])
    assert result.ok, (
        "unsuppressed mp4j-lint findings (fix them or add a reasoned "
        "suppression):\n" + "\n".join(f.format() for f in result.findings))


def test_cli_exits_zero_on_repo():
    assert main([PKG_DIR]) == 0


def test_cli_exits_zero_on_repo_strict():
    # strict mode: a baseline entry matching no finding is a B001
    # error — the accepted surface shrinks with the code
    assert main([PKG_DIR, "--strict"]) == 0


def test_package_lock_order_graph_is_cycle_free():
    """The job-wide lock-order graph over the real package has no
    cycle — the "tuner lock, then master lock" / outbox disciplines
    are machine-checked (ISSUE 14 acceptance)."""
    from ytk_mp4j_tpu.analysis.engine import Engine, Program
    contexts, errors = Engine(rules=[]).load_contexts([PKG_DIR])
    assert not errors, errors
    model = Program(contexts).locks
    # sanity: the model actually sees the package's lock landscape
    # (a refactor that silently blinds discovery must fail loudly)
    displays = {d.display for d in model.locks.values()}
    assert {"Master._lock", "_Slot.lock", "Master._tuner_lock",
            "ProcessCommSlave._tel_lock",
            "ProcessCommSlave._master_lock"} <= displays
    assert len(model.edges) >= 2, "order edges vanished — model blind?"
    assert model.cycles() == [], (
        "lock-order cycle introduced:\n" + "\n".join(
            "  " + " <-> ".join(model.locks[k].display for k in scc)
            for scc in model.cycles()))


def test_package_shared_field_locksets_clean_modulo_baseline():
    """ISSUE 16 acceptance: the package's shared-field lockset report
    is clean modulo the committed baseline — every mutable field
    reachable from >= 2 thread roots either has a consistent lockset
    or a reasoned R23 suppression naming why lock-free publication is
    safe there (the cycle-free check's sibling for data races)."""
    from ytk_mp4j_tpu.analysis import baseline as baseline_mod
    from ytk_mp4j_tpu.analysis.engine import Engine, Program
    from ytk_mp4j_tpu.analysis.rules import get_rules
    contexts, errors = Engine(rules=[]).load_contexts([PKG_DIR])
    assert not errors, errors
    model = Program(contexts).races
    # sanity: the model actually sees the package's concurrency
    # (a refactor that silently blinds root discovery must fail loudly)
    assert any(r.startswith("thread:") for r in model.roots), \
        "no thread roots discovered — model blind?"
    assert "main" in model.roots
    shared = model.shared_fields()
    assert len(shared) >= 10, "shared-field discovery collapsed"
    displays = {fr.display for fr in shared}
    assert "Master._slots" in displays
    # the verdict: racy fields exist (the documented lock-free
    # publication sites) but every one is baselined with a reason
    bl = baseline_mod.load(DEFAULT_BASELINE)
    result = Engine(rules=get_rules(["R23"]),
                    baseline=bl).lint_paths([PKG_DIR])
    assert result.ok, (
        "shared field with inconsistent lockset (fix it or add a "
        "reasoned R23 suppression):\n"
        + "\n".join(f.format() for f in result.findings))


def test_committed_baseline_exists_and_is_fully_used():
    assert os.path.exists(DEFAULT_BASELINE)
    from ytk_mp4j_tpu.analysis import baseline as baseline_mod
    bl = baseline_mod.load(DEFAULT_BASELINE)
    assert bl.entries, "baseline should carry the accepted findings"
    assert all(e.reason for e in bl.entries), \
        "every baseline entry needs a recorded reason"
    # every committed suppression must still match a real finding —
    # stale entries are B001 findings in strict mode, so the gate
    # enforces it structurally; this asserts the engine-level view
    from ytk_mp4j_tpu.analysis.engine import Engine
    result = Engine(baseline=bl, strict_baseline=True,
                    baseline_path=DEFAULT_BASELINE).lint_paths([PKG_DIR])
    assert result.ok, "\n".join(f.format() for f in result.findings)
    assert not bl.unused(), \
        f"stale baseline entries: {bl.unused()}"


def test_seeded_rank_conditional_collective_is_caught(tmp_path):
    scratch = tmp_path / "ytk_mp4j_tpu" / "comm" / "seeded.py"
    scratch.parent.mkdir(parents=True)
    scratch.write_text(textwrap.dedent("""
        def broken_step(comm, grads):       # line 2
            comm.allreduce_array(grads)     # line 3
            if comm.rank == 0:              # line 4 <- R1 here
                comm.barrier()
    """))
    result = lint_paths([str(tmp_path)])
    r1 = [f for f in result.findings if f.rule == "R1"]
    assert len(r1) == 1
    assert r1[0].path.endswith("ytk_mp4j_tpu/comm/seeded.py")
    assert r1[0].line == 4
    assert r1[0].context == "broken_step"


def test_cli_reports_seeded_finding(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("def f(c):\n    if c.rank:\n        c.barrier()\n")
    assert main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "R1" in out and "bad.py:2" in out
