"""The cell ``gbdt-bosch-968-leafwise.train`` end to end at a toy size
through ``run.main`` itself, on the CPU with the platform check stubbed
(by hand, like the rest of this directory): the contract's last line,
``correct`` true with the replay of the first job's first two trees and
its last and every job's counts and margins on its own label draw among
its checks, a window that is a whole multiple of the draws, the cell's metrics
found by name, a program without the policy refused before any table is
drawn, and the controls that must come out ``correct: false``: a tree
whose order is not best-first, histograms without their lo part, and a
trainer whose count of the rows it built is off in a tree the replay
does not visit. The lists pin what the cell MUST report, not all it
may."""

import json
import os

import numpy as np
import pytest

from benchmark import cells, run

from conftest import ROOT

CELL = "gbdt-bosch-968-leafwise.train"
# ``compared``: each number the check compared beside its limit, last
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}
# the shared entries ``gbdt-bosch-968.train`` lists and this cell's
# readers find something for (since PR 57 the kernel's roofline and the
# step's share of the peak among them, counted by the rows a tree
# needs), and the three of its own
SHARED = {"gbdt_device_idle_share", "peak_hbm_gb", "compile_s",
          "compiles_in_window", "step_builds_in_window",
          "gbdt_stage_ms_per_job", "gbdt_dispatch_ms_per_tree",
          "gbdt_dispatch_max_ms", "gbdt_fetch_wait_ms_per_job",
          "gbdt_hist_ms_per_tree", "gbdt_hist_glue_ms_per_tree",
          "gbdt_bins_relayout_ms_per_tree", "gbdt_route_ms_per_tree",
          "gbdt_best_splits_ms_per_tree", "gbdt_leaf_ms_per_tree",
          "gbdt_split_leaf_ms_per_tree", "stage_prep_ms_per_job",
          "stage_send_ms_per_job", "stage_sends_per_job",
          "stage_link_wait_ms_per_job", "stage_device_wait_ms_per_job",
          "stage_place_ms_per_job", "stage_place_device_ms_per_job",
          "stage_gbps", "setup_gbdt_table_s", "setup_gbdt_warmup_s",
          "hist_kernel_roofline", "gbdt_step_mfu"}
OWN = {"gbdt_grow_ms_per_tree", "gbdt_grow_unscoped_ms_per_tree",
       "gbdt_grow_rows_built_share"}
# whose scope lists are the level-wise tree's
NOT_HERE = {"gbdt_hist_level0_ms_per_tree", "gbdt_hist_level5_ms_per_tree",
            "hist_kernel_ms_per_tree", "gbdt_unscoped_ms_per_tree"}
RETIRED = {"gbdt_grow_hist_roofline", "score_stage_place_ms_per_job"}
# four trees: the replay visits 0, 1 and 3 and the count all four
TOY = dict(rows=3000, n_features=200, depth=5, max_leaves=8, n_trees=4)


@pytest.fixture
def toy_root(tiny_root):
    """``tiny_root`` with this cell's table cut to a toy: the width stays
    above one feature block (200 > 128), most cells stay missing, the
    budget of leaves (8 of 32) binds long before the cap on depth."""
    path = os.path.join(tiny_root, "benchmark", "configs",
                        "gbdt-bosch-968-leafwise.json")
    with open(path) as f:
        doc = json.load(f)
    doc.update(TOY)
    with open(path, "w") as f:
        json.dump(doc, f)
    return tiny_root


def _run(capsys, root, trace, seed=5300000017):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.5", "--trace", str(trace)], root=root)
    captured = capsys.readouterr()
    _run.err = captured.err.strip().splitlines()   # the last run's stderr
    return rc, captured.out.strip().splitlines()


def _window(lines) -> dict:
    return json.loads(next(ln for ln in lines if ln.startswith("window: "))
                      [len("window: "):])


def test_the_cell_reports_trees_per_s_and_its_own_layer_metrics():
    cell = cells.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.adapter_name == "gbdt_leafwise"
    assert [m["name"] for m in cell.end_to_end] == ["trees_per_s", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert SHARED | OWN <= names and not names & (NOT_HERE | RETIRED)
    for m in cell.per_layer:
        assert m["spec"]["name"] == m["name"] and "adapters" not in m["spec"]
        for key in ("layer", "moves", "source"):
            assert m["spec"][key] == m[key], (m["name"], key)
        assert CELL in m["workloads"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "readers", f"{m['spec']['reader']}.py"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name in OWN:        # the new entries are this cell's alone
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
    # gbdt-bosch-968's keys and values, but for the policy and the cut
    c = cell.config
    twin = cells.load_cell(ROOT, "gbdt-bosch-968.train").config
    differs = {k for k in set(c) | set(twin)
               if k not in c or k not in twin or c[k] != twin[k]}
    assert differs == {"name", "adapter", "architecture", "source",
                       "deployment", "grow_policy", "max_leaves", "depth",
                       "n_trees", "guarantees", "reduced", "assumed"}
    assert (c["grow_policy"], c["max_leaves"], c["depth"], c["n_trees"]) == (
        "loss", 70, 7, 24)
    assert c["architecture"] is None and list(c["reduced"]) == ["n_trees"]
    entry = next(e for e in bench["configs"]
                 if e["name"] == "gbdt-bosch-968-leafwise")
    assert entry["reduced"] == ["n_trees"] and len(entry["source"]) <= 200
    assert entry is bench["configs"][-1]
    assert bench["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2
    assert len(bench["workloads"]) == 11 and len(bench["per_layer"]) == 124
    assert not RETIRED & {m["name"] for m in bench["per_layer"]}
    assert "24 leaf-wise trees" in bench["workloads"][-1]["why"]


def test_the_accepted_cells_report_none_of_this_cells_metrics():
    for name in ("gbdt-higgs-11m.train", "gbdt-bosch-968.train",
                 "gbdt-bosch-968-raw.train-raw-chunks"):
        got = {m["name"] for m in cells.load_cell(ROOT, name).per_layer}
        assert not got & OWN and NOT_HERE <= got | {"hist_kernel_ms_per_tree"}
        assert {"hist_kernel_roofline", "gbdt_step_mfu"} <= got


def test_untraced_run(capsys, toy_root):
    rc, lines = _run(capsys, toy_root, trace=0)
    assert rc == 0
    line = json.loads(lines[-1])
    assert set(line) == LINE_KEYS
    assert list(line)[-1] == "compared" and all(
        len(pair) == 2 for pair in line["compared"].values())
    assert line["correct"] is True and line["failed"] == 0
    window = _window(lines)
    counters = window["counters"]
    # a job a label draw in turn, and a window a whole multiple of the
    # six draws however long a job takes (half a second is one job's)
    jobs = counters["jobs"]
    assert line["attempted"] == jobs and jobs >= 6 and jobs % 6 == 0
    assert window["log"]["job_draws"] == [i % 6 for i in range(jobs)]
    # what was compared, beside its limit: last on standard error too
    compared = line["compared"]
    assert compared["grow_rows_built"][0] == compared["grow_rows_built"][1]
    assert compared["grow_splits"] == [28 * jobs, 28 * jobs]
    assert compared["jobs_with_counts_off"] == [0, 0]
    assert compared["hist_prefix_sum_err"][1] == 1.6e-5
    assert _run.err[-len(compared):] == [
        f"compared: {name} {number} limit {limit}"
        for name, (number, limit) in compared.items()]
    assert set(line["metrics"]) == {"trees_per_s", "setup_s"}
    assert line["metrics"]["trees_per_s"]["value"] > 0
    assert window["compiles_in_window"] == 0
    check = window["check"]
    assert check["root_ok"] and check["heap_shaped"]
    assert check["root_split"][0] == 199 and check["root_split"][2] == 1
    assert check["missing_right_nodes"] >= 1
    # the replay, of the first job: its first two trees and its last,
    # every split the best of its node, the order best-first, the budget
    # met, no leaf under the cap
    assert check["trees_checked"] == 4 * jobs
    assert [t["tree"] for t in check["trees_replayed"]] == [0, 1, 3]
    for tree in check["trees_replayed"]:
        assert tree["leaves"] == 8 and tree["deepest_leaf"] <= 5
        assert tree["bad_nodes"] == [] and tree["replay_broken"] == []
        assert tree["replay_order"][0] == 0
        assert len(tree["replay_order"]) == 7
        # best-first is not breadth-first here: a node of the fifth
        # level was split while some of the fourth were left whole
        assert max(tree["replay_order"]) > 14
    # EVERY job is held to its own draw's label: the trainer's counts
    # are the reference's over all four trees, the margins those of the
    # returned trees on every row, logloss under ln 2
    assert check["grow_stats_ok"] and len(check["jobs_checked"]) == jobs
    for i, job in enumerate(check["jobs_checked"]):
        assert job["draw"] == i % 6 and job["counts_ok"]
        assert job["grow_stats"]["splits"] == job["splits"] == 28
        built = job["rows_built_a_tree"]
        assert len(built) == 4 and all(3000 < n < 3000 * 4.5 for n in built)
        assert job["grow_stats"]["rows_built"] == sum(built) == job[
            "rows_built"]
        # the budget of 8 leaves binds long before the cap on depth
        assert 0 <= job["rows_built_at_the_cap"] < 0.2 * job["rows_built"]
        assert job["margin_max_abs_err"] <= 1e-5 and job["logloss"] < 0.69
    first = check["jobs_checked"][0]
    assert [t["rows_built"] for t in check["trees_replayed"]] == [
        first["rows_built_a_tree"][i] for i in (0, 1, 3)]
    # two draws do not grow the same trees
    assert len({tuple(job["rows_built_a_tree"])
                for job in check["jobs_checked"][:6]}) > 1
    # the readers' count is of all the window's jobs, each its own
    assert check["counters"]["hist_rows_needed"] == sum(
        job["rows_built"] for job in check["jobs_checked"]) == counters[
            "grow_rows_built"]
    assert set(check["check_secs"]) == {
        "root", "replay_0", "replay_1", "replay_3", "jobs_routed",
        "kernel_sums"}
    assert 0 < check["hist_prefix_sum_err"] <= check[
        "hist_prefix_sum_err_bound"] == 1.6e-5
    assert check["margin_max_abs_err"] <= 1e-5
    assert counters["trees"] == 4 * jobs
    assert counters["grow_splits"] == 28 * jobs
    # of the rows the trainer says its passes read (whole slabs of a
    # child's rows), not of passes over the whole table
    assert counters["grow_rows_read"] >= counters["grow_rows_built"]
    assert counters["grow_rows_built_share"] == pytest.approx(
        100.0 * counters["grow_rows_built"] / counters["grow_rows_read"])


def test_traced_run(capsys, toy_root):
    rc, lines = _run(capsys, toy_root, trace=1)
    assert rc == 0
    line = json.loads(lines[-1])
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert line["correct"] is True and line["attempted"] == 1
    # the CPU's trace has no device plane: the trace readers find nothing
    # and their metrics are left out, none raises; counters and host
    # spans are there
    assert not set(line["metrics"]) - {
        m["name"] for m in cells.load_cell(toy_root, CELL).per_layer}
    assert {"compile_s", "compiles_in_window", "step_builds_in_window",
            "peak_hbm_gb", "gbdt_stage_ms_per_job",
            "gbdt_dispatch_ms_per_tree", "gbdt_fetch_wait_ms_per_job",
            "gbdt_grow_rows_built_share", "gbdt_step_mfu"} <= set(
                line["metrics"])
    assert line["metrics"]["step_builds_in_window"]["value"] == 0
    assert 0 < line["metrics"]["gbdt_grow_rows_built_share"]["value"] <= 100
    # the whole step's share needs the slice and the count, no kernel
    assert 0 < line["metrics"]["gbdt_step_mfu"]["value"] < 100
    assert "hist_kernel_roofline" not in line["metrics"]


def test_a_program_without_the_policy_is_refused_at_once(toy_root,
                                                        monkeypatch):
    """The parent of the PR that added ``grow_policy``: ``setup`` stops
    with the configuration's own ``TypeError`` before any table is
    drawn."""
    from benchmark import missing_table
    from ytk_mp4j_tpu.models import gbdt

    parents = gbdt.GBDTConfig

    def without_the_policy(**kw):
        if "grow_policy" in kw:
            raise TypeError("GBDTConfig.__init__() got an unexpected "
                            "keyword argument 'grow_policy'")
        return parents(**kw)

    monkeypatch.setattr(gbdt, "GBDTConfig", without_the_policy)
    monkeypatch.setattr(missing_table, "missing_binned_table",
                        lambda *a: pytest.fail("a table was drawn"))
    with pytest.raises(TypeError, match="grow_policy"):
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "0.5",
                  "--trace", "0"], root=toy_root)


@pytest.mark.parametrize("control", ["breadth_first", "lowest_gain_first",
                                     "no_lo_part", "miscounted_third_tree",
                                     "third_tree_altered",
                                     "fourth_job_altered"])
def test_a_weaker_grower_is_not_correct(capsys, toy_root, monkeypatch,
                                        control):
    """A grower that takes the open leaves in heap order, or the one of
    least gain first, still splits every node at its best candidate and
    returns the margins of its trees: only the replay tells. Histograms
    held in bf16 alone (the lo part dropped) fail the kernel's own sums
    by far, whatever the splits do. The third of four trees is one the
    replay does not visit: a trainer whose count of the rows it built
    there is off by one, and a tree altered where it is produced (a node
    frozen after the fact), are caught by the count over all the trees
    and by the margins; so is a tree altered in the window's fourth job
    alone, which trains on another draw than the replayed one."""
    import jax.numpy as jnp

    from ytk_mp4j_tpu.models import gbdt
    from ytk_mp4j_tpu.ops import hist_kernel

    if control == "breadth_first":
        monkeypatch.setattr(
            gbdt, "_pick_leaf", lambda open_, gain, heap, none:
            jnp.argmin(jnp.where(open_, heap, none)))
    elif control == "lowest_gain_first":
        monkeypatch.setattr(
            gbdt, "_pick_leaf", lambda open_, gain, heap, none:
            jnp.argmin(jnp.where(open_, gain, jnp.inf)))
    elif control in ("miscounted_third_tree", "third_tree_altered",
                     "fourth_job_altered"):
        train = gbdt.GBDTTrainer.train
        timed_jobs = []

        def altered(self, *args, **kw):
            trees, margins = train(self, *args, **kw)
            timed_jobs.extend([1] * (len(trees) == 4))
            if control == "fourth_job_altered" and len(timed_jobs) != 4:
                pass
            elif len(trees) == 4 and control == "miscounted_third_tree":
                self.grow_stats_["rows_built"] += 1
            elif len(trees) == 4:
                bin_ = np.array(trees[2][1])
                split = np.flatnonzero(bin_ != 255)
                # the deepest split node has no split node under it
                bin_[split[-1]] = 255
                trees[2] = (trees[2][0], bin_, *trees[2][2:])
            return trees, margins

        monkeypatch.setattr(gbdt.GBDTTrainer, "train", altered)
    else:
        split = hist_kernel.split_bf16

        def hi_alone(a):
            hi, lo = split(a)
            return hi, jnp.zeros_like(lo)

        monkeypatch.setattr(hist_kernel, "split_bf16", hi_alone)
        monkeypatch.setattr(gbdt, "split_bf16", hi_alone)
    rc, lines = _run(capsys, toy_root, trace=0)
    assert rc == 0
    assert json.loads(lines[-1])["correct"] is False
    check = _window(lines)["check"]
    if control in ("third_tree_altered", "fourth_job_altered"):
        assert not check["grow_stats_ok"]
        assert check["margin_max_abs_err"] > 1e-5
        off = [i for i, job in enumerate(check["jobs_checked"])
               if not job["counts_ok"] or job["margin_max_abs_err"] > 1e-5]
        assert off == ([3] if control == "fourth_job_altered"
                       else list(range(len(check["jobs_checked"]))))
        return
    assert check["margin_max_abs_err"] <= 1e-5      # its own trees' margins
    if control == "miscounted_third_tree":
        assert not check["grow_stats_ok"]
        assert all(job["grow_stats"]["rows_built"] == job["rows_built"] + 1
                   for job in check["jobs_checked"])
        assert all(t["bad_nodes"] == [] and t["replay_broken"] == []
                   for t in check["trees_replayed"])
    elif control == "no_lo_part":
        assert check["hist_prefix_sum_err"] > 50 * check[
            "hist_prefix_sum_err_bound"]
    else:
        assert any(t["replay_broken"] for t in check["trees_replayed"])
        # every split is still the best of its node, the counts agree
        assert all(t["bad_nodes"] == [] for t in check["trees_replayed"])
        assert check["grow_stats_ok"]
        assert check["hist_prefix_sum_err"] <= check[
            "hist_prefix_sum_err_bound"]


def test_a_label_draw_is_the_same_tables_other_label():
    """``missing_table.label_draw``: the seed's own, another a draw,
    balanced, and about three labels in eight away from the table's."""
    from benchmark import missing_table

    bins, y0 = missing_table.missing_binned_table(57, 40_000, 200, 256, 0.81)
    one, again, two = (missing_table.label_draw(bins, 256, 57, d)
                       for d in (1, 1, 2))
    assert np.array_equal(one, again) and not np.array_equal(one, two)
    assert not np.array_equal(
        one, missing_table.label_draw(bins, 256, 58, 1))
    for y in (one, two):
        assert y.dtype == np.float32 and set(np.unique(y)) == {0.0, 1.0}
        assert abs(y.mean() - 0.5) < 0.01
        assert 0.55 < (y == y0).mean() < 0.75
    with pytest.raises(ValueError, match="draw 0"):
        missing_table.label_draw(bins, 256, 57, 0)
    cell = cells.load_cell(ROOT, CELL)
    assert cell.traffic["name"] == "train-relabelled"
    assert cell.traffic["label_draws"] == 6


def test_the_replay_on_a_tree_drawn_by_hand():
    """``reference/gbdt_leafwise.py`` alone, no trainer: a heap of depth
    3 whose gains say 0, 2, 5 is best-first; the same gains with node 1
    split in node 5's place are not."""
    from benchmark.reference import gbdt_leafwise as reference

    B = 8
    bin_ = np.full(7, B - 1)
    bin_[[0, 2, 5]] = 3
    tree = (np.zeros(7, int), bin_, np.zeros(7, int), np.zeros(8))
    split, leaves = reference.grown(tree, B)
    assert split == [0, 2, 5] and leaves == [1, 6, 11, 12]
    assert [reference.level_of(k) for k in (0, 1, 2, 6, 7, 14)] == [
        0, 1, 1, 2, 3, 3]
    best = {0: (9.0, 0.1), 1: (2.0, 0.1), 2: (5.0, 0.1), 5: (4.0, 0.1),
            6: (1.0, 0.1), 11: (0.5, 0.1), 12: (0.5, 0.1)}
    order, broken = reference.replay(split, best, depth=3, max_leaves=4)
    assert order == [0, 2, 5] and broken == []
    # a tie within both tolerances passes, a clear loss does not
    assert reference.replay(split, {**best, 1: (4.15, 0.1)}, 3, 4)[1] == []
    order, broken = reference.replay(split, {**best, 1: (4.5, 0.1)}, 3, 4)
    assert [step for step, _ in broken] == [2] and "leaf 1" in broken[0][1]
    # budget to spare with leaves that gain (1 and 6; 11 and 12 lie at
    # the cap): finished too early
    order, broken = reference.replay(split, best, depth=3, max_leaves=5)
    assert [step for step, _ in broken] == [3, 3]
    assert ["leaf 1 " in broken[0][1], "leaf 6 " in broken[1][1]] == [
        True, True]
    # at the cap a leaf that gains is no fault: depth 2 shuts 5's children
    shut = {k: v for k, v in best.items() if k not in (11, 12)}
    bin2 = np.full(3, B - 1)
    bin2[[0, 2]] = 3
    tree2 = (np.zeros(3, int), bin2, np.zeros(3, int), np.zeros(4))
    split2, leaves2 = reference.grown(tree2, B)
    assert (split2, leaves2) == ([0, 2], [1, 5, 6])
    order, broken = reference.replay(
        split2, {**shut, 1: (-1.0, 0.1), 5: (4.0, 0.1)}, depth=2,
        max_leaves=4)
    assert order == [0, 2] and broken == []
    # a split under an unsplit node is no tree
    bad = np.full(7, B - 1)
    bad[[0, 5]] = 3
    with pytest.raises(ValueError, match="under an unsplit"):
        reference.grown((None, bad, None, None), B)
    # rows: the root's, then the smaller child's of every split
    rows = {0: 100, 1: 30, 2: 70, 5: 50, 6: 20, 11: 25, 12: 25}
    assert reference.rows_built(split, rows) == 100 + 30 + 20 + 25
    built = reference.built_from_rows(split, rows)
    assert built == {0: True, 1: True, 2: False, 5: False, 6: True,
                     11: True, 12: False}
