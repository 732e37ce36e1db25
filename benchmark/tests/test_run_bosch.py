"""The cell ``gbdt-bosch-968.train`` end to end at a toy size through
``run.main`` itself, on the CPU with the platform check stubbed (by hand,
like the rest of this directory): the contract's last line, ``correct``
true, the cell's metrics found by name."""

import json
import os

import pytest

from benchmark import cells, run

from conftest import ROOT

CELL = "gbdt-bosch-968.train"
# ``compared``: each number the check compared beside its limit, last
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}
# what the cell must report (it may report more: every name is one
# quantity's, shared with the cells that have it, since PR 49)
BOSCH = {"gbdt_hist_ms_per_tree", "hist_kernel_roofline",
         "gbdt_hist_glue_ms_per_tree", "gbdt_route_ms_per_tree",
         "gbdt_split_leaf_ms_per_tree", "gbdt_stage_ms_per_job",
         "gbdt_dispatch_ms_per_tree", "gbdt_fetch_wait_ms_per_job",
         "gbdt_device_idle_share", "peak_hbm_gb", "compile_s",
         "compiles_in_window", "step_builds_in_window",
         "stage_link_wait_ms_per_job", "stage_device_wait_ms_per_job",
         "stage_gbps", "gbdt_step_mfu"}


@pytest.fixture
def toy_root(tiny_root):
    """``tiny_root`` with this cell's table cut to a toy: the width stays
    above one feature block (200 > 128), most cells stay missing."""
    path = os.path.join(tiny_root, "benchmark", "configs",
                        "gbdt-bosch-968.json")
    with open(path) as f:
        doc = json.load(f)
    doc.update(rows=3000, n_features=200, depth=4, n_trees=2)
    with open(path, "w") as f:
        json.dump(doc, f)
    return tiny_root


def _run(capsys, root, trace, seed=3000000007):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.5", "--trace", str(trace)], root=root)
    return rc, capsys.readouterr().out.strip().splitlines()


def test_the_cell_reports_trees_per_s_and_its_own_layer_metrics():
    cell = cells.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.adapter_name == "gbdt_missing"
    assert [m["name"] for m in cell.end_to_end] == ["trees_per_s", "setup_s"]
    assert BOSCH <= {m["name"] for m in cell.per_layer}
    for m in cell.per_layer:
        assert m["spec"]["name"] == m["name"]
        for key in ("layer", "moves", "source"):
            assert m["spec"][key] == m[key], (m["name"], key)
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "readers", f"{m['spec']['reader']}.py"))
    # the configuration is the source's, cut in n_trees alone
    c = cell.config
    assert (c["rows"], c["n_features"], c["n_bins"], c["depth"]) == (
        1_183_747, 968, 256, 6)
    assert c["missing_bin"] is True and list(c["reduced"]) == ["n_trees"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == "gbdt-bosch-968")
    assert entry["reduced"] == ["n_trees"]


def test_the_accepted_cells_report_what_they_reported():
    """Every cell reports the run's three counters under their one name;
    no name carries a cell's prefix any more (PR 49)."""
    assert {m["name"] for m in cells.load_cell(
        ROOT, "gbdt-higgs-11m.train").per_layer} >= {"step_builds_in_window"}
    for name in ("gbdt-higgs-11m.train", "ffm-criteo.stream-zipf",
                 "allreduce-4rank.hist-and-bulk"):
        got = {m["name"] for m in cells.load_cell(ROOT, name).per_layer}
        assert {"peak_hbm_gb", "compile_s", "compiles_in_window"} <= got
        assert not any(n.startswith("bosch_") for n in got)


def test_untraced_run(capsys, toy_root):
    rc, lines = _run(capsys, toy_root, trace=0)
    assert rc == 0
    line = json.loads(lines[-1])
    assert set(line) == LINE_KEYS
    assert list(line)[-1] == "compared" and all(
        len(pair) == 2 for pair in line["compared"].values())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"trees_per_s", "setup_s"}
    assert line["metrics"]["trees_per_s"]["unit"] == "trees/s"
    assert line["metrics"]["trees_per_s"]["value"] > 0
    window = json.loads(next(ln for ln in lines if ln.startswith("window: "))
                        [len("window: "):])
    assert window["compiles_in_window"] == 0
    check = window["check"]
    assert check["root_ok"] and check["missing_right_nodes"] >= 1
    # the root is the split on the last column, by its missingness, and
    # the second tree is held to the reference in every node
    assert check["root_split"][0] == 199 and check["root_split"][2] == 1
    assert check["second_tree_nodes_checked"] == 15
    assert check["second_tree_bad_nodes"] == []
    assert 0 < check["hist_prefix_sum_err"] <= 1.6e-5
    assert check["margin_max_abs_err"] <= 1e-5
    assert 0.7 < check["missing_share"] < 0.9
    assert window["counters"]["trees"] == 2 * window["counters"]["jobs"]


def test_traced_run(capsys, toy_root):
    rc, lines = _run(capsys, toy_root, trace=1)
    assert rc == 0
    line = json.loads(lines[-1])
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert line["correct"] is True
    # the CPU's trace has no device plane: the trace readers find nothing
    # and their metrics are left out; counters and host spans are there
    assert set(line["metrics"]) <= {
        m["name"] for m in cells.load_cell(toy_root, CELL).per_layer}
    assert {"compile_s", "compiles_in_window", "step_builds_in_window",
            "peak_hbm_gb", "gbdt_stage_ms_per_job",
            "gbdt_dispatch_ms_per_tree", "gbdt_fetch_wait_ms_per_job",
            "gbdt_step_mfu"} <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["metrics"]["step_builds_in_window"]["value"] == 0
    assert 0 < line["metrics"]["gbdt_step_mfu"]["value"] < 100


def test_same_seed_same_table_other_seed_other_table():
    from benchmark.missing_table import missing_binned_table
    a = missing_binned_table(3000000007, 20_000, 12, 256, 0.81)
    b = missing_binned_table(3000000007, 20_000, 12, 256, 0.81)
    c = missing_binned_table(3000000008, 20_000, 12, 256, 0.81)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert (a[0] != c[0]).any()
    assert abs((a[0] == 0).mean() - 0.81) < 0.01
    assert a[0][a[0] > 0].min() == 1 and a[0].max() == 255
    assert abs(a[1].mean() - 0.5) < 1e-3
