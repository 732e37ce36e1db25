"""The cell ``gbdt-bosch-968-raw.train-raw-chunks`` end to end at a toy
size through ``run.main`` itself, on the CPU with the platform check
stubbed (by hand, like the rest of this directory): the contract's last
line, ``correct`` true with (i) to (iv) of the adapter's check printed,
the cell's metrics found by name, and the controls that must come out
``correct: false``. The lists pin what the cell MUST report, not all it
may: a later PR that appends a metric to the cell breaks nothing here
(PERF.md section 7, left by PR 34 (a))."""

import json
import os

import numpy as np
import pytest

from benchmark import cells, run

from conftest import ROOT

CELL = "gbdt-bosch-968-raw.train-raw-chunks"
# ``compared``: each number the check compared beside its limit, last
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}
# what the cell must report (it may report more)
RAW = {"raw_stage_ms_per_job", "raw_sketch_ms_per_job",
       "raw_sketch_device_ms_per_job", "raw_transform_ms_per_job",
       "raw_transform_roofline", "gbdt_hist_ms_per_tree",
       "gbdt_fetch_wait_ms_per_job", "gbdt_device_idle_share",
       "peak_hbm_gb", "compile_s", "compiles_in_window",
       "step_builds_in_window", "gbdt_route_ms_per_tree",
       "gbdt_split_leaf_ms_per_tree", "gbdt_hist_glue_ms_per_tree",
       "gbdt_stage_ms_per_job", "gbdt_dispatch_ms_per_tree",
       "stage_link_wait_ms_per_job", "gbdt_step_mfu"}
# rows that no chunk size of the toy divides: seven chunks, the last short
TOY = dict(rows=3001, n_features=200, depth=4, n_trees=2, bin_sample=2000,
           chunk_rows=448)


@pytest.fixture
def toy_root(tiny_root):
    """``tiny_root`` with this cell's table cut to a toy: the width stays
    above one feature block (200 > 128) and cuts into 52 stations, most
    cells stay empty, the sample is smaller than the table."""
    path = os.path.join(tiny_root, "benchmark", "configs",
                        "gbdt-bosch-968-raw.json")
    with open(path) as f:
        doc = json.load(f)
    doc.update(TOY)
    with open(path, "w") as f:
        json.dump(doc, f)
    return tiny_root


def _run(capsys, root, trace, seed=4400000007):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.5", "--trace", str(trace)], root=root)
    return rc, capsys.readouterr().out.strip().splitlines()


def _window(lines) -> dict:
    return json.loads(next(ln for ln in lines if ln.startswith("window: "))
                      [len("window: "):])


def test_the_cell_reports_trees_per_s_and_its_own_layer_metrics():
    cell = cells.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.adapter_name == "gbdt_raw"
    assert [m["name"] for m in cell.end_to_end] == ["trees_per_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert RAW <= set(names)
    # the kernel's roofline is read by the kernel's own name since PR 49;
    # its outside twin, by the custom call's target, would count this
    # cell's second Mosaic kernel, mp4j_bin, in
    assert "hist_kernel_roofline" in names
    assert "hist_kernel_ms_per_tree" not in names
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
    assert (c["dtype"], c["missing_rate"], c["bin_sample"]) == (
        "float32", 0.81, 1_000_000)
    assert (c["n_trees"], c["chunk_rows"]) == (4, 65_536)
    assert c["missing_bin"] is True and list(c["reduced"]) == ["n_trees"]
    assert -(-c["rows"] // c["chunk_rows"]) == 19
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["configs"]
                 if e["name"] == "gbdt-bosch-968-raw")
    assert entry["reduced"] == ["n_trees"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2


def test_the_accepted_gbdt_cells_report_none_of_this_cells_metrics():
    for name in ("gbdt-higgs-11m.train", "gbdt-bosch-968.train",
                 "gbdt-bosch-score-500.batch"):
        got = {m["name"] for m in cells.load_cell(ROOT, name).per_layer}
        assert not any(n.startswith(("raw_", "rawscore_")) for n in got)


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
    assert line["metrics"]["trees_per_s"]["value"] > 0
    window = _window(lines)
    assert window["compiles_in_window"] == 0
    check = window["check"]
    # (i) the edges, every column, each with its limit beside it
    assert check["edge_columns_checked"] == 200
    assert check["edges_outside_their_order_statistics"] == 0
    assert check["edges_max_rel_err"] <= check["edges_rel_err_limit"] == 2**-22
    assert check["edges_exact_mismatches"] == 0
    assert check["edges_equal_share"] > 0.99
    # (ii) the device's bins
    assert check["bin_rows_checked"] == 3001
    assert check["bins_off_own_edges"] == 0
    assert check["bins_off_reference_unexplained"] == 0
    assert check["bin0_is_not_nan_cells"] == 0
    # (iii) gbdt_missing's checks, on those bins
    assert check["root_ok"] and check["missing_right_nodes"] >= 1
    assert check["root_split"][0] == 199 and check["root_split"][2] == 1
    assert check["second_tree_bad_nodes"] == []
    assert 0 < check["hist_prefix_sum_err"] <= 1.6e-5
    assert check["margin_max_abs_err"] <= 1e-5
    assert 0.7 < check["missing_share"] < 0.9
    # (iv) the link carried the floats and the three row vectors
    assert (check["job_put_sharded_bytes"]
            == check["job_put_sharded_bytes_expected"]
            == 4 * 3001 * 200 + 3 * 4 * 3001)
    counters = window["counters"]
    assert counters["trees"] == 2 * counters["jobs"]
    # what the program's ``mp4j.bin.transform`` span says it issues a
    # cell: a step a level of the search in 254 edges
    assert counters["transform_compares_per_job"] == 3001 * 200 * 8
    assert counters["transform_least_bytes_per_job"] == 8 * 3001 * 200


def test_traced_run(capsys, toy_root):
    rc, lines = _run(capsys, toy_root, trace=1)
    assert rc == 0
    line = json.loads(lines[-1])
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert line["correct"] is True
    # the CPU's trace has no device plane: the trace readers find nothing
    # and their metrics are left out; counters and host spans are there
    assert {"compile_s", "compiles_in_window", "step_builds_in_window",
            "peak_hbm_gb", "raw_stage_ms_per_job", "raw_sketch_ms_per_job",
            "gbdt_fetch_wait_ms_per_job", "gbdt_stage_ms_per_job",
            "gbdt_dispatch_ms_per_tree",
            "gbdt_step_mfu"} <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["metrics"]["step_builds_in_window"]["value"] == 0


def test_a_program_without_the_entry_point_is_refused_at_once(
        toy_root, monkeypatch):
    """The parent of the PR that added ``train_raw_chunks``: ``setup``
    raises a sentence naming it before any table is drawn."""
    from benchmark import raw_table
    from ytk_mp4j_tpu.models.gbdt import GBDTTrainer

    monkeypatch.delattr(GBDTTrainer, "train_raw_chunks")
    monkeypatch.setattr(raw_table, "raw_table", lambda *a: pytest.fail(
        "a table was drawn"))
    with pytest.raises(RuntimeError, match="train_raw_chunks"):
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "0.5",
                  "--trace", "0"], root=toy_root)


@pytest.mark.parametrize("control", ["bf16_edges", "coarser_sketch",
                                     "nan_shares_bin_1", "bins_cross_back"])
def test_a_weaker_front_end_is_not_correct(capsys, toy_root, monkeypatch,
                                           control):
    """Edges rounded to bf16 or taken from every other order statistic
    fail (i); NaN cells that do not have bin 0 to themselves fail (ii);
    a binned table that crosses the link fails (iv)."""
    import jax.numpy as jnp

    from ytk_mp4j_tpu.models import binning
    from ytk_mp4j_tpu.models.gbdt import GBDTTrainer

    if control == "bf16_edges":
        edges_of = binning._edges_of
        monkeypatch.setattr(binning, "_edges_of", lambda *a: np.asarray(
            jnp.asarray(edges_of(*a)).astype(jnp.bfloat16).astype(
                jnp.float32)))
    elif control == "coarser_sketch":
        edges_of = binning._edges_of

        def coarse(picks, n, nb):
            return edges_of(picks[[1, 1, 1]], n, nb)   # no interpolation

        monkeypatch.setattr(binning, "_edges_of", coarse)
    elif control == "nan_shares_bin_1":
        count = binning._count_edges
        monkeypatch.setattr(
            binning, "_count_edges",
            lambda X, edges, shift: jnp.maximum(count(X, edges, shift), 1))
        binning._transform_program.cache_clear()
    else:
        stage = GBDTTrainer._shard_vectors

        def and_the_bins(self, y, sample_weight=None):
            self._put_sharded(np.zeros((len(y), 4), np.int32), -(
                -len(y) // self.n_shards))
            return stage(self, y, sample_weight)

        monkeypatch.setattr(GBDTTrainer, "_shard_vectors", and_the_bins)
    rc, lines = _run(capsys, toy_root, trace=0)
    binning._transform_program.cache_clear()
    assert rc == 0
    assert json.loads(lines[-1])["correct"] is False
    check = _window(lines)["check"]
    if control in ("bf16_edges", "coarser_sketch"):
        assert (check["edges_max_rel_err"] > check["edges_rel_err_limit"]
                or check["edges_outside_their_order_statistics"])
        assert check["bins_off_own_edges"] == 0
    elif control == "nan_shares_bin_1":
        assert check["bin0_is_not_nan_cells"] > 0
    else:
        assert (check["job_put_sharded_bytes"]
                > check["job_put_sharded_bytes_expected"])


def test_same_seed_same_table_other_seed_other_table():
    from benchmark.raw_table import layout, raw_table
    a = raw_table(4400000007, 20_000, 968, 0.81)
    b = raw_table(4400000007, 20_000, 968, 0.81)
    c = raw_table(4400000008, 20_000, 968, 0.81)
    assert (a[0].view(np.uint32) == b[0].view(np.uint32)).all()
    assert (a[1] == b[1]).all()
    assert (a[0].view(np.uint32) != c[0].view(np.uint32)).any()
    assert a[0].dtype == np.float32
    assert abs(np.isnan(a[0]).mean() - 0.81) < 0.02
    assert abs(a[1].mean() - 0.5) < 1e-3
    lay = layout(4400000007, 968, 0.81)
    assert len(lay["widths"]) == 52 and lay["widths"].sum() == 968
    assert 2 <= lay["widths"].min() and lay["widths"].max() <= 80
    assert lay["visit"].min() >= 0.01
    assert (lay["visit"][lay["station"][lay["label"]]] >= 0.19 - 1e-9).all()
    assert abs((lay["widths"] * lay["visit"]).sum() / 968 - 0.19) < 1e-6
    # a part has all of a station's columns or none
    ends = np.r_[0, np.cumsum(lay["widths"])]
    there = ~np.isnan(a[0])
    for start, stop in zip(ends[:-1], ends[1:]):
        assert (there[:, start:stop] == there[:, start:start + 1]).all()
    # three decimals
    present = a[0][there].astype(np.float64)
    assert np.abs(present * 1000 - np.rint(present * 1000)).max() < 1e-2
