"""The cell ``gbdt-bosch-score-500.batch`` end to end at a toy size
through ``run.main`` itself, on the CPU with the platform check stubbed
(by hand, like the rest of this directory): the contract's last line,
``correct`` true, the cell's metrics found by name, and ``correct``
false when a leaf is held in bf16."""

import json
import os

import numpy as np
import pytest

from benchmark import arith, arith_score, cells, run

from conftest import ROOT

CELL = "gbdt-bosch-score-500.batch"
# ``compared``: each number the check compared beside its limit, last
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}
# what the cell must report (it may report more)
SCORE = {"rows_device_idle_share", "score_stage_ms_per_job",
         "score_dispatch_ms_per_job", "score_fetch_wait_ms_per_job",
         "score_select_ms_per_job", "score_walk_ms_per_job",
         "peak_hbm_gb", "compile_s", "compiles_in_window",
         "step_builds_in_window", "score_roofline", "score_step_mfu",
         "score_stage_link_wait_ms_per_job",
         "score_stage_device_wait_ms_per_job", "score_stage_gbps"}


@pytest.fixture
def toy_root(tiny_root):
    """``tiny_root`` with this cell's table and ensemble cut to a toy:
    several groups of trees, several chunks of rows."""
    path = os.path.join(tiny_root, "benchmark", "configs",
                        "gbdt-bosch-score-500.json")
    with open(path) as f:
        doc = json.load(f)
    doc.update(rows=3001, n_features=200, n_trees=37)
    with open(path, "w") as f:
        json.dump(doc, f)
    return tiny_root


def _run(capsys, root, trace, seed=3000000019):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.3", "--trace", str(trace)], root=root)
    return rc, capsys.readouterr().out.strip().splitlines()


def _window(lines):
    return json.loads(next(ln for ln in lines if ln.startswith("window: "))
                      [len("window: "):])


def test_the_cell_reports_rows_per_s_and_its_own_layer_metrics():
    cell = cells.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.adapter_name == "gbdt_score"
    assert [m["name"] for m in cell.end_to_end] == ["rows_per_s", "setup_s"]
    assert SCORE <= {m["name"] for m in cell.per_layer}
    for m in cell.per_layer:
        assert m["spec"]["name"] == m["name"]
        for key in ("layer", "moves", "source"):
            assert m["spec"][key] == m[key], (m["name"], key)
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "readers", f"{m['spec']['reader']}.py"))
    # the deployment is the source's, nothing cut
    c = cell.config
    assert (c["rows"], c["n_features"], c["n_bins"], c["depth"],
            c["n_trees"]) == (1_183_748, 968, 256, 6, 500)
    assert c["missing_bin"] is True and c["reduced"] == {}
    assert c["architecture"] is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == "gbdt-bosch-score-500")
    assert entry["reduced"] == []


def test_the_accepted_cells_report_what_they_reported():
    """The FFM cell shares ``rows_per_s`` with this one and reads none of
    its metrics; no accepted cell does."""
    for name in ("gbdt-higgs-11m.train", "ffm-criteo.stream-zipf",
                 "allreduce-4rank.hist-and-bulk", "gbdt-bosch-968.train"):
        got = {m["name"] for m in cells.load_cell(ROOT, name).per_layer}
        assert not any(n.startswith("score_") for n in got)
    ffm = cells.load_cell(ROOT, "ffm-criteo.stream-zipf")
    assert [m["name"] for m in ffm.end_to_end] == ["rows_per_s", "setup_s"]


def test_untraced_run(capsys, toy_root):
    rc, lines = _run(capsys, toy_root, trace=0)
    assert rc == 0
    line = json.loads(lines[-1])
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    assert line["metrics"]["rows_per_s"]["unit"] == "rows/s"
    assert line["metrics"]["rows_per_s"]["value"] > 0
    window = _window(lines)
    assert window["compiles_in_window"] == 0
    counters, check = window["counters"], window["check"]
    assert counters["rows"] == 3001 * counters["jobs"]
    assert counters["trees"] == 37 * counters["jobs"]
    assert len(window["log"]["job_secs"]) == counters["jobs"]
    assert check["rows_checked"] == 3001 and check["trees_checked"] == 37
    assert check["margins_shape"] == [3001]
    assert check["margin_err_over_terms"] <= 2.0 ** -18
    assert check["terms_mean"] > 0


def test_a_leaf_held_in_bf16_is_not_correct(capsys, toy_root, monkeypatch):
    """The control: the same run with every leaf rounded to bf16 on its
    way into the scoring program misses the stated precision."""
    import jax.numpy as jnp

    from ytk_mp4j_tpu.models import gbdt

    score_group = gbdt._score_group

    def rounded(digits, group, out, cfg):
        feat, bin_, dir_, leaf = group
        leaf = leaf.astype(jnp.bfloat16).astype(jnp.float32)
        return score_group(digits, (feat, bin_, dir_, leaf), out, cfg)

    monkeypatch.setattr(gbdt, "_score_group", rounded)
    rc, lines = _run(capsys, toy_root, trace=0)
    assert rc == 0
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert _window(lines)["check"]["margin_err_over_terms"] > 2.0 ** -18


def test_traced_run(capsys, toy_root):
    rc, lines = _run(capsys, toy_root, trace=1)
    assert rc == 0
    line = json.loads(lines[-1])
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert line["correct"] is True and line["attempted"] == 1
    # the CPU's trace has no device plane: the trace readers find nothing
    # and their metrics are left out; counters and host spans are there
    assert set(line["metrics"]) <= {
        m["name"] for m in cells.load_cell(toy_root, CELL).per_layer}
    assert {"compile_s", "compiles_in_window", "step_builds_in_window",
            "peak_hbm_gb", "score_stage_ms_per_job",
            "score_dispatch_ms_per_job", "score_fetch_wait_ms_per_job",
            "score_step_mfu"} <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["metrics"]["step_builds_in_window"]["value"] == 0


def test_a_checkout_without_shard_bins_fails_at_once(tiny_root, monkeypatch):
    """What the parent of the PR that added the cell does with it: an
    error before the table is made, not a hang and not a result."""
    from ytk_mp4j_tpu.models.gbdt import GBDTTrainer

    monkeypatch.delattr(GBDTTrainer, "shard_bins")
    with pytest.raises(RuntimeError, match="no shard_bins"):
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "0.3",
                  "--trace", "0"], root=tiny_root)


def test_same_seed_same_ensemble_other_seed_other_ensemble():
    from benchmark.adapters.gbdt_score import drawn_ensemble
    a = drawn_ensemble(3000000019, 40, 968, 256, 6, 0.05, 0.1)
    b = drawn_ensemble(3000000019, 40, 968, 256, 6, 0.05, 0.1)
    c = drawn_ensemble(3000000020, 40, 968, 256, 6, 0.05, 0.1)
    for x, y in zip(a, b):
        assert all((p == q).all() for p, q in zip(x, y))
    assert any((p != q).any() for x, y in zip(a, c) for p, q in zip(x, y))
    feat, bin_, dir_, leaf = (np.stack(v) for v in zip(*a))
    assert feat.shape == (40, 63) and leaf.shape == (40, 64)
    assert feat.dtype == bin_.dtype == dir_.dtype == np.int32
    assert leaf.dtype == np.float32
    assert 0 <= feat.min() and feat.max() < 968
    frozen = bin_ == 255
    assert 0.02 < frozen.mean() < 0.09
    assert bin_[~frozen].min() >= 1 and bin_[~frozen].max() <= 254
    assert set(np.unique(dir_)) == {0, 1}
    assert abs(leaf.std() - 0.1) < 0.01


def test_the_reference_follows_directions_categories_and_frozen_nodes():
    from benchmark.reference import gbdt_score as reference
    # depth 2: root on feature 0 at bin 3, missing left; its left child
    # frozen on feature 0 (bin 7 of 8), missing right; its right child
    # on feature 1 by equality with bin 2, "missing right" stored
    tree = (np.array([0, 0, 1]), np.array([3, 7, 2]), np.array([0, 1, 1]),
            np.array([10.0, 20.0, 30.0, 40.0], np.float32))
    bins = np.array([[2, 5], [0, 5], [5, 5], [5, 2], [5, 0]])
    leaves = reference.leaf_of(tree, bins, 2, 8, True, categorical=(1,))
    # row 0: 2 <= 3 left; the frozen node sends a present value left
    # row 1: missing goes left at the root, right at the frozen node
    # row 2: 5 > 3 right; 5 != 2 left;  row 3: right; 2 == 2 right
    # row 4: right; a categorical node takes no notice of the direction
    assert leaves.tolist() == [0, 1, 2, 3, 2]
    margins, terms = reference.score_ensemble(
        [tree, tree], bins, 2, 0.5, 8, True, categorical=(1,))
    assert margins.tolist() == [10.0, 20.0, 30.0, 40.0, 30.0]
    assert (terms == margins).all()
    assert reference.margin_error(margins + 1e-7, margins, terms) == \
        pytest.approx(1e-8, rel=1e-3)


def test_arithmetic_of_a_job():
    flops = arith_score.score_select_flops(1_183_748, 968, 6, 500)
    assert flops == 2.0 * 1_183_748 * 968 * 64 * 500
    nbytes = arith_score.score_min_bytes(1_183_748, 968)
    assert nbytes == 4_583_472_256 + 4 * 1_183_748
    peaks = arith.peaks_for("TPU v5 lite")
    least, bound = arith.roofline_seconds(flops, nbytes, peaks)
    assert bound == "mxu" and 0.36 < least < 0.38
