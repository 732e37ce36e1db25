"""The cell ``gbdt-bosch-score-raw-500.raw-chunks`` end to end at a toy
size through ``run.main`` itself, on the CPU with the platform check
stubbed (by hand, like the rest of this directory): the contract's last
line, ``correct`` true with (i) to (v) of the adapter's check printed,
the cell's metrics found by name, and the controls that must come out
``correct: false``. The lists pin what the cell MUST report, not all it
may: a later PR that appends a metric to the cell breaks nothing here."""

import json
import os

import numpy as np
import pytest

from benchmark import cells, run

from conftest import ROOT

CELL = "gbdt-bosch-score-raw-500.raw-chunks"
# ``compared``: each number the check compared beside its limit, last
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}
# what the cell must report (ISSUE 49: at least sixteen; it may report
# more): its own three, and the shared loop's under the names
# ``gbdt-bosch-score-500.batch`` reports them by
RAWSCORE = {"rawscore_transform_ms_per_job", "rawscore_roofline",
            "rawscore_transform_roofline", "rows_device_idle_share",
            "score_stage_ms_per_job", "score_dispatch_ms_per_job",
            "score_fetch_wait_ms_per_job", "score_select_ms_per_job",
            "score_walk_ms_per_job", "score_stage_send_ms_per_job",
            "score_stage_link_wait_ms_per_job",
            "score_stage_device_wait_ms_per_job", "score_stage_gbps",
            "peak_hbm_gb", "compile_s", "compiles_in_window",
            "step_builds_in_window", "score_step_mfu"}
# rows that no chunk size of the toy divides: seven chunks, the last short
TOY = dict(rows=3001, n_features=200, depth=4, n_trees=37, bin_sample=2000,
           chunk_rows=448)


@pytest.fixture
def toy_root(tiny_root):
    """``tiny_root`` with this cell's table cut to a toy: the width still
    cuts into 52 stations, most cells stay empty, the edges' sample is
    smaller than the table, the ensemble takes three groups."""
    path = os.path.join(tiny_root, "benchmark", "configs",
                        "gbdt-bosch-score-raw-500.json")
    with open(path) as f:
        doc = json.load(f)
    doc.update(TOY)
    with open(path, "w") as f:
        json.dump(doc, f)
    return tiny_root


def _run(capsys, root, trace, seed=4700000011):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.5", "--trace", str(trace)], root=root)
    return rc, capsys.readouterr().out.strip().splitlines()


def _window(lines) -> dict:
    return json.loads(next(ln for ln in lines if ln.startswith("window: "))
                      [len("window: "):])


def test_the_cell_reports_rows_per_s_and_its_own_layer_metrics():
    cell = cells.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.adapter_name == "gbdt_score_raw"
    assert [m["name"] for m in cell.end_to_end] == ["rows_per_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert RAWSCORE <= set(names) and len(names) >= 16
    assert not any(n.startswith("raw_") for n in names)
    for m in cell.per_layer:
        assert m["spec"]["name"] == m["name"]
        for key in ("layer", "moves", "source"):
            assert m["spec"][key] == m[key], (m["name"], key)
        assert CELL in m["workloads"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "readers", f"{m['spec']['reader']}.py"))
    # the configuration is the source's: no width, row or tree is cut
    c = cell.config
    assert (c["rows"], c["n_features"], c["n_bins"], c["depth"]) == (
        1_183_748, 968, 256, 6)
    assert (c["dtype"], c["missing_rate"], c["bin_sample"]) == (
        "float32", 0.81, 1_000_000)
    assert (c["n_trees"], c["chunk_rows"]) == (500, 65_536)
    assert c["missing_bin"] is True and c["reduced"] == {}
    assert -(-c["rows"] // c["chunk_rows"]) == 19
    assert c["rows"] - 18 * c["chunk_rows"] == 4_100
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["configs"]
                 if e["name"] == "gbdt-bosch-score-raw-500")
    assert entry["reduced"] == []
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2
    assert len(bench["per_layer"]) <= 128


def test_the_accepted_cells_report_none_of_this_cells_metrics():
    for name in ("gbdt-bosch-score-500.batch",
                 "gbdt-bosch-968-raw.train-raw-chunks"):
        got = {m["name"] for m in cells.load_cell(ROOT, name).per_layer}
        assert not any(n.startswith("rawscore_") for n in got)


def test_untraced_run(capsys, toy_root):
    rc, lines = _run(capsys, toy_root, trace=0)
    assert rc == 0
    line = json.loads(lines[-1])
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    assert line["metrics"]["rows_per_s"]["value"] > 0
    window = _window(lines)
    assert window["compiles_in_window"] == 0
    check = window["check"]
    # (i) the margins, each limit beside its reading
    assert 0 < check["margin_err_over_terms"] <= check[
        "margin_err_bound"] == 2**-18
    assert check["rows_checked"] == 3001 and check["trees_checked"] == 37
    assert check["edges_a_column"] == 254
    assert 0.7 < check["bin0_share_of_checked_cells"] < 0.9
    # (ii) bit for bit the accepted path on the reference's bins
    assert check["rows_off_predict_of_reference_bins"] == 0
    # (iii) every row
    assert check["margins_shape"] == [3001] and check["margins_finite"]
    # (iv) the link carried the floats, once
    assert (check["job_put_sharded_bytes"]
            == check["job_put_sharded_bytes_expected"] == 4 * 3001 * 200)
    assert check["job_put_sharded_spans"] == 1
    # (v) nothing was built inside the window
    assert check["step_builds_in_window"] == 0
    counters = window["counters"]
    assert counters["rows"] == 3001 * counters["jobs"]
    assert counters["chunks"] == 7 * counters["jobs"]
    assert counters["trees"] == 37 * counters["jobs"]
    # what the program's build span says it issues a cell: a step a
    # level of the search in 254 edges
    assert counters["transform_compares_per_job"] == 3001 * 200 * 8
    assert counters["transform_least_bytes_per_job"] == 8 * 3001 * 200
    assert set(window["log"]["host_ms_per_job"]) >= {
        "stage", "dispatch", "fetch", "put_sharded"}


def test_traced_run(capsys, toy_root):
    rc, lines = _run(capsys, toy_root, trace=1)
    assert rc == 0
    line = json.loads(lines[-1])
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert line["correct"] is True
    assert line["attempted"] == 1
    # the CPU's trace has no device plane: the cell's metrics all read
    # the device's trace, find nothing and are left out, none raises
    assert not set(line["metrics"]) - {
        m["name"] for m in cells.load_cell(toy_root, CELL).per_layer}
    assert {"compile_s", "compiles_in_window", "step_builds_in_window",
            "peak_hbm_gb", "score_stage_ms_per_job",
            "score_dispatch_ms_per_job", "score_fetch_wait_ms_per_job",
            "score_step_mfu"} <= set(line["metrics"])
    assert line["metrics"]["step_builds_in_window"]["value"] == 0


def test_a_program_without_the_entry_point_is_refused_at_once(
        toy_root, monkeypatch):
    """The parent of the PR that added ``predict_raw_chunks``: ``setup``
    stops with the ``AttributeError`` before any table is drawn."""
    from benchmark import raw_table
    from ytk_mp4j_tpu.models.gbdt import GBDTTrainer

    monkeypatch.delattr(GBDTTrainer, "predict_raw_chunks")
    monkeypatch.setattr(raw_table, "raw_table", lambda *a: pytest.fail(
        "a table was drawn"))
    with pytest.raises(AttributeError, match="predict_raw_chunks"):
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "0.5",
                  "--trace", "0"], root=toy_root)


@pytest.mark.parametrize("control", [
    "bf16_floats", "bf16_edges", "edge_ties_go_below", "nan_shares_bin_1",
    "bf16_leaves", "bins_cross_back", "a_build_in_the_window"])
def test_a_weaker_scorer_is_not_correct(capsys, toy_root, monkeypatch,
                                        control):
    """Floats or edges rounded to bf16, ``>`` for ``>=`` at an edge and
    NaN cells that share bin 1 move whole leaves: (i) and (ii) fail.
    Leaves rounded to bf16 fail (i) by the margin's limit. A binned
    table that crosses the link fails (iv), a program built inside the
    window (v)."""
    import jax.numpy as jnp

    from ytk_mp4j_tpu.models import binning, gbdt
    from ytk_mp4j_tpu.models.gbdt import GBDTTrainer

    count = binning._count_edges

    def lower(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    if control == "bf16_floats":
        monkeypatch.setattr(gbdt, "_count_edges", lambda X, edges, shift:
                            count(lower(X), edges, shift))
    elif control == "bf16_edges":
        monkeypatch.setattr(gbdt, "_count_edges", lambda X, edges, shift:
                            count(X, lower(edges), shift))
    elif control == "edge_ties_go_below":
        monkeypatch.setattr(
            gbdt, "_count_edges", lambda X, edges, shift: jnp.where(
                jnp.isnan(X), 0, 1 + (X[..., None] > edges).sum(
                    -1, dtype=jnp.int32)))
    elif control == "nan_shares_bin_1":
        monkeypatch.setattr(gbdt, "_count_edges", lambda X, edges, shift:
                            jnp.maximum(count(X, edges, shift), 1))
    elif control == "bf16_leaves":
        stack = GBDTTrainer._stack_trees

        def rounded(self, trees):
            feat, bin_, dir_, leaf = stack(self, trees)
            return feat, bin_, dir_, lower(leaf)

        monkeypatch.setattr(GBDTTrainer, "_stack_trees", rounded)
    elif control == "bins_cross_back":
        job = GBDTTrainer.predict_raw_chunks

        def and_the_bins(self, chunks, n_rows, trees, **kw):
            self._put_sharded(np.zeros((n_rows, 4), np.int32), -(
                -n_rows // self.n_shards))
            return job(self, chunks, n_rows, trees, **kw)

        monkeypatch.setattr(GBDTTrainer, "predict_raw_chunks", and_the_bins)
    else:
        job = GBDTTrainer.predict_raw_chunks
        jobs = []

        def forgetful(self, chunks, n_rows, trees, **kw):
            jobs.append(1)
            if len(jobs) == 2:      # the first job of the window
                self._score_programs.clear()
            return job(self, chunks, n_rows, trees, **kw)

        monkeypatch.setattr(GBDTTrainer, "predict_raw_chunks", forgetful)
    rc, lines = _run(capsys, toy_root, trace=0)
    assert rc == 0
    assert json.loads(lines[-1])["correct"] is False
    check = _window(lines)["check"]
    if control in ("bf16_floats", "bf16_edges", "edge_ties_go_below",
                   "nan_shares_bin_1"):
        assert check["rows_off_predict_of_reference_bins"] > 0
        assert check["margin_err_over_terms"] > 100 * check[
            "margin_err_bound"]
    elif control == "bf16_leaves":
        # the accepted path rounds alike: only the reference tells
        assert check["margin_err_over_terms"] > 10 * check["margin_err_bound"]
    elif control == "bins_cross_back":
        assert (check["job_put_sharded_bytes"]
                > check["job_put_sharded_bytes_expected"])
        assert check["rows_off_predict_of_reference_bins"] == 0
    else:
        assert check["step_builds_in_window"] > 0
        assert check["rows_off_predict_of_reference_bins"] == 0


def test_the_reference_bins_as_the_compare_count_does():
    """``reference/gbdt_score_raw.py: bins`` (a binary search a column)
    against ``reference/gbdt_raw.py: bins`` (the plain compare-count) on
    ties, repeated edges, infinities and empty cells."""
    from benchmark.reference import gbdt_raw, gbdt_score_raw

    rng = np.random.default_rng(5)
    edges = np.sort(np.round(rng.normal(size=(7, 30)), 1), axis=1).astype(
        np.float32)
    edges[0, -3:] = np.inf
    edges[1, :4] = -np.inf
    X = np.round(rng.normal(size=(500, 7)), 1).astype(np.float32)
    X[rng.random(X.shape) < 0.3] = np.nan
    X[:3, 0], X[3:6, 1] = np.inf, -np.inf
    got = gbdt_score_raw.bins(X, edges)
    np.testing.assert_array_equal(got, gbdt_raw.bins(X, edges))
    assert ((got == 0) == np.isnan(X)).all()
    assert (X[:, 2, None] == edges[2]).any()        # ties were there
    # a count takes no notice of the order the edges come in
    np.testing.assert_array_equal(
        gbdt_score_raw.bins(X, edges[:, ::-1]), got)
