"""The cell ``ffm-criteo-adagrad.stream-zipf`` end to end at a toy size
through ``run.main`` itself, on the CPU with the platform check stubbed
(by hand, like the rest of this directory): the contract's last line,
``correct`` true, the cell's metrics found by name, and ``correct``
false under each wrong reading of the rule."""

import json
import os

import pytest

from benchmark import arith_ffm, cells, run

from conftest import ROOT

CELL = "ffm-criteo-adagrad.stream-zipf"
# what the cell must report (it may report more)
ADAGRAD = {"ffm_grad_merge_ms_per_chunk", "adagrad_rule_ms_per_chunk",
           "ffm_table_gather_ms_per_chunk", "ffm_table_update_ms_per_chunk",
           "ffm_stage_ms_per_chunk", "ffm_dispatch_ms_per_chunk",
           "ffm_throttle_wait_ms_per_chunk", "rows_device_idle_share",
           "ffm_distinct_share", "peak_hbm_gb", "compile_s",
           "compiles_in_window", "step_builds_in_window",
           "adagrad_update_roofline", "ffm_step_mfu"}


@pytest.fixture
def toy_root(tiny_root):
    path = os.path.join(tiny_root, "benchmark", "configs",
                        "ffm-criteo-adagrad.json")
    with open(path) as f:
        doc = json.load(f)
    doc.update(n_features=39 * 64)
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
    assert cell.chips == 1 and cell.adapter_name == "ffm_adagrad"
    assert [m["name"] for m in cell.end_to_end] == ["rows_per_s", "setup_s"]
    assert ADAGRAD <= {m["name"] for m in cell.per_layer}
    for m in cell.per_layer:
        assert m["spec"]["name"] == m["name"]
        for key in ("layer", "moves", "source"):
            assert m["spec"][key] == m[key], (m["name"], key)
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "readers", f"{m['spec']['reader']}.py"))
    c = cell.config
    assert (c["n_features"], c["n_fields"], c["max_nnz"], c["k"]) == (
        2 ** 22, 39, 39, 4)
    assert (c["optimizer"], c["learning_rate"], c["l2"],
            c["adagrad_init"]) == ("adagrad", 0.2, 2e-5, 1.0)
    assert c["architecture"] is None and list(c["reduced"]) == ["n_features"]
    # the traffic is the accepted FFM cell's, letter for letter
    assert cell.traffic == cells.load_cell(
        ROOT, "ffm-criteo.stream-zipf").traffic
    assert arith_ffm.block_values(39, 4) == 314


def test_the_accepted_cells_report_what_they_reported():
    for name in ("gbdt-higgs-11m.train", "ffm-criteo.stream-zipf",
                 "allreduce-4rank.hist-and-bulk", "gbdt-bosch-968.train",
                 "gbdt-bosch-score-500.batch"):
        got = {m["name"] for m in cells.load_cell(ROOT, name).per_layer}
        assert not any(n.startswith("adagrad_") for n in got)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_and_is_correct(capsys, toy_root, trace):
    rc, lines = _run(capsys, toy_root, trace)
    assert rc == 0
    line = json.loads(lines[-1])
    check = _window(lines)["check"]
    assert line["correct"] is True, check
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert check["same_sets"] and check["rows_checked"] > 0
    assert check["quiet_rows"] > 0 and check["quiet_same"]
    assert all(v <= 1.0 for v in check["excess"].values())
    if trace:
        # the CPU's trace has no device plane: the counters and the host
        # spans are there
        assert {"ffm_distinct_share", "peak_hbm_gb", "compiles_in_window",
                "ffm_stage_ms_per_chunk", "step_builds_in_window",
                "ffm_throttle_wait_ms_per_chunk",
                "ffm_step_mfu"} <= set(line["metrics"])
        assert 0 < line["metrics"]["ffm_distinct_share"]["value"] <= 100
        assert line["metrics"]["step_builds_in_window"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"rows_per_s", "setup_s"}


@pytest.mark.parametrize("wrong", ["mean_for_sum", "step_before_accumulate",
                                   "an_update_a_slot", "bf16_accumulators"])
def test_a_wrong_reading_of_the_rule_is_not_correct(capsys, toy_root,
                                                    monkeypatch, wrong):
    """Each control breaks the program, not the reference: the check has
    to come out false by at least one of its comparisons."""
    import jax.numpy as jnp

    from ytk_mp4j_tpu.models import fm
    from ytk_mp4j_tpu.ops import sparse as sparse_ops

    if wrong == "mean_for_sum":
        real = fm._weighted_mean_grads

        def mean(p, score_fn, y, sw, cfg, axis_name):
            loss, grads, denom = real(p, score_fn, y, sw, cfg, axis_name)
            return loss, tuple(g / denom for g in grads), denom
        monkeypatch.setattr(fm, "_weighted_mean_grads", mean)
    elif wrong == "step_before_accumulate":
        monkeypatch.setattr(fm, "_adagrad", lambda p, G, g, lr: (
            p - lr * g / jnp.sqrt(G), G + g * g))
    elif wrong == "an_update_a_slot":
        # no merge: every slot keeps its own gradient, so a feature a
        # chunk holds n times is set n times and one of them stays
        monkeypatch.setattr(sparse_ops, "segment_reduce_sorted",
                            lambda idx, val, capacity, operator: (idx, val))
    else:
        def bf16(p, G, g, lr):
            G = (G + g * g).astype(jnp.bfloat16).astype(jnp.float32)
            return p - lr * g / jnp.sqrt(G), G
        monkeypatch.setattr(fm, "_adagrad", bf16)
    rc, lines = _run(capsys, toy_root, 0)
    assert rc == 0
    check = _window(lines)["check"]
    assert json.loads(lines[-1])["correct"] is False, check
    assert any(v > 1.0 for v in check["excess"].values()), check
