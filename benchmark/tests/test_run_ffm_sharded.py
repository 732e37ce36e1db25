"""The cell ``ffm-criteo-sharded.stream-zipf-4chip`` end to end at a toy
size through ``run.main`` itself, on four virtual CPU devices with the
platform check stubbed (by hand, like the rest of this directory): the
contract's last line, ``correct`` true, the cell's metrics found by name,
``correct`` false where the exchange loses precision or rows, and a
program without the block form refused at once."""

import json
import os

import numpy as np
import pytest

from benchmark import arith_ffm_sharded, cells, run
from benchmark.readers import trace_scope_exposed_time as exposed

from conftest import ROOT

CELL = "ffm-criteo-sharded.stream-zipf-4chip"
# what the cell must report (it may report more)
SHARD = {"stream_next_ms_per_chunk",
         "shard_exchange_ms_per_chunk", "shard_exchange_exposed_ms_per_chunk",
         "shard_exchange_roofline", "shard_route_ms_per_chunk",
         "ffm_table_gather_ms_per_chunk", "ffm_table_update_ms_per_chunk",
         "shard_spread_ms_per_chunk", "ffm_grad_merge_ms_per_chunk",
         "ffm_distinct_share", "shard_owner_load_max_over_mean",
         "shard_exchange_rounds_per_chunk", "ffm_stage_ms_per_chunk",
         "ffm_dispatch_ms_per_chunk", "ffm_throttle_wait_ms_per_chunk",
         "step_builds_in_window", "rows_device_idle_share",
         "peak_hbm_gb", "compile_s", "compiles_in_window", "ffm_step_mfu"}


@pytest.fixture
def toy_root(tiny_root):
    for rel, cut in (
            ("configs/ffm-criteo-sharded.json", {"n_features": 39 * 64}),
            ("traffic/stream-zipf-4chip.json",
             {"rows_per_chunk": 64, "pool_chunks": 4, "trace_chunks": 3})):
        path = os.path.join(tiny_root, "benchmark", rel)
        with open(path) as f:
            doc = json.load(f)
        doc.update(cut)
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
    assert cell.chips == 4 and cell.adapter_name == "ffm_sharded"
    assert [m["name"] for m in cell.end_to_end] == ["rows_per_s", "setup_s"]
    assert SHARD <= {m["name"] for m in cell.per_layer}
    for m in cell.per_layer:
        assert m["spec"]["name"] == m["name"]
        for key in ("layer", "moves", "source"):
            assert m["spec"][key] == m[key], (m["name"], key)
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "readers", f"{m['spec']['reader']}.py"))
    c, t = cell.config, cell.traffic
    assert (c["n_features"], c["n_fields"], c["max_nnz"], c["k"]) == (
        2 ** 25, 39, 39, 4)
    assert c["architecture"] is None and list(c["reduced"]) == ["n_features"]
    assert c["table_sharding"] == "sharded" and c["sparse_grads"] is True
    # the one-chip FFM cell's traffic at four chips' rows, deeper in flight
    one = cells.load_cell(ROOT, "ffm-criteo.stream-zipf").traffic
    assert t["rows_per_chunk"] == 4 * one["rows_per_chunk"]
    assert t["max_in_flight"] == 16
    for key in ("kind", "pool_chunks", "zipf_exponent", "positive_rate"):
        assert t[key] == one[key], key
    # as deep since PR 49, and the one-chip slice four times the queue
    assert one["max_in_flight"] == 16 and one["trace_chunks"] == 64
    assert arith_ffm_sharded.block_values(39, 4) == 157
    # 28,200 blocks a chip, out and back: 35.4 MB
    assert arith_ffm_sharded.exchange_bytes_a_chip(
        4 * 28200, 4, 39, 4) == 2 * 28200 * 628


def test_the_accepted_cells_report_what_they_reported():
    for w in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))[
            "workloads"]:
        if w["name"] == CELL:
            continue
        got = {m["name"] for m in cells.load_cell(ROOT, w["name"]).per_layer}
        assert not any(n.startswith("shard_") for n in got), w["name"]


def test_exposed_time_is_what_no_other_operation_covers():
    a = exposed._merged(np.array([0., 5, 1, 20]), np.array([3., 8, 4, 25]))
    assert [list(v) for v in a] == [[0, 5, 20], [4, 8, 25]]
    b = exposed._merged(np.array([2., 6, 30]), np.array([6., 7, 40]))
    assert exposed._covered(a, b) == 4.0
    assert exposed._covered(a, exposed._merged(np.zeros(0), np.zeros(0))) == 0


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_and_is_correct(capsys, toy_root, trace):
    rc, lines = _run(capsys, toy_root, trace)
    assert rc == 0
    line = json.loads(lines[-1])
    window = _window(lines)
    check = window["check"]
    assert line["correct"] is True, check
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert check["rows_checked"] > 0 and check["quiet_same"]
    assert check["first_chunk_exchange_rounds"] == 1
    assert all(v <= 1.0 for v in check["excess"].values())
    counters = window["counters"]
    assert counters["exchange_rounds"] == counters["chunks"]
    assert 0 < counters["remote_blocks"] < counters["distinct_features"]
    assert counters["owner_load_max_over_mean"] >= 1.0
    if trace:
        # the CPU's trace has no device plane: the counters and the host
        # spans are there
        assert {"ffm_distinct_share", "shard_owner_load_max_over_mean",
                "shard_exchange_rounds_per_chunk", "peak_hbm_gb",
                "compiles_in_window", "ffm_stage_ms_per_chunk",
                "step_builds_in_window", "ffm_throttle_wait_ms_per_chunk",
                "stream_next_ms_per_chunk",
                "ffm_step_mfu"} <= set(line["metrics"])
        # three chunks with sixteen in flight: the host never waits, and
        # that is a reading
        assert line["metrics"]["ffm_throttle_wait_ms_per_chunk"][
            "value"] == 0.0
        assert line["metrics"]["shard_exchange_rounds_per_chunk"][
            "value"] == 1.0
        assert line["metrics"]["step_builds_in_window"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"rows_per_s", "setup_s"}


@pytest.mark.parametrize("wrong", ["bf16_fetch", "bf16_gradients",
                                   "a_round_not_run", "an_owner_drops"])
def test_an_exchange_that_loses_something_is_not_correct(capsys, toy_root,
                                                         monkeypatch, wrong):
    """Each control breaks the program, not the reference: the check has
    to come out false by at least one of its comparisons."""
    import jax.numpy as jnp

    from ytk_mp4j_tpu.models import fm
    from ytk_mp4j_tpu.ops import collectives

    def rounded(x):
        return x.astype(jnp.bfloat16).astype(x.dtype)

    real = collectives.all_to_all
    if wrong == "bf16_fetch":
        gather = fm._gather_blocks
        monkeypatch.setattr(fm, "_gather_blocks",
                            lambda T, feats: rounded(gather(T, feats)))
    elif wrong == "bf16_gradients":
        monkeypatch.setattr(
            collectives, "all_to_all",
            lambda x, *a, **kw: real(
                rounded(x) if x.dtype == jnp.float32 else x, *a, **kw))
    elif wrong == "a_round_not_run":
        # eight ids an owner a round, and one round whatever is left
        monkeypatch.setattr(fm, "_exchange_cap", lambda slots: 8)
        pmax = fm.lax.pmax
        monkeypatch.setattr(fm.lax, "pmax", lambda x, axis: jnp.minimum(
            pmax(x, axis), 1))
    else:
        # the owner adds the first member's list and not the others'
        fold = fm.sparse_ops.fold_live_tiles
        calls = []

        def first_only(idx, val, tile, body, carry):
            calls.append(val.ndim)
            adding = val.ndim == 2          # gradients, not positions
            if adding and sum(n == 2 for n in calls) % 4 != 1:
                return carry
            return fold(idx, val, tile, body, carry)
        monkeypatch.setattr(fm.sparse_ops, "fold_live_tiles", first_only)
    rc, lines = _run(capsys, toy_root, 0)
    assert rc == 0
    check = _window(lines)["check"]
    assert json.loads(lines[-1])["correct"] is False, check
    assert any(v > 1.0 for v in check["excess"].values()), check


def test_a_program_without_the_block_form_is_refused_at_once(
        capsys, toy_root, monkeypatch):
    from ytk_mp4j_tpu.ops import collectives

    monkeypatch.delattr(collectives, "all_to_all")
    with pytest.raises(RuntimeError, match="block form"):
        _run(capsys, toy_root, 0)
