"""``BENCHMARK.json: per_layer`` and ``benchmark/layer_metrics/`` say the
same thing, and one name is one quantity (ISSUE 49). No chip, no jax:
only ``cells.load_cell`` and the files.

Two entries are one quantity where their files name the same reader with
the same parameters and their entries the same ``moves``; PR 49 merged
every such group under one name, took the ``adapters`` filter out of
every file and gave every entry its list of cells. What a cell MUST
report is pinned below as it stood at PR 49; a later PR appends cells to
``workloads`` and entries to the list, and breaks nothing here.

By hand, like the rest of ``benchmark/tests`` (ISSUE 49 asked for this
file under ``tests/``; a ``benchmark`` PR may add no file there: PERF.md
section 7, left by PR 49)."""

import json
import os

import pytest

from benchmark import cells

from conftest import ROOT

LM = os.path.join(ROOT, "benchmark", "layer_metrics")
# what decides which number a file reads; ``instrumented`` only decides
# whether there is anything to read, ``note`` is prose
PARAMETERS = ("reader", "span", "scope", "pattern", "module", "counter",
              "stat", "per", "scale", "argument", "child", "passes")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
ENTRIES = {m["name"]: m for m in BENCH["per_layer"]}
CELLS = [w["name"] for w in BENCH["workloads"]]

# the names PR 49 retired, each a second name of the one after it (or of
# nothing: the last two); CHANGES.md, PR 49, has the table with values
RETIRED = {
    **{f"{p}_{q}": q
       for q in ("peak_hbm_gb", "compile_s", "compiles_in_window",
                 "step_builds_in_window")
       for p in ("bosch", "score", "adagrad", "ffmscore", "shard", "raw")},
    **{f"{p}_{q}": f"ffm_{q}"
       for q in ("stage_ms_per_chunk", "dispatch_ms_per_chunk",
                 "throttle_wait_ms_per_chunk", "table_gather_ms_per_chunk",
                 "table_update_ms_per_chunk", "grad_merge_ms_per_chunk")
       for p in ("adagrad", "shard")},
    "bosch_device_idle_share": "gbdt_device_idle_share",
    "raw_device_idle_share": "gbdt_device_idle_share",
    **{f"{p}_device_idle_share": "rows_device_idle_share"
       for p in ("ffm", "score", "adagrad", "ffmscore", "shard", "rawscore")},
    "raw_stage_gbps": "stage_gbps",
    "bosch_hist_ms_per_tree": "gbdt_hist_ms_per_tree",
    "raw_hist_ms_per_tree": "gbdt_hist_ms_per_tree",
    "bosch_hist_roofline": "hist_kernel_roofline",
    "bosch_hist_glue_ms_per_tree": "gbdt_hist_glue_ms_per_tree",
    "bosch_route_ms_per_tree": "gbdt_route_ms_per_tree",
    "bosch_split_leaf_ms_per_tree": "gbdt_split_leaf_ms_per_tree",
    "bosch_stage_ms_per_job": "gbdt_stage_ms_per_job",
    "bosch_dispatch_ms_per_tree": "gbdt_dispatch_ms_per_tree",
    "bosch_fetch_wait_ms_per_job": "gbdt_fetch_wait_ms_per_job",
    "raw_fetch_wait_ms_per_job": "gbdt_fetch_wait_ms_per_job",
    "adagrad_distinct_share": "ffm_distinct_share",
    "shard_distinct_share": "ffm_distinct_share",
    "ffmscore_stage_link_wait_ms_per_job": "score_stage_link_wait_ms_per_job",
    "ffmscore_stage_device_wait_ms_per_job":
        "score_stage_device_wait_ms_per_job",
    "ffmscore_stage_gbps": "score_stage_gbps",
    "ffm_scatter_gather_ms_per_chunk": None,
    "gbdt_collective_ms_per_tree": None,
    # PR 57: the leaf-wise cell's second name for the kernel's share by
    # needed rows; and the placer's launches, which no scoring call makes
    # since PR 52 (null in three cells)
    "gbdt_grow_hist_roofline": "hist_kernel_roofline",
    "score_stage_place_ms_per_job": None,
}

EVERY_CELL = {"peak_hbm_gb", "compile_s", "compiles_in_window"}
GBDT_TRAINING = EVERY_CELL | {
    "step_builds_in_window", "gbdt_device_idle_share",
    "gbdt_hist_ms_per_tree", "gbdt_hist_glue_ms_per_tree",
    "gbdt_bins_relayout_ms_per_tree", "gbdt_route_ms_per_tree",
    "gbdt_split_leaf_ms_per_tree", "gbdt_best_splits_ms_per_tree",
    "gbdt_leaf_ms_per_tree", "gbdt_stage_ms_per_job",
    "gbdt_dispatch_ms_per_tree", "gbdt_fetch_wait_ms_per_job",
    "gbdt_step_mfu", "stage_prep_ms_per_job", "stage_send_ms_per_job"}
ROW_CHUNKS = {"stage_link_wait_ms_per_job", "stage_device_wait_ms_per_job",
              "stage_gbps"}
FFM_TRAINING = EVERY_CELL | {
    "step_builds_in_window", "rows_device_idle_share",
    "ffm_stage_ms_per_chunk", "ffm_dispatch_ms_per_chunk",
    "ffm_throttle_wait_ms_per_chunk", "ffm_table_gather_ms_per_chunk",
    "ffm_table_update_ms_per_chunk", "ffm_grad_merge_ms_per_chunk",
    "ffm_step_mfu", "stream_next_ms_per_chunk"}
STAGED_SCORING = EVERY_CELL | {
    "step_builds_in_window", "score_stage_send_ms_per_job",
    "score_stage_link_wait_ms_per_job",
    "score_stage_device_wait_ms_per_job", "score_stage_gbps"}
GBDT_SCORING = STAGED_SCORING | {
    "rows_device_idle_share", "score_stage_ms_per_job",
    "score_dispatch_ms_per_job", "score_fetch_wait_ms_per_job",
    "score_select_ms_per_job", "score_walk_ms_per_job", "score_step_mfu"}
# what each cell must report, as ISSUE 49's table gives it
MUST = {
    "gbdt-higgs-11m.train": GBDT_TRAINING | {
        "hist_kernel_roofline", "hist_kernel_ms_per_tree",
        "stage_ms_per_job"},
    "gbdt-bosch-968.train": GBDT_TRAINING | ROW_CHUNKS | {
        "hist_kernel_roofline", "hist_kernel_ms_per_tree"},
    "gbdt-bosch-968-raw.train-raw-chunks": GBDT_TRAINING | ROW_CHUNKS | {
        "hist_kernel_roofline", "raw_stage_ms_per_job",
        "raw_sketch_ms_per_job",
        "raw_sketch_device_ms_per_job", "raw_transform_ms_per_job",
        "raw_transform_roofline"},
    "ffm-criteo.stream-zipf": FFM_TRAINING,
    "ffm-criteo-adagrad.stream-zipf": FFM_TRAINING | {
        "ffm_distinct_share", "adagrad_rule_ms_per_chunk",
        "adagrad_update_roofline"},
    "ffm-criteo-sharded.stream-zipf-4chip": FFM_TRAINING | {
        "ffm_distinct_share", "ffm_collective_ms_per_chunk",
        "shard_exchange_ms_per_chunk",
        "shard_exchange_exposed_ms_per_chunk", "shard_exchange_roofline",
        "shard_route_ms_per_chunk", "shard_spread_ms_per_chunk",
        "shard_owner_load_max_over_mean",
        "shard_exchange_rounds_per_chunk"},
    "gbdt-bosch-score-500.batch": GBDT_SCORING | {"score_roofline"},
    "gbdt-bosch-score-raw-500.raw-chunks": GBDT_SCORING | {
        "rawscore_transform_ms_per_job", "rawscore_roofline",
        "rawscore_transform_roofline"},
    "ffm-criteo-score.file-zipf": STAGED_SCORING | {
        "rows_device_idle_share", "ffmscore_table_gather_ms_per_job",
        "ffmscore_select_ms_per_job", "ffmscore_pairs_ms_per_job",
        "ffmscore_roofline", "ffmscore_stage_ms_per_job",
        "ffmscore_dispatch_ms_per_job", "ffmscore_fetch_wait_ms_per_job",
        "ffmscore_enter_s", "ffmscore_step_mfu"},
    # PR 53's cell, which had no row here until PR 57
    "gbdt-bosch-968-leafwise.train": GBDT_TRAINING | ROW_CHUNKS | {
        "hist_kernel_roofline", "gbdt_grow_ms_per_tree",
        "gbdt_grow_unscoped_ms_per_tree", "gbdt_grow_rows_built_share"},
    "allreduce-4rank.hist-and-bulk": EVERY_CELL | {
        "allreduce_device_idle_share", "collective_scope_us_per_tree",
        "collective_us_per_tree", "collective_ms_bulk",
        "allreduce_bulk_roofline"},
}


def _spec(name):
    with open(os.path.join(LM, f"{name}.json")) as f:
        return json.load(f)


def _quantity(name):
    spec = _spec(name)
    return (ENTRIES[name]["moves"],) + tuple(
        json.dumps(spec.get(k)) for k in PARAMETERS)


def test_the_list_fits_and_leaves_room():
    assert len(BENCH["per_layer"]) <= 128
    assert len(ENTRIES) == len(BENCH["per_layer"])      # no name twice
    assert sorted(MUST) == sorted(CELLS)


def test_every_entry_has_its_file_and_every_file_its_entry():
    files = {f[:-5] for f in os.listdir(LM) if f.endswith(".json")}
    assert files == set(ENTRIES)
    assert not [f for f in os.listdir(LM) if not f.endswith(".json")]


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_an_entry_and_its_file_agree(name):
    spec, entry = _spec(name), ENTRIES[name]
    assert spec["name"] == name and "adapters" not in spec
    for key in ("layer", "moves", "source"):
        assert spec[key] == entry[key], key
    assert spec.get("note"), "a note says what the number is in each cell"
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "readers", f"{spec['reader']}.py"))
    # an explicit list of accepted cells, each reporting what it moves: a
    # metric with no list would have to be reported by every later cell
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == entry["moves"])
    assert entry["workloads"] and len(set(entry["workloads"])) == len(
        entry["workloads"])
    for cell in entry["workloads"]:
        assert cell in CELLS
        assert cell in moved.get("workloads", CELLS), (cell, entry["moves"])


def test_one_name_a_quantity():
    seen = {}
    for name in ENTRIES:
        seen.setdefault(_quantity(name), []).append(name)
    assert not [names for names in seen.values() if len(names) > 1]


@pytest.mark.parametrize("old", sorted(RETIRED))
def test_a_retired_name_is_gone(old):
    assert old not in ENTRIES
    assert not os.path.exists(os.path.join(LM, f"{old}.json"))
    if RETIRED[old] is not None:
        assert RETIRED[old] in ENTRIES


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_reports_what_the_table_gives_it(cell):
    loaded = cells.load_cell(ROOT, cell)
    got = {m["name"] for m in loaded.per_layer}
    assert MUST[cell] <= got, sorted(MUST[cell] - got)
    # what the cell is listed for is what it loads: no file filters
    listed = {n for n, e in ENTRIES.items() if cell in e["workloads"]}
    assert got == listed
    assert not got & set(RETIRED)
    # a kernel's roofline that moves an end-to-end metric has the whole
    # step's share of the peak beside it, moving the same metric
    for m in loaded.per_layer:
        if m["name"].endswith("_roofline") and m["layer"] in (
                "kernels", "ops.sparse"):
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       for o in loaded.per_layer), m["name"]


def test_the_raw_scoring_cell_reports_what_its_twin_reports():
    """PR 47 could add three of its sixteen; PR 49 made the room."""
    raw = {m["name"] for m in cells.load_cell(
        ROOT, "gbdt-bosch-score-raw-500.raw-chunks").per_layer}
    twin = {m["name"] for m in cells.load_cell(
        ROOT, "gbdt-bosch-score-500.batch").per_layer}
    assert len(raw) >= 16
    assert twin - raw == {"score_roofline"}     # rawscore_roofline is its


@pytest.mark.parametrize("metric,counters,flops", [
    # 28 columns, 256 bins: 2 * 4 * B * F a needed row, 77M rows over
    # the slice's 2 jobs (the benchmark's count, not the program's)
    ("gbdt_step_mfu", {"jobs": 2, "hist_rows_needed": 77_000_000},
     2.0 * 4 * 256 * 28 * 77_000_000),
    # 1,183,748 x 968 against 64 one-hot rows a tree, 500 trees, 2 jobs
    ("score_step_mfu", {"jobs": 2}, 2 * 2.0 * 1_183_748 * 968 * 64 * 500),
    # 741 pairs of a 4-long dot product and the linear term a row
    ("ffmscore_step_mfu", {"rows": 6_042_135},
     6_042_135 * (741 * 10 + 78.0)),
    ("ffm_step_mfu", {"rows": 32_768}, 3 * 32_768 * (741 * 10 + 78.0)),
])
def test_a_step_mfu_is_the_slices_flops_over_the_peak(metric, counters,
                                                      flops):
    """The whole step's share of the chip's peak needs the slice and a
    count, and nothing found in the trace: it still reads where a
    kernel's roofline has gone silent."""
    spec = _spec(metric)
    reader = cells.load_module(ROOT, "readers", spec["reader"])
    cell = next(c for c in CELLS if c in ENTRIES[metric]["workloads"])
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    run = {"trace": object(), "window_ns": (1e9, 3e9), "counters": counters,
           "config": cells.load_cell(ROOT, cell).config, "peaks": peaks,
           "chips": 1}
    want = 100.0 * flops / peaks["bf16_flops"] / 2.0
    assert reader.read(spec, run) == pytest.approx(want)
    assert 0 < want < 100
    assert reader.read(spec, {**run, "trace": None}) is None
    assert reader.read(spec, {**run, "counters": {}}) is None
    assert reader.read(spec, {**run, "window_ns": (0.0, 0.0)}) is None
