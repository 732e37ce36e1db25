"""Trainer spans and device scopes (ISSUE 24): ``train()`` and
``fit_stream()`` leave exactly the ``mp4j.*`` host spans PERF.md section 3
lists in the span ring, with the shared ``job`` / ``chunk`` argument and
children inside parents; the ring switched off leaves none and changes no
result; the lowered steps carry every named scope, and the scopes change
no instruction of the optimised program."""

import contextlib
import glob
import hashlib
import json
import re
import time
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ytk_mp4j_tpu.models import fm as fm_mod
from ytk_mp4j_tpu.models._base import DataParallelTrainer
from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer
from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer
from ytk_mp4j_tpu.models.linear import LinearConfig, LinearTrainer
from ytk_mp4j_tpu.obs import spans
from ytk_mp4j_tpu.operators import Operators
from ytk_mp4j_tpu.ops import collectives
from ytk_mp4j_tpu.ops.hist_kernel import pallas_histograms
from ytk_mp4j_tpu.parallel.mesh import make_mesh
from ytk_mp4j_tpu.utils import trace, tuning

N_SHARDS = 4


@pytest.fixture
def ring():
    """A fresh, private-sized ring; the job's own size afterwards."""
    spans.configure(4096)
    try:
        yield
    finally:
        spans.configure(tuning.span_ring_capacity())


def _trainer_spans():
    return [s for s in spans.snapshot() if s[1] == "trainer"]


def _named(recorded, name):
    return [s for s in recorded if s[0] == name]


def _inside(child, parent) -> bool:
    return (parent[2] <= child[2]
            and child[2] + child[3] <= parent[2] + parent[3])


def _gbdt(rng):
    N, F, B = 512, 4, 16
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    y = (bins[:, 0] > B // 2).astype(np.float32)
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, n_trees=3,
                     loss="logistic")
    return GBDTTrainer(cfg, mesh=make_mesh(N_SHARDS)), bins, y


def _ffm(rng, n_chunks, optimizer="sgd", **kw):
    cfg = FMConfig(n_features=32, n_fields=4, k=4, max_nnz=4, model="ffm",
                   learning_rate=0.1, optimizer=optimizer)
    chunks = []
    for _ in range(n_chunks):
        feats = rng.integers(0, 32, (16, 4)).astype(np.int32)
        fields = np.tile(np.arange(4, dtype=np.int32), (16, 1))
        vals = rng.random((16, 4)).astype(np.float32) + 0.1
        chunks.append((feats, fields, vals,
                       rng.integers(0, 2, 16).astype(np.float32)))
    return FMTrainer(cfg, mesh=make_mesh(N_SHARDS), sparse_grads=True,
                     **kw), chunks


def _linear(rng, n_chunks):
    cfg = LinearConfig(n_features=5, learning_rate=0.1)
    chunks = [(rng.standard_normal((16, 5)).astype(np.float32),
               rng.standard_normal(16).astype(np.float32))
              for _ in range(n_chunks)]
    return LinearTrainer(cfg, mesh=make_mesh(N_SHARDS)), chunks


# ------------------------------------------------------------- host spans
def test_gbdt_train_leaves_exactly_its_spans(rng, ring):
    tr, bins, y = _gbdt(rng)
    tr.train(bins, y)
    got = _trainer_spans()
    assert sorted({s[0] for s in got}) == [
        "mp4j.gbdt.dispatch", "mp4j.gbdt.fetch", "mp4j.gbdt.stage",
        "mp4j.put_sharded", "mp4j.stage.prep", "mp4j.stage.send",
        "mp4j.step.build"]
    (stage,), (fetch,) = (_named(got, "mp4j.gbdt.stage"),
                          _named(got, "mp4j.gbdt.fetch"))
    dispatch = _named(got, "mp4j.gbdt.dispatch")
    assert stage[6] == {"job": 0} and fetch[6] == {"job": 0}
    assert [s[6] for s in dispatch] == [{"job": 0, "tree": i}
                                        for i in range(3)]
    puts = _named(got, "mp4j.put_sharded")
    assert len(puts) == 4                       # bins, y, preds, weights
    assert all(_inside(p, stage) for p in puts)
    assert puts[0][6] == {"bytes": bins.nbytes}
    # one hand-over a put (the one-transfer regime), inside it
    sends = _named(got, "mp4j.stage.send")
    assert [s[6] for s in sends] == [{"chunk": 0, **p[6]} for p in puts]
    assert all(_inside(s, p) for s, p in zip(sends, puts))
    # what the host builds before anything is sent: nothing for the
    # table (512 rows fill four shards, and ``shard_bins`` asks for no
    # weights), the labels' weights, the margins; inside the stage span
    # and outside every put
    preps = _named(got, "mp4j.stage.prep")
    assert [s[6] for s in preps] == [{"bytes": 0}] + [{"bytes": 4 * 512}] * 2
    assert all(_inside(s, stage) for s in preps)
    assert not any(_inside(s, p) for s in preps for p in puts)
    # in order on the host: build, stage, every tree, fetch
    order = [s[0] for s in got if s[0] != "mp4j.put_sharded"
             and not s[0].startswith("mp4j.stage.")]
    assert order == ["mp4j.step.build", "mp4j.gbdt.stage"] \
        + ["mp4j.gbdt.dispatch"] * 3 + ["mp4j.gbdt.fetch"]
    assert all(a[2] + a[3] <= b[2] for a, b in zip(
        [stage] + dispatch, dispatch + [fetch]))

    # the next job: a new ``job``, and the step is not built again
    tr.train(bins, y, n_trees=1)
    got = _trainer_spans()
    assert len(_named(got, "mp4j.step.build")) == 1
    assert [s[6]["job"] for s in _named(got, "mp4j.gbdt.stage")] == [0, 1]
    assert _named(got, "mp4j.gbdt.dispatch")[-1][6] == {"job": 1, "tree": 0}


@pytest.mark.parametrize("F,depth,hist_mode,want", [
    # hist_radix: the high digits a bin is split into, level by level
    # (1, 1, 2 nodes at depth 3); route_sliced_levels: the levels that
    # route on sliced columns (1, 2, 4 nodes on 4 columns)
    (4, 3, "pallas", {"route_sliced_levels": 3,
                      "hist_feature_block": 4, "hist_feature_blocks": 1,
                      "hist_radix": "4,4,4"}),
    # the deepest level builds 2**(depth-2) left children: at 16 nodes
    # and 256 bins a block holds at most 128 features, 968 go in 11 x 88;
    # a slice reads its tile row's eight columns, 256 of 968 at 32 nodes
    (968, 6, "pallas", {"route_sliced_levels": 6,
                        "hist_feature_block": 88,
                        "hist_feature_blocks": 11,
                        "hist_radix": "4,4,4,2,2,1"}),
    # up to 16 nodes ask for fewer columns than the table's 28
    (28, 8, "pallas", {"route_sliced_levels": 5,
                       "hist_feature_block": 28, "hist_feature_blocks": 1,
                       "hist_radix": "4,4,4,2,2,1,1,1"}),
    (968, 6, "matmul", {"route_sliced_levels": 6}),
    # a multiple of 128 rests row-major: a slice reads its lane word
    (256, 6, "matmul", {"route_sliced_levels": 2}),
])
def test_step_build_span_says_which_histogram_grid_runs(ring, F, depth,
                                                        hist_mode, want):
    cfg = GBDTConfig(n_features=F, n_bins=256, depth=depth,
                     hist_mode=hist_mode)
    GBDTTrainer(cfg, mesh=make_mesh(1))._build_step()
    (build,) = _named(_trainer_spans(), "mp4j.step.build")
    assert build[6] == want


@pytest.mark.parametrize("depth,want", [
    (0, []), (1, [1]), (2, [1, 1]), (6, [1, 1, 2, 4, 8, 16])])
def test_levels_build_the_root_then_the_left_children(depth, want):
    """What ``_build_tree`` loops over and the build span reports the
    last of."""
    from ytk_mp4j_tpu.models.gbdt import hist_level_nodes
    assert hist_level_nodes(depth) == want


def test_row_placer_build_is_a_step_build_span(rng, ring):
    """The program that places row chunks of an oversized shard is
    built once a (table, chunk) shape, inside a ``mp4j.step.build`` span
    of its own: a job that built it anew would show, as a rebuilt step
    does."""
    tr, bins, y = _gbdt(rng)
    tr._ONE_TRANSFER_BYTES = bins.nbytes // N_SHARDS
    tr._EACH_CHUNK_BYTES = 1024
    for _ in range(2):
        tr.train(bins, y, n_trees=1)
    builds = [s[6] for s in _named(_trainer_spans(), "mp4j.step.build")]
    assert len(builds) == 2 and "hist_feature_block" in builds[0]
    assert builds[1] == {"key": "row_placer",
                         "rows": 1024 // (bins.shape[1] * 4)}


@pytest.mark.parametrize("max_in_flight", [0, 2])
@pytest.mark.parametrize("family", ["ffm", "linear"])
def test_fit_stream_leaves_exactly_its_spans(rng, ring, family,
                                             max_in_flight):
    n = 5
    tr, chunks = (_ffm if family == "ffm" else _linear)(rng, n)
    tr.fit_stream(iter(chunks), max_in_flight=max_in_flight)
    got = _trainer_spans()
    stream = ["mp4j.stream.dispatch", "mp4j.stream.fetch",
              "mp4j.stream.next", "mp4j.stream.stage", "mp4j.stage.send"]
    if max_in_flight < n - 1:
        stream.append("mp4j.stream.throttle")
    # the linear step is built outside the loop and is not a span; the
    # FFM stream converts its table on the way in and on the way out
    built = (["mp4j.step.build", "mp4j.stream.widen", "mp4j.stream.narrow"]
             if family == "ffm" else [])
    assert sorted({s[0] for s in got}) == sorted(
        stream + ["mp4j.put_sharded"] + built)

    stage = _named(got, "mp4j.stream.stage")
    dispatch = _named(got, "mp4j.stream.dispatch")
    assert [s[6] for s in stage] == [{"chunk": k} for k in range(n)]
    assert [s[6] for s in dispatch] == [{"chunk": k} for k in range(n)]
    # the caller's iterator is asked once a chunk and once more, the call
    # that finds it exhausted; chunk k is taken before it is staged
    nexts = _named(got, "mp4j.stream.next")
    assert [s[6] for s in nexts] == [{"chunk": k} for k in range(n + 1)]
    assert all(nexts[k][2] + nexts[k][3] <= stage[k][2] for k in range(n))
    # the throttle names the chunk it waits for: after chunk k is
    # launched, the one ``max_in_flight`` before it
    throttle = _named(got, "mp4j.stream.throttle")
    assert [s[6]["chunk"] for s in throttle] == list(
        range(n - 1 - max_in_flight))
    (fetch,) = _named(got, "mp4j.stream.fetch")
    assert fetch[6] == {"chunks": n}
    # chunk k is staged while step k - 1 runs: after its dispatch
    assert all(dispatch[k - 1][2] + dispatch[k - 1][3] <= stage[k][2]
               for k in range(1, n))
    puts = _named(got, "mp4j.put_sharded")
    assert len(puts) % n == 0 and all(
        any(_inside(p, s) for s in stage) for p in puts)
    sends = _named(got, "mp4j.stage.send")
    assert len(sends) == len(puts) and all(
        _inside(s, p) and s[6] == {"chunk": 0, **p[6]}
        for s, p in zip(sends, puts))
    if built:
        converters, build = _named(got, "mp4j.step.build")
        (widen,) = _named(got, "mp4j.stream.widen")
        (narrow,) = _named(got, "mp4j.stream.narrow")
        assert _inside(converters, widen)
        assert converters[6] == {"key": "table_converters",
                                 "block_features": 32}
        assert _inside(build, dispatch[0])
        # one gather descriptor a (sample, feature) and one scatter
        # descriptor a distinct feature, the linear weight's included
        assert build[6] == {"key": (16 // N_SHARDS) * 4,
                            "table_form": "blocks",
                            "descriptors": (16 // N_SHARDS) * 4,
                            "dead_rows": 0,
                            "index_streams": 1, "optimizer": "sgd",
                            "block_width": 128, "capacity": 32,
                            "update_tile": 32, "update_tiles": 1,
                            "select_columns": "component"}
        # the table is converted before the first chunk is staged and
        # after the last loss is fetched
        assert widen[2] + widen[3] <= stage[0][2]
        assert fetch[2] + fetch[3] <= narrow[2]


def test_a_new_padded_shape_is_a_second_build_span(rng, ring):
    tr, chunks = _ffm(rng, 2)
    tr.fit_stream(iter(chunks), batch_rows=16)
    tr.fit_stream(iter(chunks), batch_rows=16)      # same step again
    tr.fit_stream(iter(chunks), batch_rows=32)      # padded shape changed
    keys = [s[6]["key"] for s in _named(_trainer_spans(),
                                        "mp4j.step.build")]
    # the converters are built once, the step once a padded shape
    assert keys == ["table_converters", 16, 32]


@pytest.mark.parametrize("kw,carries", [
    ({}, True), ({"sparse_capacity": 8}, True),
    ({"table_sharding": "sharded"}, False),
    ({"optimizer": "adagrad"}, True)],
    ids=["replicated", "dedupe", "sharded", "adagrad"])
def test_step_build_span_says_the_table_form(rng, ring, kw, carries):
    """The replicated sparse step indexes by feature (N x K descriptors
    of a shard) and by nothing else (one index stream: the linear
    weights ride in the blocks; AdaGrad's accumulators too, in a block
    twice as wide). The sharded step is in the same form, a member's
    share of the blocks: it says how many owners the table is cut over
    and how many ids a member asks of one owner a round of the exchange
    (no more than the member has slots), and carries no ``capacity``
    (all of a member's slots are merged) and no ``optimizer`` (SGD, the
    only rule it has). Both replicated steps walk the merged list's live
    prefix in tiles and say the tile."""
    tr, chunks = _ffm(rng, 1, **kw)
    tr.fit_stream(iter(chunks))
    build = [s[6] for s in _named(_trainer_spans(), "mp4j.step.build")
             if s[6]["key"] == 16]
    want = {"key": 16}
    if carries:
        # 17 floats, or 17 and their 17 accumulators, in one 128-lane word
        want.update(table_form="blocks", descriptors=16, dead_rows=0,
                    index_streams=1,
                    optimizer=kw.get("optimizer", "sgd"), block_width=128,
                    capacity=kw.get("sparse_capacity", 32),
                    select_columns="component",
                    # the update loop's tile (the whole merged list where
                    # it is shorter than one; SGD's step walks it too
                    # since PR 39) and its trips when every slot holds
                    # another feature
                    update_tile=kw.get("sparse_capacity", 32),
                    update_tiles=1)
    if "table_sharding" in kw:
        want.update(table_sharding="sharded", table_form="blocks",
                    owners=N_SHARDS, exchange_cap=16, exchange_tile=16,
                    descriptors=16, index_streams=1,
                    block_width=128, select_columns="component")
    assert build == [want]


@pytest.mark.parametrize("optimizer", ["sgd", "adagrad"])
@pytest.mark.parametrize("rows,dead", [(2048, 8), (2000, 0)])
def test_step_build_span_says_the_dead_rows(ring, optimizer, rows, dead):
    """A member's chunk whose slots are a whole number of 1,024s (2,048
    rows of 39) is stepped with eight dead rows after it
    (``fm._with_dead_rows``), any other as it comes: ``dead_rows`` says
    which form was built, and ``descriptors`` is the step's own count,
    the dead rows' slots included; ``key`` and ``capacity`` stay the
    caller's slots (the merge drops the dead rows' after its sort)."""
    cfg = FMConfig(n_features=1 << 20, n_fields=39, k=4, max_nnz=39,
                   model="ffm", optimizer=optimizer)
    tr = FMTrainer(cfg, mesh=make_mesh(1), sparse_grads=True)
    tr._build_step(rows * 39)
    (build,) = [s[6] for s in _named(_trainer_spans(), "mp4j.step.build")]
    assert build["key"] == rows * 39 == build["capacity"]
    assert build["dead_rows"] == dead
    assert build["descriptors"] == (rows + dead) * 39
    assert build["optimizer"] == optimizer


def _chunked(n_shards, per=1000, width=16):
    """A trainer whose shards cross in sixteen chunks of 64 rows, the
    last one early, whichever way (``tests/test_row_chunk_staging.py``)."""
    t = DataParallelTrainer(n_devices=n_shards)
    t._ONE_TRANSFER_BYTES = per * width * 4
    t._EACH_CHUNK_BYTES = 4096
    return t, np.arange(n_shards * per * width, dtype=np.int32).reshape(
        n_shards * per, width)


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("taker", ["table", "pieces"])
def test_row_chunks_leave_a_send_a_chunk_and_the_paces_waits(ring, n_shards,
                                                             taker):
    """One pace whoever takes the pieces: the table builder
    (``_put_sharded``), which launches its placer under ``place``, or a
    caller that reads each piece once (``_pieces``: a scoring call),
    which launches what it likes and leaves no ``place``."""
    t, a = _chunked(n_shards)
    calls = []
    if taker == "table":
        got = np.asarray(t._put_sharded(a, 1000))
    else:
        got = np.zeros((n_shards, 1000, 16), np.int32)
        for k, piece, shard, start, stop, turns in t._pieces(a, 1000):
            calls.append((start, stop))
            got[:, start:stop] = np.asarray(piece).reshape(n_shards, -1, 16)
            turns.append(piece.reshape(-1)[:1])
    np.testing.assert_array_equal(got.reshape(a.shape), a)
    recorded = _trainer_spans()
    (put,) = _named(recorded, "mp4j.put_sharded")
    assert put[6] == {"bytes": a.nbytes}
    stage = [s for s in recorded if s[0].startswith("mp4j.stage.")]
    assert all(_inside(s, put) for s in stage)
    # children on one thread: together no longer than their parent
    assert sum(s[3] for s in stage) <= put[3]
    send, place = (_named(stage, "mp4j.stage.send"),
                   _named(stage, "mp4j.stage.place"))
    # what is handed to the runtime: sixteen whole chunks of every shard,
    # so the 24 rows that the early last chunk brings again count twice
    assert [s[6] for s in send] == [
        {"chunk": k, "bytes": n_shards * 64 * 16 * 4} for k in range(16)]
    assert sum(s[6]["bytes"] for s in send) \
        == a.nbytes + n_shards * 24 * 16 * 4
    link = [s[6]["chunk"] for s in _named(stage, "mp4j.stage.link_wait")]
    device = [s[6]["chunk"] for s in _named(stage, "mp4j.stage.device_wait")]
    # two crossing, so chunk k - 1 has crossed before k + 1 is sent; the
    # device is waited for only when twelve wait for their turn
    assert link == list(range(15)) and device == list(range(4))
    if taker == "table":
        assert [s[6] for s in place] == [{"chunk": k} for k in range(16)]
        assert all(s[2] + s[3] <= p[2] for s, p in zip(send, place))
    else:
        assert place == [] and t._row_placers == {}
        assert calls == [(min(64 * k, 1000 - 64), min(64 * k, 1000 - 64) + 64)
                         for k in range(16)]
    assert {s[0] for s in stage} == {
        "mp4j.stage.send", "mp4j.stage.device_wait",
        "mp4j.stage.link_wait"} | ({"mp4j.stage.place"} if place else set())


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("n_rows,width,chunk_rows,words", [
    (1000, 16, 64, True),       # a chunk's cells fill rows of 128 words
    (1003, 16, 64, True),       # ... and four shards pad the last one
    (1000, 24, 45, False),      # 1,080 cells a chunk: in its own shape
    (1003, 24, 45, False),
])
def test_train_stages_a_table_over_one_chunk_to_the_bit(
        rng, ring, monkeypatch, n_shards, n_rows, width, chunk_rows, words):
    """``train()``'s staging of a table over one chunk: what rests on the
    mesh is the host's array to the bit, the last chunk ragged and the
    last shard padded or not; every chunk is one ``mp4j.stage.send`` of
    ``_EACH_CHUNK_BYTES``; and nothing N-sized is built for the table
    (``shard_bins`` asks ``_pad_rows`` for no weights)."""
    tr = GBDTTrainer(GBDTConfig(n_features=width, n_bins=16, depth=2,
                                loss="logistic", hist_mode="matmul"),
                     n_devices=n_shards)
    tr._ONE_TRANSFER_BYTES = tr._EACH_CHUNK_BYTES = chunk_rows * width * 4
    bins = rng.integers(0, 16, (n_rows, width)).astype(np.int32)
    crossed = []
    put = jax.make_array_from_callback
    monkeypatch.setattr(
        jax, "make_array_from_callback",
        lambda shape, *a, **kw: crossed.append(shape) or put(shape, *a, **kw))
    table = tr.shard_data(bins, np.zeros(n_rows, np.float32))[0]
    per = -(-n_rows // n_shards)
    want = np.zeros((n_shards * per, width), np.int32)
    want[:n_rows] = bins
    assert table.shape == (n_shards, per, width) and table.dtype == np.int32
    np.testing.assert_array_equal(np.asarray(table).reshape(-1, width), want)
    n_chunks = -(-per // chunk_rows)
    assert per % chunk_rows and n_chunks > 3    # over a chunk, and ragged
    recorded = _trainer_spans()
    sends = [s[6] for s in _named(recorded, "mp4j.stage.send")]
    assert sends[:n_chunks] == [
        {"chunk": k, "bytes": n_shards * tr._EACH_CHUNK_BYTES}
        for k in range(n_chunks)]
    assert len(sends) == n_chunks + 3       # labels, margins, weights: one each
    assert crossed[:n_chunks] == [
        (n_shards, chunk_rows * width // 128, 128) if words
        else (n_shards, chunk_rows, width)] * n_chunks
    # the table's prep: a padded copy where the shards need one, and never
    # the 4 bytes a row of a weight vector
    padded = want.nbytes if n_shards * per > n_rows else 0
    assert [s[6]["bytes"] for s in _named(recorded, "mp4j.stage.prep")] == [
        padded, 4 * n_shards * per + (4 * n_shards * per if padded else 0),
        4 * n_shards * per]


def test_the_bosch_width_crosses_in_128_mib_pieces(ring):
    """The constants as they are: 36,000 rows of the Bosch table's 968
    columns (139 MB) cross in two pieces of 34,560 rows (whole rows of
    128 lanes under 128 MiB), the second starting early, and rest to the
    bit; ``shard_bins`` builds nothing on the host."""
    assert (DataParallelTrainer._EACH_CHUNK_BYTES,
            DataParallelTrainer._CHUNKS_CROSSING,
            DataParallelTrainer._CHUNKS_AHEAD) == (128 * 2 ** 20, 2, 12)
    assert not hasattr(DataParallelTrainer, "_CHUNK_BYTES")
    tr = GBDTTrainer(GBDTConfig(n_features=968, n_bins=256, depth=2,
                                loss="logistic", hist_mode="matmul"),
                     n_devices=1)
    tr._ONE_TRANSFER_BYTES = tr._EACH_CHUNK_BYTES
    bins = (np.arange(36_000 * 968, dtype=np.int32) % 251).reshape(-1, 968)
    table = tr.shard_bins(bins)
    assert np.array_equal(np.asarray(table)[0], bins)
    recorded = _trainer_spans()
    assert [s[6] for s in _named(recorded, "mp4j.stage.send")] == [
        {"chunk": k, "bytes": 34_560 * 968 * 4} for k in range(2)]
    assert 128 * 2 ** 20 - 128 * 968 * 4 < 34_560 * 968 * 4 <= 128 * 2 ** 20
    assert [s[6] for s in _named(recorded, "mp4j.stage.prep")] == [
        {"bytes": 0}]
    (build,) = _named(recorded, "mp4j.step.build")
    assert build[6] == {"key": "row_placer", "rows": 34_560}


@pytest.mark.parametrize("family", ["ffm", "linear"])
def test_a_slow_iterator_shows_in_stream_next_and_nowhere_else(rng, ring,
                                                               family):
    n, slow, nap = 4, 2, 0.25
    tr, chunks = (_ffm if family == "ffm" else _linear)(rng, n)

    def reader():
        for k, chunk in enumerate(chunks):
            if k == slow:
                time.sleep(nap)
            yield chunk

    tr.fit_stream(reader())
    got = _trainer_spans()
    nexts = _named(got, "mp4j.stream.next")
    assert [s[6]["chunk"] for s in nexts] == list(range(n + 1))
    assert nexts[slow][3] >= nap
    assert all(s[3] < nap / 2 for s in nexts if s is not nexts[slow])
    # no other span holds any of it: none overlaps a next
    others = [s for s in got if s[0] != "mp4j.stream.next"]
    assert len(others) > 3 * n and all(
        s[2] + s[3] <= nx[2] or nx[2] + nx[3] <= s[2]
        for s in others for nx in nexts)


@pytest.mark.parametrize("family", ["gbdt", "ffm", "row-chunks",
                                    "row-chunks-scored"])
def test_ring_off_leaves_no_span_and_the_same_bits(rng, family):
    def run():
        r = np.random.default_rng(7)
        if family == "row-chunks":
            t, a = _chunked(N_SHARDS)
            return [np.asarray(t._put_sharded(a, 1000))]
        if family == "row-chunks-scored":
            # a table scored in pieces as they cross: no table, no placer
            tr, bins, y = _gbdt(r)
            trees, _ = tr.train(bins, y)
            tr._ONE_TRANSFER_BYTES, tr._EACH_CHUNK_BYTES = 1, 16 * 4 * 4
            return [tr.predict(bins, trees)]
        if family == "gbdt":
            tr, bins, y = _gbdt(r)
            trees, margins = tr.train(bins, y)
            return [np.asarray(a) for t in trees for a in t] + [margins]
        tr, chunks = _ffm(r, 3)
        params, losses = tr.fit_stream(iter(chunks))
        return [np.asarray(p) for p in params] + [losses]

    try:
        spans.configure(4096)
        on = run()
        assert _trainer_spans()
        spans.configure(0)
        off = run()
        assert not spans.enabled() and spans.snapshot() == []
    finally:
        spans.configure(tuning.span_ring_capacity())
    assert len(on) == len(off)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)


def test_span_records_through_an_exception_and_passes_it_on(ring):
    with pytest.raises(KeyError):
        with spans.span("mp4j.test.raises", chunk=3):
            raise KeyError("x")
    (s,) = _named(spans.snapshot(), "mp4j.test.raises")
    assert s[1] == "trainer" and s[3] >= 0 and s[6] == {"chunk": 3}
    with spans.span("mp4j.test.bare", cat="other"):
        pass
    (s,) = _named(spans.snapshot(), "mp4j.test.bare")
    assert s[1] == "other" and s[6] is None


def test_span_is_on_the_profilers_clock_under_a_profile(tmp_path, ring):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with spans.span("mp4j.test.outer", job=4):
            with spans.span("mp4j.test.inner", chunk=9):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    found = {e.name: (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in data.planes for line in plane.lines
             for e in line.events if e.name.startswith("mp4j.test.")}
    outer, inner = found["mp4j.test.outer"], found["mp4j.test.inner"]
    assert outer[2] == {"job": 4} and inner[2] == {"chunk": 9}
    assert outer[0] <= inner[0] and inner[1] <= outer[1]
    # and in the ring as ever
    assert [s[0] for s in _trainer_spans()] == ["mp4j.test.inner",
                                                "mp4j.test.outer"]


def test_chrome_trace_holds_trainer_events_with_monotone_ts(rng, ring,
                                                            tmp_path):
    tr, chunks = _ffm(rng, 3)
    tr.fit_stream(iter(chunks))
    path = str(tmp_path / "trace.json")
    n = spans.export_chrome_trace(path)
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    trainer = [e for e in events if e["cat"] == "trainer"]
    assert n == len(events) and len(trainer) == len(_trainer_spans())
    assert {"mp4j.stream.stage", "mp4j.stream.dispatch",
            "mp4j.stream.fetch"} <= {e["name"] for e in trainer}
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in trainer)
    ts = [e["ts"] for e in trainer]
    assert ts == sorted(ts)
    stage = next(e for e in trainer if e["name"] == "mp4j.stream.stage")
    assert stage["args"] == {"chunk": 0}


def test_trace_collectives_profiles_like_the_benchmark(monkeypatch,
                                                       tmp_path):
    """``profile_dir=`` starts the profiler with the python tracer off,
    as ``benchmark/run.py`` does: an operator's trace and the
    benchmark's are the same kind."""
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append((d, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    with trace.trace_collectives(profile_dir=str(tmp_path)):
        pass
    ((where, kw),) = calls
    assert where == str(tmp_path)
    options = kw["profiler_options"]
    assert options.python_tracer_level == 0
    assert options.host_tracer_level == 2


# ----------------------------------------------------------- device scopes
def _batch_avals(tr, rows):
    slots = (tr.n_shards, rows // tr.n_shards, tr.cfg.max_nnz)
    i32, f32 = (jax.ShapeDtypeStruct(slots, jnp.int32),
                jax.ShapeDtypeStruct(slots, jnp.float32))
    row = jax.ShapeDtypeStruct(slots[:2], jnp.float32)
    return i32, i32, f32, f32, row, row


def _lower_ffm(rng, **kw):
    tr, _ = _ffm(rng, 0, **kw)
    # a sparse step takes its own state
    state = tr._enter(tr.init_params(0))
    step = tr._build_step((16 // tr.n_shards) * tr.cfg.max_nnz)
    return step.lower(state, *_batch_avals(tr, 16))


def _lower_gbdt(rng):
    tr, bins, y = _gbdt(rng)
    data = tr.shard_data(bins, y)
    kd = jax.random.key_data(jax.random.key(0))
    return tr._build_step().lower(*data, kd)


def _lower_collectives(rng):
    mesh = make_mesh(N_SHARDS)

    @partial(jax.shard_map, mesh=mesh, in_specs=P("mp4j"),
             out_specs=P("mp4j"), check_vma=False)
    def every(x):
        s = collectives.allreduce(x, Operators.SUM, "mp4j")
        r = collectives.reduce_scatter(s, Operators.SUM, "mp4j")
        return collectives.allgather(r, "mp4j") + s

    return jax.jit(every).lower(jnp.ones((N_SHARDS * 8, 2), jnp.float32))


@pytest.mark.parametrize("lower,scopes", [
    (_lower_gbdt, ["gbdt.hist", "gbdt.route", "gbdt.best_splits",
                   "gbdt.leaf"]),
    (_lower_ffm, ["ffm.table_gather", "ffm.grad_merge", "ffm.table_update",
                  "sparse.sort_by_key", "sparse.segment_reduce"]),
    (partial(_lower_ffm, sparse_capacity=8),
     ["ffm.table_gather", "ffm.grad_merge", "ffm.table_update",
      "sparse.sort_by_key", "sparse.segment_reduce"]),
    (partial(_lower_ffm, table_sharding="sharded"),
     ["ffm.shard.route", "mp4j.all_to_all", "ffm.table_gather",
      "ffm.shard.spread", "ffm.grad_merge", "ffm.table_update",
      "sparse.sort_by_key", "sparse.segment_reduce"]),
    (_lower_collectives, ["mp4j.allreduce", "mp4j.reduce_scatter",
                          "mp4j.allgather"]),
], ids=["gbdt", "ffm", "ffm-dedupe", "ffm-sharded", "collectives"])
def test_lowered_step_names_every_scope(rng, lower, scopes):
    text = lower(rng).as_text(debug_info=True)
    for scope in scopes:
        # a name stack, not a file's path: the scope ends a component
        assert re.search(rf'loc\("(?:[^"]*/)?{re.escape(scope)}[/"]', text), \
            scope
    assert "jit(step)" in text or "jit(every)" in text


def test_histogram_kernel_has_a_name(rng):
    bins = rng.integers(0, 16, (64, 4)).astype(np.int32)
    g = rng.standard_normal(64).astype(np.float32)
    jaxpr = jax.make_jaxpr(partial(pallas_histograms, n_nodes=2, F=4, B=16,
                                   interpret=True))(
        bins, g, g, np.zeros(64, np.int32))
    assert "mp4j_hist" in str(jaxpr)


def test_scopes_change_no_instruction_of_the_ffm_step(rng, monkeypatch):
    """Metadata only: the optimised HLO of the sparse step has the same
    instructions with the scopes and without them."""
    from tests.helpers import program_without_provenance

    def instructions(text):
        # names go too: an instruction inlined from a jitted helper is
        # named after its name stack (``%jit_triu_`` under ``ffm.pairs``)
        return [ln.strip()
                for ln in program_without_provenance(text).splitlines()
                if " = " in ln]

    with_scopes = _lower_ffm(np.random.default_rng(0)).compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert fm_mod.jax.named_scope("x").__class__ is contextlib.nullcontext
    without = _lower_ffm(np.random.default_rng(0)).compile().as_text()
    assert "ffm.table_update" in with_scopes
    assert "ffm.table_" not in without
    a, b = instructions(with_scopes), instructions(without)
    assert len(a) == len(b) > 20
    assert a == b


# ------------------------------------------- the spans are the host's only
def _lower_placer(rng):
    t, a = _chunked(N_SHARDS)
    t._put_sharded(a, 1000)
    ((shape, dtype, rows), place), = t._row_placers.items()
    sharding = t._row_sharding()
    return place.lower(
        jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=sharding),
        jax.ShapeDtypeStruct((N_SHARDS, rows * 16 // 128, 128),
                             np.dtype(dtype), sharding=sharding),
        np.int32(0))


def _lower_score(rng):
    tr, bins, y = _gbdt(rng)
    trees, _ = tr.train(bins, y)
    table = tr.shard_bins(bins)
    per = table.shape[1]
    margins = jnp.zeros((N_SHARDS, 1, per), jnp.float32,
                        device=tr._row_sharding())
    return tr._build_score(table.shape, per, len(trees)).lower(
        table, tr._stack_trees(trees), margins, np.int32(0))


def _lower_chunk_placer(rng):
    """``_put_row_chunks``' program: a piece of floats into one shard."""
    t = DataParallelTrainer(n_devices=1)
    per, width, rows = 512, 16, 64
    wire = (rows * width // 128, 128)
    return t._row_chunk_placer(per, width, rows, wire).lower(
        jax.ShapeDtypeStruct((1, per, width), jnp.float32),
        jax.ShapeDtypeStruct(wire, jnp.float32), np.int32(0))


# sha256 of the lowered text of every program a staging or a stream span
# is near, taken on the parent of the PR that added those spans (ISSUE 34)
# with this file's helpers: the spans are the host's and no line of a
# jitted function moved. A PR that changes one of these programs on
# purpose prints the new digest with this test and pins it (PR 35: the
# AdaGrad step's, whose merge tells ``segment_sum`` its ids ascend; PR 37:
# both FFM steps', whose select's output columns run component by
# component; PR 39: the SGD FFM step's, which merges its slots' gradients
# and scatter-adds the merged list's live prefix in tiles, the AdaGrad
# step's as it was; PR 45: the GBDT step's, whose routing slices a level's
# split columns out of the table where ``route_sliced`` says so; PR 52:
# the scoring program's, which takes the piece that crossed, here a whole
# table in one transfer, and returns its first word beside the margins).
LOWERED = {
    "placer": (
        _lower_placer,
        "2582168225277df2d3bae310438d4541f84c320762e7cd20458db82dc974884a"),
    # (ISSUE 52: taken on the parent too, where this helper lay in
    # tests/test_trainer_scopes.py: both table builders' programs are
    # the parent's while the scoring programs take the pieces)
    "placer-chunks": (
        _lower_chunk_placer,
        "8dd770276c0e0094b448006f93a10334ef5e2fe8e3539bd2a1030b43a4c441e9"),
    "gbdt": (
        _lower_gbdt,
        "c3e37de0608b1e8c44a42c1d84f131879470c6edac17d5653f29d6035e212cbd"),
    "score": (
        _lower_score,
        "be2711e774c1f0b618daa789b01328e705b0ce2d6053a038d962d494c230180f"),
    "ffm": (
        _lower_ffm,
        "5fc1fd06e712c79b80a05be56bbf588a5809d6cb094459d1c50524c713ad2510"),
    "ffm-adagrad": (
        partial(_lower_ffm, optimizer="adagrad"),
        "4a36d027bb96c7faace1521c2076ca6a7123209d8f68a75a434bc5d43585d047"),
}


@pytest.mark.parametrize("program", sorted(LOWERED))
def test_lowered_programs_are_the_parents_to_the_letter(program):
    lower, want = LOWERED[program]
    text = lower(np.random.default_rng(0)).as_text()
    assert "func.func public @main" in text
    assert hashlib.sha256(text.encode()).hexdigest() == want
