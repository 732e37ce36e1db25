"""``DataParallelTrainer._put_sharded``: a shard too large for one
host-to-device transfer (2**32 bytes: the runtime's cliff, PERF.md PR 26)
crosses in row chunks that the device places in the donated table
(models/_base.py). What is checked is that the chunked path places
exactly what the plain path places, on one shard and on four; and that
the same pieces, taken by a caller that reads each once (``_pieces``,
``_reader_pieces``: a scoring call, ISSUE 52), are those rows, each
brought once, at the same pace and with no table anywhere."""

import numpy as np
import pytest

import jax

from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.models._base import DataParallelTrainer
from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("per,width,chunk_bytes", [
    (1000, 16, 4096),       # 16 chunks of 64 rows, the last one early
    (37, 40, 4096),         # two chunks that overlap
    (129, 8, 4096),         # one row past a chunk
    (5, 8, 4096),           # under a chunk: one chunk of 5 rows
    (64, 24, 24 * 4),       # a row a chunk
    (1000, 8, 300 * 32),    # 300 rows fit: 256 go, whole rows of 128 lanes
])
def test_row_chunks_place_what_the_plain_path_places(rng, n_shards, per,
                                                     width, chunk_bytes):
    t = DataParallelTrainer(n_devices=n_shards)
    t._EACH_CHUNK_BYTES = chunk_bytes
    a = rng.integers(0, 256, (n_shards * per, width)).astype(np.int32)
    got = t._put_in_row_chunks(a.reshape(n_shards, per, width))
    assert (got.shape, got.dtype) == ((n_shards, per, width), a.dtype)
    want = jax.make_array_from_callback(
        (n_shards, per, width), t._row_sharding(),
        lambda idx: a.reshape(n_shards, per, width)[idx])
    assert got.sharding == want.sharding and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_shards,shape,limit,chunked", [
    (1, (4096, 8), 4096 * 8 * 4, True),         # at the limit: chunks
    (1, (4096, 8), 4096 * 8 * 4 + 1, False),    # a byte under it: as it is
    (4, (4096, 8), 1024 * 8 * 4, True),         # the limit is a shard's
    (4, (4096, 8), 1024 * 8 * 4 + 1, False),
    (1, (4096,), 4096 * 4, True),               # labels would, too
    (1, (1024, 4, 3), 2 ** 30, False),
])
def test_which_arrays_go_in_row_chunks(rng, monkeypatch, n_shards, shape,
                                       limit, chunked):
    t = DataParallelTrainer(n_devices=n_shards)
    t._ONE_TRANSFER_BYTES, t._EACH_CHUNK_BYTES = limit, 4096
    calls = []
    inner = t._put_in_row_chunks
    monkeypatch.setattr(
        t, "_put_in_row_chunks",
        lambda a: calls.append(a.shape) or inner(a))
    a = rng.integers(0, 256, shape).astype(np.int32)
    got = t._put_sharded(a, shape[0] // n_shards)
    assert bool(calls) is chunked
    assert got.sharding == t._row_sharding()
    np.testing.assert_array_equal(np.asarray(got).reshape(shape), a)


def test_the_limit_is_the_measured_one():
    assert DataParallelTrainer._ONE_TRANSFER_BYTES == 2 ** 32
    # the Higgs table (11M x 28 int32) goes as it always did; the Bosch
    # table (1,183,747 x 968) is over the limit
    assert 11_000_000 * 28 * 4 < 2 ** 32 <= 1_183_747 * 968 * 4


@pytest.mark.parametrize("n_shards", [1, 4])
def test_train_is_the_same_whichever_way_the_table_went(rng, monkeypatch,
                                                        n_shards):
    N, F, B = 1000, 8, 16
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    y = (bins[:, 0] + bins[:, 1] > B).astype(np.float32)
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, loss="logistic",
                     hist_mode="matmul")

    def train(chunked):
        tr = GBDTTrainer(cfg, n_devices=n_shards)
        tr._EACH_CHUNK_BYTES = 2048
        calls = []
        if chunked:
            tr._ONE_TRANSFER_BYTES = N // n_shards * F * 4
            inner = tr._put_in_row_chunks
            monkeypatch.setattr(
                tr, "_put_in_row_chunks",
                lambda a: calls.append(a.shape) or inner(a))
        trees, margins = tr.train(bins, y, n_trees=2)
        return trees, margins, calls

    t0, m0, c0 = train(False)
    t1, m1, c1 = train(True)
    assert c0 == [] and c1 == [(n_shards, N // n_shards, F)]   # bins alone
    np.testing.assert_array_equal(m0, m1)
    for a, b in zip(jax.tree_util.tree_leaves(t0),
                    jax.tree_util.tree_leaves(t1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_second_table_of_the_same_shape_builds_nothing(rng):
    """A job after the first stages its table with the programs the
    first built (the benchmark counts programs built inside its window
    and refuses a run that built one)."""
    t = DataParallelTrainer(n_devices=1)
    t._EACH_CHUNK_BYTES = 4096
    a = rng.integers(0, 256, (1, 300, 16)).astype(np.int32)
    t._put_in_row_chunks(a)
    (place,) = t._row_placers.values()
    assert place._cache_size() == 1
    got = t._put_in_row_chunks(a + 1)
    assert list(t._row_placers.values()) == [place]
    assert place._cache_size() == 1
    np.testing.assert_array_equal(np.asarray(got), a + 1)
    # another shape is another program, kept beside the first
    t._put_in_row_chunks(a[:, :100])
    assert len(t._row_placers) == 2


# --------------------------------------- the pieces, for who builds no table
GEOMETRIES = [
    (1000, 16, 4096),       # 16 pieces of 64 rows, the last one early
    (37, 40, 4096),         # two pieces that overlap
    (129, 8, 4096),         # one row past a piece
    (5, 8, 4096),           # under a piece: one piece of 5 rows
    (64, 24, 24 * 4),       # a row a piece
    (1000, 8, 300 * 32),    # 300 rows fit: 256 go, whole rows of 128 lanes
]


def _new_arrays(before):
    return [x for x in jax.live_arrays() if id(x) not in before]


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("per,width,chunk_bytes", GEOMETRIES)
def test_pieces_are_the_rows_the_plain_path_places(rng, n_shards, per, width,
                                                   chunk_bytes):
    """``_pieces`` of a shard over the limit: every row of every shard
    comes in a piece, in order, only the last piece beginning with rows
    the one before it brought; and while they come no device array is as
    large as the input (with more than one piece), placer or table."""
    t = DataParallelTrainer(n_devices=n_shards)
    t._ONE_TRANSFER_BYTES, t._EACH_CHUNK_BYTES = 1, chunk_bytes
    a = rng.integers(0, 256, (n_shards * per, width)).astype(np.int32)
    before = {id(x) for x in jax.live_arrays()}
    got = np.full((n_shards, per, width), -1, np.int32)
    times = np.zeros(per, np.int64)
    cuts, largest = [], 0
    for k, piece, shard, start, stop, turns in t._pieces(a, per):
        assert shard is None and k == len(cuts)
        assert piece.sharding == t._row_sharding()
        cuts.append((start, stop))
        got[:, start:stop] = np.asarray(piece).reshape(n_shards, -1, width)
        times[start:stop] += 1
        largest = max(largest, *(x.nbytes for x in _new_arrays(before)))
        del piece
    np.testing.assert_array_equal(got.reshape(a.shape), a)
    rows = cuts[0][1]
    assert all(stop - start == rows for start, stop in cuts)
    assert [c[0] for c in cuts[:-1]] == list(range(0, per - rows, rows))
    assert cuts[-1] == (per - rows, per)
    assert times.max() == (2 if per % rows else 1)
    assert (times[:cuts[-1][0]] == 1).all() or len(cuts) == 1
    assert largest == n_shards * rows * width * 4
    assert t._row_placers == {}                     # and no placer
    if len(cuts) > 1:
        assert largest < a.nbytes


@pytest.mark.parametrize("n_shards", [1, 4])
def test_a_table_under_the_limit_is_one_piece_itself(rng, n_shards):
    """``_pieces`` of what crosses in one transfer: the array, as
    ``_put_sharded`` places it, rows 0 to ``per`` of every shard."""
    t = DataParallelTrainer(n_devices=n_shards)
    t._EACH_CHUNK_BYTES = 4096
    a = rng.integers(0, 256, (n_shards * 300, 16)).astype(np.int32)
    (k, piece, shard, start, stop, turns), = t._pieces(a, 300)
    assert (k, shard, start, stop, turns) == (0, None, 0, 300, [])
    want = t._put_sharded(a, 300)
    assert piece.shape == want.shape and piece.sharding == want.sharding
    np.testing.assert_array_equal(np.asarray(piece), np.asarray(want))


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("n_rows,cuts", [
    (1000, (64, 128, 700)),             # whole shards on four
    (1003, (250, 252, 900)),            # a chunk spans two shards; the
                                        # last shard ends in a padding row
    (1003, ()),                         # one chunk spans them all
    (3, (1,)),                          # fewer rows than shards
])
def test_reader_pieces_go_where_their_rows_rest(rng, n_shards, n_rows, cuts):
    """``_reader_pieces``: a reader's chunks cut at shard ends and under
    the cap, every piece on the one device that holds its shard, every
    row of the table brought exactly once and a shard's padding never;
    what the table builder makes of the same chunks is those rows."""
    width = 16
    t = DataParallelTrainer(n_devices=n_shards)
    t._EACH_CHUNK_BYTES = 100 * width * 4
    X = rng.standard_normal((n_rows, width)).astype(np.float32)
    bounds = [0, *cuts, n_rows]
    chunks = [X[a:b] for a, b in zip(bounds[:-1], bounds[1:])]
    per = max(1, -(-n_rows // n_shards))
    devices = t._shard_devices((n_shards, per, width))
    before = {id(x) for x in jax.live_arrays()}
    got = np.full((n_shards, per, width), np.nan, np.float32)
    times = np.zeros((n_shards, per), np.int64)
    largest = 0
    for k, piece, shard, start, stop, turns in t._reader_pieces(
            iter(chunks), n_rows, width):
        assert piece.devices() == {devices[shard]}
        assert stop - start <= 100 and stop <= per
        got[shard, start:stop] = np.asarray(piece).reshape(-1, width)
        times[shard, start:stop] += 1
        largest = max(largest, *(x.nbytes for x in _new_arrays(before)))
        del piece
    assert times.reshape(-1)[:n_rows].tolist() == [1] * n_rows
    assert not times.reshape(-1)[n_rows:].any()
    np.testing.assert_array_equal(got.reshape(-1, width)[:n_rows], X)
    assert largest <= t._EACH_CHUNK_BYTES and t._row_placers == {}
    table = t._put_row_chunks(iter(chunks), n_rows, width)
    np.testing.assert_array_equal(np.asarray(table), got)


def test_reader_pieces_refuse_what_the_table_builder_refuses(rng):
    t = DataParallelTrainer(n_devices=4)
    X = rng.standard_normal((100, 8)).astype(np.float32)
    for chunks, n_rows, match in [
            ([X[:10], X[10:20, :5]], 100, r"chunk 1 must be \[rows, 8\]"),
            ([X[:60], X[60:]], 90, "more than n_rows=90"),
            ([X[:60], X[60:]], 110, "hold 100 rows, n_rows=110")]:
        for take in (lambda: list(t._reader_pieces(iter(chunks), n_rows, 8)),
                     lambda: t._put_row_chunks(iter(chunks), n_rows, 8)):
            with pytest.raises(Mp4jError, match=match):
                take()
