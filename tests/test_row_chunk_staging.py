"""``DataParallelTrainer._put_sharded``: a shard too large for one
host-to-device transfer (2**32 bytes: the runtime's cliff, PERF.md PR 26)
crosses in row chunks that the device places in the donated table
(models/_base.py). What is checked is that the chunked path places
exactly what the plain path places, on one shard and on four."""

import numpy as np
import pytest

import jax

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
        lambda a, each=None: calls.append(a.shape) or inner(a, each))
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
                lambda a, each=None: calls.append(a.shape) or inner(a, each))
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
