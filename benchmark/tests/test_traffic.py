"""The traffic generator: the same seed gives the same inputs, and the
inputs have the properties the cells' ``why`` lines claim."""

import numpy as np

import jax.numpy as jnp

from benchmark import traffic


def test_binned_table_is_seeded_balanced_and_learnable():
    bins, y = traffic.binned_table(7, 50_000, 28, 256)
    again = traffic.binned_table(7, 50_000, 28, 256)
    other = traffic.binned_table(8, 50_000, 28, 256)
    assert bins.dtype == np.int32 and bins.shape == (50_000, 28)
    assert y.dtype == np.float32 and set(np.unique(y)) == {0.0, 1.0}
    assert np.array_equal(bins, again[0]) and np.array_equal(y, again[1])
    assert not np.array_equal(bins, other[0])
    assert bins.min() == 0 and bins.max() == 255
    assert 0.45 < y.mean() < 0.55
    # feature 2 enters the label linearly: it alone separates the classes
    hi, lo = y[bins[:, 2] > 200].mean(), y[bins[:, 2] < 55].mean()
    assert hi - lo > 0.3
    # feature 10 does not enter at all
    assert abs(y[bins[:, 10] > 200].mean() - y[bins[:, 10] < 55].mean()) < 0.05


def test_zipf_pool_fields_ranges_and_skew():
    n_fields, per = 39, 1000
    pool = traffic.zipf_chunk_pool(3, n_fields * per, n_fields, 512, 4,
                                   1.1, 0.5)
    again = traffic.zipf_chunk_pool(3, n_fields * per, n_fields, 512, 4,
                                    1.1, 0.5)
    assert len(pool) == 4
    for (feats, fields, vals, y), (f2, _, _, y2) in zip(pool, again):
        assert feats.dtype == np.int32 and feats.shape == (512, n_fields)
        assert np.array_equal(feats, f2) and np.array_equal(y, y2)
        assert np.array_equal(feats // per, fields)      # own id range
        assert np.array_equal(fields[0], np.arange(n_fields))
        assert (vals == 1.0).all()
        assert set(np.unique(y)) <= {0.0, 1.0}
    feats = np.concatenate([c[0] for c in pool])
    col = feats[:, 5]
    top_share = np.bincount(col - 5 * per).max() / col.size
    uniform = traffic.zipf_chunk_pool(3, n_fields * per, n_fields, 512, 4,
                                      0.0, 0.5)
    ucol = np.concatenate([c[0] for c in uniform])[:, 5]
    assert top_share > 0.1                     # Zipf(1.1): a hot head
    assert np.bincount(ucol - 5 * per).max() / ucol.size < 0.02
    assert not np.array_equal(pool[0][0], pool[1][0])


def test_small_ints_same_in_numpy_and_jax_and_exactly_summable():
    idx = np.arange(10_000, dtype=np.uint32)
    for rank in range(4):
        a = traffic.small_ints(np, idx, rank, 11)
        b = np.asarray(traffic.small_ints(jnp, jnp.asarray(idx), rank, 11))
        assert a.dtype == np.float32 and np.array_equal(a, b)
        assert a.min() >= 0 and a.max() <= 7 and len(np.unique(a)) == 8
    assert not np.array_equal(traffic.small_ints(np, idx, 0, 11),
                              traffic.small_ints(np, idx, 1, 11))
    assert not np.array_equal(traffic.small_ints(np, idx, 0, 11),
                              traffic.small_ints(np, idx, 0, 12))
