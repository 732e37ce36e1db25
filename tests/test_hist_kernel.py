"""Pallas histogram kernel (ops/hist_kernel.py): differential checks
against a numpy oracle, in interpret mode on the CPU test rig."""

import numpy as np
import pytest

import jax.numpy as jnp

from ytk_mp4j_tpu.ops.hist_kernel import (pallas_hist_supported,
                                          pallas_histograms)


def np_hist(bins, g, node_ids, n_nodes, F, B):
    out = np.zeros((n_nodes, F, B), np.float64)
    for i in range(bins.shape[0]):
        for f in range(F):
            out[node_ids[i], f, bins[i, f]] += g[i]
    return out


@pytest.mark.parametrize("n_nodes", [1, 4])
@pytest.mark.parametrize("N", [64, 77, 300])
def test_matches_numpy(rng, n_nodes, N):
    """Odd N exercises the single-step sublane-rounding path (N < tile)
    and the zero-padded rows."""
    F, B = 3, 16
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    g = rng.standard_normal(N).astype(np.float32)
    h = rng.random(N).astype(np.float32)
    nid = rng.integers(0, n_nodes, N).astype(np.int32)
    hg, hh = pallas_histograms(
        jnp.array(bins), jnp.array(g), jnp.array(h), jnp.array(nid),
        n_nodes, F, B, interpret=True)
    np.testing.assert_allclose(np.asarray(hg),
                               np_hist(bins, g, nid, n_nodes, F, B),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hh),
                               np_hist(bins, h, nid, n_nodes, F, B),
                               rtol=1e-4, atol=1e-4)


def test_multi_tile_grid(rng):
    """N > tile: accumulation across grid steps, plus pad-row zeroing."""
    N, F, B = 100, 2, 8
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    g = rng.standard_normal(N).astype(np.float32)
    h = np.ones(N, np.float32)
    nid = np.zeros(N, np.int32)
    hg, hh = pallas_histograms(
        jnp.array(bins), jnp.array(g), jnp.array(h), jnp.array(nid),
        1, F, B, tile=32, interpret=True)
    np.testing.assert_allclose(np.asarray(hg),
                               np_hist(bins, g, nid, 1, F, B),
                               rtol=1e-4, atol=1e-4)
    assert float(np.asarray(hh).sum()) == pytest.approx(N * F, rel=1e-4)


def test_zero_weight_rows_contribute_nothing(rng):
    """g == h == 0 rows (shard padding) must leave exact zeros — the
    trainer relies on this for distributed/single-device equivalence."""
    N, F, B = 40, 2, 8
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    g = np.zeros(N, np.float32)
    h = np.zeros(N, np.float32)
    nid = np.zeros(N, np.int32)
    hg, hh = pallas_histograms(
        jnp.array(bins), jnp.array(g), jnp.array(h), jnp.array(nid),
        1, F, B, interpret=True)
    assert np.all(np.asarray(hg) == 0)
    assert np.all(np.asarray(hh) == 0)


def test_hi_lo_split_precision(rng):
    """The bf16 hi/lo split must beat plain-bf16 rounding by orders of
    magnitude: values near 1 with tiny perturbations accumulate to ~1e-7
    relative error, where a single bf16 cast alone rounds at ~4e-3."""
    N, F, B = 4096, 1, 8
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    g = (1.0 + 1e-3 * rng.standard_normal(N)).astype(np.float32)
    h = np.ones(N, np.float32)
    nid = np.zeros(N, np.int32)
    hg, _ = pallas_histograms(
        jnp.array(bins), jnp.array(g), jnp.array(h), jnp.array(nid),
        1, F, B, interpret=True)
    want = np_hist(bins, g.astype(np.float64), nid, 1, F, B)
    rel = np.abs(np.asarray(hg, np.float64) - want).max() / want.max()
    assert rel < 1e-5


def test_supported_gate():
    assert pallas_hist_supported(256, 28)
    assert pallas_hist_supported(128, 4)
    assert not pallas_hist_supported(100, 28)   # B not lane-aligned
    assert not pallas_hist_supported(8, 5)      # B not lane-aligned
    # depth-6 trees (32 nodes) fit the VMEM accumulator budget...
    assert pallas_hist_supported(256, 28, n_nodes=32)
    # ...but depth-8 (128 nodes -> ~14.7 MB accumulator) does not
    assert not pallas_hist_supported(256, 28, n_nodes=128)


@pytest.mark.parametrize("F,B,n_nodes", [(3, 16, 1), (28, 256, 128)])
def test_pallas_mode_raises_on_unsupported_compiled_shape(rng, F, B,
                                                          n_nodes):
    """On a non-interpreted call hist_mode="pallas" compiles the kernel
    or raises, naming the constraint and the explicit alternative — it
    never hands over to the matmul strategy without a word."""
    from ytk_mp4j_tpu.exceptions import Mp4jError
    from ytk_mp4j_tpu.models.gbdt import GBDTConfig, build_histograms

    N = 64
    bins = jnp.array(rng.integers(0, B, (N, F)).astype(np.int32))
    g = jnp.ones(N, jnp.float32)
    nid = jnp.zeros(N, jnp.int32)
    cfg = GBDTConfig(n_features=F, n_bins=B)
    with pytest.raises(Mp4jError, match="hist_mode='matmul'") as e:
        build_histograms(bins, g, g, nid, n_nodes, cfg, interpret=False)
    assert f"n_bins={B}" in str(e.value)
    # the explicit choice serves the same shape
    explicit = GBDTConfig(n_features=F, n_bins=B, hist_mode="matmul")
    hg, _ = build_histograms(bins, g, g, nid, n_nodes, explicit,
                             interpret=False)
    assert hg.shape == (n_nodes, F, B)
