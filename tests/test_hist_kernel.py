"""Pallas histogram kernel (ops/hist_kernel.py): differential checks
against a numpy oracle, in interpret mode on the CPU test rig."""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import ytk_mp4j_tpu.ops.hist_kernel as hist_kernel
from ytk_mp4j_tpu.ops.hist_kernel import (_MAX_ACC_BYTES,
                                          _acc_bytes_a_feature,
                                          feature_blocks, hist_radix,
                                          pallas_hist_supported,
                                          pallas_histograms)


def bincount_hist(bins, v, node_ids, n_nodes, F, B):
    """float64 reference: ids outside [0, n_nodes) count for nothing."""
    keep = (node_ids >= 0) & (node_ids < n_nodes)
    out = np.zeros((n_nodes, F, B), np.float64)
    for f in range(F):
        flat = node_ids[keep].astype(np.int64) * B + bins[keep, f]
        out[:, f, :] = np.bincount(flat, weights=v[keep].astype(np.float64),
                                   minlength=n_nodes * B
                                   ).reshape(n_nodes, B)
    return out


def assert_matches_bincount(hg, hh, bins, g, h, nid, n_nodes, F, B):
    for got, v in ((hg, g), (hh, h)):
        np.testing.assert_allclose(
            np.asarray(got), bincount_hist(bins, v, nid, n_nodes, F, B),
            rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n_nodes", [1, 4])
@pytest.mark.parametrize("N", [64, 77, 300])
def test_matches_numpy(rng, n_nodes, N):
    """Odd N exercises the single-step lane-rounding path (N < tile)
    and the masked lanes past the table's end."""
    F, B = 3, 16
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    g = rng.standard_normal(N).astype(np.float32)
    h = rng.random(N).astype(np.float32)
    nid = rng.integers(0, n_nodes, N).astype(np.int32)
    hg, hh = pallas_histograms(
        jnp.array(bins), jnp.array(g), jnp.array(h), jnp.array(nid),
        n_nodes, F, B, interpret=True)
    assert_matches_bincount(hg, hh, bins, g, h, nid, n_nodes, F, B)


# samples lie on the lanes: what is delicate is where N falls against
# the tile (a multiple of 128 lanes) and what rests past the table's end
_LANE_CASES = [
    # (N, tile, n_nodes)
    (50, 128, 1),        # N < 128: one step, most lanes masked
    (127, 128, 16),      # tile - 1
    (128, 128, 1),       # exact: no mask compiled in
    (129, 128, 16),      # tile + 1: a second step with one live lane
    (300, 128, 4),       # N not a multiple of the tile, three steps
    (300, 1024, 16),     # default tile, rounded down to 384 lanes
    (1025, 1024, 1),     # default tile + 1
]


@pytest.mark.parametrize("N,tile,n_nodes", _LANE_CASES)
def test_lane_layout_matches_bincount(rng, N, tile, n_nodes):
    """Ragged tails, sentinel ids (n_nodes and negative: the sibling
    subtraction's right children) and zero-weight rows (shard padding)
    against a float64 bincount, to the hi/lo bf16 split's ~2^-17 a
    summand."""
    F, B = 5, 16
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    g = rng.standard_normal(N).astype(np.float32)
    h = rng.random(N).astype(np.float32)
    nid = rng.integers(-1, n_nodes + 1, N).astype(np.int32)
    g[::7] = 0.0
    h[::7] = 0.0                                  # weight-0 rows
    hg, hh = pallas_histograms(
        jnp.array(bins), jnp.array(g), jnp.array(h), jnp.array(nid),
        n_nodes, F, B, tile=tile, interpret=True)
    assert hg.shape == hh.shape == (n_nodes, F, B)
    assert_matches_bincount(hg, hh, bins, g, h, nid, n_nodes, F, B)


def test_all_sentinel_ids_leave_exact_zeros(rng):
    N, F, B, n_nodes = 200, 3, 8, 2
    bins = jnp.array(rng.integers(0, B, (N, F)).astype(np.int32))
    g = jnp.array(rng.standard_normal(N).astype(np.float32))
    nid = jnp.array(np.where(np.arange(N) % 2, n_nodes, -1).astype(np.int32))
    hg, hh = pallas_histograms(bins, g, g, nid, n_nodes, F, B, tile=128,
                               interpret=True)
    assert np.all(np.asarray(hg) == 0) and np.all(np.asarray(hh) == 0)


def test_kernel_under_shard_map(rng):
    """Each shard of a CPU mesh runs the kernel on its rows (ragged
    against the tile) and the psum is the whole table's histogram.
    check_vma is off because the Pallas interpreter is not vma-aware;
    the compiled kernel's vma out_shape is exercised by
    tests/test_gbdt_aot.py on a described four-chip mesh."""
    n_dev, per, F, B, n_nodes = 4, 150, 3, 16, 2
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("mp4j",))
    bins = rng.integers(0, B, (n_dev, per, F)).astype(np.int32)
    g = rng.standard_normal((n_dev, per)).astype(np.float32)
    h = rng.random((n_dev, per)).astype(np.float32)
    nid = rng.integers(0, n_nodes + 1, (n_dev, per)).astype(np.int32)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(P("mp4j"),) * 4,
             out_specs=P(), check_vma=False)
    def hist(b, g, h, i):
        a, c = pallas_histograms(b[0], g[0], h[0], i[0], n_nodes, F, B,
                                 tile=128, interpret=True)
        return lax.psum(a, "mp4j"), lax.psum(c, "mp4j")

    hg, hh = hist(bins, g, h, nid)
    assert_matches_bincount(hg, hh, bins.reshape(-1, F), g.reshape(-1),
                            h.reshape(-1), nid.reshape(-1), n_nodes, F, B)


def test_multi_tile_grid(rng):
    """N > tile: accumulation across grid steps, plus the masked tail."""
    N, F, B = 100, 2, 8
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    g = rng.standard_normal(N).astype(np.float32)
    h = np.ones(N, np.float32)
    nid = np.zeros(N, np.int32)
    hg, hh = pallas_histograms(
        jnp.array(bins), jnp.array(g), jnp.array(h), jnp.array(nid),
        1, F, B, tile=32, interpret=True)
    np.testing.assert_allclose(np.asarray(hg),
                               bincount_hist(bins, g, nid, 1, F, B),
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


@pytest.mark.parametrize("B", [8, 128, 256])     # radix 1, 4, 4
def test_hi_lo_split_precision(rng, B):
    """The bf16 hi/lo split must beat plain-bf16 rounding by orders of
    magnitude: values near 1 with tiny perturbations accumulate to ~1e-7
    relative error, where a single bf16 cast alone rounds at ~4e-3."""
    N, F = 4096, 1
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    g = (1.0 + 1e-3 * rng.standard_normal(N)).astype(np.float32)
    h = np.ones(N, np.float32)
    nid = np.zeros(N, np.int32)
    hg, _ = pallas_histograms(
        jnp.array(bins), jnp.array(g), jnp.array(h), jnp.array(nid),
        1, F, B, interpret=True)
    want = bincount_hist(bins, g.astype(np.float64), nid, 1, F, B)
    rel = np.abs(np.asarray(hg, np.float64) - want).max() / want.max()
    assert rel < 1e-5


@pytest.mark.parametrize("B,F,n_nodes,ok", [
    (256, 28, 1, True),
    (128, 4, 1, True),
    (100, 28, 1, False),        # B not lane-aligned
    (8, 5, 1, False),           # B not lane-aligned
    (256, 28, 32, True),        # depth 6, the Higgs width
    # the number of features bounds nothing (until PR 26 the whole
    # [4*n_nodes, F*B] accumulator had to fit: F <= 128 at 16 nodes)
    (256, 28, 128, True),       # depth 8: two blocks of 14
    (256, 968, 16, True),       # Bosch: 11 blocks of 88
    (256, 2000, 32, True),      # Epsilon
    (256, 28, 2048, True),      # one feature's accumulator: exactly 8 MiB
    (256, 28, 4096, False),     # ... and 16 MiB: no block can hold it
    (4096, 2, 128, True),
    (4096, 2, 256, False),
])
def test_supported_gate(B, F, n_nodes, ok):
    assert pallas_hist_supported(B, F, n_nodes) is ok


@pytest.mark.parametrize("F,B,n_nodes,want", [
    (28, 256, 1, (28, 1)),          # Higgs: one block at every level
    (28, 256, 16, (28, 1)),
    (28, 256, 128, (14, 2)),        # depth 8: the accumulator bounds it
    (968, 256, 1, (88, 11)),        # Bosch rests (8, 128)-tiled: whole
    (968, 256, 16, (88, 11)),       # sublane tiles, none idle
    (130, 256, 16, (65, 2)),
    (131, 256, 16, (66, 2)),        # ragged: the last block holds 65
    (250, 128, 16, (125, 2)),
    (257, 128, 16, (86, 3)),        # ragged: 86 + 86 + 85
    (136, 256, 16, (72, 2)),        # tiled and ragged: 72 + 64
    (2000, 256, 16, (80, 25)),      # Epsilon: 25 x 80, none idle
    (8, 4096, 128, (1, 8)),         # under a sublane tile a block
])
def test_feature_blocks_rule(F, B, n_nodes, want):
    blk, n_blocks = feature_blocks(F, B, n_nodes)
    assert (blk, n_blocks) == want
    assert (n_blocks - 1) * blk < F <= n_blocks * blk
    assert blk * _acc_bytes_a_feature(B, n_nodes) <= _MAX_ACC_BYTES


# several feature blocks, ragged last blocks in both operand forms
# ([F, 1, N] rows and [F, N] in sublane tiles), N against the tile and
# sentinel ids, at the blocks the compiled kernel would take
_BLOCK_CASES = [
    # (F, B, N, tile, n_nodes)
    (28, 128, 300, 128, 16),     # one block, the Higgs width
    (130, 128, 300, 128, 16),    # two blocks of 65
    (131, 128, 200, 128, 8),     # ragged rows: 66 + 65
    (250, 128, 260, 256, 16),    # two blocks of 125, N one past the tile
    (257, 128, 130, 128, 1),     # three blocks, ragged: 86 + 86 + 85
    (136, 128, 300, 128, 16),    # sublane tiles, ragged: 72 + 64
    (264, 128, 100, 128, 2),     # sublane tiles: 3 x 88
]


@pytest.mark.parametrize("F,B,N,tile,n_nodes", _BLOCK_CASES)
def test_feature_blocks_match_bincount(rng, F, B, N, tile, n_nodes):
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    bins[rng.random((N, F)) < 0.8] = 0           # mostly the missing bucket
    g = rng.standard_normal(N).astype(np.float32)
    h = rng.random(N).astype(np.float32)
    nid = rng.integers(-1, n_nodes + 1, N).astype(np.int32)
    hg, hh = pallas_histograms(
        jnp.array(bins), jnp.array(g), jnp.array(h), jnp.array(nid),
        n_nodes, F, B, tile=tile, interpret=True)
    assert hg.shape == hh.shape == (n_nodes, F, B)
    assert_matches_bincount(hg, hh, bins, g, h, nid, n_nodes, F, B)


@pytest.mark.parametrize("n_nodes,B,want", [
    # 256 bins, a tree's levels: 16 rows against 64 low digits at the
    # root, 32 against 64, 32 and 64 against 128, then the undivided
    # kernel, whose 64 rows already keep the MXU as long as its pushes
    (1, 256, 4), (2, 256, 4), (4, 256, 2), (8, 256, 2), (16, 256, 1),
    (32, 256, 1), (128, 256, 1),
    (3, 256, 4),                     # 48 rows: three matmuls, four pushes
    (1, 128, 4), (2, 128, 2), (4, 128, 2), (8, 128, 1),
    (1, 1024, 8), (2, 1024, 8), (4, 1024, 4), (8, 1024, 2),
    (1, 4096, 16), (2, 4096, 8), (32, 4096, 1),
    (1, 16, 1), (1, 8, 1),           # one push either way
    (1, 100, 1), (4, 96, 1), (1, 255, 1),   # no power of two: no digits
])
def test_hist_radix_rule(n_nodes, B, want):
    R = hist_radix(n_nodes, B)
    assert R == want
    assert B % R == 0 and (R == 1 or B // R >= 16)


@pytest.mark.parametrize("B", [128, 256, 4096])
def test_hist_radix_never_pays_for_a_split(B):
    """Deeper levels never take a larger radix, and the accumulator's
    bytes, lane padding included, are what ``feature_blocks`` reckons
    with."""
    radices = [hist_radix(2 ** d, B) for d in range(9)]
    assert radices == sorted(radices, reverse=True) and radices[-1] == 1
    for d, R in enumerate(radices):
        assert _acc_bytes_a_feature(B, 2 ** d) \
            == R * 4 * 2 ** d * max(B // R, 128) * 4


# every radix the rule can choose, in both operand forms ([F, 1, N] rows
# at 28 features, sublane tiles with a ragged last block at 136: 72 +
# 64) and both forms of the out block (low digits in whole lane words
# side by side; fewer, a block [R*C, BL] a feature), N ragged against
# the tile, sentinel ids and zero-weight rows
_RADIX_SHAPES = [
    # (F, B, N, tile)
    (28, 256, 300, 128),
    (136, 256, 200, 128),
    (28, 128, 300, 256),
    (3, 4096, 150, 128),
]
_RADIX_NODES = [1, 2, 4, 8, 16]


def _radix_case(rng, F, B, N, n_nodes):
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    bins[rng.random((N, F)) < 0.3] = 0           # the missing bucket
    bins[0], bins[-1] = B - 1, B - 1             # the last bin, both ends
    g = rng.standard_normal(N).astype(np.float32)
    h = rng.random(N).astype(np.float32)
    nid = rng.integers(-1, n_nodes + 1, N).astype(np.int32)
    g[::7] = 0.0
    h[::7] = 0.0                                  # weight-0 rows
    return bins, g, h, nid


@pytest.mark.parametrize("n_nodes", _RADIX_NODES)
@pytest.mark.parametrize("F,B,N,tile", _RADIX_SHAPES)
def test_every_radix_matches_bincount(rng, F, B, N, tile, n_nodes):
    bins, g, h, nid = _radix_case(rng, F, B, N, n_nodes)
    hg, hh = pallas_histograms(
        jnp.array(bins), jnp.array(g), jnp.array(h), jnp.array(nid),
        n_nodes, F, B, tile=tile, interpret=True)
    assert hg.shape == hh.shape == (n_nodes, F, B)
    assert_matches_bincount(hg, hh, bins, g, h, nid, n_nodes, F, B)


@pytest.mark.parametrize("n_nodes", _RADIX_NODES)
@pytest.mark.parametrize("F,B,N,tile", _RADIX_SHAPES)
def test_every_radix_matches_the_matmul_strategy(rng, F, B, N, tile,
                                                 n_nodes):
    """The XLA one-hot matmul takes the same hi / lo halves and sums
    them in f32 too (chip_smoke.py holds the compiled kernel to it on
    the chip)."""
    from ytk_mp4j_tpu.models.gbdt import (GBDTConfig,
                                          _build_histograms_matmul)

    bins, g, h, nid = (jnp.array(a) for a in
                       _radix_case(rng, F, B, N, n_nodes))
    got = pallas_histograms(bins, g, h, nid, n_nodes, F, B, tile=tile,
                            interpret=True)
    want = _build_histograms_matmul(
        bins, g, h, nid, n_nodes, GBDTConfig(n_features=F, n_bins=B))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("F,B,n_nodes", [
    (28, 256, 1), (28, 256, 8), (136, 256, 2), (28, 128, 1), (3, 4096, 2)])
def test_the_split_changes_no_sum(rng, monkeypatch, F, B, n_nodes):
    """The same products into the same f32 sums, the masked rows adding
    exact zeros: the undivided kernel (radix 1) gives the same
    histogram."""
    N = 300
    args = [jnp.array(a) for a in _radix_case(rng, F, B, N, n_nodes)]
    assert hist_radix(n_nodes, B) > 1
    split = pallas_histograms(*args, n_nodes, F, B, tile=128,
                              interpret=True)
    monkeypatch.setattr(hist_kernel, "hist_radix", lambda n_nodes, B: 1)
    whole = pallas_histograms(*args, n_nodes, F, B, tile=128,
                              interpret=True)
    for a, b in zip(split, whole):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("F,B,n_nodes", [
    (3, 16, 1),           # bins not lane-aligned
    (2, 4096, 256),       # one feature's accumulator is 16 MiB
])
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
