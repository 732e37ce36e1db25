"""Pallas TPU kernel for (node x feature x bin) gradient histograms.

The GBDT hot op (SURVEY.md section 6: "GBDT histogram allreduce —
Higgs 11Mx28, 256 bins"). The XLA "matmul" strategy in models/gbdt.py
routes the histogram onto the MXU via a one-hot matmul, but XLA
materializes the per-tile one-hot and the hi/lo-split A operand through
HBM between the compare and the dot. This kernel fuses the whole
per-tile pipeline in VMEM:

  1. build A = [g_hi | g_lo | h_hi | h_lo] x node-one-hot, a
     [tile, 4*n_nodes] bf16 operand, from g/h/node_ids tiles
     (hi/lo mantissa bit-split for near-f32 accuracy);
  2. for each feature, generate the [tile, B] bin one-hot in VMEM and
     feed the MXU directly (contraction over the tile axis);
  3. accumulate the [4*n_nodes, F*B] f32 output across grid steps
     (constant out index_map -> the accumulator stays resident in VMEM).

Measured on TPU v5 lite, F=28, B=256, inside the train step, from the
device trace (PERF.md section 6, PR 22): 8.6-8.8 ms a level at N=1M,
95.6 ms a level at N=11M (573.6 ms a tree of six levels), 17.9% of the
MXU roofline: the one-hot GENERATION on the VPU (compare + select over
N*F*B lanes) is the floor, not the matmul; element throughput is
dtype-independent, so the remaining cost is algorithmic, not layout.
(The 2026-07 figures of the previous installation, 14.5-20.2 ms a
level at 1M against 19.2-25.4 ms for the XLA matmul mode, no longer
hold.)

Constraints (checked by ``pallas_hist_supported``): B and F*B must be
lane-aligned (multiples of 128) for the compiled path; any shape works
in interpret mode (used by the CPU test suite).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TILE = 1024  # contraction tile (samples per grid step)

# The [4*n_nodes, F*B] f32 accumulator stays pinned in VMEM for the
# whole grid (constant out index_map); leave headroom for the input
# blocks, the A operand and the per-feature one-hot within ~16 MB/core.
_MAX_ACC_BYTES = 8 * 2 ** 20


def split_bf16(a):
    """Split f32 ``a`` into bf16 (hi, lo) with ``hi + lo ~= a`` to ~24
    bits. ``hi`` zeroes the low 16 mantissa bits via bit-masking — NOT
    ``a - f32(bf16(a))``, which XLA's algebraic simplifier folds to
    zero — so ``lo = a - hi`` is exact in f32 and only rounds at the
    final bf16 cast (<= 2^-17 relative). Shared by this kernel and the
    XLA matmul strategy in models/gbdt.py."""
    hi = lax.bitcast_convert_type(
        lax.bitcast_convert_type(a, jnp.uint32) & jnp.uint32(0xFFFF0000),
        jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


# what pallas_hist_supported checks, for error messages
PALLAS_HIST_CONSTRAINT = (
    "the compiled kernel needs n_bins % 128 == 0 and a "
    "[4*n_nodes, n_features*n_bins] f32 accumulator of at most "
    f"{_MAX_ACC_BYTES // 2 ** 20} MiB")


def pallas_hist_supported(n_bins: int, n_features: int,
                          n_nodes: int = 1) -> bool:
    """Compiled-path constraints: lane-aligned bin rows (static lane
    slices at multiples of B must be 128-aligned) and a VMEM-resident
    accumulator small enough to leave room for the operand buffers."""
    acc_bytes = 4 * n_nodes * n_features * n_bins * 4
    return n_bins % 128 == 0 and acc_bytes <= _MAX_ACC_BYTES


def _hist_kernel(bins_ref, g_ref, h_ref, nid_ref, out_ref, *, tile, F, B,
                 n_nodes):
    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # A: [tile, 4*n_nodes] bf16 = [g_hi | g_lo | h_hi | h_lo] per node
    nid = nid_ref[:]                                      # [tile] i32
    iota_n = lax.broadcasted_iota(jnp.int32, (tile, n_nodes), 1)
    noh = nid[:, None] == iota_n                          # [tile, n]

    def hilo(v):
        return split_bf16(jnp.where(noh, v[:, None], 0.0))

    g_hi, g_lo = hilo(g_ref[:])
    h_hi, h_lo = hilo(h_ref[:])
    A = jnp.concatenate([g_hi, g_lo, h_hi, h_lo], axis=1)  # [tile, 4n]

    # The int32 compare+select below is the measured best formulation
    # of the one-hot (round-2 pricing on v5e, B=256, N=1M): a bf16
    # arithmetic one-hot (relu(1 - |b - i|), exact for integers <= 256)
    # was 9% faster STANDALONE (17.6 vs 19.3 ms) but ~20% slower in the
    # fused train step (11.2-11.5 vs 14.1-14.2 trees/sec, alternating
    # A/B) — the 16-bit intermediates interact badly with the unrolled
    # multi-level program; direct bf16/int16 == compares crash the
    # Mosaic compiler outright. Tile 1024 beat 2048/4096.
    iota_b = lax.broadcasted_iota(jnp.int32, (tile, B), 1)
    ball = bins_ref[:]                                    # [tile, F]

    for f in range(F):  # static unroll: lane slices must be static
        oh = (ball[:, f:f + 1] == iota_b).astype(jnp.bfloat16)
        part = lax.dot_general(A, oh, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
        out_ref[:, f * B:(f + 1) * B] += part


def pallas_histograms(bins, g, h, node_ids, n_nodes: int, F: int, B: int,
                      tile: int = _TILE, interpret: bool = False):
    """Per-(node, feature, bin) gradient/hessian sums on the MXU.

    bins: [N, F] int32 in [0, B); g, h: [N] f32; node_ids: [N] int32 —
    ids outside [0, n_nodes) contribute exactly nothing (the one-hot
    matches no column; the GBDT sibling-subtraction path relies on this
    to exclude right-child samples via a sentinel id). Returns
    (hist_g, hist_h): [n_nodes, F, B] f32. Rows with g == h == 0
    (shard padding) contribute exactly nothing.
    """
    N = bins.shape[0]
    if N == 0:
        z = jnp.zeros((n_nodes, F, B), jnp.float32)
        return z, z
    if N < tile:
        tile = -(-N // 8) * 8          # single step, sublane-aligned
    T = -(-N // tile)
    pad = T * tile - N
    if pad:  # zero g/h rows contribute exact-zero products
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        g = jnp.pad(g, (0, pad))
        h = jnp.pad(h, (0, pad))
        node_ids = jnp.pad(node_ids, (0, pad))
    C = 4 * n_nodes
    # under shard_map with check_vma, the out_shape must carry the
    # union of the inputs' varying-across-mesh-axes sets
    vma = frozenset().union(*(
        getattr(jax.typeof(x), "vma", None) or frozenset()
        for x in (bins, g, h, node_ids)))
    if vma:
        out_shape = jax.ShapeDtypeStruct((C, F * B), jnp.float32, vma=vma)
    else:
        out_shape = jax.ShapeDtypeStruct((C, F * B), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, tile=tile, F=F, B=B,
                          n_nodes=n_nodes),
        grid=(T,),
        in_specs=[
            pl.BlockSpec((tile, F), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((C, F * B), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=out_shape,
        interpret=interpret,
        name="mp4j_hist",
    )(bins, g, h, node_ids)
    out = out.reshape(2, 2, n_nodes, F, B)      # [g/h, hi/lo, n, F, B]
    return out[0, 0] + out[0, 1], out[1, 0] + out[1, 1]
