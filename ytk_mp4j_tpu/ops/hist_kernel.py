"""Pallas TPU kernel for (node x feature x bin) gradient histograms.

The GBDT hot op (SURVEY.md section 6: "GBDT histogram allreduce —
Higgs 11Mx28, 256 bins"). The XLA "matmul" strategy in models/gbdt.py
routes the histogram onto the MXU via a one-hot matmul, but XLA
materializes the per-tile one-hot and the hi/lo-split A operand through
HBM between the compare and the dot. This kernel fuses the whole
per-tile pipeline in VMEM, with the SAMPLES ON THE LANES:

  1. build A^T = [g_hi | g_lo | h_hi | h_lo] x node-one-hot, a
     [4*n_nodes, tile] bf16 operand, from [1, tile] rows of g / h /
     node_ids broadcast along sublanes (hi/lo mantissa bit-split for
     near-f32 accuracy);
  2. for each feature, generate the [B, tile] bin one-hot in VMEM (one
     row of the bins block against a sublane iota) and feed the MXU
     directly (contraction over the lane axis of both operands);
  3. accumulate the [4*n_nodes, F*B] f32 output across grid steps
     (constant out index_map -> the accumulator stays resident in VMEM).

Why feature-major (PR 25): on the TPU a [N, 28] int32 table rests with
N on the lanes (``s32[1,N,28]{1,0,2:T(1,128)}``, unpadded). The kernel
takes it as [F, 1, N] in blocks (F, 1, tile), which is that very
layout, so the step holds a bitcast of its parameter and no copy, and
the ragged last tile is masked in the kernel instead of padded. The
row-major kernel it replaced ([tile, F] blocks, samples on sublanes)
made XLA copy and pad the table at 28 of 128 lanes in every tree (two
temporaries of 5.63 GB at 11M rows, which routing then read six times)
and spent half its own bundles on layout: 128 lane broadcasts of
``ball[:, f]`` a feature a tile, a lane-sparse A and a transposition
of A inside the dot. Final bundles a 1,024-sample tile at n_nodes
1/2/4/8/16 (``--xla_jf_dump_to`` for v5e, libtpu 0.0.34, no chip):
11,704/11,705/11,730/12,067/12,687 then, 5,617/5,546/5,826/6,164/7,267
now.

Measured on TPU v5 lite, F=28, B=256 (my chip runs, PR 25): 4.85-4.92
ms a level at N=1M and 52.3-53.1 ms at N=11M standalone; inside the
train step, from the device trace, 313.9 ms a tree of six levels at
N=11M (573.6 for the row-major kernel), 32.6% of the MXU roofline
(17.9%). The tile: 512 / 1024 / 2048 / 4096 samples gave 54.8 / 53.1 /
52.3 / 51.9 ms a level at n_nodes=1 and 2.834 / 2.875 / 2.896 trees/s
in the step for the last three; 2048 is taken because 4096 runs out of
VMEM at shapes ``pallas_hist_supported`` admits (F=512: the bins block
is F*tile*4 bytes, twice buffered) and 8192 slows down at n_nodes=32.
What remains is the one-hot itself: 7,168 int32 compares and 3,584
mask packs a 1,024-sample tile on the VPU against 3,584 MXU pushes.

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

_TILE = 2048  # samples (lanes) per grid step; see the docstring

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


def _hist_kernel(bins_ref, g_ref, h_ref, nid_ref, out_ref, *, tile, N, F,
                 B, n_nodes):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # A^T: [4*n_nodes, tile] bf16, rows [g_hi | g_lo | h_hi | h_lo] x
    # node, samples on lanes. Row r holds quantity r // n_nodes of node
    # r % n_nodes (worked out on a [C, 1] column: lax.div / lax.rem on
    # int32, since jnp's // and % do not lower in Mosaic under x64);
    # the [1, tile] rows of g / h / node_ids broadcast along sublanes.
    C = 4 * n_nodes
    row = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    quantity = lax.div(row, jnp.int32(n_nodes))           # 0..3
    live = nid_ref[:] == lax.rem(row, jnp.int32(n_nodes))  # [C, tile]
    if N % tile:
        # the last grid step reads past the table's end: whatever rests
        # there (any bin, any NaN) is selected away, never multiplied
        lane = lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        live &= i * tile + lane < N
    v = jnp.where(live, jnp.where(quantity < 2, g_ref[:], h_ref[:]), 0.0)
    hi, lo = split_bf16(v)
    At = jnp.where(lax.rem(quantity, jnp.int32(2)) == 0, hi, lo)

    # The one-hot of feature f is [B, tile]: row f of the bins block
    # ([F, 1, tile]) broadcast along sublanes against a sublane iota.
    # The int32 compare + select is the measured best formulation. The
    # dead ends below were measured ON THE ROW-MAJOR KERNEL that PR 25
    # replaced (round-2 pricing on v5e, B=256, N=1M) and have not been
    # tried again in this layout: a bf16 arithmetic one-hot
    # (relu(1 - |b - i|), exact for integers <= 256) was 9% faster
    # STANDALONE (17.6 vs 19.3 ms) but ~20% slower in the fused train
    # step (11.2-11.5 vs 14.1-14.2 trees/sec, alternating A/B); direct
    # bf16/int16 == compares crashed the Mosaic compiler outright; tile
    # 1024 beat 2048/4096 there (here 2048 beats 1024, see the module
    # docstring).
    iota_b = lax.broadcasted_iota(jnp.int32, (B, tile), 0)

    for f in range(F):  # static unroll: one sublane row a feature
        oh = (bins_ref[f] == iota_b).astype(jnp.bfloat16)
        part = lax.dot_general(At, oh, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
        out_ref[:, f * B:(f + 1) * B] += part


def pallas_histograms(bins, g, h, node_ids, n_nodes: int, F: int, B: int,
                      tile: int = _TILE, interpret: bool = False):
    """Per-(node, feature, bin) gradient/hessian sums on the MXU.

    bins: [N, F] int32 in [0, B); g, h: [N] f32; node_ids: [N] int32 —
    ids outside [0, n_nodes) contribute exactly nothing (the one-hot
    matches no row of A^T; the GBDT sibling-subtraction path relies on
    this to exclude right-child samples via a sentinel id). Returns
    (hist_g, hist_h): [n_nodes, F, B] f32. Rows with g == h == 0
    (shard padding) contribute exactly nothing.

    The kernel reads the table as [F, 1, N]: on the TPU a [N, 28] int32
    table already rests that way (N on the lanes), so inside a jitted
    step the transposition is a bitcast of the parameter. Nothing is
    padded: the ragged last tile is masked inside the kernel.
    """
    N = bins.shape[0]
    if N == 0:
        z = jnp.zeros((n_nodes, F, B), jnp.float32)
        return z, z
    if N < tile:
        tile = -(-N // 128) * 128      # single step, lane-aligned
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

    def lanes(*lead):
        """``tile`` samples on the lanes, the leading dimensions whole.
        The index map returns int32 whatever jax_enable_x64 says:
        Mosaic cannot legalize the i64 a bare 0 becomes under x64."""
        return pl.BlockSpec(
            lead + (tile,),
            lambda i: (jnp.int32(0),) * len(lead) + (i,),
            memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        functools.partial(_hist_kernel, tile=tile, N=N, F=F, B=B,
                          n_nodes=n_nodes),
        grid=(-(-N // tile),),
        in_specs=[lanes(F, 1), lanes(1), lanes(1), lanes(1)],
        out_specs=pl.BlockSpec((C, F * B), lambda i: (jnp.int32(0),) * 2,
                               memory_space=pltpu.VMEM),
        out_shape=out_shape,
        interpret=interpret,
        name="mp4j_hist",
    )(jnp.transpose(bins[:, None, :], (2, 1, 0)), g.reshape(1, N),
      h.reshape(1, N), node_ids.reshape(1, N))
    out = out.reshape(2, 2, n_nodes, F, B)      # [g/h, hi/lo, n, F, B]
    return out[0, 0] + out[0, 1], out[1, 0] + out[1, 1]
