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
  2. for each feature of the block, generate the [B, tile] bin one-hot
     in VMEM (one row of the bins block against a sublane iota) and
     feed the MXU directly (contraction over the lane axis of both
     operands);
  3. accumulate one feature block's [4*n_nodes, F_blk*B] f32 output
     across the sample tiles (the out index_map is constant along the
     minor grid axis -> the accumulator stays resident in VMEM and is
     written once a block).

The grid is (feature block, sample tile), sample tile minor (PR 26).
``feature_blocks`` works the block out from n_nodes and B: as many
features as keep the accumulator within ``_MAX_ACC_BYTES`` (8 MiB,
single-buffered: ``pl.Buffered(1)``, or two of them would not fit), at
most ``_MAX_BLOCK_FEATURES``, and of the sizes in the upper half of that
range the one that leaves the fewest idle rows in the last block. F at
or under the cap is the one-block case of the same code (F = 28: grid
(1, tiles), and the bundle count PR 25 left: 10,932 / 14,505 a 2,048
sample tile at n_nodes 1 / 16 against 10,936 / 14,509). g, h and
node_ids are read once a block (12 B a row against the block's
F_blk * 4) and A^T is rebuilt once a (block, tile). The unroll over
features is the block. Until PR 26 the whole [4*n_nodes, F*B]
accumulator had to fit, which held F to 128 at depth 6 and 256 bins.

Why feature-major (PR 25): on the TPU a [N, 28] int32 table rests with
N on the lanes (``s32[1,N,28]{1,0,2:T(1,128)}``, unpadded). The kernel
takes it as [F, 1, N] in blocks (F_blk, 1, tile), which is that very
layout, so the step holds a bitcast of its parameter and no copy, and
the ragged last tile is masked in the kernel instead of padded. A table
whose width is a multiple of 8 (968) rests as [F, N] in (8, 128) tiles
(``{1,2,0:T(8,128)}``) and is taken as [F, N] in blocks (F_blk, tile),
F_blk a multiple of 8: again a bitcast (``_rests_tiled``). A multiple
of 128 rests row-major and costs one transposing copy a step either
way; no cell has such a table. The
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
VMEM at a block of 512 features (the bins block is F_blk*tile*4 bytes,
twice buffered) and 8192 slows down at n_nodes=32.
What remains is the one-hot itself: 7,168 int32 compares and 3,584
mask packs a 1,024-sample tile on the VPU against 3,584 MXU pushes.

F=968, B=256, N=1,183,747 (my chip runs, PR 26), the kernel alone, ms a
level at n_nodes 1 / 4 / 16 by ``_MAX_BLOCK_FEATURES`` (the blocks it
gives): 32 (41 x 24) 197.6 / 197.8 / 201.0; 64 (25 x 40) 199.7 / 199.9
/ 201.9; 128 (11 x 88) 192.6 / 192.8 / 193.5; 256 (4 x 248 at 1 and 4
nodes, the accumulator's 11 x 88 at 16) 218.4 / 219.1 / 193.6, with a
first call of 23 s against 5-11. 128 is the constant: 1.019 ns a table
cell a tree at F=28, 1.007 here. Against a float64 bincount on 16
features: max abs error 0.0044 of sums up to 727 (g), 0.14 of 160,320
(h); ragged last blocks in both operand forms (F = 131, 136) agree to
the same digits. In the step: 1,153.5 ms a tree, 33.0% of the roofline.

Constraints (checked by ``pallas_hist_supported``): B must be
lane-aligned (a multiple of 128) for the compiled path and one
feature's [4*n_nodes, B] accumulator must fit; any shape works in
interpret mode (used by the CPU test suite).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TILE = 2048  # samples (lanes) per grid step; see the docstring

# One feature block's [4*n_nodes, F_blk*B] f32 accumulator stays pinned
# in VMEM while the sample tiles stream past (the out index_map is
# constant along the sample axis); leave headroom for the input blocks,
# the A operand and the per-feature one-hot within ~16 MB/core.
_MAX_ACC_BYTES = 8 * 2 ** 20
# Most features a block holds, which is also the length of the static
# unroll; see the docstring for the sweep.
_MAX_BLOCK_FEATURES = 128


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
    "the compiled kernel needs n_bins % 128 == 0 and one feature's "
    "[4*n_nodes, n_bins] f32 accumulator of at most "
    f"{_MAX_ACC_BYTES // 2 ** 20} MiB (any number of features: they "
    "are taken in blocks)")


def pallas_hist_supported(n_bins: int, n_features: int,
                          n_nodes: int = 1) -> bool:
    """Compiled-path constraints: lane-aligned bin rows (static lane
    slices at multiples of B must be 128-aligned) and a VMEM-resident
    accumulator for at least one feature. ``n_features`` bounds
    nothing: the kernel takes the features in blocks
    (``feature_blocks``)."""
    del n_features
    return n_bins % 128 == 0 and 4 * n_nodes * n_bins * 4 <= _MAX_ACC_BYTES


def feature_blocks(F: int, B: int, n_nodes: int) -> tuple[int, int]:
    """(features a block, blocks) of the kernel's grid for this shape.

    A block holds as many features as keep its accumulator within
    ``_MAX_ACC_BYTES`` and at most ``_MAX_BLOCK_FEATURES``; F at or
    under that is one block of F. Above it the block is the size in
    the upper half of that range that leaves the fewest idle rows in
    the ragged last block (968 features at 16 nodes: 11 blocks of 88,
    none idle), in whole sublane tiles of 8 where the table rests
    (8, 128)-tiled (see ``pallas_histograms``)."""
    cap = max(1, min(_MAX_ACC_BYTES // (4 * n_nodes * B * 4),
                     _MAX_BLOCK_FEATURES))
    if F <= cap:
        return F, 1
    step = 8 if _rests_tiled(F) and cap >= 8 else 1
    cap -= cap % step
    blk = min(range(cap, cap // 2, -step), key=lambda c: (-(-F // c) * c, -c))
    return blk, -(-F // blk)


def _rests_tiled(F: int) -> bool:
    """Whether a [N, F] int32 table rests on the TPU as [F, N] in (8,
    128) tiles (``{1,2,0:T(8,128)}``: F a multiple of 8) rather than as
    F rows of N lanes (``{1,0,2:T(1,128)}``, e.g. F = 28). The layout
    is the runtime's choice for the shape, the same for every program
    (AOT for v5e, libtpu 0.0.34; ``tests/test_gbdt_aot.py`` pins it at
    F = 8, 28, 136, 250, 700, 968, 1024 and 2000, so a runtime that
    chooses otherwise fails a test before it costs a copy of the table
    a tree); a multiple of 128 rests row-major and costs a transposing
    copy either way."""
    return F % 8 == 0


def _hist_kernel(bins_ref, g_ref, h_ref, nid_ref, out_ref, *, tile, N,
                 F_blk, B, n_nodes):
    i = pl.program_id(1)        # the sample tile: the minor grid axis

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
    # broadcast along sublanes against a sublane iota. The int32
    # compare + select is the measured best formulation. The
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

    # static unroll, bounded by the block: one sublane row a feature.
    # In a ragged last block the rows past F hold whatever rests there;
    # their one-hots land in output columns that are never written back
    for f in range(F_blk):
        bins_row = (bins_ref[f] if len(bins_ref.shape) == 3
                    else bins_ref[f:f + 1, :])              # [1, tile]
        oh = (bins_row == iota_b).astype(jnp.bfloat16)
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

    The kernel reads the table feature-major, the way it already
    rests on the TPU, so inside a jitted step the transposition is a
    bitcast of the parameter: as [F, 1, N] in blocks (F_blk, 1, tile)
    where a [N, F] int32 table rests as F rows of N lanes (F = 28), as
    [F, N] in blocks (F_blk, tile) where it rests in (8, 128) tiles
    (F = 968; ``_rests_tiled``). The grid is (feature block, sample
    tile), sample tile minor: a block's accumulator stays in VMEM
    while the samples stream past and is written once; g, h and
    node_ids are read once a feature block. Nothing is padded: the
    ragged last tile is masked inside the kernel, and of a ragged last
    feature block only the columns under F are written back.
    """
    N = bins.shape[0]
    if N == 0:
        z = jnp.zeros((n_nodes, F, B), jnp.float32)
        return z, z
    if N < tile:
        tile = -(-N // 128) * 128      # single step, lane-aligned
    C = 4 * n_nodes
    F_blk, n_blocks = feature_blocks(F, B, n_nodes)
    tiled = _rests_tiled(F) and F_blk % 8 == 0  # whole sublane tiles
    # under shard_map with check_vma, the out_shape must carry the
    # union of the inputs' varying-across-mesh-axes sets
    vma = frozenset().union(*(
        getattr(jax.typeof(x), "vma", None) or frozenset()
        for x in (bins, g, h, node_ids)))
    if vma:
        out_shape = jax.ShapeDtypeStruct((C, F * B), jnp.float32, vma=vma)
    else:
        out_shape = jax.ShapeDtypeStruct((C, F * B), jnp.float32)

    # The index maps return int32 whatever jax_enable_x64 says: Mosaic
    # cannot legalize the i64 a bare 0 becomes under x64.
    if tiled:
        table = bins.T                                      # [F, N]
        bins_spec = pl.BlockSpec((F_blk, tile), lambda j, i: (j, i),
                                 memory_space=pltpu.VMEM)
    else:
        table = jnp.transpose(bins[:, None, :], (2, 1, 0))  # [F, 1, N]
        bins_spec = pl.BlockSpec((F_blk, 1, tile),
                                 lambda j, i: (j, jnp.int32(0), i),
                                 memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, tile), lambda j, i: (jnp.int32(0), i),
                            memory_space=pltpu.VMEM)    # g, h, node_ids

    out = pl.pallas_call(
        functools.partial(_hist_kernel, tile=tile, N=N, F_blk=F_blk, B=B,
                          n_nodes=n_nodes),
        grid=(n_blocks, -(-N // tile)),
        in_specs=[bins_spec, row_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((C, F_blk * B), lambda j, i: (jnp.int32(0), j),
                               memory_space=pltpu.VMEM,
                               pipeline_mode=pl.Buffered(1)),
        out_shape=out_shape,
        # feature blocks are independent work; the sample axis
        # accumulates into the resident block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mp4j_hist",
    )(table, g.reshape(1, N), h.reshape(1, N), node_ids.reshape(1, N))
    out = out.reshape(2, 2, n_nodes, F, B)      # [g/h, hi/lo, n, F, B]
    return out[0, 0] + out[0, 1], out[1, 0] + out[1, 1]
