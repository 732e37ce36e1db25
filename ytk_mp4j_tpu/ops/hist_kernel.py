"""Pallas TPU kernel for (node x feature x bin) gradient histograms.

The GBDT hot op (SURVEY.md section 6: "GBDT histogram allreduce —
Higgs 11Mx28, 256 bins"). The XLA "matmul" strategy in models/gbdt.py
routes the histogram onto the MXU via a one-hot matmul, but XLA
materializes the per-tile one-hot and the hi/lo-split A operand through
HBM between the compare and the dot. This kernel fuses the whole
per-tile pipeline in VMEM, with the SAMPLES ON THE LANES:

  1. build A^T = [g_hi | g_lo | h_hi | h_lo] x node, C = 4*n_nodes rows
     of a tile's samples, from [1, tile] rows of g / h / node_ids
     broadcast along sublanes (hi/lo mantissa bit-split for near-f32
     accuracy);
  2. for each feature of the block, generate the bin one-hot in VMEM
     (one row of the bins block against a sublane iota) and feed the
     MXU directly (contraction over the lane axis of both operands);
  3. accumulate one feature block's f32 output across the sample tiles
     (the out index_map is constant along the minor grid axis -> the
     accumulator stays resident in VMEM and is written once a block).

A bin in two digits (PR 29). ``bin = hd * BL + ld`` with R high digits
and BL = B // R low ones, R from ``hist_radix``. Then ``hist[c, hd*BL +
ld] = sum_s P[(hd, c), s] * L[ld, s]``: L is the [BL, tile] one-hot of
the low digit and P is A^T R times over, R*C rows, row (hd, c) masked
to the samples whose high digit is hd (``where(hd == hrow, At, 0)``:
Mosaic makes a masked vmatmul of it and emits no select). The products
and the f32 sums are the undivided kernel's, the masked rows add exact
zeros, and R = 1 is the same function with the mask statically left
out. The out block is [R*C, F_blk*BL] where BL fills whole 128-lane
words and [F_blk, R*C, BL] where it does not (static lane slices must
stay 128-aligned; R*C and BL are then the full last two dimensions);
``pallas_histograms`` puts the level's histogram back to [n_nodes, F,
B] with one transposition of at most 32 MB.

What sets the kernel's time is the MXU and not the VPU's bundles (my
chip runs, PR 29, the kernel alone, TPU v5 lite): a feature's 128
samples cost max(pushes, 2 * matmuls) units, the pushes of the one-hot
(one a 16 low digits: a packed bf16 vreg of MXU weights) and the
matmuls (one a 16 operand rows and 128 low digits) running side by
side. A unit is 2.03 cycles at 1.5 GHz: a 16-row matmul is 16 row
cycles over four MXUs. Ms a level at F=968, N=1,183,747, B=256, by
n_nodes (rows) and R, with (pushes, matmuls) a feature a 128 samples:

  n_nodes   R=1             R=2             R=4             R=8
  1         193.4 (16, 2)    97.6 (8, 1)     50.1 (4, 1)     50.4 (2, 2)
  2         193.3 (16, 2)    97.7 (8, 1)     50.5 (4, 2)
  4         193.7 (16, 2)    98.3 (8, 2)     98.4 (4, 4)
  8         193.8 (16, 4)    99.0 (8, 4)
  16        194.7 (16, 8)   195.3 (8, 8)

and at F=28, N=11M (each call also pays 9.9 ms to relayout a [N, 28]
table that the train step gets as a bitcast): 62.2 / 36.8 / 24.0 / 24.1
at 1 node, 62.3 / 36.7 / 24.4 / 37.4 at 2, 62.5 / 37.0 / 37.3 at 4,
62.6 / 37.5 / 63.2 at 8, 63.1 / 63.6 at 16, 114.4 / 115.3 at 32. The
undivided kernel was flat over the levels because its 16 pushes hid up
to 8 matmuls; at 16 nodes the matmuls (64 rows against 256 bins) take
as long as the pushes did, so no radix helps there: that level rests
on the MXU's row rate for this operand, which is 1/16 dense (one node
a sample) and half sentinel rows. ``hist_radix`` is that count: 4, 4,
4, 2, 2, 1 over a depth-6 tree at 256 bins, 44 units where there were
96. Every split result of the sweep equals the R = 1 result of the
same tile to the last bit (``vs_R1_max_abs`` 0.0, 36 configurations).

Final bundles a 2,048-sample tile (``--xla_mosaic_dump_to`` /
``--xla_jf_dump_to`` for v5e, libtpu 0.0.34, no chip), F=28 rows of
(1, 128) | an 88-feature block in sublane tiles:

  n_nodes   R=1              R=2              R=4              R=8
  1         11,201 | 34,331   6,015 | 17,987   3,564 |  9,533   3,625 | 10,202
  2         11,155 | 33,993   6,696 | 17,930   4,183 | 11,282   6,924 | 19,741
  4         11,076 | 33,759   7,041 | 20,302   7,099 | 20,842  13,021 | 39,271
  8         12,385 | 37,768   7,910 | 23,496  13,684 | 41,115
  16        14,912 | 45,530  13,858 | 41,541

One tile at F=28, n_nodes=1 holds 14,370 ``vcmp.eq.s32``, 7,169
``vmpackc``, 7,168 ``vmatpush.bf16.xpose.msk`` and 896 ``vmatmul`` at
R = 1 and 4,516 / 2,242 / 1,792 / 448 at R = 4. Bundles follow the
compares and packs (about 2 a bundle) and the chip's time does not
follow the bundles: 7,041 against 7,099 and 13,858 against 14,912 are
equal times, 3,564 against 6,015 is 0.65x.

The grid is (feature block, sample tile), sample tile minor (PR 26).
``feature_blocks`` works the block out from n_nodes and B: as many
features as keep the accumulator within ``_MAX_ACC_BYTES`` (8 MiB,
single-buffered: ``pl.Buffered(1)``, or two of them would not fit; a
row of fewer than 128 low digits is padded to a lane word in VMEM,
twice the bytes at 64), at most ``_MAX_BLOCK_FEATURES``, and of the
sizes in the upper half of that range the one that leaves the fewest
idle rows in the last block. F at or under the cap is the one-block
case of the same code. g, h and node_ids are read once a block (12 B a
row against the block's F_blk * 4) and A^T is rebuilt once a (block,
tile). The unroll over features is the block. Until PR 26 the whole
[4*n_nodes, F*B] accumulator had to fit, which held F to 128 at depth 6
and 256 bins.

Why feature-major (PR 25): on the TPU a [N, 28] int32 table rests with
N on the lanes (``s32[1,N,28]{1,0,2:T(1,128)}``, unpadded). The kernel
takes it as [F, 1, N] in blocks (F_blk, 1, tile), which is that very
layout, so the step holds a bitcast of its parameter and no copy, and
the ragged last tile is masked in the kernel instead of padded. A table
whose width is a multiple of 8 (968) rests as [F, N] in (8, 128) tiles
(``{1,2,0:T(8,128)}``) and is taken as [F, N] in blocks (F_blk, tile),
F_blk a multiple of 8: again a bitcast (``_rests_tiled``). A multiple
of 128 rests row-major and costs one transposing copy a step either
way; no cell has such a table. The row-major kernel it replaced
([tile, F] blocks, samples on sublanes) made XLA copy and pad the table
at 28 of 128 lanes in every tree (two temporaries of 5.63 GB at 11M
rows, which routing then read six times) and spent half its own
bundles on layout (11,704 a 1,024-sample tile against 5,617).

In the train step, from the device trace (ms a tree of six levels,
share of the MXU roofline by ``benchmark/arith.py``): F=28, N=11M:
573.6 (17.9%) row-major, 313.9 (32.6%) since PR 25; F=968,
N=1,183,747: 1,153.5 (33.0%) since PR 26; with the bin in two digits
(PR 29) 149.4 (68.6%) and 534.3 (71.4%). The tile: 512 / 1024 / 2048 / 4096 samples gave 54.8 / 53.1
/ 52.3 / 51.9 ms a level undivided (PR 25); at R = 4, 1 node: 1024 /
2048 / 4096 / 8192 give 24.8 / 24.0 / 23.5 / 23.3 at F=28 and 51.0 /
50.1 / 49.6 at F=968 (1024 / 2048 / 4096), while 4096 at 16 nodes and
F=968 takes 224.1 against 194.3 (PR 29). 2048 stays. The cap on a
block's features (PR 26, F=968, ms a level at 1 / 4 / 16 nodes): 32
(41 x 24) 197.6 / 197.8 / 201.0; 64 (25 x 40) 199.7 / 199.9 / 201.9;
128 (11 x 88) 192.6 / 192.8 / 193.5; 256 (4 x 248, the accumulator's
11 x 88 at 16) 218.4 / 219.1 / 193.6. Against a float64 bincount on 16
features: max abs error 0.0044 of sums up to 727 (g), 0.14 of 160,320
(h).

Dead ends. In this layout (PR 29, AOT for v5e unless a time is given):
16-bit compares: Mosaic refuses them for this target ("Target does not
support this comparison" on int16 operands, "16-bit iota not supported
by hardware"), so the one-hot's masks stay int32 compares packed in
pairs. One mask a high digit shared by all its rows ([8, tile] compares
instead of [R*C, tile]): the compares fall from 14,742 to 8,267 a tile
at 16 nodes, R = 2, the bundles from 13,858 to 12,666, and the chip's
time by 1% or nothing (63.0 against 63.6 ms; 194.3 against 195.3 at
F=968; 36.7 against 36.7 at 2 nodes), because the MXU paces the
kernel; the second construction was not kept. On the row-major kernel that PR 25
replaced (round-2 pricing on v5e, B=256, N=1M), not tried again: a
bf16 arithmetic one-hot (relu(1 - |b - i|), exact for integers <= 256)
was 9% faster standalone and ~20% slower in the fused train step. Not
tried on a level-wise tree: the deepest level (sort or compact the
samples by node so that the operand is dense: a 4-row operand at every
level is 24 units a tree). What compacting costs is priced since PR 54,
where the leaf-wise grower does it for one node a split
(``models/gbdt.py: _grow_tree``; PERF.md section 6, PR 54: the rows
found from prefix counts, gathered from a second form of the table in
which a row is one descriptor, unpacked and handed to this kernel as it
is): a level's worth of rows pays the gather, the unpacking and this
kernel's one-node pass on top of the pass it saves, so it is to be
reckoned against the deepest level's 39.5 and 144.2 ms a tree and not
against nothing. Not tried: int8 one-hots (twice the MXU rate, but A
would need three or four int8 parts).

Constraints (checked by ``pallas_hist_supported``): B must be
lane-aligned (a multiple of 128) for the compiled path and one
feature's accumulator must fit; any shape works in interpret mode (used
by the CPU test suite).
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
    return (n_bins % 128 == 0
            and _acc_bytes_a_feature(n_bins, n_nodes) <= _MAX_ACC_BYTES)


def hist_radix(n_nodes: int, B: int) -> int:
    """How many high digits R the kernel splits a bin into at this
    level: ``bin = hd * (B // R) + ld``. The one-hot shrinks to the
    B // R low digits and the A operand grows to R * 4*n_nodes rows,
    the high digit as a mask on it.

    The radix is the one that holds the MXU for the shortest time, by
    the count the chip's sweep gave (module docstring): a feature's 128
    samples cost the larger of the pushes of the one-hot (one a 16 low
    digits) and twice the matmuls (one a 16 operand rows and 128 low
    digits, lane padding included); the two run side by side. On a tie
    the smaller radix, and 1 is the undivided kernel. At 256 bins: 4
    at 1 and 2 nodes, 2 at 4 and 8, 1 from 16 on. A B that is no power
    of two has no digits to split."""
    if B & (B - 1):
        return 1

    def mxu_units(R):
        low = B // R
        return max(-(-low // 16),
                   2 * -(-R * 4 * n_nodes // 16) * -(-low // 128))

    # under 16 low digits a push carries no fewer rows
    radices = [1] + [1 << k for k in range(1, B.bit_length())
                     if B >> k >= 16]
    return min(radices, key=lambda R: (mxu_units(R), R))


def _acc_bytes_a_feature(B: int, n_nodes: int) -> int:
    """VMEM bytes of one feature's f32 accumulator: [R * 4*n_nodes,
    B // R], a row padded to whole 128-lane words where the low digits
    are fewer (twice the bytes at 64)."""
    R = hist_radix(n_nodes, B)
    return R * 4 * n_nodes * max(B // R, 128) * 4


def feature_blocks(F: int, B: int, n_nodes: int) -> tuple[int, int]:
    """(features a block, blocks) of the kernel's grid for this shape.

    A block holds as many features as keep its accumulator within
    ``_MAX_ACC_BYTES`` and at most ``_MAX_BLOCK_FEATURES``; F at or
    under that is one block of F. Above it the block is the size in
    the upper half of that range that leaves the fewest idle rows in
    the ragged last block (968 features at 16 nodes: 11 blocks of 88,
    none idle), in whole sublane tiles of 8 where the table rests
    (8, 128)-tiled (see ``pallas_histograms``)."""
    cap = max(1, min(_MAX_ACC_BYTES // _acc_bytes_a_feature(B, n_nodes),
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
                 F_blk, B, n_nodes, R):
    i = pl.program_id(1)        # the sample tile: the minor grid axis

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # A^T: [R * 4*n_nodes, tile], samples on lanes, the C = 4*n_nodes
    # rows [g_hi | g_lo | h_hi | h_lo] x node once a high digit: row r
    # holds quantity (r % C) // n_nodes of node r % n_nodes for high
    # digit r // C (worked out on a [R*C, 1] column: lax.div / lax.rem
    # on int32, since jnp's // and % do not lower in Mosaic under x64);
    # the [1, tile] rows of g / h / node_ids broadcast along sublanes.
    C, BL = 4 * n_nodes, B // R
    row = lax.broadcasted_iota(jnp.int32, (R * C, 1), 0)
    quantity = lax.div(lax.rem(row, jnp.int32(C)), jnp.int32(n_nodes))
    live = nid_ref[:] == lax.rem(row, jnp.int32(n_nodes))  # [R*C, tile]
    if N % tile:
        # the last grid step reads past the table's end: whatever rests
        # there (any bin, any NaN) is selected away, never multiplied
        lane = lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        live &= i * tile + lane < N
    v = jnp.where(live, jnp.where(quantity < 2, g_ref[:], h_ref[:]), 0.0)
    hi, lo = split_bf16(v)
    At = jnp.where(lax.rem(quantity, jnp.int32(2)) == 0, hi, lo)
    if R > 1:
        # masked a feature below and cast again after the mask (bf16 ->
        # f32 is exact, so the rounding stays split_bf16's): Mosaic
        # makes of that select and cast the mask of a masked vmatmul
        At = At.astype(jnp.float32)
        hrow = lax.div(row, jnp.int32(C))                   # [R*C, 1]

    # The low digit's one-hot of feature f is [BL, tile]: row f of the
    # bins block broadcast along sublanes against a sublane iota (the
    # int32 compare + select; the module docstring has the dead ends).
    iota_l = lax.broadcasted_iota(jnp.int32, (BL, tile), 0)

    # static unroll, bounded by the block: one sublane row a feature.
    # In a ragged last block the rows past F hold whatever rests there;
    # their one-hots land in output columns that are never written back
    for f in range(F_blk):
        bins_row = (bins_ref[f] if len(bins_ref.shape) == 3
                    else bins_ref[f:f + 1, :])              # [1, tile]
        P = At
        if R > 1:
            hd = lax.shift_right_logical(bins_row,
                                         jnp.int32(BL.bit_length() - 1))
            P = jnp.where(hd == hrow, At, 0.0).astype(jnp.bfloat16)
            bins_row = bins_row & jnp.int32(BL - 1)
        oh = (bins_row == iota_l).astype(jnp.bfloat16)
        part = lax.dot_general(P, oh, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
        if len(out_ref.shape) == 3:
            out_ref[f] += part
        else:
            out_ref[:, f * BL:(f + 1) * BL] += part


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

    A bin is taken as two digits, ``hist_radix`` high ones and B // R
    low ones (module docstring): the kernel's rows are (high digit,
    quantity, node), its columns (feature, low digit), and the level's
    histogram is put back to [n_nodes, F, B] here.
    """
    N = bins.shape[0]
    if N == 0:
        z = jnp.zeros((n_nodes, F, B), jnp.float32)
        return z, z
    if N < tile:
        tile = -(-N // 128) * 128      # single step, lane-aligned
    R = hist_radix(n_nodes, B)
    RC, BL = R * 4 * n_nodes, B // R
    F_blk, n_blocks = feature_blocks(F, B, n_nodes)
    tiled = _rests_tiled(F) and F_blk % 8 == 0  # whole sublane tiles
    # Static lane slices must stay 128-aligned: low digits that fill
    # whole lane words lie side by side, a feature after another;
    # fewer are a block of their own a feature, [R*C, BL] being the
    # full last two dimensions.
    if BL % 128 == 0:
        shape = (RC, F * BL)
        out_spec = pl.BlockSpec((RC, F_blk * BL),
                                lambda j, i: (jnp.int32(0), j),
                                memory_space=pltpu.VMEM,
                                pipeline_mode=pl.Buffered(1))
    else:
        shape = (F, RC, BL)
        out_spec = pl.BlockSpec((F_blk, RC, BL),
                                lambda j, i: (j, jnp.int32(0), jnp.int32(0)),
                                memory_space=pltpu.VMEM,
                                pipeline_mode=pl.Buffered(1))
    # under shard_map with check_vma, the out_shape must carry the
    # union of the inputs' varying-across-mesh-axes sets
    vma = frozenset().union(*(
        getattr(jax.typeof(x), "vma", None) or frozenset()
        for x in (bins, g, h, node_ids)))
    if vma:
        out_shape = jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)
    else:
        out_shape = jax.ShapeDtypeStruct(shape, jnp.float32)

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
                          n_nodes=n_nodes, R=R),
        grid=(n_blocks, -(-N // tile)),
        in_specs=[bins_spec, row_spec, row_spec, row_spec],
        out_specs=out_spec,
        out_shape=out_shape,
        # feature blocks are independent work; the sample axis
        # accumulates into the resident block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mp4j_hist",
    )(table, g.reshape(1, N), h.reshape(1, N), node_ids.reshape(1, N))
    # rows are (high digit, g/h, hi/lo, node); hi + lo first, so that
    # half as much is put back to (g/h, node, feature, bin)
    if BL % 128 == 0:
        out = out.reshape(R, 2, 2, n_nodes, F, BL)
        out = jnp.transpose(out[:, :, 0] + out[:, :, 1], (1, 2, 3, 0, 4))
    else:
        out = out.reshape(F, R, 2, 2, n_nodes, BL)
        out = jnp.transpose(out[:, :, :, 0] + out[:, :, :, 1],
                            (2, 3, 0, 1, 4))
    out = out.reshape(2, n_nodes, F, B)
    return out[0], out[1]
