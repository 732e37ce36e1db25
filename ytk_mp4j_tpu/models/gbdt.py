"""TPU-native distributed GBDT — the north-star workload.

ytk-mp4j's flagship consumer is ytk-learn's distributed GBDT, whose inner
loop is a per-tree-level (node x feature x bin) gradient/hessian
HISTOGRAM ALLREDUCE across data-parallel workers (BASELINE.json:
"ytk-learn GBDT histogram allreduce — Higgs 11Mx28, 256 bins"). This
module is that consumer rebuilt TPU-first so the collectives library can
be measured end-to-end:

- samples are sharded over the mesh (pure data parallelism, the only
  parallelism the reference stack has — SURVEY.md section 2);
- each device builds local histograms with a single XLA segment-sum over
  ``node*F*B + f*B + bin`` flat ids (static shapes, no Python loops over
  samples);
- ``lax.psum`` over the mesh axis IS the histogram allreduce that the
  reference performs with Kryo-socket recursive halving;
- split finding (regularized gain over bin-cumulative G/H), node
  routing, and leaf updates are all jit-compiled; the per-level loop is
  unrolled (depth is static).

Everything runs inside ONE jitted ``shard_map`` training step per tree —
the histogram allreduce never leaves the device.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ytk_mp4j_tpu.models._base import (DataParallelTrainer, EarlyStopper,
                                       per_example_loss,
                                       stage_softmax_labels)
from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.models.binning import _count_edges
from ytk_mp4j_tpu.obs import spans
from ytk_mp4j_tpu.ops.bin_kernel import bin_blocks, search_steps
from ytk_mp4j_tpu.ops.hist_kernel import _rests_tiled, split_bf16


@dataclass(frozen=True)
class GBDTConfig:
    n_features: int = 28
    n_bins: int = 256           # byte-binned, like ytk-learn's 256-bin hists
    depth: int = 6
    # "squared": regression (g = pred - y, h = 1); "logistic": binary
    # classification on {0,1} labels with second-order (Newton) leaf
    # values, the reference consumer's Higgs objective; "softmax":
    # multiclass on integer labels — one tree per class per round
    # against the diagonal softmax gradient/hessian
    loss: str = "squared"
    n_classes: int = 2          # used by loss="softmax" only
    # stochastic boosting (ytk-learn's sample_rate / feature_sample_rate):
    # per tree, each sample is kept with prob ``subsample`` (dropped
    # samples get weight 0; kept ones are scaled 1/subsample so
    # gradient sums stay unbiased) and each feature is kept with prob
    # ``colsample`` (masked features never win a split)
    subsample: float = 1.0
    colsample: float = 1.0
    # split regularization (ytk-learn's min-gain / min-child thresholds):
    # a node whose best gain < min_split_gain stops splitting (routes all
    # samples left, equivalent to keeping the node a leaf); candidate
    # splits whose left or right hessian sum < min_child_hessian are
    # disqualified
    min_split_gain: float = 0.0
    min_child_hessian: float = 0.0
    learning_rate: float = 0.1
    reg_lambda: float = 1.0
    n_trees: int = 10
    # "pallas": fused one-hot MXU matmul in VMEM (default; ~25% over
    # "matmul", see ops/hist_kernel.py; compiled on TPU it needs
    # n_bins % 128 == 0 and one feature's [4*n_nodes, n_bins] f32
    # accumulator within 8 MiB, and raises otherwise; any number of
    # features, taken in blocks); "matmul": XLA one-hot
    # MXU matmul (~5x the scatter strategies on v5e — see the
    # performance note below; the explicit choice where the pallas
    # constraints don't hold); "pair": feature-pair joint scatter
    # histograms (exact in f32, the differential oracle); "flat": one
    # scatter per feature
    hist_mode: str = "pallas"
    # Missing-value handling (ytk-learn routes missing by a learned
    # per-split default direction): when True, bin 0 is the RESERVED
    # missing bucket across all features (QuantileBinner(...,
    # missing_bucket=True) emits this convention) and every split
    # evaluates both "missing goes left" and "missing goes right",
    # keeping the better gain; the chosen direction is stored per node
    # and replayed at predict time.
    missing_bin: bool = False
    # Categorical features (ytk-learn's one-hot split type): listed
    # feature indices split by EQUALITY — "bin == b goes right, rest
    # left" — instead of the ordered "bin <= b" rule. Bin B-1 cannot be
    # a split category (it doubles as the node-freeze sentinel); bin
    # categorical values into [0, B-2] (and into [1, B-2] under
    # missing_bin, where 0 is the missing bucket).
    categorical_features: tuple = ()
    # How a tree grows. "level": a complete tree of ``depth`` levels, a
    # level at a time (``_build_tree``). "loss": leaf by leaf, best
    # first (LightGBM's policy, XGBoost's ``lossguide``, ytk-learn's
    # ``tree_grow_policy: "loss"``): of all open leaves the one whose
    # best split gains most is split, until ``max_leaves`` leaves stand;
    # ``depth`` is then the cap on any leaf's depth (``_grow_tree``).
    grow_policy: str = "level"
    max_leaves: int | None = None

    def __post_init__(self):
        # Mp4jError for ALL input validation, matching train() and the
        # linear/FM config classes (the library-wide exception type)
        if self.hist_mode not in ("pallas", "matmul", "pair", "flat"):
            raise Mp4jError(
                f"hist_mode must be 'pallas', 'matmul', 'pair' or "
                f"'flat', got {self.hist_mode!r}")
        if self.loss not in ("squared", "logistic", "softmax"):
            raise Mp4jError(
                f"loss must be 'squared', 'logistic' or 'softmax', "
                f"got {self.loss!r}")
        if self.loss == "softmax" and self.n_classes < 2:
            raise Mp4jError(
                f"softmax needs n_classes >= 2, got {self.n_classes}")
        if self.grow_policy not in ("level", "loss"):
            raise Mp4jError(
                f"grow_policy must be 'level' or 'loss', got "
                f"{self.grow_policy!r}")
        if self.grow_policy == "level":
            if self.max_leaves is not None:
                raise Mp4jError(
                    f"max_leaves={self.max_leaves!r} needs "
                    "grow_policy='loss': a level-wise tree has 2**depth "
                    "leaves")
        elif (isinstance(self.max_leaves, bool)
              or not isinstance(self.max_leaves, (int, np.integer))
              or not 2 <= self.max_leaves <= 2 ** self.depth):
            raise Mp4jError(
                f"grow_policy='loss' needs an int max_leaves in [2, "
                f"2**depth = {2 ** self.depth}], got {self.max_leaves!r}")
        if not (0.0 < self.subsample <= 1.0
                and 0.0 < self.colsample <= 1.0):
            raise Mp4jError(
                f"subsample/colsample must be in (0, 1], got "
                f"{self.subsample}/{self.colsample}")
        cats = []
        for f in self.categorical_features:
            if isinstance(f, bool) or not isinstance(f, (int, np.integer)):
                raise Mp4jError(
                    f"categorical_features must be int feature indices, "
                    f"got {f!r}")
            if not 0 <= f < self.n_features:
                raise Mp4jError(
                    f"categorical_features must be indices in [0, "
                    f"{self.n_features}), got {f}")
            cats.append(int(f))
        object.__setattr__(self, "categorical_features", tuple(cats))

    def _cat_mask(self) -> np.ndarray | None:
        """Static [F] bool mask of equality-split features (None when
        there are none — keeps the all-numeric compiled graph
        unchanged)."""
        if not self.categorical_features:
            return None
        m = np.zeros(self.n_features, bool)
        m[list(self.categorical_features)] = True
        return m


# ----------------------------------------------------------------------
# histogram building (the hot op)
#
# TPU performance note (measured on v5e, N=1M x F=28 x B=256): a scatter
# (segment_sum) histogram is bound by the chip's serial scatter unit at
# ~13 ns per (sample, feature) contribution, independent of bucket
# count. Widening scatter rows ([M,2]/[M,4]/[M,8] updates) is 4x SLOWER
# (XLA emulates row scatters element-wise); pre-sorting indices does not
# help; complex64 / 64-bit packed scatters are emulated 10-20x slower;
# v5e has no SparseCore. Within the scatter family the one lever is
# element count: feature-PAIR joint (B x B) histograms halve elements
# (mode "pair", exact in f32, ~1.3x).
#
# The way OFF the serial unit is the MXU: hist[q,n,(f,b)] =
# A^T @ OH with A[i,(q,n)] = q_i * [node_i == n] (bf16, hi/lo-split for
# near-f32 accuracy) and OH[i,(f,b)] = [bins[i,f] == b] (bf16 one-hot,
# exact), tiled with lax.scan so OH never materializes beyond one tile.
# The one-hot "wastes" B x the FLOPs but rides the otherwise-idle
# systolic array: measured 51-66 ms/level vs 220-368 ms for the best
# scatter (4-6x), rel err ~5e-6. The hi/lo split MUST be computed by
# mantissa bit-masking: written as a - f32(bf16(a)), XLA's algebraic
# simplifier folds the convert pair and the low part silently becomes
# zero (measured: identical error to plain bf16).
#
# The per-level full-N scan is the measured optimum, not an oversight
# (round-2 pricing on v5e at N=1M): active-sample
# compaction (scan only the ~N/2 left-child rows below the root) costs
# argsort 25 ms + row/vector gathers 62/46 ms per level on the serial
# unit against ~21 ms of histogram saved; leaf-wise growth needs the
# same gathers; int8 one-hot/accumulation and narrower A operands are
# within noise of bf16 because the one-hot GENERATION (a VPU compare
# per (sample, feature, bin)) — not the matmul — is the floor.
# ----------------------------------------------------------------------
_MATMUL_TILE = 1024  # contraction tile; OH tile = tile*F*B*2 bytes in VMEM


@jax.named_scope("gbdt.hist")
def build_histograms(bins, g, h, node_ids, n_nodes: int, cfg: GBDTConfig,
                     interpret: bool | None = None):
    """Per-(node, feature, bin) gradient/hessian sums.

    bins: [N, F] int32 (values in [0, B)); g, h: [N] f32;
    node_ids: [N] int32 — CONTRACT for every strategy: ids outside
    [0, n_nodes) contribute nothing (the one-hot strategies match no
    column; the scatter strategies rely on JAX's drop-out-of-bounds
    scatter semantics). The sibling-subtraction path in _build_tree
    passes a sentinel id for right-child samples and depends on this.
    Returns (hist_g, hist_h): [n_nodes, F, B] f32.

    Strategy "pallas" (default): the fused VMEM one-hot MXU kernel
    (ops/hist_kernel.py), features in blocks, so any width. Compiled,
    it either fits the kernel's constraints (n_bins, n_nodes) or
    raises — there is no hand-over to another strategy;
    ``hist_mode="matmul"`` is the explicit choice for other shapes.
    ``interpret`` selects the kernel's interpret mode (None: interpret
    unless running on TPU — the CPU test suite and the virtual CPU
    meshes take the interpreted path). Strategy "matmul": XLA
    one-hot MXU matmul per tile (see the performance note). Strategy
    "pair" (when F is even and the joint table fits): one scatter of
    N*F/2 elements into per-feature-PAIR joint (B x B) histograms, then
    marginalize. Strategy "flat": one scatter of N*F elements (the
    fallback, and the shape the socket baseline mirrors).
    """
    F, B = cfg.n_features, cfg.n_bins
    if cfg.hist_mode == "pallas":
        from ytk_mp4j_tpu.ops.hist_kernel import (PALLAS_HIST_CONSTRAINT,
                                                  pallas_hist_supported,
                                                  pallas_histograms)
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        if interpret:
            # the pallas HLO interpreter is not vma-aware, so
            # interpreting inside shard_map trips check_vma; the matmul
            # strategy is the semantically identical stand-in there
            # (CPU test meshes)
            if getattr(jax.typeof(g), "vma", None):
                return _build_histograms_matmul(bins, g, h, node_ids,
                                                n_nodes, cfg)
            return pallas_histograms(bins, g, h, node_ids, n_nodes, F, B,
                                     interpret=True)
        if not pallas_hist_supported(B, F, n_nodes):
            raise Mp4jError(
                f"hist_mode='pallas' cannot compile n_bins={B}, "
                f"n_features={F}, n_nodes={n_nodes}: "
                f"{PALLAS_HIST_CONSTRAINT}; choose hist_mode='matmul' "
                f"explicitly for this shape")
        return pallas_histograms(bins, g, h, node_ids, n_nodes, F, B)
    if cfg.hist_mode == "matmul":
        return _build_histograms_matmul(bins, g, h, node_ids, n_nodes, cfg)
    joint_mb = n_nodes * (F // 2) * B * B * 4 * 2 / 1e6
    if cfg.hist_mode == "pair" and F % 2 == 0 and joint_mb <= 1024:
        return _build_histograms_pair(bins, g, h, node_ids, n_nodes, cfg)
    return _build_histograms_flat(bins, g, h, node_ids, n_nodes, cfg)


def _build_histograms_matmul(bins, g, h, node_ids, n_nodes, cfg):
    F, B = cfg.n_features, cfg.n_bins
    N = bins.shape[0]
    tile = min(_MATMUL_TILE, N) if N else 1   # N == 0: scan over 0 tiles
    T = -(-N // tile)
    pad = T * tile - N
    if pad:  # zero g/h rows contribute exact-zero products
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        g = jnp.pad(g, (0, pad))
        h = jnp.pad(h, (0, pad))
        node_ids = jnp.pad(node_ids, (0, pad))
    iota_b = jnp.arange(B, dtype=bins.dtype)
    iota_n = jnp.arange(n_nodes, dtype=node_ids.dtype)

    def tile_fn(acc, xs):
        bt, gt, ht, nt = xs
        oh = (bt[:, :, None] == iota_b).astype(jnp.bfloat16)
        oh = oh.reshape(tile, F * B)                  # exact 0/1
        noh = nt[:, None] == iota_n

        def amat(v):
            hi, lo = split_bf16(jnp.where(noh, v[:, None], 0.0))
            return jnp.concatenate([hi, lo], 1)       # [tile, 2*n_nodes]

        A = jnp.concatenate([amat(gt), amat(ht)], 1)  # [tile, 4*n_nodes]
        part = lax.dot_general(A, oh, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
        return acc + part, None

    xs = (bins.reshape(T, tile, F), g.reshape(T, tile),
          h.reshape(T, tile), node_ids.reshape(T, tile))
    # the data dependence on g marks the carry as device-varying so the
    # scan carry types line up when this runs per-shard inside
    # shard_map; isfinite keeps the marker an exact 0 even when g[0] is
    # inf/NaN (a bare `g[0] * 0` would poison every bin)
    marker = jnp.isfinite(g[0] if N else jnp.float32(0)).astype(jnp.float32) * 0
    acc0 = jnp.zeros((4 * n_nodes, F * B), jnp.float32) + marker
    out, _ = lax.scan(tile_fn, acc0, xs)
    out = out.reshape(2, 2, n_nodes, F, B)            # [q, hi/lo, n, F, B]
    return out[0, 0] + out[0, 1], out[1, 0] + out[1, 1]


def _build_histograms_flat(bins, g, h, node_ids, n_nodes, cfg):
    F, B = cfg.n_features, cfg.n_bins
    flat_ids = (node_ids[:, None] * (F * B)
                + jnp.arange(F, dtype=jnp.int32)[None, :] * B
                + bins)                                   # [N, F]
    seg = flat_ids.reshape(-1)
    gs = jnp.broadcast_to(g[:, None], bins.shape).reshape(-1)
    hs = jnp.broadcast_to(h[:, None], bins.shape).reshape(-1)
    hist_g = jax.ops.segment_sum(gs, seg, num_segments=n_nodes * F * B)
    hist_h = jax.ops.segment_sum(hs, seg, num_segments=n_nodes * F * B)
    return (hist_g.reshape(n_nodes, F, B), hist_h.reshape(n_nodes, F, B))


def _build_histograms_pair(bins, g, h, node_ids, n_nodes, cfg):
    """Joint (feature-pair, B x B) histograms + marginalization: halves
    the scatter elements (the serial-unit bound above), exactly."""
    F, B = cfg.n_features, cfg.n_bins
    P = F // 2
    b1 = bins[:, 0::2]                                    # [N, P]
    b2 = bins[:, 1::2]
    flat = (node_ids[:, None] * (P * B * B)
            + jnp.arange(P, dtype=jnp.int32)[None, :] * (B * B)
            + b1 * B + b2).reshape(-1)
    gs = jnp.broadcast_to(g[:, None], b1.shape).reshape(-1)
    hs = jnp.broadcast_to(h[:, None], b1.shape).reshape(-1)
    HG = jax.ops.segment_sum(gs, flat, num_segments=n_nodes * P * B * B)
    HH = jax.ops.segment_sum(hs, flat, num_segments=n_nodes * P * B * B)
    HG = HG.reshape(n_nodes, P, B, B)
    HH = HH.reshape(n_nodes, P, B, B)
    # marginalize the joint table: even features sum out b2, odd sum b1
    hg = jnp.stack([HG.sum(-1), HG.sum(-2)], 2).reshape(n_nodes, F, B)
    hh = jnp.stack([HH.sum(-1), HH.sum(-2)], 2).reshape(n_nodes, F, B)
    return hg, hh


# ----------------------------------------------------------------------
# gather-free routing primitives
#
# TPU performance note (measured on v5e, N=1M): per-sample gathers run
# on the chip's serial scatter/gather unit — jnp.take_along_axis over
# [N, F] costs ~24 ms and even a 64-entry table lookup ~9 ms, while the
# equivalent one-hot select (compare + multiply + row-sum on the VPU)
# costs ~7 ms and is EXACT (one term of the sum is nonzero). The leaf
# G/H segment-sum (~12 ms on the scatter unit) becomes a hi/lo-split
# bf16 one-hot matmul on the MXU like the histograms.
# ----------------------------------------------------------------------
def _onehot_select(table, idx, n: int):
    """``table[idx]`` per sample without the serial gather unit.

    table: [n] (any dtype); idx: [N] int32 in [0, n).
    Exact: the one-hot picks a single term per row. Masked with
    ``where`` — NOT ``table * noh`` — so a non-finite table entry
    (e.g. a NaN leaf value from an empty leaf at reg_lambda=0) reaches
    only the rows that select it, exactly like the gather it replaces.
    """
    noh = idx[:, None] == jnp.arange(n, dtype=idx.dtype)
    return jnp.where(noh, table[None, :], 0).sum(1)


def _onehot_row_select(mat, col_idx):
    """``mat[i, col_idx[i]]`` per row without the serial gather unit."""
    F = mat.shape[1]
    noh = col_idx[:, None] == jnp.arange(F, dtype=col_idx.dtype)
    return jnp.where(noh, mat, 0).sum(1)


def _onehot_segment_sum2(val_a, val_b, seg_ids, n_segments: int):
    """Per-segment sums of two value vectors in ONE MXU pass (hi/lo
    bf16 split, ~2^-17 relative like the histogram path) instead of the
    serial scatter unit; the [N, n_segments] one-hot operand is
    streamed once for both."""
    noh = (seg_ids[:, None]
           == jnp.arange(n_segments, dtype=seg_ids.dtype)
           ).astype(jnp.bfloat16)
    a_hi, a_lo = split_bf16(val_a)
    b_hi, b_lo = split_bf16(val_b)
    A = jnp.stack([a_hi, a_lo, b_hi, b_lo], 1)      # [N, 4] bf16
    out = lax.dot_general(A, noh, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    return out[0] + out[1], out[2] + out[3]         # [n_segments] f32 x2


def _chain_select(table, ids, n: int):
    """``table[ids]`` per sample as a chain of ``n`` selects: ``table``
    an [n] array or a list of n arrays of ``ids``' shape, ``ids`` of
    any shape and in [0, n) (0 elsewhere, like ``_onehot_select``).
    Elementwise throughout, so XLA keeps it in whatever fusion reads
    ``table[j]``, where ``_onehot_select`` reduces an [N, n] one-hot."""
    out = 0
    for j in range(n):
        out = jnp.where(ids == j, table[j], out)
    return out


def route_sliced(n_nodes: int, F: int) -> bool:
    """Whether a level of ``n_nodes`` nodes routes on its split columns
    sliced out of the [N, F] table (see ``_route_samples``): while the
    slices read no more columns than the table has. A slice reads its
    column where the table rests as F rows of N lanes, the eight
    columns of its sublane tile where it rests (8, 128)-tiled
    (``_rests_tiled``), and the 128 of its lane word where a multiple
    of 128 rests row-major (not measured; counted from the layout)."""
    reads = 128 if F % 128 == 0 else 8 if _rests_tiled(F) else 1
    return n_nodes * reads <= F


@jax.named_scope("gbdt.route")
def _route_samples(bins, node_ids, feat, bin_, n_nodes: int, dir_=None,
                   cat_mask=None, missing_bin: bool = False,
                   n_bins: int | None = None):
    """One level of sample routing: ``node_ids*2 + go_right``, where
    ``go_right`` is ``bins[i, feat[n]] > bin_[n]`` for numeric features,
    ``== bin_[n]`` for categorical ones (never at the freeze sentinel
    B-1), and the node's learned default direction ``dir_`` for the
    missing bucket (bin 0) under ``missing_bin``; everything is selected
    by exact selects, nothing gathered.

    A level splits on at most ``n_nodes`` columns. Where those are few
    beside the table (``route_sliced``, from the shapes alone) each is
    taken out by a ``dynamic_slice`` and a sample's value, threshold,
    direction and kind are chosen among the nodes' by its node id, all
    in the slices' own [N, 1] shape, so that one fusion reads the
    slices and nothing else of the table. The barrier before the last
    reshape holds that shape: without it XLA hoists the reshape onto
    every slice and writes each column out first (as it does for a
    concatenation of the slices), and ``jnp.take(bins, feat, axis=1)``
    lowers to gathers beside a copy of the table. Elsewhere a sample's
    feature number is selected among all F columns and every column is
    read, which is the cheaper read once a level needs most of them.
    Measured (my chip runs, PR 45), the scope's sum over a tree of
    depth 6 in the train step: 1,183,747 x 968 in (8, 128) tiles, 37.37
    ms whole at every level against 3.29 sliced at every level (a
    column costs its tile row); 11,000,000 x 28 as rows of N lanes,
    13.79 ms whole against 9.38, 7.26, 5.21 and 12.93 with the first
    three, four, five and all six levels sliced (32 nodes ask for more
    columns than the table has, and XLA cuts a level of 32 slices into
    several fusions with masks and broadcasts written out between
    them). One level alone, which pays the reshape that the step's
    sliced levels share, at 1, 2, 4, 8, 16, 32 nodes: 6.08, 6.24, 6.24,
    6.24, 6.25, 6.27 ms whole against 0.14, 0.19, 0.30, 0.51, 0.93,
    2.13 sliced at the first shape, 2.01, 2.51, 2.51, 2.51, 2.76, 3.07
    against 0.61, 0.69, 1.01, 1.52, 2.77, 14.57 at the second.

    (A fused Pallas version was measured 2x SLOWER — 13.3 vs 7.6 ms
    standalone at N=1M — a kernel block of [tile, F] pins F=28 on the
    128-wide lane dimension at 22% occupancy, while XLA is free to lay
    the N axis across lanes and to fuse the selects into neighboring
    passes.)"""
    sliced = route_sliced(n_nodes, bins.shape[1])
    if sliced:
        ids, select = node_ids[:, None], _chain_select  # the slices' shape
        v = select([lax.dynamic_slice_in_dim(bins, feat[j], 1, axis=1)
                    for j in range(n_nodes)], ids, n_nodes)
    else:
        ids, select = node_ids, _onehot_select
        v = _onehot_row_select(bins, select(feat, ids, n_nodes))
    nb = select(bin_, ids, n_nodes)
    go_right = v > nb
    if missing_bin:
        nd = select(dir_, ids, n_nodes)
        go_right = jnp.where(v == 0, nd > 0, go_right)
    if cat_mask is not None:
        # is this sample's node split on a categorical feature?
        node_cat = jnp.asarray(cat_mask)[feat]        # [n_nodes] bool
        sc = select(node_cat.astype(jnp.int32), ids, n_nodes) > 0
        go_right = jnp.where(sc, (v == nb) & (nb != n_bins - 1),
                             go_right)
    out = ids * 2 + go_right.astype(jnp.int32)
    return lax.optimization_barrier(out)[:, 0] if sliced else out


@jax.named_scope("gbdt.best_splits")
def best_splits(hist_g, hist_h, reg_lambda: float, feat_mask=None,
                min_child_hessian: float = 0.0, cat_mask=None,
                missing_bin: bool = False):
    """Regularized best split per node.

    hist_*: [n_nodes, F, B]. Returns (feat [n_nodes], bin [n_nodes],
    gain [n_nodes], dir [n_nodes]) — numeric features split "bin <= b
    goes left"; features flagged in ``cat_mask`` ([F] bool, optional)
    split "bin == b goes right". ``dir`` is the learned default
    direction for the missing bucket (1 = right; all zeros unless
    ``missing_bin``): with ``missing_bin`` every numeric candidate is
    scored with bin 0's G/H on the left AND on the right, and the
    better variant wins — ytk-learn's sparsity-aware split. ``feat_mask``
    ([F] bool, optional) disqualifies masked-out features (column
    sampling): their gain is -inf so they can never win; candidates
    whose left or right hessian sum < ``min_child_hessian`` are
    likewise disqualified.
    """
    cg = jnp.cumsum(hist_g, axis=-1)        # G_left for split at bin b
    ch = jnp.cumsum(hist_h, axis=-1)
    Gt = cg[..., -1:]
    Ht = ch[..., -1:]
    lam = reg_lambda
    mch = min_child_hessian

    def score(G, H):
        return (G * G) / (H + lam)

    def variant_gain(GL, HL):
        """Gain of a (left, right) partition given the left sums.

        A 0/0 score (empty child at reg_lambda == 0) is NaN; it must be
        disqualified HERE, per variant — NaN would propagate through the
        jnp.maximum combining missing-left/right variants (killing a
        valid sibling variant) and would win jnp.argmax (freezing a node
        with good splits elsewhere). The numpy oracle's ``gain > best``
        ignores NaN the same way; an all-degenerate node still freezes
        via gain = -inf."""
        g = score(GL, HL) + score(Gt - GL, Ht - HL) - score(Gt, Ht)
        if mch > 0.0:
            ok = (HL >= mch) & (Ht - HL >= mch)
            g = jnp.where(ok, g, -jnp.inf)
        return jnp.where(jnp.isnan(g), -jnp.inf, g)

    gain = variant_gain(cg, ch)             # missing (bin 0) left
    direction = jnp.zeros(gain.shape, bool)
    if missing_bin:
        # move bin 0 (the reserved missing bucket) to the right child
        gain_r = variant_gain(cg - hist_g[..., :1], ch - hist_h[..., :1])
        # at b=0 the right-variant's left child is empty BY CONSTRUCTION
        # (bin 0 moved right leaves nothing <= 0): never a split, and at
        # reg_lambda=0 its 0/0 NaN would otherwise win argmax in EVERY
        # node and freeze the whole tree
        gain_r = gain_r.at[..., 0].set(-jnp.inf)
        direction = gain_r > gain
        gain = jnp.maximum(gain, gain_r)
    if cat_mask is not None:
        # equality split: category b alone goes right
        cat_gain = variant_gain(Gt - hist_g, Ht - hist_h)
        cat = jnp.asarray(cat_mask)[None, :, None]
        gain = jnp.where(cat, cat_gain, gain)
        direction = jnp.where(cat, False, direction)
    # splitting at the last bin sends everything left (numeric) /
    # doubles as the freeze sentinel (categorical) — never a candidate
    gain = gain.at[..., -1].set(-jnp.inf)
    if feat_mask is not None:
        gain = jnp.where(feat_mask[None, :, None], gain, -jnp.inf)
    flat = gain.reshape(gain.shape[0], -1)
    best = jnp.argmax(flat, axis=-1)
    B = hist_g.shape[-1]
    dir_flat = direction.reshape(direction.shape[0], -1)
    best_dir = jnp.take_along_axis(dir_flat, best[:, None], axis=-1)[:, 0]
    return ((best // B).astype(jnp.int32), (best % B).astype(jnp.int32),
            jnp.take_along_axis(flat, best[:, None], axis=-1)[:, 0],
            best_dir.astype(jnp.int32))


# ----------------------------------------------------------------------
# one boosting round (tree build) — per-shard body
# ----------------------------------------------------------------------
def hist_level_nodes(depth: int) -> list[int]:
    """The nodes whose histograms each level of a tree builds from the
    rows: the root, then the left children of the level above (a right
    child's is its parent's less its sibling's)."""
    return ([1] + [2 ** (d - 1) for d in range(1, depth)])[:depth]


def _build_tree(bins, g, h, cfg: GBDTConfig, axis_name, interpret,
                feat_mask=None):
    """Grow one tree from per-sample gradients/hessians; the per-level
    histogram psum over ``axis_name`` is THE distributed allreduce.
    Returns (delta [N] — the learning-rate-scaled leaf value each sample
    receives — and the tree)."""
    N = bins.shape[0]
    node_ids = jnp.zeros((N,), dtype=jnp.int32)
    n_internal = 2 ** cfg.depth - 1
    tree_feat = jnp.zeros((n_internal,), dtype=jnp.int32)
    tree_bin = jnp.zeros((n_internal,), dtype=jnp.int32)
    tree_dir = jnp.zeros((n_internal,), dtype=jnp.int32)
    cat_mask = cfg._cat_mask()

    def reduced_histograms(ids, n):
        """Local histogram build + the distributed allreduce (psum)."""
        a, b = build_histograms(bins, g, h, ids, n, cfg,
                                interpret=interpret)
        if axis_name is not None:
            a = lax.psum(a, axis_name)      # THE histogram allreduce
            b = lax.psum(b, axis_name)
        return a, b

    level_start = 0
    prev_hg = prev_hh = None
    # depth static -> unrolled
    for d, n_half in enumerate(hist_level_nodes(cfg.depth)):
        # a name a level (metadata only): a device trace reads a kernel
        # call as gbdt.level.5/gbdt.hist/mp4j_hist, and the scopes inside
        # keep their names
        with jax.named_scope(f"gbdt.level.{d}"):
            n_nodes = 2 ** d
            if d == 0:
                hg, hh = reduced_histograms(node_ids, n_nodes)
            else:
                # histogram-subtraction trick (the classic GBDT sibling
                # identity hist(parent) = hist(left) + hist(right)): build
                # only the LEFT children — samples in right nodes map to an
                # out-of-range sentinel id and contribute nothing — then
                # derive the right siblings from the previous level's
                # (already psum'd) parent histograms. Halves both the MXU
                # work and the allreduce bytes at every level below the
                # root. Precision caveat: the derived right child inherits
                # error RELATIVE TO ITS PARENT's magnitude (~5e-6 in the
                # bf16 hist modes), so a tiny right child's histogram is
                # noisier than a directly-built one; the hessian clamp
                # below keeps that noise from producing negative hessian
                # sums (which could cross H + reg_lambda through zero in
                # best_splits and crown a garbage split).
                left_ids = jnp.where(node_ids % 2 == 0, node_ids // 2,
                                     n_half)
                hl_g, hl_h = reduced_histograms(left_ids, n_half)
                hg = jnp.stack([hl_g, prev_hg - hl_g],
                               axis=1).reshape(n_nodes, *hl_g.shape[1:])
                hh = jnp.stack([hl_h, jnp.maximum(prev_hh - hl_h, 0.0)],
                               axis=1).reshape(n_nodes, *hl_h.shape[1:])
            prev_hg, prev_hh = hg, hh
            feat, bin_, gain, dir_ = best_splits(
                hg, hh, cfg.reg_lambda, feat_mask, cfg.min_child_hessian,
                cat_mask, cfg.missing_bin)
            # freeze any node whose best gain does not clear the threshold:
            # bin B-1 routes every sample left (v > B-1 is never true for
            # numeric, and categorical routing never goes right at B-1),
            # keeping the node whole. The ~(gain > thr) form also freezes
            # gain == 0 (empty/pure nodes would otherwise record a phantom
            # feat-0 "split", poisoning feature_importance), gain == -inf
            # (no admissible candidate, e.g. min_child_hessian disqualified
            # everything), and NaN gains (0/0 at reg_lambda == 0).
            freeze = ~(gain > cfg.min_split_gain)
            bin_ = jnp.where(freeze, cfg.n_bins - 1, bin_)
            dir_ = jnp.where(freeze, 0, dir_)   # frozen: missing stays left
            at = (level_start,)
            tree_feat = lax.dynamic_update_slice(tree_feat, feat, at)
            tree_bin = lax.dynamic_update_slice(tree_bin, bin_, at)
            tree_dir = lax.dynamic_update_slice(tree_dir, dir_, at)
            # route samples: go right if bin value > split bin (gather-free,
            # see the routing performance note above)
            node_ids = _route_samples(bins, node_ids, feat, bin_, n_nodes,
                                      dir_, cat_mask, cfg.missing_bin,
                                      cfg.n_bins)
            level_start += n_nodes

    # leaf values from (all-reduced) leaf G/H
    n_leaves = 2 ** cfg.depth
    with jax.named_scope("gbdt.leaf"):
        leaf_g, leaf_h = _onehot_segment_sum2(g, h, node_ids, n_leaves)
        if axis_name is not None:
            leaf_g = lax.psum(leaf_g, axis_name)
            leaf_h = lax.psum(leaf_h, axis_name)
        leaf_val = -leaf_g / (leaf_h + cfg.reg_lambda)
        delta = cfg.learning_rate * _onehot_select(leaf_val, node_ids,
                                                   n_leaves)
    return delta, (tree_feat, tree_bin, tree_dir, leaf_val)


# ----------------------------------------------------------------------
# a leaf-wise pass reads its child's rows, not the table (ISSUE 54)
#
# A split's histogram is built from the smaller child's rows: 14,000 of
# 1,183,747 on average at 70 leaves on the Bosch table, where the
# kernel's pass over the table as it rests ([F, N], samples on the
# lanes) costs 48.8 ms whatever the node holds. So the grower keeps a
# second form of the table in which a row is one descriptor
# (``pack_rows``: the bins four to a 32-bit word, a row padded to whole
# 128-lane words so that it rests row-major; made once a job and only
# read after), finds the built child's row numbers from prefix counts of
# its mask (``_ranks`` once a split over all rows, ``_kth_rows`` a slot
# of the slab: compares and two row gathers, no sort and no scatter),
# gathers them into a slab of one static size, as often as the child
# needs, the tree's g and h by the same row numbers beside them, and
# hands each slab, unpacked, to the unchanged kernel. PERF.md section 6
# (PR 54) has the chip's prices of each piece and of the forms not
# taken.
# ----------------------------------------------------------------------
# Rows a slab: a child is read in ``ceil(rows / _SLAB_ROWS)`` slabs, so an
# empty child (a step that splits nothing, a shard that holds none of it)
# in none. One size and a loop, not a ladder of sizes behind a ``switch``:
# every size is one more Mosaic kernel to lower and compile in a job's
# set-up, and in the step a kernel call costs what its rows cost (0.085
# ms on 2,048 rows, 41 ns a row), so a ladder buys nothing a loop does
# not. My chip runs, PR 54, the Bosch leaf-wise cell, ``trees_per_s`` and
# warm ``setup_s`` (10.5 s before): seven sizes 2,048 to 131,072 2.86 and
# 25.7 s; 2,048 / 8,192 / 32,768 2.82 and 15.8; 2,048 / 8,192 3.14 and
# 12.4; one size of 2,048 3.08-3.14, 4,096 3.02-3.10, 8,192 2.98-2.99,
# each 7.7-7.8 s. Three in five of that table's built children hold
# under 2,048 rows, so a larger slab reads more rows that count for
# nothing (the kernel 89.5 ms a tree at 4,096, 99.7 at 8,192); a smaller
# one calls the kernel more often, and each call leaves two copies of
# its output (9.5 us each) that XLA gives no name, which the residual
# ``gbdt_grow_unscoped_ms_per_tree`` is asked to keep under 10 ms: 14.6
# at 2,048, 9.9 at 4,096, 8.3 at 8,192. So 8,192 is not the fastest
# size: it gives up 1-4% of ``trees_per_s`` to keep the unnamed time
# under that threshold, and it is fitted to this one table's children
# (2,048 is the size to take once those copies have a name).
_SLAB_ROWS = 8192
_RANK_LANES = 128   # rows a block of the prefix counts (a lane word)


def packed_shape(F: int, n_bins: int) -> tuple[int, int, int]:
    """``(bins a word, words of bins a row, words a row)`` of the
    table's second form: a bin in 8 bits while 256 bins allow (16, 32
    beyond), feature f in digit ``f // bin_words`` of word ``f %
    bin_words`` with ``bin_words`` in whole sublane tiles of 8, so that
    unpacking is ``per`` aligned blocks of rows one after the other;
    the row filled to whole 128-lane words (968 features: 4, 248, 256:
    1,024 B a row)."""
    def whole(n, unit):
        return -(-n // unit) * unit

    per = 4 if n_bins <= 2 ** 8 else 2 if n_bins <= 2 ** 16 else 1
    bin_words = whole(-(-F // per), 8)
    return per, bin_words, whole(bin_words, 128)


def pack_rows(bins, n_bins: int):
    """The second form of a shard's table: ``bins`` [N, F] int32 as
    [N, words] uint32 (``packed_shape``). A
    row is then one contiguous run of memory and one descriptor of a
    gather, where the table as it rests ([F, N]) has a row's cells F
    strides apart. The words are made where the table rests, blocks of
    its rows against one another, and transposed once: the layout
    constraint holds them [bin_words, N] row-major, without which XLA
    turns the whole table row-major first, 4.85 GB beside it (AOT for
    v5e, PR 54: 80M estimated cycles and 6.06 GB of temporaries against
    34M and 1.21). Under ``stage.place``: it is staging's work on the
    device, once a job."""
    from jax.experimental.layout import Layout, with_layout_constraint

    F = bins.shape[1]
    per, bin_words, words = packed_shape(F, n_bins)
    with jax.named_scope("stage.place"), jax.named_scope("gbdt.grow.pack"):
        cells, word = bins.T, None
        for k in range(per):
            part = cells[k * bin_words:min((k + 1) * bin_words, F)]
            part = jnp.pad(part, ((0, bin_words - part.shape[0]), (0, 0)))
            part = part << (k * (32 // per))
            word = part if word is None else word | part
        word = with_layout_constraint(
            lax.bitcast_convert_type(word, jnp.uint32),
            Layout(major_to_minor=(0, 1)))
        return jnp.pad(word, ((0, words - bin_words), (0, 0))).T


def _unpack_rows(slab, F: int, n_bins: int):
    """The bins [F, S] int32 of gathered rows ``slab`` [S, words]: the
    table's first form again for these rows, the samples on the lanes
    as the kernel reads them."""
    per, bin_words, _ = packed_shape(F, n_bins)
    bits = 32 // per
    word = slab.T[:bin_words]
    # digit k holds features k * bin_words and on: whole sublane tiles,
    # one after the other, the last digit's cut to the table's width
    return jnp.concatenate([
        ((word[:F - k * bin_words] >> (bits * k)) & (2 ** bits - 1)
         if per > 1 else word[:F]).astype(jnp.int32)
        for k in range(per) if k * bin_words < F], 0)


def _ranks(mask):
    """Prefix counts of ``mask`` [N] on three levels, int32: ``within``
    [blocks, lanes], a row's count of marked rows from the start of its
    block of ``_RANK_LANES`` rows to itself; ``upto`` [groups, lanes],
    a block's from the table's start to its own end, a group of blocks
    a row (the table's count past the last block); ``top`` [groups], a
    group's. One pass over the rows a split. A row of 128 is summed
    along itself on the MXU, against a triangle of ones (counts to 128
    are bf16 numbers and the sums f32's, to 2**24 rows exact): as a
    ``cumsum`` XLA makes a ``reduce_window`` of it that takes 0.23 ms
    at 1,183,747 rows, a tenth of a split (my chip run, PR 54)."""
    i32 = jnp.int32
    N = mask.shape[0]
    blocks = -(-N // _RANK_LANES)
    groups = -(-blocks // _RANK_LANES)
    lane = jnp.arange(_RANK_LANES)
    upper = (lane[:, None] <= lane[None, :]).astype(jnp.bfloat16)

    def along(rows):
        return jnp.dot(rows.astype(jnp.bfloat16), upper,
                       preferred_element_type=jnp.float32).astype(i32)

    within = along(jnp.pad(mask, (0, blocks * _RANK_LANES - N)).reshape(
        blocks, _RANK_LANES))
    ends = along(jnp.pad(within[:, -1], (0, groups * _RANK_LANES - blocks)
                         ).reshape(groups, _RANK_LANES))
    top = jnp.cumsum(ends[:, -1], dtype=i32)
    return within, ends + (top - ends[:, -1])[:, None], top


def _kth_rows(counts, ranks, N: int):
    """The row numbers of the marked rows of ranks ``ranks`` [S] (0 the
    first) from ``_ranks``' ``counts``. A rank's group is the count of
    groups that end at or under it, its block the count of its group's
    blocks that do, its lane the count of its block's lanes at or under
    what is left of it: compares against a short vector and against two
    gathered rows of 512 B, and nothing is sorted, searched or
    scattered. A rank past the last marked row gives some row under
    N."""
    i32 = jnp.int32
    within, upto, top = counts
    ranks = ranks[:, None]
    under = top[None, :] <= ranks                       # [S, groups]
    group = jnp.minimum(under.sum(1, dtype=i32), top.shape[0] - 1)
    ends = upto[group]                                  # [S, lanes]
    ended = ends <= ranks
    block = jnp.minimum(group * _RANK_LANES + ended.sum(1, dtype=i32),
                        within.shape[0] - 1)
    # the marked rows before the block: the greatest count at or under
    # the rank, which is the block's before it
    before = jnp.maximum(jnp.where(under, top[None, :], 0).max(1),
                         jnp.where(ended, ends, 0).max(1))
    lane = (within[block] <= ranks - before[:, None]).sum(1, dtype=i32)
    return jnp.minimum(block * _RANK_LANES + lane, N - 1)


def _pick_leaf(open_, gain, heap, none):
    """The slot of the open leaf to split next: the greatest gain, ties
    to the lowest heap index (``none``, past every heap index, where no
    leaf is open: the pick is then slot 0 and the caller's ``open_.any()``
    says that nothing is to be split)."""
    best = jnp.max(jnp.where(open_, gain, -jnp.inf))
    return jnp.argmin(jnp.where(open_ & (gain == best), heap, none))


def _grow_tree(bins, g, h, cfg: GBDTConfig, axis_name, interpret,
               feat_mask=None, packed=None):
    """Grow one tree leaf by leaf (``grow_policy="loss"``): of all open
    leaves the one whose best split gains most is split, ``max_leaves -
    1`` times over, and no leaf lies deeper than ``depth``. Takes what
    ``_build_tree`` takes, and the table's second form ``packed``
    (``pack_rows``; made here where none comes), and returns
    ``_build_tree``'s ``(delta, tree)`` with the counts of the work
    behind them: ``(delta [N], tree, (built [max_leaves] int32, splits
    int32, read [max_leaves] int32))``.

    The unit of work is one split, each waiting for the last, so the
    splits are a loop of the program and the open leaves its carry: a
    row's leaf is a slot of a table of ``max_leaves`` slots that holds
    each leaf's heap index, depth, best (gain, feature, bin, direction)
    and its own [F, B] histograms. A step picks the open leaf of
    greatest gain among those above ``depth`` whose gain clears
    ``min_split_gain`` (ties: the lowest heap index), writes its split
    at its heap index, routes that leaf's rows by ``_route_samples``'
    rule, builds the histogram of the child with fewer rows (summed over
    ``axis_name``, so that every shard builds the same child; ties: the
    left) from its rows alone, and takes its sibling's as the parent's
    less it, the hessians clamped as ``_build_tree`` clamps them. The
    left child keeps the slot, the right child takes slot ``step + 1``.
    Where no leaf is open the step changes nothing (the trip count is
    static) and its child is empty. ``built`` holds the rows whose
    histogram was built from rows: the tree's rows for the root, then
    the smaller child's for every step.

    The root's pass reads the table as it rests. A split's reads the
    child's rows gathered from ``packed`` into slabs of ``_SLAB_ROWS``
    rows, g and h by the same row numbers, as many slabs as hold this
    shard's rows of the child (a shard counts its own, the sum over
    ``axis_name`` standing after the loop), every slot past the child's
    last row on the sentinel id with g = h = 0, and the kernel is the
    one ``build_histograms`` calls for one node, on a slab's rows. ``read`` holds the rows the passes
    read: the tree's for the root, the slabs' for every step.

    The tree is ``_build_tree``'s level-order heap of depth ``depth``:
    a node that was never split is frozen the way ``_build_tree``
    freezes one (bin ``B - 1``, direction 0: every row goes left), so a
    leaf at depth k is found, by every reader of such a tree, at its
    level-local index shifted left by ``depth - k``, where its value
    stands."""
    N = bins.shape[0]
    L, depth, B, F = cfg.max_leaves, cfg.depth, cfg.n_bins, cfg.n_features
    n_internal = 2 ** depth - 1
    cat_mask = cfg._cat_mask()
    i32 = jnp.int32

    def psum(x):
        return x if axis_name is None else lax.psum(x, axis_name)

    def slab_histogram(counts, count):
        """This shard's histogram of the ``count`` rows that ``counts``
        (``_ranks``) mark, a slab at a time."""
        def fill(i, sums):
            with jax.named_scope("gbdt.grow.book"), \
                    jax.named_scope("gbdt.grow.compact"):
                ranks = i * i32(_SLAB_ROWS) + jnp.arange(_SLAB_ROWS,
                                                         dtype=i32)
                live = ranks < count
                rows = _kth_rows(counts, ranks, N)
                slab_bins = _unpack_rows(packed[rows], F, B)
                slab_g, slab_h = jnp.where(live, gh[:, rows], 0.0)
                ids = jnp.where(live, i32(0), i32(1))
            (a,), (b,) = build_histograms(slab_bins.T, slab_g, slab_h, ids,
                                          1, cfg, interpret=interpret)
            with jax.named_scope("gbdt.grow.book"):
                return sums[0] + a, sums[1] + b

        none = jnp.zeros((F, B), jnp.float32)
        if axis_name is not None:
            none = lax.pcast(none, axis_name, to="varying")
        return lax.fori_loop(i32(0), -(-count // i32(_SLAB_ROWS)), fill,
                             (none, none))

    def search(hg, hh):
        return best_splits(hg, hh, cfg.reg_lambda, feat_mask,
                           cfg.min_child_hessian, cat_mask,
                           cfg.missing_bin)

    slot = jnp.arange(L, dtype=i32)
    node = jnp.arange(n_internal, dtype=i32)
    row_leaf = jnp.zeros((N,), i32)         # every row in the root's slot
    if axis_name is not None:
        row_leaf = lax.pcast(row_leaf, axis_name, to="varying")
    root_g, root_h = (psum(a)[0] for a in build_histograms(
        bins, g, h, row_leaf, 1, cfg, interpret=interpret))
    feat, bin_, gain, dir_ = search(root_g[None], root_h[None])
    with jax.named_scope("gbdt.grow.book"):
        first = slot == 0
        leaves = (jnp.zeros((L,), i32), jnp.zeros((L,), i32),   # heap, depth
                  jnp.where(first, gain[0], -jnp.inf),
                  *(jnp.where(first, v[0], 0) for v in (feat, bin_, dir_)))
        hists = tuple(jnp.zeros((L, *root.shape), root.dtype).at[0].set(root)
                      for root in (root_g, root_h))
        rows = N if axis_name is None else N * lax.psum(1, axis_name)
        built = jnp.where(first, i32(rows), 0)
        with jax.named_scope("gbdt.grow.compact"):
            if packed is None:
                packed = pack_rows(bins, B)
            # this tree's g and h side by side, the samples on the
            # lanes: a slab's are gathered by its row numbers. (In the
            # second form's spare words a row's descriptor would bring
            # them, but writing two columns of it costs a tree what
            # these gathers cost, 5.9 against 5.4 ms on the Bosch table,
            # and makes the second form a donated result of every step:
            # my chip run, PR 54, call F.)
            gh = jnp.stack([g, h])
    tree = (jnp.zeros((n_internal,), i32), jnp.full((n_internal,), B - 1, i32),
            jnp.zeros((n_internal,), i32))

    def split(step, carry):
        row_leaf, leaves, (hist_g, hist_h), tree, built, splits, read = carry
        heap, level, gain, feat, bin_, dir_ = leaves
        new = (step + 1).astype(i32)
        with jax.named_scope("gbdt.grow.pick"):
            # the ~(gain > thr) form of _build_tree: NaN and -inf stay shut
            open_ = (level < depth) & (gain > cfg.min_split_gain)
            s = _pick_leaf(open_, gain, heap, n_internal).astype(i32)
            go = open_.any()
            at, f, b, d = heap[s], feat[s], bin_[s], dir_[s]
        routed = _route_samples(bins, jnp.zeros_like(row_leaf), f[None],
                                b[None], 1, d[None], cat_mask,
                                cfg.missing_bin, B)
        with jax.named_scope("gbdt.grow.book"):
            here = go & (node == at)
            tree = tuple(jnp.where(here, v, t)
                         for v, t in zip((f, b, d), tree))
            in_leaf = go & (row_leaf == s)
            right = in_leaf & (routed > 0)
            mine = jnp.stack([in_leaf.sum(dtype=i32), right.sum(dtype=i32)])
            n_in, n_right = psum(mine)
            small_right = n_right < n_in - n_right      # ties: the left
            with jax.named_scope("gbdt.grow.compact"):
                # this shard's rows of the built child, and their slabs
                counts = _ranks(in_leaf & (right == small_right))
                count = jnp.where(small_right, mine[1], mine[0] - mine[1])
                slab_rows = psum(-(-count // _SLAB_ROWS) * _SLAB_ROWS)
        # THE histogram allreduce, after the loop: the shards' trip
        # counts differ
        small_g, small_h = (psum(a) for a in slab_histogram(counts, count))
        with jax.named_scope("gbdt.grow.book"):
            parent_g, parent_h = hist_g[s], hist_h[s]
            other_g = parent_g - small_g
            other_h = jnp.maximum(parent_h - small_h, 0.0)
            left_g = jnp.where(small_right, other_g, small_g)
            left_h = jnp.where(small_right, other_h, small_h)
            right_g = jnp.where(small_right, small_g, other_g)
            right_h = jnp.where(small_right, small_h, other_h)
        found = search(jnp.stack([left_g, right_g]),
                       jnp.stack([left_h, right_h]))
        with jax.named_scope("gbdt.grow.book"):
            is_left, is_right = go & (slot == s), go & (slot == new)

            def booked(old, for_left, for_right):
                return jnp.where(is_left, for_left,
                                 jnp.where(is_right, for_right, old))

            c_feat, c_bin, c_gain, c_dir = found
            leaves = (booked(heap, 2 * at + 1, 2 * at + 2),
                      booked(level, level[s] + 1, level[s] + 1),
                      booked(gain, c_gain[0], c_gain[1]),
                      booked(feat, c_feat[0], c_feat[1]),
                      booked(bin_, c_bin[0], c_bin[1]),
                      booked(dir_, c_dir[0], c_dir[1]))
            # a step that split nothing leaves slot ``s`` as it was and
            # slot ``new`` shut (its gain stays -inf and no row is in it).
            # The barrier holds the children's histograms as arrays of
            # their own: without it XLA reads the parent's slot inside
            # the fusions that write the table, and copies the whole
            # table (139 MB at 70 x 968 x 256) in and out of every step
            # to keep the two apart.
            written = lax.optimization_barrier(
                (jnp.where(go, left_g, parent_g), right_g,
                 jnp.where(go, left_h, parent_h), right_h))
            hists = tuple(
                lax.dynamic_update_index_in_dim(
                    lax.dynamic_update_index_in_dim(hist, left, s, 0),
                    right, new, 0)
                for hist, left, right in ((hist_g, *written[:2]),
                                          (hist_h, *written[2:])))
            row_leaf = jnp.where(right, new, row_leaf)
            built = jnp.where(slot == new, jnp.minimum(n_right,
                                                       n_in - n_right), built)
            splits = splits + go.astype(i32)
            read = jnp.where(slot == new, slab_rows, read)
        return row_leaf, leaves, hists, tree, built, splits, read

    # (the root's pass read the rows it built from: ``read`` starts as
    # ``built`` does)
    row_leaf, leaves, _, tree, built, splits, read = lax.fori_loop(
        0, L - 1, split,
        (row_leaf, leaves, hists, tree, built, i32(0), built))

    # leaf values from (all-reduced) leaf G/H, as _build_tree ends: on
    # each row's leaf at depth ``depth``
    n_leaves = 2 ** depth
    with jax.named_scope("gbdt.leaf"):
        heap, level = leaves[:2]
        deepest = (heap + 1 - (1 << level)) << (depth - level)
        node_ids = _onehot_select(deepest, row_leaf, L)
        leaf_g, leaf_h = _onehot_segment_sum2(g, h, node_ids, n_leaves)
        if axis_name is not None:
            leaf_g = lax.psum(leaf_g, axis_name)
            leaf_h = lax.psum(leaf_h, axis_name)
        leaf_val = -leaf_g / (leaf_h + cfg.reg_lambda)
        delta = cfg.learning_rate * _onehot_select(leaf_val, node_ids,
                                                   n_leaves)
    return delta, (*tree, leaf_val), (built, splits, read)


def _sampling_masks(rng_key, cfg: GBDTConfig, N: int, axis_name):
    """Per-tree stochastic-boosting masks (None when inactive).

    Returns (sample_scale [N] f32 | None, feat_mask [F] bool | None).
    The feature mask is derived from the key alone, so it is identical
    on every shard; the sample mask folds in the shard index so shards
    draw independent keeps. Kept samples are scaled 1/subsample to keep
    gradient sums unbiased; at least one feature always survives."""
    sample_scale = None
    feat_mask = None
    if rng_key is None:
        return sample_scale, feat_mask
    if cfg.colsample < 1.0:
        keep = jax.random.bernoulli(jax.random.fold_in(rng_key, 1),
                                    cfg.colsample, (cfg.n_features,))
        # all-dropped draw: rescue a UNIFORMLY RANDOM feature (a fixed
        # index would bias the ensemble toward it at small colsample)
        rescue = jax.random.randint(jax.random.fold_in(rng_key, 3), (),
                                    0, cfg.n_features)
        fallback = (jnp.arange(cfg.n_features) == rescue) & ~keep.any()
        feat_mask = keep | fallback
    if cfg.subsample < 1.0:
        k = jax.random.fold_in(rng_key, 2)
        if axis_name is not None:
            k = jax.random.fold_in(k, lax.axis_index(axis_name))
        keep = jax.random.bernoulli(k, cfg.subsample, (N,))
        sample_scale = keep.astype(jnp.float32) / cfg.subsample
    return sample_scale, feat_mask


def train_tree_shard(bins, y, preds, cfg: GBDTConfig, axis_name=None,
                     weights=None, interpret=None, rng_key=None):
    """One boosting round on this shard's samples. Returns
    (new_preds, tree): :func:`_train_tree_round` without the grower's
    counts."""
    return _train_tree_round(bins, y, preds, cfg, axis_name, weights,
                             interpret, rng_key)[:2]


def _train_tree_round(bins, y, preds, cfg: GBDTConfig, axis_name=None,
                      weights=None, interpret=None, rng_key=None,
                      packed=None):
    """One boosting round on this shard's samples. Returns
    (new_preds, tree, grown).

    ``cfg.grow_policy`` chooses the grower: ``_build_tree`` ("level")
    or ``_grow_tree`` ("loss"), which everything below reaches alike.
    ``grown`` is what the grower counted: nothing, ``()``, for a
    level-wise tree, whose work follows from its shape; ``(built
    [max_leaves], splits, read [max_leaves])`` for a leaf-wise one
    (``_grow_tree``), with a leading class axis under softmax.
    ``packed`` is the leaf-wise grower's second form of the table
    (``pack_rows``; None for a level-wise tree, which has none).

    ``weights`` ([N] f32, default all-ones) scales each sample's
    gradient/hessian contribution — the driver uses weight 0 to neutralize
    shard-padding rows so padded and unpadded runs are bit-equivalent.
    ``rng_key`` drives per-tree stochastic boosting when
    cfg.subsample/colsample < 1 (no key -> deterministic full-data
    trees regardless of the rates).

    Scalar objectives ("squared", "logistic"): preds/y are [N]; one tree
    is grown; tree = (feats [nodes], bins [nodes], leaf values
    [2^depth]) in level-order heap layout. "softmax" (multiclass, the
    ytk-learn classification objective): preds are margins [N, C], y is
    integer class labels [N]; one tree is grown PER CLASS against the
    diagonal softmax g/h (g_c = p_c - 1[y=c], h_c = p_c (1 - p_c));
    tree = a C-tuple of per-class trees.
    """
    sample_scale, feat_mask = _sampling_masks(rng_key, cfg,
                                              bins.shape[0], axis_name)
    if sample_scale is not None:
        weights = (sample_scale if weights is None
                   else weights * sample_scale)
    if cfg.grow_policy == "loss":
        grow = partial(_grow_tree, packed=packed)
    else:
        def grow(*args):
            return (*_build_tree(*args), ())

    if cfg.loss == "softmax":
        C = cfg.n_classes
        p = jax.nn.softmax(preds, axis=1)          # [N, C]
        trees = []
        deltas = []
        counts = []
        for c in range(C):                         # C static -> unrolled
            onehot_y = (y.astype(jnp.int32) == c).astype(jnp.float32)
            g = p[:, c] - onehot_y
            h = p[:, c] * (1.0 - p[:, c])
            if weights is not None:
                g = g * weights
                h = h * weights
            delta, tree, grown = grow(bins, g, h, cfg, axis_name,
                                      interpret, feat_mask)
            deltas.append(delta)
            trees.append(tree)
            counts.append(grown)
        with jax.named_scope("gbdt.leaf"):
            return (preds + jnp.stack(deltas, axis=1), tuple(trees),
                    tuple(jnp.stack(c) for c in zip(*counts)))

    # gradient/hessian of the scalar objective at the current margin
    if cfg.loss == "logistic":
        p = jax.nn.sigmoid(preds)
        g = p - y
        h = p * (1.0 - p)
    else:  # squared error: g = pred - y, h = 1
        g = preds - y
        h = jnp.ones_like(preds)
    if weights is not None:
        g = g * weights
        h = h * weights
    delta, tree, grown = grow(bins, g, h, cfg, axis_name, interpret,
                              feat_mask)
    with jax.named_scope("gbdt.leaf"):
        return preds + delta, tree, grown


def predict_tree(bins, tree, cfg: GBDTConfig):
    """Route samples through one tree (level-order heap layout)."""
    tree_feat, tree_bin, tree_dir, leaf_val = tree
    cat_mask = cfg._cat_mask()
    N = bins.shape[0]
    node = jnp.zeros((N,), dtype=jnp.int32)   # level-local node index
    level_start = 0
    for d in range(cfg.depth):
        n_nodes = 2 ** d
        level_feat = lax.dynamic_slice(tree_feat, (level_start,),
                                       (n_nodes,))
        level_bin = lax.dynamic_slice(tree_bin, (level_start,), (n_nodes,))
        level_dir = lax.dynamic_slice(tree_dir, (level_start,), (n_nodes,))
        node = _route_samples(bins, node, level_feat, level_bin, n_nodes,
                              level_dir, cat_mask, cfg.missing_bin,
                              cfg.n_bins)
        level_start += n_nodes
    return _onehot_select(leaf_val, node, 2 ** cfg.depth)


# ----------------------------------------------------------------------
# batch scoring: all trees are known, so the order is free
#
# Training routes a level at a time because tree t+1 waits for tree t.
# Scoring an ensemble that way, with every level reading the whole table
# as routing did until PR 45, moves trees x depth x table through HBM:
# 13.75 TB for 500 trees of depth 6 on 1,183,748 x 968 (40.5 s a job
# with the transfer, my chip run, PR 30); reading a level's split
# columns alone (``_route_samples``) it is still 3,000 passes over the
# rows, one after the other. Here the rows are taken in chunks, and for
# a chunk the trees in groups: one MXU matmul of the chunk against
# the group's one-hot of split features selects the bins every node of
# every tree of the group asks for (exact: one term of a sum is nonzero,
# and a bin digit 0..255 is a bf16 number), every node is decided at
# once in the matmul's output, and the leaf is found from the decisions
# by selects from the deepest level up. The table is read once a job, a
# chunk's bf16 copy once a group; nothing is gathered. Rows rest on the
# lanes throughout, as the table itself does on the chip ([F, N]), so
# nothing is transposed either (0.61 s a job, staging included).
# ----------------------------------------------------------------------
_SCORE_TREE_GROUP = 16      # trees a matmul selects for (x 2**depth rows)
_SCORE_ROW_CHUNK = 2 ** 17  # rows a chunk: bounds the [nodes, rows] select
# A chunk's rows rest on the lanes: one that is not whole 128-lane words
# is scored with empty rows after it. Left as it came, XLA rests the
# chunk's [1, rows] sums rows-major and the loop over the tree groups
# takes as long for a fourth of the rows (my chip runs, PR 52, the
# scoring program alone: the last piece's 8,708 new rows of the Bosch
# table 15.17 ms, its group loop 14.65 against 14.93 for a whole piece
# of 34,560; filled to 8,832 rows 4.44 ms; the float file's last 4,100
# rows 7.13 -> 2.65 as 4,224; the same bits).
_SCORE_LANES = 128


def score_group_size(n_trees: int, n_classes: int = 1) -> int:
    """Rounds a group of the scoring program holds: the ensemble in the
    fewest groups of at most ``_SCORE_TREE_GROUP`` trees (a softmax
    round is ``n_classes`` trees), evened out so that the last group is
    not mostly padding."""
    most = max(1, _SCORE_TREE_GROUP // n_classes)
    groups = max(1, -(-n_trees // most))
    return max(1, -(-n_trees // groups))


def score_row_chunks(n_rows: int) -> tuple[int, int]:
    """(rows a chunk, chunks) the scoring program walks ``n_rows`` rows
    of a shard in: the fewest chunks of at most ``_SCORE_ROW_CHUNK``
    rows, of equal length in whole 128-lane words. The last chunk
    starts early rather than run over the end, and rescoring the few
    rows it shares with the one before gives them the same margins."""
    chunks = max(1, -(-n_rows // _SCORE_ROW_CHUNK))
    if chunks == 1:
        return n_rows, 1
    rows = -(-n_rows // chunks)
    return min(n_rows, -(-rows // 128) * 128), chunks


def _bin_digits(n_bins: int) -> int:
    """Base-256 digits a bin id takes: each is exact in bf16."""
    return max(1, -(-(int(n_bins) - 1).bit_length() // 8))


def _score_group(digits, group, out, cfg: GBDTConfig):
    """Add one group's trees to ``out`` ([C, R] f32 margins of a chunk),
    in tree order. ``digits``: the chunk's bins as bf16 [F, R] arrays,
    least significant first; ``group``: (feat, bin, dir, leaf), each
    [2**depth, G, C]: node (level order, one unused slot) or leaf, then
    round, then class. Rows rest on the lanes and nodes are the major
    axis throughout, so a level is a run of whole registers and a
    node's two children are every other one."""
    feat, bin_, dir_, leaf = group
    width, G, C = feat.shape
    F, R = digits[0].shape
    with jax.named_scope("gbdt.score.select"):
        picks = feat.reshape(-1, 1) == jnp.arange(F, dtype=feat.dtype)
        onehot = picks.astype(jnp.bfloat16)
        v = None
        for k, digit in enumerate(digits):
            # f32 accumulation of one nonzero term; bf16 holds 0..255
            part = jnp.dot(onehot, digit,
                           preferred_element_type=jnp.bfloat16
                           ).astype(jnp.float32)
            v = part if v is None else v + part * float(256 ** k)
    with jax.named_scope("gbdt.score.walk"):
        def nodes(a):
            return a.reshape(-1, 1).astype(jnp.float32)

        nb = nodes(bin_)
        right = v > nb
        if cfg.missing_bin:
            right = jnp.where(v == 0, nodes(dir_) > 0, right)
        cat_mask = cfg._cat_mask()
        if cat_mask is not None:
            node_cat = (picks & jnp.asarray(cat_mask)).any(1, keepdims=True)
            right = jnp.where(node_cat, (v == nb) & (nb != cfg.n_bins - 1),
                              right)
        right = right.reshape(width, G * C, R)
        # from the leaves up: a node's value is its right child's where
        # the row goes right there, its left child's elsewhere; only
        # selects, so a non-finite leaf reaches the rows that reach it.
        # One array a node, so that the whole walk is one elementwise
        # expression over the decisions and nothing in between is kept.
        leaf = leaf.reshape(width, G * C, 1)
        val = [leaf[j] for j in range(width)]
        for d in reversed(range(cfg.depth)):
            val = [jnp.where(right[2 ** d - 1 + j], val[2 * j + 1],
                             val[2 * j]) for j in range(2 ** d)]
        delta = jnp.broadcast_to(val[0], (G * C, R)).reshape(G, C, R)
        for g in range(G):
            out = out + cfg.learning_rate * delta[g]
    return out


def score_shard(rows, stacked, out, start, cfg: GBDTConfig, axis_name=None,
                edges=None, shift: bool = False, skip: int = 0):
    """Score ``rows`` [R, F], the rows a scoring call was handed (a
    piece of the input, or a shard that crossed in one transfer), from
    row ``skip`` on under the whole ensemble, and write their margins
    into ``out`` ([C, N] f32, C = 1 unless softmax; the other columns
    are passed on) from column ``start`` on: ``start`` only says where
    the margins go. ``stacked``: (feat, bin, dir, leaf), each [groups,
    2**depth, G, C], as ``_stack_trees`` lays them out. Per row the sum
    runs over the trees in their order, f32, as ``predict_tree`` after
    ``predict_tree`` would give it. The rows go through in
    ``score_row_chunks``' chunks; no table is sliced.

    With ``edges`` ([F, E] f32) the rows are floats, NaN where a cell is
    empty, and a chunk is binned where it rests
    (``binning._count_edges``: on a TPU the ``mp4j_bin`` kernel;
    ``shift``: the binner's reserved missing bucket) under the scope
    ``bin.transform``: its bins exist for the length of its turn, as
    the bf16 digits the select reads."""
    R, F = rows.shape
    C = out.shape[0]
    n_digits = _bin_digits(cfg.n_bins)
    row_chunk, chunks = score_row_chunks(R - skip)
    fill = -row_chunk % _SCORE_LANES     # empty rows, margins dropped

    def chunk_fn(out, c):
        at = jnp.minimum(c * row_chunk, R - skip - row_chunk)
        with jax.named_scope("gbdt.score.select" if edges is None
                             else "bin.transform"):
            # one chunk: the rows as they rest
            part = rows[skip:] if chunks == 1 else lax.dynamic_slice(
                rows, (skip + at, jnp.int32(0)), (row_chunk, F))
            if fill:
                part = jnp.pad(part, ((0, fill), (0, 0)))
            if edges is not None:
                # the counts come back as [F, rows] rest, so the
                # transposition below is the kernel's own undone
                part = _count_edges(part, edges, shift)
            part = part.T
            digits = [((part >> (8 * k)) & 255 if n_digits > 1 else part
                       ).astype(jnp.bfloat16) for k in range(n_digits)]
        acc = jnp.zeros((C, row_chunk + fill), jnp.float32)
        if axis_name is not None:
            acc = lax.pcast(acc, axis_name, to="varying")
        acc, _ = lax.scan(
            lambda acc, group: (_score_group(digits, group, acc, cfg), None),
            acc, stacked)
        with jax.named_scope("gbdt.score.walk"):
            return lax.dynamic_update_slice(
                out, acc[:, :row_chunk], (jnp.int32(0), start + at)), None

    out, _ = lax.scan(chunk_fn, out, jnp.arange(chunks, dtype=jnp.int32))
    return out


# ----------------------------------------------------------------------
# driver: full training under shard_map over a mesh
# ----------------------------------------------------------------------
class GBDTTrainer(DataParallelTrainer):
    """Data-parallel GBDT over a mesh (1-D or hierarchical)."""

    def __init__(self, cfg: GBDTConfig, mesh=None, n_devices=None):
        super().__init__(mesh=mesh, n_devices=n_devices)
        self.cfg = cfg
        self._step = None
        self._jobs = 0         # train() calls so far: the spans' ``job``
        self._score_jobs = 0   # predict() calls so far, likewise
        self._score_programs = {}   # (piece shape, per, rows, ..) -> program
        self._margin_step = None
        self._stacked_trees = None
        self.eval_history_: list[float] = []
        # the last job's leaf-wise work (grow_policy="loss"): ``splits``
        # and ``rows_built``, the rows whose histogram was built from
        # rows, and beside them the rows the passes read to build them
        # (the table's for a root, the slabs' for a split)
        self.grow_stats_: dict[str, int] = {}
        self.grow_rows_read_ = 0
        self._pack = None      # the leaf-wise job's packer (_build_pack)
        self.binner_ = None    # fitted by train_raw; rides save_model

    def _build_step(self):
        cfg = self.cfg
        axes = self.axes
        spec = P(axes)
        # the pallas kernel compiles only on TPU meshes; interpret it on
        # the virtual CPU meshes the tests and the driver dry-run use
        interpret = self.mesh.devices.flat[0].platform != "tpu"

        sampling = cfg.subsample < 1.0 or cfg.colsample < 1.0

        leafwise = cfg.grow_policy == "loss"

        # a leaf-wise step takes the table's second form (``_build_pack``)
        # after the key and only reads it; a level-wise step is the
        # program it was
        @partial(jax.shard_map, mesh=self.mesh,
                 in_specs=(spec, spec, spec, spec, P()) + (spec,) * leafwise,
                 out_specs=(spec, P(None), P()))
        def step(bins, y, preds, weights, key_data, *packed):
            rng_key = (jax.random.wrap_key_data(key_data)
                       if sampling else None)
            new_preds, tree, grown = _train_tree_round(
                bins[0], y[0], preds[0], cfg, axes, weights=weights[0],
                interpret=interpret, rng_key=rng_key,
                packed=packed[0] if leafwise else None)
            return new_preds[None], tree, grown

        if leafwise:
            # every pass of a leaf-wise tree builds one node
            levels = [1]
            grid = {"grow_policy": "loss", "max_leaves": cfg.max_leaves}
        else:
            levels = hist_level_nodes(cfg.depth)
            # how many of a tree's levels route on sliced columns
            grid = {"route_sliced_levels": sum(
                route_sliced(2 ** d, cfg.n_features)
                for d in range(cfg.depth))}
        if cfg.hist_mode == "pallas":
            # which grid the deepest level's kernel runs (it builds the
            # left children of the last split level), and into how many
            # high digits each level's kernel splits a bin
            from ytk_mp4j_tpu.ops.hist_kernel import (feature_blocks,
                                                      hist_radix)
            block, blocks = feature_blocks(
                cfg.n_features, cfg.n_bins, max(levels, default=1))
            grid.update(hist_feature_block=block,
                        hist_feature_blocks=blocks,
                        hist_radix=",".join(
                            str(hist_radix(n, cfg.n_bins)) for n in levels))
        with spans.span("mp4j.step.build", **grid):
            return jax.jit(step)

    def _build_pack(self):
        """The program that makes the table's second form on the mesh,
        a shard from its own rows (``pack_rows``): what a leaf-wise
        job's staging runs once the table is placed. The cells never
        cross the link a second time."""
        cfg, spec = self.cfg, P(self.axes)

        @partial(jax.shard_map, mesh=self.mesh, in_specs=spec,
                 out_specs=spec)
        def pack(bins):
            return pack_rows(bins[0], cfg.n_bins)

        with spans.span("mp4j.step.build", key="gbdt_grow_pack"):
            return jax.jit(pack)

    def shard_data(self, bins: np.ndarray, y: np.ndarray,
                   sample_weight: np.ndarray | None = None):
        """Pad + reshape host data to [n_shards, N/shard, ...] and place
        on the mesh. Padding rows get sample weight 0 so they contribute
        nothing to histograms or leaves (distributed results stay
        equivalent to single-device for any N — EXCEPT under
        cfg.subsample < 1, where each shard deliberately draws an
        independent keep mask, so distributed and single-device runs
        are different but equally valid stochastic realizations).
        ``sample_weight`` ([N] f32, optional — ytk-learn's instance
        weights) scales each sample's gradient/hessian contribution and
        composes with the padding zeros."""
        return (self.shard_bins(bins),
                *self._shard_vectors(y, sample_weight))

    def _shard_vectors(self, y: np.ndarray, sample_weight=None):
        """The labels, zero margins and weights of ``shard_data``, each
        placed beside the table's rows."""
        N = y.shape[0]
        (y,), per, w = self._pad_rows([y])
        margins = (y.shape + (self.cfg.n_classes,)
                   if self.cfg.loss == "softmax" else y.shape)
        with spans.span("mp4j.stage.prep", bytes=4 * int(np.prod(margins))):
            w[:N] *= self._stage_weights(sample_weight, N)
            preds = np.zeros(margins, np.float32)
        return (self._put_sharded(y, per), self._put_sharded(preds, per),
                self._put_sharded(w, per))

    def shard_bins(self, bins: np.ndarray):
        """Pad a binned table [N, n_features] to whole shards and place
        it on the mesh as [n_shards, N/shard, n_features], rows sharded
        (``_put_sharded``: a shard of 2**32 bytes or more crosses in
        row chunks). What ``train`` stages; the padding rows weigh
        nothing there. ``predict`` sends the same rows the same way and
        keeps no table (``_pieces``)."""
        self._check_bins_width(bins)
        (bins,), per, _ = self._pad_rows([bins], weights=False)
        return self._put_sharded(bins, per)

    def train(self, bins: np.ndarray, y: np.ndarray,
              n_trees: int | None = None, seed: int = 0,
              sample_weight: np.ndarray | None = None,
              eval_set=None, early_stopping_rounds: int | None = None):
        """Full boosting run; returns (trees, final margins [padded] —
        [N] for scalar objectives, [N, n_classes] for softmax).
        ``seed`` drives the per-tree stochastic-boosting masks when
        cfg.subsample/colsample < 1 (same seed -> same trees);
        ``sample_weight`` scales per-instance g/h contributions.

        ``eval_set=(bins_va, y_va)`` evaluates the objective's metric on
        held-out data after every round (margins updated incrementally,
        one tree per round — not a full re-predict); with
        ``early_stopping_rounds=k`` training stops after k rounds
        without improvement and the returned ensemble is truncated to
        the best round. The per-round metric history is available as
        ``self.eval_history_`` afterwards.
        """
        def stage(job):
            va = None
            if eval_set is not None:
                va_host = np.asarray(eval_set[0], np.int32)
                self._check_bins_width(va_host, "eval_set bins")
                va = (jnp.asarray(va_host), np.asarray(eval_set[1]))
            return (*self.shard_data(np.asarray(bins, np.int32),
                                     self._labels(y),
                                     sample_weight=sample_weight), va)

        return self._train(stage, n_trees, seed, early_stopping_rounds,
                           held_out=eval_set is not None)

    def _labels(self, y) -> np.ndarray:
        if self.cfg.loss == "softmax":
            return stage_softmax_labels(y, self.cfg.n_classes)
        return np.asarray(y, np.float32)

    def _train(self, stage, n_trees, seed, early_stopping_rounds,
               held_out: bool):
        """:meth:`train` from the point where the table is on the mesh,
        wherever it came from. ``stage(job)`` runs inside the job's
        ``mp4j.gbdt.stage`` span and returns ``(dbins, dy, dpreds, dw,
        va)``: the four arrays ``shard_data`` places, and the held-out
        ``(bins on a device, labels)`` or None (``held_out`` says which,
        before anything is staged). ``train`` hands over its
        host bins; ``train_raw_chunks`` and ``train_raw`` the bins they
        made on the mesh from the floats they staged."""
        if early_stopping_rounds is not None and not held_out:
            raise Mp4jError("early_stopping_rounds requires an eval_set")
        if self._step is None:
            self._step = self._build_step()
        job, self._jobs = self._jobs, self._jobs + 1
        with spans.span("mp4j.gbdt.stage", job=job):
            dbins, dy, dpreds, dw, va = stage(job)
            # a leaf-wise job's second form of the table, made on the
            # mesh from the first and read by its steps
            packed = ()
            if self.cfg.grow_policy == "loss":
                if self._pack is None:
                    self._pack = self._build_pack()
                packed = (self._pack(dbins),)
        va_margins = None
        stopper = EarlyStopper(early_stopping_rounds)
        self.eval_history_ = stopper.history

        base_key = jax.random.key(seed)
        trees = []
        grown = []      # what each leaf-wise tree counted, on the device
        for i in range(n_trees if n_trees is not None
                       else self.cfg.n_trees):
            with spans.span("mp4j.gbdt.dispatch", job=job, tree=i):
                kd = jax.random.key_data(jax.random.fold_in(base_key, i))
                dpreds, tree, counts = self._step(dbins, dy, dpreds, dw, kd,
                                                  *packed)
            trees.append(tree)
            grown.append(counts)
            if va is not None:
                va_margins = self._update_margins(va[0], tree, va_margins)
                metric = self._eval_metric(np.asarray(va_margins), va[1])
                # state: the margin snapshot matching the kept ensemble
                if stopper.update(metric, i, state=dpreds):
                    if stopper.best_state is not None:
                        trees = trees[:stopper.best_round + 1]
                        dpreds = stopper.best_state
                    break
        with spans.span("mp4j.gbdt.fetch", job=job) as fetched:
            preds = self._to_host(dpreds)
            # the counts of the trees the job grew (all of them, also
            # where early stopping keeps fewer) come with the margins:
            # the device has finished, so no wait is theirs
            self.grow_stats_, self.grow_rows_read_ = {}, 0
            if self.cfg.grow_policy == "loss":
                built, splits, read = (np.stack(a)
                                       for a in zip(*jax.device_get(grown)))
                self.grow_stats_ = {
                    "splits": int(splits.sum(dtype=np.int64)),
                    "rows_built": int(built.sum(dtype=np.int64))}
                self.grow_rows_read_ = int(read.sum(dtype=np.int64))
                fetched.args.update(self.grow_stats_,
                                    rows_read=self.grow_rows_read_)
        if self.cfg.loss == "softmax":
            return trees, preds.reshape(-1, self.cfg.n_classes)
        return trees, preds.reshape(-1)

    def train_raw_chunks(self, chunks, n_rows: int,
                         n_trees: int | None = None, seed: int = 0,
                         binner=None, bin_sample: int | None = 1_000_000):
        """Boosted training straight from a table that arrives in
        pieces, floats and gaps as they are: ``chunks`` is any iterable
        of ``(X [m, n_features] float32 with NaN where a cell is empty,
        y [m])`` in the table's order, ``n_rows`` their total (a reader
        knows it from its index or a line count). Nobody holds the table
        as one array: ytk-learn's GBDT reads its training file line by
        line, a CSV is read a block of rows at a time.

        The job, in order. (a) Each chunk crosses the host link as it
        arrives and is placed into a float table ``[n_shards, rows a
        shard, n_features]`` allocated once from ``n_rows``
        (``_put_row_chunks``: span ``mp4j.gbdt.raw.stage``). (b) A
        :class:`~ytk_mp4j_tpu.models.binning.QuantileBinner` with
        ``n_bins=cfg.n_bins`` and ``missing_bucket=cfg.missing_bin`` is
        fitted there (``fit_staged``: the rows of
        ``default_rng(seed).choice(n_rows, bin_sample, replace=False)``,
        all of them where ``n_rows <= bin_sample``; the edges are
        ``np.nanquantile``'s of that sample, to the bit), unless
        ``binner`` comes fitted, whose edges are used as they are. (c)
        The table is binned there (``transform_staged``) into the int32
        table the step takes, and the floats are freed. (d)
        :meth:`train`'s loop. The floats cross once and no binned cell
        crosses in either direction; what comes back is the edges' picks
        (968 x 254 x 3 floats at the Bosch width) and the margins.

        A chunk of another width, chunks that do not add up to
        ``n_rows``, labels that do not match their chunk, or a column
        with no finite value in the sample raise ``Mp4jError``. Returns
        ``(trees, margins)`` like :meth:`train`; the fitted binner is
        kept as ``self.binner_``, so :meth:`predict_raw` and
        :meth:`save_model` work as after :meth:`train_raw`. The bins,
        and so the trees, are a function of the table and the seed, not
        of where the chunks were cut."""
        return self._train_raw(chunks, n_rows, n_trees, seed,
                               self._checked_binner(binner), bin_sample)

    def _checked_binner(self, binner):
        """``binner``, or a fresh one of the configuration's size."""
        from ytk_mp4j_tpu.models.binning import QuantileBinner

        if binner is None:
            return QuantileBinner(n_bins=self.cfg.n_bins,
                                  missing_bucket=self.cfg.missing_bin)
        # a finer binner would emit bin ids >= cfg.n_bins, which the
        # histogram one-hot silently drops from every gradient sum —
        # the same silent-misrouting class _check_bins_width guards;
        # coarser is legal (load_model's rule). missing-bucket
        # conventions must agree or NaN routing silently changes.
        if binner.n_bins > self.cfg.n_bins:
            raise Mp4jError(
                f"binner.n_bins={binner.n_bins} exceeds "
                f"cfg.n_bins={self.cfg.n_bins}: out-of-range bin ids "
                "would silently vanish from the histograms (a coarser "
                "binner is fine)")
        if bool(binner.missing_bucket) != bool(self.cfg.missing_bin):
            raise Mp4jError(
                f"binner.missing_bucket={binner.missing_bucket} but "
                f"cfg.missing_bin={self.cfg.missing_bin}: the reserved "
                "bin-0 conventions must match or NaN routing silently "
                "changes")
        return binner

    def shard_raw_chunks(self, chunks, n_rows: int):
        """Steps (a) of :meth:`train_raw_chunks` alone: the floats of
        ``chunks`` placed on the mesh as ``[n_shards, rows a shard,
        n_features]`` f32 (the rows that pad the last shard are NaN, so
        their bins are the zeros :meth:`shard_bins` pads with), and
        their labels as one host array [n_rows]."""
        labels = []

        def floats():
            for k, (X, y) in enumerate(chunks):
                y = np.asarray(y)
                if y.shape[:1] != np.shape(X)[:1]:
                    raise Mp4jError(
                        f"chunk {k} has {np.shape(X)[0]} rows and "
                        f"{y.shape[0]} labels")
                labels.append(y)
                yield X

        table = self._put_row_chunks(floats(), n_rows, self.cfg.n_features)
        return table, (np.concatenate(labels) if labels
                       else np.zeros(0, np.float32))

    def _train_raw(self, chunks, n_rows, n_trees, seed, binner, bin_sample,
                   sample_weight=None, eval_set=None,
                   early_stopping_rounds=None):
        """The one raw front end (:meth:`train_raw_chunks`), and the
        loop after it. ``binner`` either comes fitted (a caller's edges,
        or the host sketches ``train_raw`` still runs) or is fitted on
        the staged floats."""
        n_rows = int(n_rows)

        def stage(job):
            n_chunks = 0

            def counted():
                nonlocal n_chunks
                for n_chunks, chunk in enumerate(chunks, 1):
                    yield chunk

            # ``chunks`` is known when the loop ends: on the ring's
            # record, not on the profiler's event
            with spans.span("mp4j.gbdt.raw.stage", job=job, rows=n_rows,
                            bytes=4 * n_rows * (self.cfg.n_features + 1)
                            ) as staged:
                table, y = self.shard_raw_chunks(counted(), n_rows)
                staged.args["chunks"] = n_chunks
            if binner.edges is None:
                binner.fit_staged(table, n_rows, sample=bin_sample,
                                  seed=seed)
            self.binner_ = binner
            dbins = binner.transform_staged(table)
            del table       # freed when the last row is binned
            va = None
            if eval_set is not None:
                va_host = np.asarray(eval_set[0], np.float32)
                self._check_bins_width(va_host, "eval_set X")
                va = (binner.transform_staged(jnp.asarray(va_host)),
                      np.asarray(eval_set[1]))
            return (dbins, *self._shard_vectors(self._labels(y),
                                                sample_weight), va)

        return self._train(stage, n_trees, seed, early_stopping_rounds,
                           held_out=eval_set is not None)

    def train_raw(self, X, y, n_trees: int | None = None, seed: int = 0,
                  sample_weight: np.ndarray | None = None,
                  eval_set=None, early_stopping_rounds: int | None = None,
                  binner=None, comm=None,
                  bin_sample: int | None = 1_000_000):
        """The ytk-learn consumer entry point for a table held as ONE
        array: RAW continuous features [N, F] -> internal quantile
        binning -> boosted training, in one call (the reference
        consumer bins internally; SURVEY.md section 1 flagship consumer
        + section 3b). It is :meth:`train_raw_chunks` over row slices
        of ``X`` (a staging chunk each): the floats cross to the mesh
        once, the sketch and the transform run there, and the binned
        table never visits the host.

        A :class:`~ytk_mp4j_tpu.models.binning.QuantileBinner` with
        ``n_bins=cfg.n_bins`` and ``missing_bucket=cfg.missing_bin`` is
        fitted on X: on the mesh (``fit_staged``) in the plain case;
        on the host, as before, where the fit is weighted
        (``sample_weight``) or distributed — via ``fit_distributed``
        over ``comm`` when one is given (an mp4j comm with ``slave_num
        > 1``: every rank calls ``train_raw`` together, each sketches
        its OWN X and one allgather merges, so raw features never leave
        their rank). Everything after the edges is the device path
        either way. NaN feature values flow to the missing bucket (pair
        with ``cfg.missing_bin=True`` for learned default directions).

        The fitted binner is kept as ``self.binner_`` and persisted by
        :meth:`save_model`; ``eval_set=(X_va, y_va)`` takes RAW
        features, binned on the device by the same transform. Pass a
        pre-fitted ``binner`` to reuse edges (its edges are used
        as-is). ``sample_weight`` both weights the quantile sketch (a
        heavily weighted region earns finer bins, ytk-learn's weighted
        training) and scales the boosting gradients. Returns ``(trees,
        margins)`` like :meth:`train`; serve raw features with
        :meth:`predict_raw`."""
        X = np.asarray(X, np.float32)
        self._check_bins_width(X, "X")
        y = np.asarray(y)
        binner = self._checked_binner(binner)
        if binner.edges is None:
            if comm is not None and comm.slave_num > 1:
                binner.fit_distributed(X, comm, sample=bin_sample,
                                       seed=seed,
                                       sample_weight=sample_weight)
            elif sample_weight is not None:
                binner.fit(X, sample=bin_sample, seed=seed,
                           sample_weight=sample_weight)
        rows = max(1, self._EACH_CHUNK_BYTES // (4 * X.shape[1]))
        slices = ((X[s:s + rows], y[s:s + rows])
                  for s in range(0, X.shape[0], rows))
        return self._train_raw(
            slices, X.shape[0], n_trees, seed, binner, bin_sample,
            sample_weight=sample_weight, eval_set=eval_set,
            early_stopping_rounds=early_stopping_rounds)

    def predict_raw_chunks(self, chunks, n_rows: int, trees,
                           proba: bool = False, binner=None) -> np.ndarray:
        """:meth:`predict` straight from a table that arrives in pieces,
        floats and gaps as they are: ``chunks`` is any iterable of ``X
        [m, n_features]`` float32 with NaN where a cell is empty, in the
        table's order, ``n_rows`` their total, as
        :meth:`train_raw_chunks` takes them (without labels); ``binner``
        a fitted :class:`~ytk_mp4j_tpu.models.binning.QuantileBinner`,
        by default the one the raw training entry points left on
        ``self.binner_`` (or :meth:`load_model`'s caller put there).

        Each chunk crosses the host link as it arrives, to the device
        that holds its rows' margins, in pieces of 128 MiB at most
        (``_reader_pieces``), and a piece is scored there as soon as it
        is on its way, while the next ones cross: the scoring program
        takes the piece as it crossed, bins its rows (``score_shard``
        with that device's copy of the edges) and reads the bins as
        :meth:`predict`'s reads a piece of a binned table. No table of
        floats is built: a piece is let go when its turn is over, so a
        file larger than the mesh's memory is scored like any other,
        and every shard's pieces are scored as they arrive. The floats
        cross once, no binned cell crosses in either direction or
        outlives its piece's turn, and the host holds no binned copy.
        Returns what :meth:`predict` returns for the binner's bins of
        the same table, to the bit, wherever the chunks were cut.

        No fitted binner, a chunk of another width or chunks that do not
        add up to ``n_rows`` raise ``Mp4jError``."""
        if binner is None:
            binner = self.binner_
        if binner is None or binner.edges is None:
            raise Mp4jError(
                "no fitted binner on this trainer: train with "
                "train_raw_chunks or train_raw, pass binner=, or set "
                "trainer.binner_ (load_model returns the persisted "
                "binner) before predict_raw_chunks / predict_raw")
        F = self.cfg.n_features
        if binner.edges.shape[0] != F:
            raise Mp4jError(
                f"the binner has edges for {binner.edges.shape[0]} "
                f"features, cfg.n_features={F}")
        return self._predict(self._reader_pieces(chunks, int(n_rows), F),
                             int(n_rows), trees, proba, binner)

    def predict_raw(self, X, trees, proba: bool = False):
        """Serve RAW continuous features [N, n_features] held as ONE
        array through the binner fitted by :meth:`train_raw` /
        :meth:`train_raw_chunks` (or installed on ``self.binner_`` by
        :meth:`load_model`'s caller): :meth:`predict_raw_chunks` over
        row slices of ``X`` (a staging chunk each), so the floats cross
        to the mesh once and are binned there."""
        X = np.asarray(X, np.float32)
        self._check_bins_width(X, "X")
        rows = max(1, self._EACH_CHUNK_BYTES // (4 * X.shape[1]))
        return self.predict_raw_chunks(
            (X[s:s + rows] for s in range(0, X.shape[0], rows)),
            X.shape[0], trees, proba)

    def _check_bins_width(self, bins, what: str = "bins") -> None:
        """A bin matrix narrower/wider than cfg.n_features would make
        one-hot feature routing silently select value 0 for
        out-of-range split features (routing every sample left), so
        wrong widths must be an error, not plausible-looking margins."""
        if bins.ndim != 2 or bins.shape[1] != self.cfg.n_features:
            raise Mp4jError(
                f"{what} must be [N, n_features={self.cfg.n_features}], "
                f"got {bins.shape}")

    def _update_margins(self, bins, tree, margins):
        """Incrementally add one round's tree output to held-out
        margins (jitted once per trainer)."""
        cfg = self.cfg
        if self._margin_step is None:
            softmax = cfg.loss == "softmax"

            @jax.jit
            def add(bins, tree, margins):
                if softmax:
                    delta = jnp.stack(
                        [predict_tree(bins, t, cfg) for t in tree],
                        axis=1)
                else:
                    delta = predict_tree(bins, tree, cfg)
                return margins + cfg.learning_rate * delta

            self._margin_step = add
        if margins is None:
            shape = ((bins.shape[0], cfg.n_classes)
                     if cfg.loss == "softmax" else (bins.shape[0],))
            margins = jnp.zeros(shape, jnp.float32)
        # trees from the shard_map step may span non-addressable devices
        # on multi-process meshes; fetch them for this local jit
        return self._margin_step(bins, self._local_values(tree), margins)

    def _eval_metric(self, margins: np.ndarray, y: np.ndarray) -> float:
        """The objective's validation metric (lower is better):
        squared -> mse, logistic -> logloss, softmax -> logloss."""
        if self.cfg.loss == "squared":
            return float(np.mean((margins - y) ** 2))
        if self.cfg.loss == "logistic":
            return float(np.mean(np.asarray(
                per_example_loss(margins, y, "logistic"))))
        z = margins - margins.max(axis=1, keepdims=True)
        logp = z - np.log(np.exp(z).sum(axis=1, keepdims=True))
        return float(-np.mean(logp[np.arange(len(y)), y.astype(int)]))

    def _build_score(self, shape, rows: int, rounds: int, binning=None):
        """The scoring program for pieces that cross as ``shape``, of
        which it scores the last ``rows`` rows (all, but for a last piece
        that began early) under ``rounds`` rounds. It takes (piece,
        ensemble, margins, first row[, edges]) and returns the margins,
        donated, with those rows filled in from ``first row`` on, and
        the piece's first word, which is there when the device has had
        the piece. The piece is put into rows of ``n_features`` inside
        the program, under the scope ``stage.place``, and ``score_shard``
        scores those.

        Three dimensions are a slice of every shard ([n_shards, M, 128]
        words, or [n_shards, rows, n_features]: a table that crossed in
        one transfer too): ``score_shard`` under ``shard_map``, the
        ensemble replicated, margins [n_shards, C, rows a shard]. Two
        are a piece that went to one device (``_reader_pieces``): the
        same body as that device's own program, margins [1, C, rows a
        shard]. ``binning``: None for bins; for floats ``(edges a
        column, the binner's missing_bucket)``, and the program takes
        the edges [n_features, edges] last."""
        cfg = self.cfg
        mapped = len(shape) == 3
        axes = self.axes if mapped else None
        n_edges, shift = binning or (None, False)
        F = cfg.n_features
        held = int(np.prod(shape[1:] if mapped else shape)) // F

        def score(piece, stacked, out, start, *edges):
            # the device's side of the hand-over, by name in a trace
            with jax.named_scope("stage.place"):
                marker, piece = piece.reshape(-1)[:1], piece.reshape(held, F)
            return score_shard(piece, stacked, out[0], start, cfg, axes,
                               *edges, shift=shift,
                               skip=held - rows)[None], marker

        if mapped:
            score = jax.shard_map(
                score, mesh=self.mesh, out_specs=(P(axes), P(axes)),
                in_specs=(P(axes), P(), P(axes), P())
                + (P(),) * (binning is not None))
        n_classes = cfg.n_classes if cfg.loss == "softmax" else 1
        row_chunk, chunks = score_row_chunks(rows)
        # a float piece's program says how many compares a cell it
        # issues for that many edges, which block of a piece the TPU's
        # kernel takes at a time, and that it bins before it selects (no
        # float select)
        said = {"key": "gbdt_score"}
        if binning is not None:
            columns, block_rows = bin_blocks(n_edges)
            said = {"key": "gbdt_score_raw", "edges": n_edges,
                    "compares": search_steps(n_edges),
                    "bin_block_columns": columns,
                    "bin_block_rows": block_rows, "form": "bins"}
        with spans.span("mp4j.step.build", **said,
                        group=score_group_size(rounds, n_classes),
                        rows=rows, row_chunk=row_chunk, row_chunks=chunks):
            return jax.jit(score, donate_argnums=2)

    def predict(self, bins: np.ndarray, trees,
                proba: bool = False) -> np.ndarray:
        """Ensemble prediction: the sum of learning-rate-scaled tree
        outputs over any binned matrix. Returns raw margins ([N], or
        [N, n_classes] for softmax); ``proba=True`` applies the sigmoid
        (logistic) or softmax.

        The rows cross as ``train``'s do (``_pieces``: padded to whole
        shards and sharded over the trainer's mesh, a shard of 2**32
        bytes or more in 128 MiB pieces, two crossing at a time and up
        to twelve ahead of the device; a smaller one in one transfer, as
        one piece), the ensemble is replicated, and one jitted
        ``shard_map`` program scores a piece as it crossed
        (``score_shard``) while the next ones cross: rows outermost in
        chunks of at most 2**17, trees in groups of up to 16 inside a
        chunk, so a row is read once a job and not once a level of every
        tree. No table is built: a piece is let go when its turn is
        over. A row's margin is the f32 sum over the trees in their
        order. The program is kept by (piece shape, rows a shard, rows a
        call, tree count): a repeated ``predict`` of the same shape
        builds nothing. :meth:`predict_raw_chunks` is the same loop over
        pieces of floats."""
        bins = np.asarray(bins, np.int32)
        self._check_bins_width(bins)
        (padded,), per, _ = self._pad_rows([bins], weights=False)
        return self._predict(self._pieces(padded, per), bins.shape[0],
                             trees, proba)

    def _predict(self, pieces, N: int, trees, proba: bool, binner=None):
        """:meth:`predict` from the point where rows are on their way to
        the mesh, whatever they hold: ``pieces`` yields them as
        ``_crossed`` does (``_pieces`` of a host table of bins,
        ``_reader_pieces`` of a reader's chunks of floats, which the
        program bins by ``binner``'s edges), and each is scored where it
        went and let go. A piece of every shard is scored by the
        ``shard_map`` program into one array of margins; a piece of one
        shard by that device's program, with its copy of the ensemble
        and edges, into that shard's margins, which are put together
        once at the end (a shard's padding rows are no piece: their
        margins stay zero and are cut). One cache of programs (its key
        says a float piece's binning too), the spans of one job
        (``source``: what the pieces hold), one fetch."""
        trees = list(trees)
        softmax = self.cfg.loss == "softmax"
        C = self.cfg.n_classes if softmax else 1
        if not trees or not N:
            # an untrained / zero-round ensemble leaves the margins zero
            out = np.zeros((N, C), np.float32)
        else:
            job, self._score_jobs = self._score_jobs, self._score_jobs + 1
            said = {"job": job,
                    "source": "bins" if binner is None else "floats"}
            model = (self._stack_trees(trees),)
            binning = ()
            if binner is not None:
                binning = ((binner.edges.shape[1],
                            bool(binner.missing_bucket)),)
                model += (self._place_replicated(
                    np.asarray(binner.edges, np.float32)),)
            n, per = self.n_shards, -(-N // self.n_shards)
            # where a piece's margins rest: rows sharded over the mesh
            # (shard None: a piece of every shard), or on the one device
            # that a shard's pieces go to
            homes = {None: self._row_sharding(),
                     **self._shard_devices((n, C, per))}
            held, scored = {}, {}

            def home(shard):
                """[zero margins where ``shard``'s rest, the model there]"""
                return [jnp.zeros((n if shard is None else 1, C, per),
                                  jnp.float32, device=homes[shard]),
                        model if shard is None
                        else self._replica_on(model, homes[shard])]

            with spans.span("mp4j.gbdt.score.stage", **said):
                for _, piece, shard, start, stop, turns in pieces:
                    # the last piece of a table held whole starts early,
                    # over rows that the one before it brought: those
                    # are done
                    start = max(start, scored.get(shard, 0))
                    scored[shard] = stop
                    # ``per``: jit would trace again for other margins
                    # and say nothing
                    key = (piece.shape, per, stop - start,
                           len(trees)) + binning
                    program = self._score_programs.get(key)
                    if program is None:
                        program = self._score_programs[key] = \
                            self._build_score(key[0], *key[2:])
                    with spans.span("mp4j.gbdt.score.dispatch", **said,
                                    trees=len(trees), start=start,
                                    rows=stop - start):
                        if shard not in held:
                            held[shard] = home(shard)
                        margins, (stacked, *edges) = held[shard]
                        held[shard][0], done = program(
                            piece, stacked, margins, np.int32(start), *edges)
                    turns.append(done)
                    del piece, margins
            with spans.span("mp4j.gbdt.score.fetch", **said):
                if None in held:
                    margins = held[None][0]
                else:
                    # a shard at a time: together now, and a shard that
                    # no piece reached (all padding) is zero
                    margins = jax.make_array_from_single_device_arrays(
                        (n, C, per), homes[None],
                        [(held.get(s) or home(s))[0]
                         for s in sorted(set(homes) - {None})])
                out = self._to_host(margins)
            # [n_shards, C, rows a shard] -> [N, C]
            out = out.transpose(0, 2, 1).reshape(-1, C)[:N]
        if not softmax:
            out = out[:, 0]
        if not proba:
            return out
        if softmax:
            z = out - out.max(axis=1, keepdims=True)   # overflow-free
            e = np.exp(z)
            return e / e.sum(axis=1, keepdims=True)
        # two-branch sigmoid: exp only ever sees non-positive
        # arguments, so large |margin| cannot overflow
        p = np.empty_like(out)
        pos = out >= 0
        p[pos] = 1.0 / (1.0 + np.exp(-out[pos]))
        e = np.exp(out[~pos])
        p[~pos] = e / (1.0 + e)
        return p

    def _stack_trees(self, trees: list):
        """The ensemble as the scoring program takes it: (feat, bin,
        dir, leaf), each [groups, 2**depth, G, C], replicated on the
        mesh: a tree's nodes in level order with one unused slot (or
        its leaves), G rounds a group (``score_group_size``), C trees a
        round (1 unless softmax). The last group is filled up with
        trees of frozen nodes and zero leaves, which add 0.0. The
        ensemble is fetched in one ``device_get`` (the hop off
        non-addressable devices on multi-process meshes too) and the
        result kept by tree identity (holding the list keeps ids
        stable), so repeated predict() on the same ensemble pays for it
        once."""
        cached = self._stacked_trees
        if (cached is not None and len(cached[0]) == len(trees)
                and all(a is b for a, b in zip(cached[0], trees))):
            return cached[1]
        cfg = self.cfg
        host = jax.device_get(trees)
        if cfg.loss != "softmax":
            host = [(rnd,) for rnd in host]
        T, C, width = len(host), len(host[0]), 2 ** cfg.depth
        G = score_group_size(T, C)
        groups = -(-T // G)
        stacked = []
        for j, (dtype, fill) in enumerate((
                (np.int32, 0), (np.int32, cfg.n_bins - 1), (np.int32, 0),
                (np.float32, 0.0))):
            a = np.full((groups * G, C, width), fill, dtype)
            part = np.stack([[np.asarray(cls[j]) for cls in rnd]
                             for rnd in host])
            a[:T, :, :part.shape[2]] = part
            stacked.append(np.ascontiguousarray(
                a.reshape(groups, G, C, width).transpose(0, 3, 1, 2)))
        stacked = self._place_replicated(tuple(stacked))
        self._stacked_trees = (trees, stacked)
        return stacked

    def feature_importance(self, trees) -> np.ndarray:
        """Split-count feature importance over the ensemble (ytk-learn's
        model-report style): how many internal nodes split on each
        feature, normalized to sum to 1. Frozen nodes (split bin B-1
        routes everything left — no real split) are excluded."""
        counts = np.zeros(self.cfg.n_features, np.int64)
        for round_trees in trees:
            per_class = (round_trees if self.cfg.loss == "softmax"
                         else (round_trees,))
            for tf, tb, _td, _lv in per_class:
                real = np.asarray(tb) != self.cfg.n_bins - 1
                np.add.at(counts, np.asarray(tf)[real], 1)
        total = counts.sum()
        return (counts / total if total else
                np.zeros(self.cfg.n_features)).astype(np.float64)

    def save_model(self, path: str, trees, binner=None) -> None:
        """Persist the ensemble (and the fitted binner's edges — the
        one from :meth:`train_raw` by default) as a portable .npz —
        the reference consumer's train-then-serve flow."""
        from ytk_mp4j_tpu.models._base import save_npz

        if binner is None:
            binner = self.binner_
        arrays = {"n_trees": np.int64(len(trees))}
        for i, round_trees in enumerate(trees):
            per_class = (round_trees if self.cfg.loss == "softmax"
                         else (round_trees,))
            for c, (tf, tb, td, lv) in enumerate(per_class):
                arrays[f"feat_{i}_{c}"] = np.asarray(tf)
                arrays[f"bin_{i}_{c}"] = np.asarray(tb)
                arrays[f"dir_{i}_{c}"] = np.asarray(td)
                arrays[f"leaf_{i}_{c}"] = np.asarray(lv)
        if binner is not None and binner.edges is not None:
            arrays["bin_edges"] = binner.edges
            arrays["bin_missing"] = np.bool_(binner.missing_bucket)
        save_npz(path, self.cfg, arrays)

    @staticmethod
    def load_model(path: str):
        """Load a saved ensemble; returns (cfg, trees, binner|None)."""
        from ytk_mp4j_tpu.models._base import load_npz
        from ytk_mp4j_tpu.models.binning import QuantileBinner

        cfg, z = load_npz(path, GBDTConfig)
        C = cfg.n_classes if cfg.loss == "softmax" else 1

        def tree(i, c):
            tf = z[f"feat_{i}_{c}"]
            # models saved before default-direction support have no dir
            # arrays; all-left (0) IS their training-time behavior
            td = z.get(f"dir_{i}_{c}")
            if td is None:
                td = np.zeros_like(tf)
            return (tf, z[f"bin_{i}_{c}"], td, z[f"leaf_{i}_{c}"])

        if cfg.loss == "softmax":
            trees = [tuple(tree(i, c) for c in range(C))
                     for i in range(int(z["n_trees"]))]
        else:
            trees = [tree(i, 0) for i in range(int(z["n_trees"]))]
        binner = None
        if "bin_edges" in z:
            # binning granularity may differ from cfg.n_bins (a
            # coarser binner feeding a finer histogram is legal);
            # derive it from the saved edges + missing-bucket flag
            edges = z["bin_edges"]
            mb = bool(z.get("bin_missing", False))
            binner = QuantileBinner(edges.shape[1] + (2 if mb else 1),
                                    missing_bucket=mb)
            binner.edges = edges
        return cfg, trees, binner


# ----------------------------------------------------------------------
# serve adapter (ISSUE 19): the reduce-mode sharded entry point
# ----------------------------------------------------------------------
class GBDTServable:
    """Tree-shard serve adapter for a trained ensemble.

    ``kind="reduce"``: unlike the embedding families there is no row
    to pull — every example visits every tree — so the serve
    dispatcher shards the ENSEMBLE (round ``t`` lives on rank
    ``t % size``), each rank routes the batch through its own trees,
    and the per-rank partial margins meet in one fixed-shape
    ``allreduce``. The host router mirrors ``_route_samples`` /
    :func:`predict_tree` exactly (ordered splits, categorical
    equality splits, the learned missing-bucket direction, the B-1
    freeze sentinel), and margins accumulate per example in float64
    in fixed tree order — so partial sums are independent of batch
    composition and batched == sequential stays bitwise true through
    the deterministic reduce.
    """

    kind = "reduce"
    family = "gbdt"

    def __init__(self, trees, cfg: GBDTConfig):
        self.cfg = cfg
        self.n_rounds = len(trees)
        self.req_width = cfg.n_features
        self.resp_width = (cfg.n_classes if cfg.loss == "softmax"
                          else 1)
        # [T, C, ...] host component arrays (C=1 unless softmax)
        def _host(rnd):
            per_class = rnd if cfg.loss == "softmax" else (rnd,)
            return [tuple(np.asarray(jax.device_get(a))
                          for a in cls) for cls in per_class]
        self._trees = [_host(rnd) for rnd in trees]
        self._cat_mask = cfg._cat_mask()

    def _route(self, bins: np.ndarray, tree) -> np.ndarray:
        """[N] leaf values — numpy mirror of :func:`predict_tree`."""
        cfg = self.cfg
        tree_feat, tree_bin, tree_dir, leaf_val = tree
        N = bins.shape[0]
        node = np.zeros(N, np.int64)
        rows = np.arange(N)
        start = 0
        for d in range(cfg.depth):
            n_nodes = 2 ** d
            f = np.asarray(tree_feat[start:start + n_nodes])[node]
            b = np.asarray(tree_bin[start:start + n_nodes])[node]
            v = bins[rows, f]
            go_right = v > b
            if cfg.missing_bin:
                dd = np.asarray(
                    tree_dir[start:start + n_nodes])[node]
                go_right = np.where(v == 0, dd > 0, go_right)
            if self._cat_mask is not None:
                sc = self._cat_mask[f]
                go_right = np.where(
                    sc, (v == b) & (b != cfg.n_bins - 1), go_right)
            node = node * 2 + go_right.astype(np.int64)
            start += n_nodes
        return np.asarray(leaf_val)[node]

    def partial_margins(self, bins: np.ndarray, rank: int,
                        size: int) -> np.ndarray:
        """[N, resp_width] float64 margins over THIS rank's tree shard
        (rounds ``t % size == rank``); summing the partials of all
        ranks reproduces :meth:`GBDTTrainer.predict`'s raw margins up
        to the f32->f64 accumulation swap."""
        bins = np.asarray(bins, np.int64)
        out = np.zeros((bins.shape[0], self.resp_width), np.float64)
        lr = np.float64(self.cfg.learning_rate)
        for t in range(rank, self.n_rounds, size):
            for c, cls in enumerate(self._trees[t]):
                out[:, c] += lr * self._route(bins, cls).astype(
                    np.float64)
        return out

    def link(self, margins: np.ndarray) -> list:
        """Frontend head: margins [N, resp_width] -> one float64
        prediction vector per example (proba via the same two-branch
        sigmoid / max-shifted softmax as :meth:`GBDTTrainer.predict`;
        squared stays the raw margin)."""
        out = []
        for m in margins:
            if self.cfg.loss == "logistic":
                z = float(m[0])
                if z >= 0:
                    p = 1.0 / (1.0 + np.exp(-z))
                else:
                    e = np.exp(z)
                    p = e / (1.0 + e)
                out.append(np.asarray([p], np.float64))
            elif self.cfg.loss == "softmax":
                z = m - m.max()
                e = np.exp(z)
                out.append(e / e.sum())
            else:
                out.append(np.asarray([float(m[0])], np.float64))
        return out


def servable(trees, cfg: GBDTConfig) -> GBDTServable:
    """The serve plane's per-family entry point (ISSUE 19)."""
    return GBDTServable(trees, cfg)
