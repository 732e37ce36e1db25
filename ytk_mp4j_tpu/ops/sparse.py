"""Device-native sparse collectives — the TPU replacement for the
reference's ``Map<K, V>`` Kryo path.

The reference's sparse allreduce serializes whole hash maps with Kryo and
merges key-wise per socket round — an allocation-heavy host loop
(SURVEY.md section 3c). The TPU-native design packs each rank's sparse
contribution into dense ``(index, value)`` buffers of STATIC capacity and
rides XLA collectives:

    all_gather(idx), all_gather(val)      # one ICI collective each
    sort by idx                           # XLA sort, fused
    segment-reduce runs of equal idx      # jax.ops.segment_*
    compact to static out-capacity        # scatter into [capacity]

Everything is static-shaped (XLA requirement): unused slots carry a
SENTINEL index and the operator's identity value, so padding never
perturbs results. Host-side key<->code translation (for string keys)
lives in ``comm.tpu_comm``; this module is pure device code usable inside
``shard_map`` (e.g. embedding-gradient aggregation inside a jitted train
step — the FFM workload of BASELINE.json configs[4]).
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ytk_mp4j_tpu.operators import Operator, Operators
from ytk_mp4j_tpu.ops.collectives import _axis_size, flat_index

# Index sentinel for padding slots. int32 max keeps sorts stable (padding
# sorts to the end) and is never a legal key code.
SENTINEL = jnp.iinfo(jnp.int32).max

# keyed by the BUILTIN Operator objects (frozen dataclass equality),
# not by name: a user-defined Operator.custom("MAX", fn, ...) must take
# the generic reduction with ITS OWN fn, not silently inherit the
# builtin segment_max
_SEGMENT_REDUCERS = {
    Operators.SUM: jax.ops.segment_sum,
    Operators.PROD: jax.ops.segment_prod,
    Operators.MAX: jax.ops.segment_max,
    Operators.MIN: jax.ops.segment_min,
}


@jax.named_scope("sparse.sort_by_key")
def sort_by_key(idx, val, keep=None):
    """Jointly sort ``(idx, val)`` ascending by ``idx`` with ONE
    multi-operand ``lax.sort`` — key and payload ride the same sort
    network, so there is no post-sort gather.

    The previous formulation (``order = argsort(idx); idx[order],
    val[order]``) routed the payload through a fancy-index row gather;
    the v5e-8 AOT compile of the FFM sparse step costed that program at
    180.5 GB bytes-accessed (previous installation, 2026-07) for 16 MB
    of live data — the gather's multi-chip lowering is pathological.
    The multi-operand sort carries each payload column through the
    sort comparators instead.

    ``val`` may be [L] or [L, ...]; trailing dims ride as extra static
    payload columns. Beyond ``_MAX_SORT_PAYLOAD_COLS`` columns the
    comparator payload would dominate the sort network, so wide rows
    fall back to sorting (key, iota) pairs and gathering rows once.

    ``keep``: return the first ``keep`` entries of the sorted stream
    only, for a caller that knows the rest to be SENTINEL padding. Wide
    rows are then gathered for those entries alone, so whatever follows
    the sort runs on lists of the caller's choosing (a told scatter-add
    of 80,184 rows costs 12.6% more than one of 79,872, whole 1,024s, on
    a v5e: PERF.md section 6, PR 40).
    """
    def head(a):
        return a if keep is None else a[:keep]

    if val.ndim == 1:
        si, sv = lax.sort((idx, val), dimension=0, num_keys=1)
        return head(si), head(sv)
    L = idx.shape[0]
    cols = math.prod(val.shape[1:])
    if cols == 0:
        # zero-width payload carries no data; only the keys need sorting
        return head(lax.sort(idx, dimension=0)), head(val)
    flat = val.reshape(L, cols)
    if cols > _MAX_SORT_PAYLOAD_COLS:
        order = head(jnp.argsort(idx))
        return idx[order], val[order]
    out = lax.sort((idx,) + tuple(flat[:, j] for j in range(cols)),
                   dimension=0, num_keys=1)
    return (head(out[0]),
            head(jnp.stack(out[1:], axis=1).reshape(val.shape)))


# Widest value row that still rides the sort network as payload; wider
# rows fall back to argsort + one row gather (the comparator cost grows
# linearly with payload width while the gather cost is width-invariant).
# Under one 128-lane word: a whole word or more is an FFM block
# (``models/fm.py:_block_width``), which every sparse step merges since
# PR 39, and the TPU's compiler takes over 19 minutes for a sort of 129
# operands where it takes 13 s for the argsort and the gather (AOT for
# v5e, 45,056 slots of a 22-field FFM; the CPU backend runs it five
# times slower). Narrow rows still pay the network's compile: 136 s at
# 9 columns, 323 s at 16, against 11 s (PERF.md section 7, PR 39).
_MAX_SORT_PAYLOAD_COLS = 127


def pad_to(idx, val, capacity: int, operator: Operator = Operators.SUM):
    """Pad/truncate ``(idx, val)`` to static ``capacity`` slots, filling
    with SENTINEL / the operator identity."""
    L = idx.shape[0]
    if L > capacity:
        raise ValueError(f"{L} entries exceed capacity {capacity}")
    ident = jnp.asarray(operator.identity(val.dtype), dtype=val.dtype)
    pad_i = jnp.full((capacity - L,), SENTINEL, dtype=jnp.int32)
    pad_v = jnp.full((capacity - L,) + val.shape[1:], ident, dtype=val.dtype)
    return (jnp.concatenate([idx.astype(jnp.int32), pad_i]),
            jnp.concatenate([val, pad_v]))


@jax.named_scope("sparse.segment_reduce")
def segment_reduce_sorted(idx, val, capacity: int,
                          operator: Operator = Operators.SUM):
    """Reduce runs of equal index in an idx-sorted stream into at most
    ``capacity`` unique (idx, val) slots. Returns (out_idx, out_val) with
    SENTINEL/identity padding; unique entries are packed at the front in
    ascending idx order."""
    # run starts -> segment ids (cumsum of boundary flags)
    first = jnp.ones((1,), dtype=jnp.int32)
    bounds = jnp.concatenate([first, (idx[1:] != idx[:-1]).astype(jnp.int32)])
    # padding slots (SENTINEL) must not open new live segments; they sort
    # to the end so they share one trailing segment region
    seg = jnp.cumsum(bounds) - 1
    reducer = _SEGMENT_REDUCERS.get(operator)
    if reducer is not None:
        # seg is a cumulative sum: told so, XLA neither sorts the ids
        # nor gathers the rows by that order again before its scatter
        # (1.77 -> 1.24 ms for [79,872, 232] f32 on a v5e, the same sums
        # to the bit). Its sorted scatter is a pass over the OPERAND, so
        # this pays where the output is small, as here, and never into a
        # table (PERF.md section 6, PR 35)
        out_val = reducer(val, seg, num_segments=capacity,
                          indices_are_sorted=True)
    else:
        # generic associative op: log-step doubling combine over the
        # sorted stream (scan-free, static shapes)
        out_val = _generic_segment_reduce(val, seg, capacity, operator)
    # mode="drop": with a full union the sentinel segment id equals
    # `capacity` and must be discarded, not clipped onto the last slot
    out_idx = (jnp.full((capacity,), SENTINEL, dtype=jnp.int32)
               .at[seg].set(idx, mode="drop"))
    # overwrite segments that only contain sentinel slots; values may be
    # N-D (map-of-arrays operands) — broadcast the liveness mask
    ident = jnp.asarray(operator.identity(val.dtype), dtype=val.dtype)
    live = (out_idx != SENTINEL).reshape(
        (capacity,) + (1,) * (out_val.ndim - 1))
    out_val = jnp.where(live, out_val, ident)
    return out_idx, out_val


def distinct_sorted(idx, capacity: int):
    """``(out_idx, seg)`` of an ascending list ``idx``: its distinct ids
    packed at the front of ``out_idx`` [capacity], ascending, SENTINEL
    after them (:func:`segment_reduce_sorted`'s ``out_idx``), and
    ``seg[i]``, the position of entry i's id in that list. For a caller
    that needs the inverse as well as the merge: the blocks fetched for
    ``out_idx`` are spread back over the entries by ``seg``, and
    ``segment_reduce_sorted(idx, val, capacity)`` sums ``val`` into the
    same positions. SENTINEL entries share the one segment after the live
    ones, whose ``out_idx`` stays SENTINEL."""
    first = jnp.ones((1,), dtype=jnp.int32)
    bounds = jnp.concatenate([first, (idx[1:] != idx[:-1]).astype(jnp.int32)])
    seg = jnp.cumsum(bounds) - 1
    # seg is a cumulative sum and the operand is the list itself: the
    # sorted form of the scatter is the cheap one here (PERF.md section
    # 6, PR 35)
    out_idx = (jnp.full((capacity,), SENTINEL, dtype=jnp.int32)
               .at[seg].set(idx, mode="drop", indices_are_sorted=True))
    return out_idx, seg


def fold_live_tiles(idx, val, tile: int, body, carry):
    """Fold ``body`` over the LIVE prefix of a packed ``(idx, val)`` list,
    ``tile`` entries at a time: ``carry = body(carry, idx_tile, val_tile)``
    for tiles 0 .. ceil(n_live / tile) - 1, where ``n_live`` counts the
    entries that are not SENTINEL. The list is
    :func:`segment_reduce_sorted`'s: live entries first, SENTINEL after
    them. The trip count is read from the data, so the work goes with
    what the list holds and not with its static length; a list of
    sentinels runs no tile and returns ``carry`` as it came.

    Tiles are disjoint and cover the prefix once: a length that ``tile``
    does not divide is padded with SENTINEL / zeros first (a
    ``dynamic_slice`` that clamped at the end would hand ``body`` the
    entries before it a second time). Only the last tile reached may
    hold sentinels; ``body`` drops them as a whole-list pass would.
    The list is ascending with every live id once, so each tile is too,
    its sentinels trailing: bodies may rely on it.
    Inside ``shard_map`` every member must hold the same list (the trip
    count is per member and the loop holds no collective of its own).

    The three sparse FFM steps of ``models/fm.py`` are this loop with
    another body: the SGD step scatter-adds a tile's summed gradients
    into the table it carries, the AdaGrad step gathers, updates and
    sets a tile's blocks, the sharded step's owner gathers or
    scatter-adds each member's list. The serial unit charges a
    descriptor live or dropped, so what the loop saves is the list's
    dead tail."""
    L = idx.shape[0]
    # the loop's own counter, slices and copies carry this name in a
    # device trace; ``body``'s scopes nest inside it
    with jax.named_scope("sparse.fold_live_tiles"):
        if L % tile:
            idx, val = pad_to(idx, val, -(-L // tile) * tile)
        n_live = jnp.sum(idx != SENTINEL, dtype=jnp.int32)

        def step(t, carry):
            return body(carry,
                        lax.dynamic_slice_in_dim(idx, t * tile, tile),
                        lax.dynamic_slice_in_dim(val, t * tile, tile))

        return lax.fori_loop(0, (n_live + (tile - 1)) // tile, step, carry)


def _generic_segment_reduce(val, seg, capacity: int, operator: Operator):
    """Segment reduction for user-defined operators via a segmented
    suffix scan (Hillis-Steele): after round k, acc[i] covers elements
    [i, i+2^k) of i's segment; segment contiguity in the sorted stream
    makes the same-segment test sufficient. O(log L) rounds, static."""
    L = val.shape[0]
    acc = val
    stride = 1
    idxs = jnp.arange(L)
    expand = (L,) + (1,) * (val.ndim - 1)
    while stride < L:
        partner = idxs + stride
        partner_ok = partner < L
        p = jnp.clip(partner, 0, L - 1)
        same = ((seg[p] == seg) & partner_ok).reshape(expand)
        merged = operator.jnp_fn(acc, acc[p])
        acc = jnp.where(same, merged, acc)
        stride *= 2
    # heads of segments carry the full reduction
    head = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    out = jnp.full((capacity,) + val.shape[1:],
                   operator.identity(val.dtype), dtype=val.dtype)
    out = out.at[jnp.where(head, seg, capacity)].set(acc, mode="drop")
    return out


def sparse_allreduce(idx, val, capacity: int,
                     operator: Operator = Operators.SUM,
                     axis_name: str = "mp4j"):
    """Key-union sparse allreduce inside ``shard_map``.

    Each member contributes up to ``local_capacity`` (= idx.shape[0])
    entries (SENTINEL-padded). Every member receives the union of keys
    with values reduced by ``operator``, packed ascending into
    ``capacity`` static slots (SENTINEL/identity padding).
    """
    gi = lax.all_gather(idx, axis_name, axis=0, tiled=True)
    gv = lax.all_gather(val, axis_name, axis=0, tiled=True)
    si, sv = sort_by_key(gi, gv)
    return segment_reduce_sorted(si, sv, capacity, operator)


def block_owner(codes, size: int, n: int):
    """Owning member of each key code under the BLOCK partition of the
    key space ``[0, size)`` — jit-side twin of :func:`meta.owner_of`
    (ranks ``0..size%n-1`` own ``ceil(size/n)`` codes, the rest
    ``floor``). SENTINEL (or any out-of-range) codes map to ``n`` so
    callers can mask them with one compare."""
    base, rem = divmod(size, n)
    cut = rem * (base + 1)
    small = codes // max(base + 1, 1)
    big = rem + (codes - cut) // max(base, 1)
    owner = jnp.where(codes < cut, small, big)
    return jnp.where((codes >= 0) & (codes < size), owner, n)


def sparse_reduce_scatter(idx, val, capacity: int, size: int,
                          operator: Operator = Operators.SUM,
                          axis_name: str = "mp4j"):
    """Key-union sparse reduce-scatter inside ``shard_map``: the union
    is reduced exactly like :func:`sparse_allreduce`, then each member
    KEEPS only the keys it owns under the block partition of the key
    space ``[0, size)`` (:func:`block_owner`), packed ascending into
    ``capacity`` SENTINEL/identity-padded slots.

    The placement rule is block-by-code, not the host backends'
    blake2b ``meta.key_partition``: in-jit there is no original key to
    hash, only its int code — and block ownership is exactly what a
    mesh-sharded parameter table (member r owns rows
    ``[r*V/n, (r+1)*V/n)``) needs from its gradient reduce-scatter.
    """
    oi, ov = sparse_allreduce(idx, val, capacity, operator, axis_name)
    me = flat_index(axis_name)
    mine = block_owner(oi, size, _axis_size(axis_name)) == me
    ident = jnp.asarray(operator.identity(ov.dtype), dtype=ov.dtype)
    keep_i = jnp.where(mine, oi, SENTINEL)
    keep_v = jnp.where(
        mine.reshape((capacity,) + (1,) * (ov.ndim - 1)), ov, ident)
    # repack the surviving entries to the front: dropped slots carry
    # SENTINEL and sort to the end (stably, preserving ascending order)
    return sort_by_key(keep_i, keep_v)


def sparse_allgather(idx, val, axis_name: str = "mp4j"):
    """Concatenate every member's (idx, val) entries and sort them by
    key code: the disjoint-union gather of the map family, in-jit.
    Output is ``[n * L]`` with all live entries ascending and SENTINEL
    padding at the end. Duplicate codes across members are RETAINED as
    adjacent entries (static shapes cannot raise data-dependently; feed
    the result to :func:`segment_reduce_sorted` to merge, which is
    exactly :func:`sparse_allreduce`)."""
    gi = lax.all_gather(idx, axis_name, axis=0, tiled=True)
    gv = lax.all_gather(val, axis_name, axis=0, tiled=True)
    return sort_by_key(gi, gv)


# ----------------------------------------------------------------------
# Host-side numpy twins of the segment-reduce kernels.
#
# The socket backend's columnar map plane (process_comm) merges
# (codes:int32, values:[n, *vshape]) column pairs with these instead of
# the per-key dict loop: same sorted-union + segment-reduce shape as the
# device kernels above, expressed over numpy so the CPU reference path
# and the TPU path share one merge algorithm. Bit-exactness contract:
# for two per-map-unique sorted streams concatenated LEFT column first,
# the stable sort keeps equal codes in (left, right) order and
# ``ufunc.reduceat`` applies the operator left-to-right — exactly
# ``op(acc[k], src[k])``, the dict loop's operand order, so the two
# paths agree bit-for-bit on every dtype.
# ----------------------------------------------------------------------
def np_sort_columns(codes, val):
    """Host twin of :func:`sort_by_key`: jointly sort ``(codes, val)``
    ascending by code with one stable argsort (payload rows ride a
    single take)."""
    order = np.argsort(codes, kind="stable")
    return codes[order], val[order]


def np_segment_reduce_sorted(codes, val, np_fn):
    """Host twin of :func:`segment_reduce_sorted` over a code-sorted
    stream: reduce runs of equal code with ``np_fn`` (a binary numpy
    ufunc — ``Operator.np_fn`` for the builtins), packing unique codes
    ascending. No sentinel padding: host shapes are dynamic."""
    if codes.size == 0:
        return codes, val
    head = np.empty(codes.size, bool)
    head[0] = True
    np.not_equal(codes[1:], codes[:-1], out=head[1:])
    starts = np.flatnonzero(head)
    if starts.size == codes.size:       # all unique: nothing to reduce
        return codes, val
    # dtype pinned: reduceat otherwise promotes narrow ints to the
    # platform int (np.sum rules), which would break the bit-exactness
    # contract with the per-key scalar merge (int32+int32 -> int32)
    return codes[starts], np_fn.reduceat(val, starts, axis=0,
                                         dtype=val.dtype)


def np_merge_sorted_columns(ca, va, cb, vb, np_fn):
    """Sorted-union merge of two code-sorted column pairs (each with
    unique codes): the vectorized replacement for the socket map path's
    per-key dict merge. ``(ca, va)`` is the ACCUMULATOR side — it is
    concatenated first, so shared codes reduce as ``np_fn(acc, src)``
    (see the section comment's bit-exactness contract)."""
    if ca.size == 0:
        return cb, vb
    if cb.size == 0:
        return ca, va
    codes = np.concatenate([ca, cb])
    val = np.concatenate([va, vb])
    return np_segment_reduce_sorted(*np_sort_columns(codes, val), np_fn)


def sparse_to_dense(idx, val, size: int,
                    operator: Operator = Operators.SUM):
    """Scatter (idx, val) into a dense [size] vector (identity-filled);
    SENTINEL slots are dropped."""
    ident = jnp.asarray(operator.identity(val.dtype), dtype=val.dtype)
    out = jnp.full((size,) + val.shape[1:], ident, dtype=val.dtype)
    safe = jnp.where(idx == SENTINEL, size, idx)
    return out.at[safe].set(val, mode="drop")
