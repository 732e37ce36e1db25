"""Device-level sparse collective ops (ops.sparse) on the virtual mesh."""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ytk_mp4j_tpu.operators import Operator, Operators
from ytk_mp4j_tpu.ops import sparse as sp
from ytk_mp4j_tpu.parallel import make_mesh


def run_sparse_allreduce(per_rank, capacity, operator, vshape=()):
    """per_rank: list of (idx list, val list) per rank."""
    n = len(per_rank)
    mesh = make_mesh(n)
    Lmax = max(len(i) for i, _ in per_rank)
    idx = np.full((n, Lmax), sp.SENTINEL, dtype=np.int32)
    ident = operator.identity(np.float64)
    val = np.full((n, Lmax) + vshape, ident, dtype=np.float64)
    for r, (ii, vv) in enumerate(per_rank):
        for j, (i, v) in enumerate(zip(ii, vv)):
            idx[r, j] = i
            val[r, j] = v

    @jax.jit
    @partial(shard_map, mesh=mesh, check_vma=False,
             in_specs=(P("mp4j"), P("mp4j")),
             out_specs=(P(None), P(None)))
    def f(i, v):
        return sp.sparse_allreduce(i[0], v[0], capacity, operator, "mp4j")

    oi, ov = f(idx, val)
    return np.asarray(oi), np.asarray(ov)


def test_sparse_allreduce_sum_union():
    per_rank = [([1, 5, 9], [1.0, 2.0, 3.0]),
                ([5, 7], [10.0, 20.0]),
                ([1, 9, 11], [100.0, 200.0, 300.0])]
    oi, ov = run_sparse_allreduce(per_rank, capacity=8, operator=Operators.SUM)
    got = {int(i): float(v) for i, v in zip(oi, ov) if i != sp.SENTINEL}
    assert got == {1: 101.0, 5: 12.0, 7: 20.0, 9: 203.0, 11: 300.0}


def test_sparse_allreduce_exact_capacity():
    # union exactly fills capacity; sentinel segment must be dropped
    per_rank = [([0, 1], [1.0, 2.0]), ([2, 3], [3.0, 4.0])]
    oi, ov = run_sparse_allreduce(per_rank, capacity=4,
                                  operator=Operators.SUM)
    got = {int(i): float(v) for i, v in zip(oi, ov) if i != sp.SENTINEL}
    assert got == {0: 1.0, 1: 2.0, 2: 3.0, 3: 4.0}


def test_sparse_allreduce_max():
    per_rank = [([3, 4], [5.0, -2.0]), ([3, 6], [1.0, 9.0])]
    oi, ov = run_sparse_allreduce(per_rank, capacity=4,
                                  operator=Operators.MAX)
    got = {int(i): float(v) for i, v in zip(oi, ov) if i != sp.SENTINEL}
    assert got == {3: 5.0, 4: -2.0, 6: 9.0}


def test_sparse_allreduce_custom_operator():
    absmax = Operator.custom(
        "ABSMAX", lambda x, y: jnp.where(jnp.abs(x) >= jnp.abs(y), x, y),
        0.0)
    per_rank = [([0, 2], [-5.0, 1.0]), ([0, 2], [3.0, -4.0]),
                ([7], [2.0])]
    oi, ov = run_sparse_allreduce(per_rank, capacity=4, operator=absmax)
    got = {int(i): float(v) for i, v in zip(oi, ov) if i != sp.SENTINEL}
    assert got == {0: -5.0, 2: -4.0, 7: 2.0}


def test_sparse_allreduce_vector_values():
    per_rank = [([2], [[1.0, 2.0]]), ([2, 4], [[10.0, 20.0], [5.0, 6.0]])]
    oi, ov = run_sparse_allreduce(per_rank, capacity=4,
                                  operator=Operators.SUM, vshape=(2,))
    got = {int(i): list(v) for i, v in zip(oi, ov) if i != sp.SENTINEL}
    assert got == {2: [11.0, 22.0], 4: [5.0, 6.0]}


def test_block_owner_matches_meta():
    from ytk_mp4j_tpu import meta

    for size, n in ((10, 3), (8, 8), (7, 8), (100, 4), (5, 2)):
        codes = jnp.arange(size, dtype=jnp.int32)
        got = np.asarray(jax.jit(
            lambda c: sp.block_owner(c, size, n))(codes))
        want = [meta.owner_of(i, 0, size, n) for i in range(size)]
        np.testing.assert_array_equal(got, want)
    # sentinel / out-of-range codes map to n (maskable)
    codes = jnp.array([sp.SENTINEL, -1, 10], dtype=jnp.int32)
    got = np.asarray(sp.block_owner(codes, 10, 4))
    np.testing.assert_array_equal(got, [4, 4, 4])


def _stage_per_rank(per_rank, vshape=()):
    n = len(per_rank)
    Lmax = max(len(i) for i, _ in per_rank)
    idx = np.full((n, Lmax), sp.SENTINEL, dtype=np.int32)
    val = np.zeros((n, Lmax) + vshape, dtype=np.float64)
    for r, (ii, vv) in enumerate(per_rank):
        for j, (i, v) in enumerate(zip(ii, vv)):
            idx[r, j] = i
            val[r, j] = v
    return idx, val


@pytest.mark.parametrize("n,size,capacity", [(4, 20, 32), (8, 13, 16),
                                             (3, 7, 8)])
def test_sparse_reduce_scatter(n, size, capacity, rng):
    """Each member ends with exactly its block-owned share of the
    reduced union, packed ascending; shares are disjoint and cover the
    union. ``capacity >= size`` bounds the union like real callers do."""
    from ytk_mp4j_tpu import meta

    per_rank = []
    for r in range(n):
        k = int(rng.integers(1, size))
        ii = sorted(rng.choice(size, k, replace=False).tolist())
        per_rank.append((ii, [float(r * 100 + i) for i in ii]))
    idx, val = _stage_per_rank(per_rank)
    mesh = make_mesh(n)

    @jax.jit
    @partial(shard_map, mesh=mesh, check_vma=False,
             in_specs=(P("mp4j"), P("mp4j")),
             out_specs=(P("mp4j"), P("mp4j")))
    def f(i, v):
        oi, ov = sp.sparse_reduce_scatter(i[0], v[0], capacity, size,
                                          Operators.SUM, "mp4j")
        return oi[None], ov[None]

    oi, ov = map(np.asarray, f(idx, val))
    want = {}
    for ii, vv in per_rank:
        for i, v in zip(ii, vv):
            want[i] = want.get(i, 0.0) + v
    seen = {}
    for r in range(n):
        live = oi[r] != sp.SENTINEL
        codes = oi[r][live]
        assert (np.diff(codes) > 0).all()       # ascending, deduped
        for c, v in zip(codes, ov[r][live]):
            assert meta.owner_of(int(c), 0, size, n) == r
            assert int(c) not in seen           # disjoint shares
            seen[int(c)] = float(v)
    assert seen == want


def test_sparse_allgather():
    per_rank = [([5, 9], [1.0, 2.0]),
                ([1], [3.0]),
                ([5, 7], [4.0, 5.0])]   # 5 duplicates across members
    idx, val = _stage_per_rank(per_rank)
    mesh = make_mesh(3)

    @jax.jit
    @partial(shard_map, mesh=mesh, check_vma=False,
             in_specs=(P("mp4j"), P("mp4j")),
             out_specs=(P(None), P(None)))
    def f(i, v):
        return sp.sparse_allgather(i[0], v[0], "mp4j")

    oi, ov = map(np.asarray, f(idx, val))
    live = oi != sp.SENTINEL
    pairs = sorted(zip(oi[live].tolist(), ov[live].tolist()))
    assert pairs == [(1, 3.0), (5, 1.0), (5, 4.0), (7, 5.0), (9, 2.0)]
    # sentinel padding sits at the end
    assert not live[live.argmin():].any() or live.all()


def test_sparse_allgather_then_reduce_is_allreduce():
    """The documented composition: allgather + segment_reduce_sorted
    == sparse_allreduce."""
    per_rank = [([2, 4], [1.0, 2.0]), ([2, 6], [10.0, 20.0])]
    idx, val = _stage_per_rank(per_rank)
    mesh = make_mesh(2)

    @jax.jit
    @partial(shard_map, mesh=mesh, check_vma=False,
             in_specs=(P("mp4j"), P("mp4j")),
             out_specs=(P(None), P(None)))
    def f(i, v):
        gi, gv = sp.sparse_allgather(i[0], v[0], "mp4j")
        return sp.segment_reduce_sorted(gi, gv, 4, Operators.SUM)

    oi, ov = map(np.asarray, f(idx, val))
    got = {int(i): float(v) for i, v in zip(oi, ov) if i != sp.SENTINEL}
    assert got == {2: 11.0, 4: 2.0, 6: 20.0}


def test_sparse_to_dense():
    idx = jnp.array([0, 3, sp.SENTINEL], dtype=jnp.int32)
    val = jnp.array([1.5, 2.5, 99.0])
    out = sp.sparse_to_dense(idx, val, 5)
    np.testing.assert_allclose(np.asarray(out), [1.5, 0, 0, 2.5, 0])


def test_pad_to():
    idx = jnp.array([4, 2], dtype=jnp.int32)
    val = jnp.array([1.0, 2.0])
    pi, pv = sp.pad_to(idx, val, 5, Operators.PROD)
    assert pi.shape == (5,) and pv.shape == (5,)
    assert int(pi[4]) == sp.SENTINEL
    assert float(pv[3]) == 1.0  # PROD identity
    with pytest.raises(ValueError):
        sp.pad_to(idx, val, 1)


def test_sort_by_key_wide_payload_fallback(rng):
    """Payload rows wider than _MAX_SORT_PAYLOAD_COLS take the
    argsort+gather fallback; results must match the sort-network path's
    contract exactly (pairs preserved, keys ascending)."""
    L, W = 64, sp._MAX_SORT_PAYLOAD_COLS + 2
    idx = rng.integers(0, 30, L).astype(np.int32)
    val = rng.standard_normal((L, W)).astype(np.float32)
    si, sv = jax.jit(sp.sort_by_key)(jnp.asarray(idx), jnp.asarray(val))
    si, sv = np.asarray(si), np.asarray(sv)
    assert (si[1:] >= si[:-1]).all()
    order = np.argsort(idx, kind="stable")
    np.testing.assert_array_equal(si, idx[order])
    np.testing.assert_array_equal(sv, val[order])


@pytest.mark.parametrize("shape", [(), (0,), (3,), (5, 2),
                                   (sp._MAX_SORT_PAYLOAD_COLS + 2,)],
                         ids=["flat", "empty", "narrow", "two-dims", "wide"])
def test_sort_by_key_keeps_the_head_of_the_sorted_stream(rng, shape):
    """``keep=n`` is the first n entries of what the call without it
    returns, whichever way the payload rides (the sort network, or the
    argsort and a row gather of those n rows alone): a caller whose
    last entries are SENTINEL padding sheds them there."""
    L, n = 64, 40
    idx = rng.integers(0, 30, L).astype(np.int32)
    idx[rng.choice(L, L - n, replace=False)] = sp.SENTINEL
    val = rng.standard_normal((L,) + shape).astype(np.float32)
    want_i, want_v = sp.sort_by_key(jnp.asarray(idx), jnp.asarray(val))
    si, sv = jax.jit(lambda i, v: sp.sort_by_key(i, v, keep=n))(
        jnp.asarray(idx), jnp.asarray(val))
    assert si.shape == (n,) and sv.shape == (n,) + shape
    np.testing.assert_array_equal(si, np.asarray(want_i)[:n])
    np.testing.assert_array_equal(sv, np.asarray(want_v)[:n])
    assert (np.asarray(si) != sp.SENTINEL).all()
    assert (np.asarray(want_i)[n:] == sp.SENTINEL).all()


def test_sparse_allreduce_wide_vector_values(rng):
    """Map-of-arrays operands wider than the sort-payload cutoff ride
    the fallback inside sparse_allreduce; differential vs numpy."""
    W = sp._MAX_SORT_PAYLOAD_COLS + 5
    v0 = rng.standard_normal(W)
    v1 = rng.standard_normal(W)
    v2 = rng.standard_normal(W)
    per_rank = [([3], [v0]), ([3, 1], [v1, v2])]
    oi, ov = run_sparse_allreduce(per_rank, capacity=4,
                                  operator=Operators.SUM, vshape=(W,))
    got = {int(i): v for i, v in zip(oi, ov) if i != sp.SENTINEL}
    assert set(got) == {1, 3}
    np.testing.assert_allclose(got[3], v0 + v1, rtol=1e-6)
    np.testing.assert_allclose(got[1], v2, rtol=1e-6)


def test_custom_operator_shadowing_builtin_name():
    """A user operator NAMED like a builtin must run its own fn through
    the generic segment reduction — not silently inherit segment_max
    (round-4 review regression: the reducer table was keyed by name)."""
    absmax = Operator.custom(
        "MAX", lambda a, b: jnp.where(jnp.abs(a) >= jnp.abs(b), a, b),
        0.0)
    per_rank = [([3], [-5.0]), ([3], [3.0])]
    oi, ov = run_sparse_allreduce(per_rank, capacity=2, operator=absmax)
    got = {int(i): float(v) for i, v in zip(oi, ov) if i != sp.SENTINEL}
    assert got == {3: -5.0}, got          # builtin MAX would say 3.0


@pytest.mark.parametrize("L,tile,n_live", [
    (24, 8, 0),         # a list of sentinels: no trip
    (24, 8, 5),         # fewer than one tile
    (24, 8, 16),        # exactly two tiles: the third is never reached
    (24, 8, 17),        # one more than a multiple
    (24, 8, 24),        # every entry live: every tile
    (20, 8, 20),        # a length the tile does not divide, all live
    (20, 8, 17),        # ... and its padded last tile reached half full
    (5, 8, 3),          # a list shorter than a tile
])
def test_fold_live_tiles_visits_the_live_prefix_once(L, tile, n_live, rng):
    """Against a numpy loop over the list: each live entry is handed to
    the body once, with its own value, and of the sentinel tail only what
    fills the last tile reached."""
    size = 64
    idx = np.full(L, sp.SENTINEL, np.int32)
    idx[:n_live] = np.sort(rng.choice(size, n_live, replace=False))
    val = np.zeros((L, 3), np.float32)
    val[:n_live] = rng.standard_normal((n_live, 3))

    def body(carry, ti, tv):
        seen, total, trips, dead = carry
        assert ti.shape == (tile,) and tv.shape == (tile, 3)
        live = ti != sp.SENTINEL
        at = jnp.where(live, ti, size)
        return (seen.at[at].add(1, mode="drop"),
                total.at[at].add(tv, mode="drop"),
                trips + 1, dead + jnp.sum(~live, dtype=jnp.int32))

    init = (jnp.zeros(size, jnp.int32), jnp.zeros((size, 3), jnp.float32),
            jnp.int32(0), jnp.int32(0))
    seen, total, trips, dead = jax.jit(
        lambda i, v: sp.fold_live_tiles(i, v, tile, body, init))(idx, val)

    want_seen = np.zeros(size, np.int32)
    want_total = np.zeros((size, 3), np.float32)
    want_trips = 0
    for start in range(0, n_live, tile):            # the live prefix alone
        for j in range(start, min(start + tile, n_live)):
            want_seen[idx[j]] += 1
            want_total[idx[j]] += val[j]
        want_trips += 1
    assert np.array_equal(np.asarray(seen), want_seen)
    assert want_seen.max(initial=0) <= 1
    assert np.array_equal(np.asarray(total), want_total)
    assert int(trips) == want_trips == -(-n_live // tile)
    assert int(dead) == want_trips * tile - n_live


# ------------------- what the merge's reducer is told of its segment ids
@pytest.mark.parametrize("operator,reducer", [
    (Operators.SUM, jax.ops.segment_sum),
    (Operators.PROD, jax.ops.segment_prod),
    (Operators.MAX, jax.ops.segment_max),
    (Operators.MIN, jax.ops.segment_min)], ids=["SUM", "PROD", "MAX", "MIN"])
@pytest.mark.parametrize("L,n_keys,dead,capacity", [
    (40, 9, 6, 40),     # runs of duplicates, a sentinel tail, empty segments
    (40, 9, 0, 40),     # no sentinel at all
    (40, 40, 0, 40),    # every key its own run: a full union
    (40, 5, 40, 8),     # a list of sentinels
    (40, 30, 3, 16),    # more runs than capacity: the overflow is dropped
], ids=["duplicates", "all_live", "full_union", "all_dead", "overflow"])
def test_segment_reduce_told_sorted_is_the_untold_reduction(
        monkeypatch, operator, reducer, L, n_keys, dead, capacity, rng):
    """Against the same function with the reducer told nothing, as it
    was: the same packed list, bit for bit (and the reduction itself)."""
    keys = rng.integers(0, n_keys, L).astype(np.int32)
    if n_keys == L:
        keys = rng.permutation(L).astype(np.int32)
    keys[rng.choice(L, dead, replace=False)] = sp.SENTINEL
    val = rng.uniform(0.5, 1.5, (L, 3)).astype(np.float32)
    si, sv = sp.sort_by_key(jnp.asarray(keys), jnp.asarray(val))
    gi, gv = jax.jit(lambda i, v: sp.segment_reduce_sorted(
        i, v, capacity, operator))(si, sv)
    monkeypatch.setitem(
        sp._SEGMENT_REDUCERS, operator,
        lambda v, s, num_segments, indices_are_sorted: reducer(
            v, s, num_segments=num_segments))
    wi, wv = jax.jit(lambda i, v: sp.segment_reduce_sorted(
        i, v, capacity, operator))(si, sv)
    assert np.array_equal(np.asarray(gi), np.asarray(wi))
    assert np.array_equal(np.asarray(gv).view(np.int32),
                          np.asarray(wv).view(np.int32))
    # and both are the reduction: against numpy, key by key
    live = np.unique(keys[keys != sp.SENTINEL])[:capacity]
    assert np.array_equal(np.asarray(gi)[:len(live)], live)
    assert np.all(np.asarray(gi)[len(live):] == sp.SENTINEL)
    for k, row in zip(live, np.asarray(gv)):
        np.testing.assert_allclose(
            row, operator.np_fn.reduce(val[keys == k], axis=0), rtol=1e-5)


def test_segment_reduce_tells_its_reducer_the_ids_ascend():
    """``seg`` is a cumulative sum; the scatter-add under ``segment_sum``
    carries the promise, so on the TPU XLA neither sorts the ids itself
    nor gathers the rows by that order a second time. The index set
    beside it stays untold: 0.375 ms on the chip either way (PR 35)."""
    jaxpr = jax.make_jaxpr(lambda i, v: sp.segment_reduce_sorted(i, v, 8))(
        jnp.zeros(8, jnp.int32), jnp.zeros((8, 3)))

    def eqns(jaxpr):
        for e in jaxpr.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)

    told = {e.primitive.name: e.params["indices_are_sorted"]
            for e in eqns(jaxpr.jaxpr)
            if e.primitive.name.startswith("scatter")}
    assert told == {"scatter-add": True, "scatter": False}
