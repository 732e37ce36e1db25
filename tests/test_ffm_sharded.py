"""The FFM with its table sharded by feature (``table_sharding="sharded"``):
the step in the block form, the exchange by owner through
``ops/collectives.all_to_all``, and the shard-local conversions.

Held to a float64 reference that knows nothing of owners, blocks or
rounds: a plain SGD step on the whole public ``(w0, w, V)``, every
parameter updated. Tolerances (f32 steps against it; table values of
~0.1, which three steps move by up to 6e-3): rtol 2e-5 and atol 2e-7,
where three steps measured here on the CPU differ from the reference by
at most 5.8e-8 on a table value (half an f32 ulp of 0.5 is 3e-8) and
1.3e-7 of the loss: room for another backend's order of summation, and a
hundred times below what one dropped or doubled gradient row moves
(1e-4 and up).
"""

import re
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.models import fm as fm_mod
from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer
from ytk_mp4j_tpu.obs import spans
from ytk_mp4j_tpu.ops import collectives
from ytk_mp4j_tpu.parallel import make_hier_mesh, make_mesh

N = 4               # members
RTOL, ATOL = 2e-5, 2e-7
COLLECTIVE = re.compile(
    r"all_to_all|all_gather|all_reduce|collective_permute|reduce_scatter"
    r"|all-to-all|all-gather|all-reduce|collective-permute|reduce-scatter")


# ------------------------------------------------------------ reference
def reference_step(params, chunk, cfg, sw=None):
    """One SGD step in float64 on the whole public ``(w0, w, V)``:
    ``(loss, (w0, w, V))``. Loops over rows and slot pairs as the model
    is written down (module docstring of ``models/fm.py``)."""
    w0, w, V = (np.asarray(p, np.float64) for p in params)
    feats, fields, vals, y = chunk
    rows, K = feats.shape
    sw = np.ones(rows) if sw is None else np.asarray(sw, np.float64)
    nf = cfg.n_fields if cfg.model == "ffm" else 1
    g0, gw, gV = 0.0, np.zeros_like(w), np.zeros_like(V)
    loss = 0.0
    for i in range(rows):
        x = vals[i].astype(np.float64)
        z = w0 + np.sum(w[feats[i]] * x)
        pairs = [(a, b) for a in range(K) for b in range(a + 1, K)]
        for a, b in pairs:
            ra = feats[i, a] * nf + (fields[i, b] if nf > 1 else 0)
            rb = feats[i, b] * nf + (fields[i, a] if nf > 1 else 0)
            z += V[ra] @ V[rb] * x[a] * x[b]
        loss += sw[i] * (max(z, 0) - z * y[i] + np.log1p(np.exp(-abs(z))))
        dz = sw[i] * (1.0 / (1.0 + np.exp(-z)) - y[i])
        g0 += dz
        np.add.at(gw, feats[i], dz * x)
        for a, b in pairs:
            ra = feats[i, a] * nf + (fields[i, b] if nf > 1 else 0)
            rb = feats[i, b] * nf + (fields[i, a] if nf > 1 else 0)
            va, vb = V[ra].copy(), V[rb].copy()
            gV[ra] += dz * vb * x[a] * x[b]
            gV[rb] += dz * va * x[a] * x[b]
    denom = max(sw.sum(), 1.0)
    lr, l2 = cfg.learning_rate, cfg.l2
    return loss / denom, (w0 - lr * g0 / denom,
                          w - lr * (gw / denom + l2 * w),
                          V - lr * (gV / denom + l2 * V))


def _cfg(n_features=64, model="ffm", **kw):
    kw = dict(dict(learning_rate=0.5, init_scale=0.1, l2=1e-3), **kw)
    return FMConfig(n_features=n_features, n_fields=4, k=4, max_nnz=4,
                    model=model, **kw)


def _chunk(rng, rows, low, high):
    """``rows`` instances of four slots, one feature a field, drawn from
    [low, high); vals in (0.1, 1.1)."""
    feats = rng.integers(low, high, (rows, 4)).astype(np.int32)
    fields = np.tile(np.arange(4, dtype=np.int32), (rows, 1))
    vals = (rng.random((rows, 4)) + 0.1).astype(np.float32)
    y = rng.integers(0, 2, rows).astype(np.float32)
    return feats, fields, vals, y


def _sharded(cfg, mesh=None):
    return FMTrainer(cfg, mesh=mesh if mesh is not None else make_mesh(N),
                     sparse_grads=True, table_sharding="sharded")


def _host(tr, params):
    return (float(params[0]), np.asarray(params[1]), tr.full_table(params))


def _assert_params(got, want):
    for g, w, name in zip(got, want, ("w0", "w", "V")):
        np.testing.assert_allclose(g, w, rtol=RTOL, atol=ATOL, err_msg=name)


# ------------------------------------------------- (a) against float64
@pytest.mark.parametrize("model", ["ffm", "fm"])
def test_three_sharded_steps_equal_the_float64_reference(rng, model):
    cfg = _cfg(model=model)
    tr = _sharded(cfg)
    chunks = [_chunk(rng, 24, 0, 64) for _ in range(3)]
    params = tr.init_params(3)
    want = _host(tr, params)
    got, losses = tr.fit_stream(iter(chunks), params=params, batch_rows=24)
    want_losses = []
    for chunk in chunks:
        loss, want = reference_step(want, chunk, cfg)
        want_losses.append(loss)
    np.testing.assert_allclose(losses, want_losses, rtol=RTOL)
    _assert_params(_host(tr, got), want)
    assert tr.exchange_rounds_ == 3        # one round a step


# --------------------------------------- (b) sharded against replicated
@pytest.mark.parametrize("mesh", ["flat", "hier"])
def test_sharded_and_replicated_steps_agree(rng, mesh):
    cfg = _cfg()
    make = (partial(make_hier_mesh, 2, 2) if mesh == "hier"
            else partial(make_mesh, N))
    chunks = [_chunk(rng, 32, 0, 64) for _ in range(4)]
    rep = FMTrainer(cfg, mesh=make(), sparse_grads=True)
    p_rep, l_rep = rep.fit_stream(iter(chunks), seed=5, batch_rows=32)
    sh = _sharded(cfg, make())
    p_sh, l_sh = sh.fit_stream(iter(chunks), seed=5, batch_rows=32)
    np.testing.assert_allclose(l_sh, l_rep, rtol=1e-6, atol=1e-7)
    _assert_params(_host(sh, p_sh),
                   (float(p_rep[0]), np.asarray(p_rep[1]),
                    np.asarray(p_rep[2])))


# ------------------------------------------ (c) whatever the owners' loads
def _step_with_cap(tr, cap):
    """The trainer's step with ``cap`` ids a member an owner a round: the
    function's own argument, under the shard_map ``_build_step`` gives
    it."""
    axes = tr.axes
    step_fn = partial(fm_mod.train_step_sparse_sharded, cfg=tr.cfg,
                      n=tr.n_shards, cap=cap, axis_name=axes)
    pspec = (P(), P(axes), P())

    @partial(jax.shard_map, mesh=tr.mesh, check_vma=False,
             in_specs=(pspec,) + (P(axes),) * 6, out_specs=(pspec, P()))
    def step(params, *batch):
        return step_fn(params, tuple(a[0] for a in batch))

    return jax.jit(step)


LOADS = {
    # (n_features, ids drawn from, cap or None for the trainer's own,
    #  rounds a step)
    "even": (64, (0, 64), None, 1),
    "one-owner": (64, (16, 32), None, 1),           # member 1 owns it all
    "an-owner-idle": (64, (0, 48), None, 1),        # member 3: nothing
    "ragged-features": (61, (0, 61), None, 1),      # 61 over 4 members
    "second-round": (64, (0, 64), 4, None),         # 4 ids an owner a round
    "second-round-one-owner": (64, (16, 32), 4, None),
}


@pytest.mark.parametrize("load", sorted(LOADS))
def test_nothing_is_dropped_whatever_the_owners_loads(rng, load):
    n_features, (low, high), cap, rounds = LOADS[load]
    cfg = _cfg(n_features)
    tr = _sharded(cfg)
    chunk = _chunk(rng, 32, low, high)
    params = tr.init_params(1)
    want_loss, want = reference_step(_host(tr, params), chunk, cfg)
    if cap is None:
        got, losses = tr.fit_stream(iter([chunk]), params=params,
                                    batch_rows=32)
        loss, ran = losses[0], tr.exchange_rounds_
    else:
        state = tr._enter(params)
        data = tr.shard_data(*chunk)
        state, loss = _step_with_cap(tr, cap)(state, *data)
        ran = int(state[2])
        got = tr._leave(state)
        # the fullest (member, owner) pair of this chunk, in rounds of cap
        per = chunk[0].reshape(N, -1)
        B = tr.n_features_padded // N
        most = max(np.unique(ids[ids // B == m]).size
                   for ids in per for m in range(N))
        rounds = -(-most // cap)
        assert rounds >= 2
    assert ran == rounds
    np.testing.assert_allclose(loss, want_loss, rtol=RTOL)
    _assert_params(_host(tr, got), want)


def test_a_feature_two_members_hold_gets_both_gradients(rng):
    """Every member's rows hold feature 5 and nothing else in slot 0: its
    block's gradient is the sum over all four members' lists."""
    cfg = _cfg(l2=0.0)
    tr = _sharded(cfg)
    chunk = _chunk(rng, 16, 0, 64)
    chunk[0][:, 0] = 5
    params = tr.init_params(2)
    want_loss, want = reference_step(_host(tr, params), chunk, cfg)
    got, losses = tr.fit_stream(iter([chunk]), params=params, batch_rows=16)
    np.testing.assert_allclose(losses[0], want_loss, rtol=RTOL)
    _assert_params(_host(tr, got), want)


# --------------------------------------------------- (d) padding rows
def test_padding_rows_of_a_short_chunk_touch_nothing(rng):
    cfg = _cfg(l2=0.0)
    tr = _sharded(cfg)
    chunk = _chunk(rng, 10, 1, 32)          # padded to 16: rows of weight 0
    params = tr.init_params(4)
    before = _host(tr, params)
    want_loss, want = reference_step(before, chunk, cfg)
    got, losses = tr.fit_stream(iter([chunk]), params=params, batch_rows=16)
    got = _host(tr, got)
    np.testing.assert_allclose(losses[0], want_loss, rtol=RTOL)
    _assert_params(got, want)
    # the padding rows carry feature 0 in every slot, which no real row
    # holds: its parameters keep their bits, as do those of every other
    # feature the chunk lacks
    lacks = np.setdiff1d(np.arange(64), chunk[0])
    assert 0 in lacks
    np.testing.assert_array_equal(got[1][lacks], before[1][lacks])
    rows = (lacks[:, None] * 4 + np.arange(4)).reshape(-1)
    np.testing.assert_array_equal(got[2][rows], before[2][rows])


# -------------------------------------------- (e) 2n chunks are n and n
def test_two_streams_of_n_chunks_are_one_of_2n(rng):
    cfg = _cfg()
    chunks = [_chunk(rng, 16, 0, 64) for _ in range(4)]
    one = _sharded(cfg)
    p_one, l_one = one.fit_stream(iter(chunks), seed=2, batch_rows=16)
    assert one.exchange_rounds_ == 4
    two = _sharded(cfg)
    half, l_a = two.fit_stream(iter(chunks[:2]), seed=2, batch_rows=16)
    p_two, l_b = two.fit_stream(iter(chunks[2:]), params=half, batch_rows=16)
    assert two.exchange_rounds_ == 2       # the last call's
    np.testing.assert_array_equal(np.concatenate([l_a, l_b]), l_one)
    for a, b in zip(_host(one, p_one), _host(two, p_two)):
        np.testing.assert_array_equal(a, b)


def test_the_narrow_span_says_how_many_rounds_ran(rng):
    spans.clear()
    tr = _sharded(_cfg())
    tr.fit_stream(iter([_chunk(rng, 16, 0, 64)] * 3), batch_rows=16)
    said = [s[6] for s in spans.snapshot() if s[0] == "mp4j.stream.narrow"]
    assert said == [{"exchange_rounds": 3}]
    # a replicated table has no exchange
    rep = FMTrainer(_cfg(), mesh=make_mesh(N), sparse_grads=True)
    rep.fit_stream(iter([_chunk(rng, 16, 0, 64)]), batch_rows=16)
    assert rep.exchange_rounds_ is None


# ------------------------------------------------------ (f) all_to_all
def _all_to_all(mesh, axes, x, **kw):
    @partial(jax.shard_map, mesh=mesh, in_specs=P(axes), out_specs=P(axes),
             check_vma=False)
    def run(x):
        return collectives.all_to_all(x[0], axes, **kw)[None]

    return jax.jit(run)


@pytest.mark.parametrize("members", [1, 2, 4, "hier"])
def test_all_to_all_is_the_transpose_of_who_holds_what(rng, members):
    if members == "hier":
        mesh, axes, n = make_hier_mesh(2, 2), ("inter", "intra"), 4
    else:
        mesh, axes, n = make_mesh(members), "mp4j", members
    x = rng.standard_normal((n, n, 3, 5)).astype(np.float32)   # [i, j, ...]
    got = np.asarray(_all_to_all(mesh, axes, x)(x))
    # member i's j-th slice arrives as member j's i-th
    np.testing.assert_array_equal(got, np.swapaxes(x, 0, 1))
    # tiled: the member axis folded into the rows
    flat = x.reshape(n, n * 3, 5)
    got = np.asarray(_all_to_all(mesh, axes, flat, tiled=True)(flat))
    np.testing.assert_array_equal(
        got, np.swapaxes(x, 0, 1).reshape(n, n * 3, 5))
    # other axes: split the last, concatenate on the first
    y = rng.standard_normal((n, 3, n)).astype(np.float32)
    got = np.asarray(_all_to_all(mesh, axes, y, split_axis=1,
                                 concat_axis=0)(y))
    assert got.shape == (n, n, 3)
    np.testing.assert_array_equal(got, np.transpose(y, (2, 0, 1)))


def test_all_to_all_has_its_scope_and_refuses_a_bad_split():
    x = jnp.zeros((N, N, 8), jnp.float32)
    text = _all_to_all(make_mesh(N), "mp4j", x).lower(x).as_text(
        debug_info=True)
    assert re.search(r'loc\("(?:[^"]*/)?mp4j\.all_to_all[/"]', text)
    assert "all_to_all" in text
    bad = jnp.zeros((N, N + 1, 8), jnp.float32)
    with pytest.raises(Mp4jError, match="all_to_all"):
        _all_to_all(make_mesh(N), "mp4j", bad)(bad)


def test_models_exchange_through_the_library_only():
    import inspect

    source = inspect.getsource(fm_mod)
    assert "lax.all_to_all" not in source
    assert source.count("collectives.all_to_all(") >= 5


# ----------------------------------------------------- (g) conversions
@pytest.mark.parametrize("n_features,model", [(64, "ffm"), (61, "ffm"),
                                              (61, "fm")])
def test_conversions_round_trip_where_the_features_rest(rng, n_features,
                                                        model):
    cfg = _cfg(n_features, model)
    tr = _sharded(cfg)
    w0, w, V = tr.init_params(6)
    params = tr._place_params(
        (jnp.float32(0.25),
         jnp.asarray(rng.standard_normal(n_features), jnp.float32), V))
    state = tr._enter(params)
    B = tr.n_features_padded // N
    assert state[1].shape == (tr.n_features_padded, fm_mod._block_width(cfg))
    assert {s.data.shape[0] for s in state[1].addressable_shards} == {B}
    assert int(state[2]) == 0
    # a feature's block: its vectors' entries and its weight, where
    # ``_field_columns`` marks them
    T, table = np.asarray(state[1]), tr.full_table(params)
    nf = tr.n_rows // n_features
    f = n_features - 1                      # on the last member
    if model == "ffm":
        stride, wcol = fm_mod._block_stride(cfg), fm_mod._weight_column(cfg)
        for fl in range(nf):
            np.testing.assert_array_equal(
                T[f, fl + stride * np.arange(cfg.k)], table[f * nf + fl])
        assert T[f, wcol] == np.asarray(params[1])[f]
    else:
        np.testing.assert_array_equal(T[f, :cfg.k], table[f])
        assert T[f, cfg.k] == np.asarray(params[1])[f]
    assert not T[n_features:].any()         # the padding features: zeros
    back = tr._leave(state)
    for a, b in zip(back, params):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert back[2].sharding == params[2].sharding


def test_conversions_move_nothing_of_the_table_between_members():
    tr = _sharded(_cfg(61))
    params = tr._place_params(tr.init_params(0))
    widen, narrow = tr._build_converters()
    low_w, low_n = widen.lower(params), narrow.lower(tr._state_avals())
    # as written: no collective in either
    assert not COLLECTIVE.search(low_w.as_text())
    assert not COLLECTIVE.search(low_n.as_text())
    # as compiled: none in widen; narrow gathers the linear weights into
    # the replicated vector of the public form, and nothing else
    assert not COLLECTIVE.search(low_w.compile().as_text())
    found = re.findall(r"= (\S+) (all-\w+|collective-permute|"
                       r"reduce-scatter)(?:-start)?\(",
                       low_n.compile().as_text())
    assert [op for _, op in found] == ["all-gather"]
    assert found[0][0].startswith(f"f32[{tr.n_features_padded}]")


# ------------------------------------------------------------- refusals
def test_adagrad_on_a_sharded_table_says_what_is_missing():
    cfg = FMConfig(n_features=16, n_fields=2, k=2, max_nnz=2, model="ffm",
                   optimizer="adagrad")
    with pytest.raises(Mp4jError, match="accumulators in the sharded block"):
        FMTrainer(cfg, mesh=make_mesh(2), sparse_grads=True,
                  table_sharding="sharded")


def test_the_step_refuses_the_public_params(rng):
    tr = _sharded(_cfg())
    params = tr._place_params(tr.init_params(0))
    data = tr.shard_data(*_chunk(rng, 16, 0, 64))
    with pytest.raises(Mp4jError, match="table by feature"):
        tr._build_step(16)(params, *data)
