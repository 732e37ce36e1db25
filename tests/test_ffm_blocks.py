"""The replicated sparse FM/FFM step in block form: the table held by
feature, one gather and one scatter descriptor a (sample, feature), the
field vectors picked out of the block on the device and the feature's
linear weight in the block's last column.

Everything goes through the public surface (``fit`` / ``fit_stream`` on
the [n_rows, k] table) and is held to a float64 numpy SGD step written
here from the model's definition, slot pair by slot pair on the public
table's rows."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ytk_mp4j_tpu.models import fm as fm_mod
from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer
from ytk_mp4j_tpu.parallel import make_mesh

NFEAT, NFIELDS, KDIM, NNZ = 40, 5, 3, 6
LR = 0.2


def _np_step(cfg, params, feats, fields, vals, y, sw):
    """One float64 SGD step of the weighted mean logloss; FM or FFM."""
    w0, w, V = (np.asarray(p, np.float64) for p in params)
    nf = cfg.n_fields
    N, K = feats.shape
    sw = np.ones(N) if sw is None else np.asarray(sw, np.float64)
    denom = max(sw.sum(), 1.0)
    g0, gw, gV = 0.0, np.zeros_like(w), np.zeros_like(V)
    loss = 0.0
    for n in range(N):
        f, fl, x = feats[n], fields[n], vals[n].astype(np.float64)
        z = w0 + np.sum(w[f] * x)
        pairs = []
        for a in range(K):
            for b in range(a + 1, K):
                if cfg.model == "ffm":
                    ra, rb = f[a] * nf + fl[b], f[b] * nf + fl[a]
                else:
                    ra, rb = f[a], f[b]
                z += V[ra] @ V[rb] * x[a] * x[b]
                pairs.append((ra, rb, x[a] * x[b]))
        loss += sw[n] * (max(z, 0) - z * y[n] + np.log1p(np.exp(-abs(z))))
        dz = sw[n] * (1.0 / (1.0 + np.exp(-z)) - y[n]) / denom
        g0 += dz
        np.add.at(gw, f, dz * x)
        for ra, rb, xx in pairs:
            gV[ra] += dz * xx * V[rb]
            gV[rb] += dz * xx * V[ra]
    lr, l2 = cfg.learning_rate, cfg.l2
    return (loss / denom,
            (w0 - lr * g0, w - lr * (gw + l2 * w), V - lr * (gV + l2 * V)))


def _instances(rng, case, n=24):
    """(feats, fields, vals, sample_weight) for one named shape of
    traffic; fields are drawn so that each case has what its name says."""
    K = NNZ
    feats = rng.integers(0, NFEAT, (n, K)).astype(np.int32)
    fields = np.stack([rng.permutation(NFIELDS)[:K] if K <= NFIELDS
                       else rng.integers(0, NFIELDS, K)
                       for _ in range(n)]).astype(np.int32)
    vals = (rng.random((n, K)) + 0.5).astype(np.float32)
    sw = None
    if case == "field_twice":
        fields[:, 1] = fields[:, 0]         # two slots of a row, one field
        fields[:, 4] = fields[:, 0]
    elif case == "fields_absent":
        fields = rng.choice([0, 3], (n, K)).astype(np.int32)
    elif case == "padded_slots":
        vals[:, 3:] = 0.0                   # padding: value 0, any id
        vals[::3, 1] = 0.0
    elif case == "one_feature_many_rows":
        feats[:, 0] = 7
        feats[::2, 2] = 7                   # twice in one row as well
    elif case == "k_below_n_fields":
        feats, fields, vals = feats[:, :2], fields[:, :2], vals[:, :2]
    elif case == "sample_weight":
        sw = rng.integers(0, 4, n).astype(np.float32)
        sw[0] = 2.0
    elif case != "plain":
        raise AssertionError(case)
    return feats, fields, vals, sw


CASES = ["plain", "field_twice", "fields_absent", "padded_slots",
         "one_feature_many_rows", "k_below_n_fields", "sample_weight"]


def _cfg(model, **kw):
    args = dict(n_features=NFEAT, n_fields=NFIELDS, k=KDIM, max_nnz=NNZ,
                model=model, learning_rate=LR, init_scale=0.3)
    args.update(kw)
    return FMConfig(**args)


def _start(cfg, rng):
    n_rows = NFEAT * (cfg.n_fields if cfg.model == "ffm" else 1)
    return (np.float32(0.1),
            (0.1 * rng.standard_normal(NFEAT)).astype(np.float32),
            (0.3 * rng.standard_normal((n_rows, cfg.k))).astype(np.float32))


@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("model", ["ffm", "fm"])
def test_block_step_matches_a_float64_step(rng, model, case, n_shards):
    cfg = _cfg(model)
    feats, fields, vals, sw = _instances(rng, case)
    y = rng.integers(0, 2, feats.shape[0]).astype(np.float32)
    start = _start(cfg, rng)
    tr = FMTrainer(cfg, mesh=make_mesh(n_shards), sparse_grads=True)
    got, losses = tr.fit(feats, fields, vals, y, n_steps=1, params=start,
                         sample_weight=sw)
    want_loss, want = _np_step(cfg, start, feats, fields, vals, y, sw)
    np.testing.assert_allclose(losses[0], want_loss, rtol=2e-6)
    assert got[2].shape == start[2].shape
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), w, rtol=2e-5, atol=2e-7)


@pytest.mark.parametrize("l2", [0.0, 1e-2])
@pytest.mark.parametrize("capacity", [None, NFEAT, 17])
@pytest.mark.parametrize("model,k", [("ffm", KDIM), ("ffm", 4), ("fm", KDIM)])
def test_three_block_steps_with_decay_and_dedupe(rng, model, k, l2,
                                                 capacity):
    """Several steps, with the multiplicative l2 decay and with the
    merged list at its default length, at ``n_features`` and at a tight
    one: a capacity in FEATURES (17 holds the distinct features of both
    shards' batches here, which are merged after the all_gather). Feature
    8 is in every row, twice in every other one; the last slot of every
    row is padding (value 0, id 0) and no other slot holds feature 0.
    At k = 4 the runs fill the block's 128 columns and the weight's
    column is the tail of the last run."""
    cfg = _cfg(model, k=k, l2=l2)
    feats, fields, vals, _ = _instances(rng, "one_feature_many_rows")
    feats = 1 + feats % 15                  # at most 16 distinct features
    feats[:, -1], vals[:, -1] = 0, 0.0
    y = rng.integers(0, 2, feats.shape[0]).astype(np.float32)
    start = _start(cfg, rng)
    tr = FMTrainer(cfg, mesh=make_mesh(2), sparse_grads=True,
                   sparse_capacity=capacity)
    got, losses = tr.fit(feats, fields, vals, y, n_steps=3, params=start)
    want, want_losses = start, []
    for _ in range(3):
        loss, want = _np_step(cfg, want, feats, fields, vals, y, None)
        want_losses.append(loss)
    np.testing.assert_allclose(losses, want_losses, rtol=5e-6)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), w, rtol=5e-5, atol=5e-7)
    # the linear weight of every touched feature moved (to where the
    # float64 steps took it: above)
    touched = np.unique(feats[:, :-1])
    got_w = np.asarray(got[1])
    assert (got_w[touched] != start[1][touched]).all()
    # a padded slot's gradient is exactly 0.0: feature 0's weight sees
    # the decay alone, bit for bit
    w_pad = start[1][0]
    for _ in range(3):
        w_pad = w_pad * np.float32(1.0 - cfg.learning_rate * l2)
    assert got_w[0].view(np.uint32) == np.float32(w_pad).view(np.uint32)


# ------------------------- the update loop over the merged list (PR 39)
WIDE = 160      # features: all 24 x 6 slots of a batch can be distinct
TILE = 8


def _batch_holding(rng, case):
    """24 rows x 6 slots whose ids are what ``case`` says; the distinct
    count against a tile of 8: one, 144 (18 tiles), 21 (two tiles and
    five), 32 (four whole tiles), 5 (under one)."""
    feats, fields, vals, _ = _instances(rng, "plain")
    S = feats.size
    if case == "one_feature_in_every_slot":
        feats[:] = 7
    elif case == "padded_slots":
        # value 0, id 0, in the last two slots; no other slot holds 0
        feats = 1 + feats % 20
        feats[:, -2:], vals[:, -2:] = 0, 0.0
    else:
        distinct = {"every_slot_distinct": S, "two_tiles_and_five": 21,
                    "four_whole_tiles": 32, "under_one_tile": 5}[case]
        ids = rng.choice(WIDE, distinct, replace=False)
        feats = rng.permutation(np.concatenate(
            [ids, rng.choice(ids, S - distinct)])).reshape(
                feats.shape).astype(np.int32)
        assert np.unique(feats).size == distinct
    return feats, fields, vals


@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("tile", [TILE, 7], ids=["tile_8", "tile_7"])
@pytest.mark.parametrize("case", [
    "one_feature_in_every_slot", "every_slot_distinct",
    "two_tiles_and_five", "four_whole_tiles", "under_one_tile",
    "padded_slots"])
def test_the_merged_update_reaches_what_the_batch_holds(monkeypatch, rng,
                                                        case, tile,
                                                        n_shards):
    """The step sums a batch's slot gradients by feature and scatter-adds
    the merged list's live prefix a tile at a time: a float64 step's
    parameters whatever the distinct features number against the tile
    (one segment of all slots, every slot its own, a count the tile does
    not divide, whole tiles, fewer than one; a tile of 7 does not divide
    the list's 144 entries either), and every feature the batch does not
    hold keeps its bits. Padded slots (value 0, id 0) sum to a gradient of
    exactly 0.0: feature 0 keeps its bits too."""
    monkeypatch.setattr(fm_mod, "_UPDATE_TILE", tile)
    cfg = _cfg("ffm", n_features=WIDE)
    feats, fields, vals = _batch_holding(rng, case)
    y = rng.integers(0, 2, feats.shape[0]).astype(np.float32)
    start = (np.float32(0.1),
             (0.1 * rng.standard_normal(WIDE)).astype(np.float32),
             (0.3 * rng.standard_normal((WIDE * NFIELDS, KDIM))).astype(
                 np.float32))
    tr = FMTrainer(cfg, mesh=make_mesh(n_shards), sparse_grads=True)
    got, losses = tr.fit(feats, fields, vals, y, n_steps=1, params=start)
    want_loss, want = _np_step(cfg, start, feats, fields, vals, y, None)
    np.testing.assert_allclose(losses[0], want_loss, rtol=2e-6)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), w, rtol=2e-5, atol=2e-7)
    held = np.zeros(WIDE, bool)
    held[feats[vals > 0]] = True
    got_w, got_V = np.asarray(got[1]), np.asarray(got[2])
    assert (got_w[held] != start[1][held]).all()
    assert np.array_equal(got_w[~held].view(np.uint32),
                          start[1][~held].view(np.uint32))
    rows = np.repeat(~held, NFIELDS)
    assert np.array_equal(got_V[rows].view(np.uint32),
                          start[2][rows].view(np.uint32))


@pytest.mark.parametrize("capacity,tile,tiles", [
    (None, 144, 1), (NFEAT, NFEAT, 1), (17, 17, 1), (None, TILE, 18)],
    ids=["all_slots", "n_features", "17", "tile_8"])
def test_step_build_span_says_the_update_loops_tile(monkeypatch, rng,
                                                    capacity, tile, tiles):
    """``mp4j.step.build`` of the SGD step: the tile of the loop over the
    merged list (the whole list where that is shorter than
    ``_UPDATE_TILE``) and its trips when every slot holds another feature;
    ``sparse_capacity`` is the merged list's length."""
    from ytk_mp4j_tpu.obs import spans

    if tile == TILE:
        monkeypatch.setattr(fm_mod, "_UPDATE_TILE", TILE)
    cfg = _cfg("ffm", n_features=WIDE if capacity is None else NFEAT)
    feats, fields, vals, _ = _instances(rng, "plain")
    feats = 1 + feats % 15
    y = rng.integers(0, 2, feats.shape[0]).astype(np.float32)
    tr = FMTrainer(cfg, mesh=make_mesh(2), sparse_grads=True,
                   sparse_capacity=capacity)
    tr.fit(feats, fields, vals, y, n_steps=1)
    (build,) = [s[6] for s in spans.snapshot()
                if s[0] == "mp4j.step.build" and s[6].get("key") == 72
                and s[6].get("capacity") == (capacity or 144)][-1:]
    assert build["update_tile"] == tile and build["update_tiles"] == tiles
    assert build["optimizer"] == "sgd" and build["descriptors"] == 72


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize(
    "rows,slots,n_shards", [(128, 8, 1), (1024, 1, 1), (256, 8, 2)],
    ids=["128x8", "1024x1", "256x8-on-two"])
@pytest.mark.parametrize("model", ["ffm", "fm"])
def test_dead_rows_on_whole_1024s_change_no_bit(monkeypatch, rng, model,
                                                rows, slots, n_shards):
    """A member's chunk whose slots are a whole number of 1,024s is
    stepped with ``_DEAD_ROWS`` dead rows after it (``_with_dead_rows``:
    the table gather's index list is then one XLA pads itself). The rows
    weigh nothing and the merge drops their slots after its sort, every
    member's (``_merge_slots``): the same weights and table, to the bit,
    as the step without them, and feature 0, which the dead slots name
    and no live slot holds, keeps the bits it came with. The loss and
    the bias's gradient are sums over the rows, the same terms and eight
    of 0.0 after them, summed in f32 in the order the backend picks for
    a vector that much longer: the last bits may differ (the loss's does
    on the CPU, by one ulp)."""
    cfg = _cfg(model, max_nnz=slots)
    feats = rng.integers(1, NFEAT, (rows, slots)).astype(np.int32)
    fields = rng.integers(0, NFIELDS, (rows, slots)).astype(np.int32)
    vals = (rng.random((rows, slots)) + 0.5).astype(np.float32)
    vals[::5, 0] = 0.0                      # padded slots among the live
    sw = rng.integers(0, 3, rows).astype(np.float32)
    y = rng.integers(0, 2, rows).astype(np.float32)
    start = _start(cfg, rng)

    def one_step(dead):
        monkeypatch.setattr(fm_mod, "_DEAD_ROWS", dead)
        tr = FMTrainer(cfg, mesh=make_mesh(n_shards), sparse_grads=True)
        return tr.fit(feats, fields, vals, y, n_steps=1, params=start,
                      sample_weight=sw)

    assert fm_mod._DEAD_ROWS == 8
    assert fm_mod._dead_rows(rows // n_shards * slots) == 8
    (got, got_loss), (want, want_loss) = one_step(8), one_step(0)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6, atol=0)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=0)
    for g, w in zip(got[1:], want[1:]):
        assert np.array_equal(_bits(g), _bits(w))
    per = NFIELDS if model == "ffm" else 1
    assert np.array_equal(_bits(got[1])[0], _bits(start[1])[0])
    assert np.array_equal(_bits(got[2])[:per], _bits(start[2])[:per])
    assert not np.array_equal(_bits(got[1])[1:], _bits(start[1])[1:])


@pytest.mark.parametrize("model", ["ffm", "fm"])
def test_a_chunk_off_the_1024s_lowers_to_the_program_it_was(monkeypatch,
                                                            model):
    """100 rows of 8 slots are no whole number of 1,024s: the step takes
    no dead row and lowers to the same text whatever ``_DEAD_ROWS`` says;
    128 rows of 8 do, and the step is traced on 136."""
    cfg = _cfg(model, max_nnz=8)

    def lowered(rows, dead):
        monkeypatch.setattr(fm_mod, "_DEAD_ROWS", dead)
        tr = FMTrainer(cfg, mesh=make_mesh(1), sparse_grads=True)
        slots = (1, rows, 8)
        i32, f32, row = (jax.ShapeDtypeStruct(slots, jnp.int32),
                         jax.ShapeDtypeStruct(slots, jnp.float32),
                         jax.ShapeDtypeStruct(slots[:2], jnp.float32))
        return tr._build_step(rows * 8).lower(
            tr._state_avals(), i32, i32, f32, f32, row, row).as_text()

    assert lowered(100, 8) == lowered(100, 0)
    assert "108x8" not in lowered(100, 8)
    assert lowered(128, 8) != lowered(128, 0)
    assert "136x8" in lowered(128, 8) and "136x8" not in lowered(128, 0)


@pytest.mark.parametrize("n_shards", [1, 2])
def test_a_field_no_row_has_keeps_its_vectors_bit_for_bit(rng, n_shards):
    """A feature's block is scattered whole; the fields its rows lack
    get a gradient of exactly 0.0, so their vectors do not move at all.
    Fields 1, 2 and 4 appear in no row here."""
    cfg = _cfg("ffm")
    feats, fields, vals, _ = _instances(rng, "fields_absent")
    y = rng.integers(0, 2, feats.shape[0]).astype(np.float32)
    start = _start(cfg, rng)
    tr = FMTrainer(cfg, mesh=make_mesh(n_shards), sparse_grads=True)
    got, _ = tr.fit(feats, fields, vals, y, n_steps=3, params=start)
    before = start[2].reshape(NFEAT, NFIELDS, KDIM)
    after = np.asarray(got[2]).reshape(NFEAT, NFIELDS, KDIM)
    absent, present = [1, 2, 4], [0, 3]
    np.testing.assert_array_equal(after[:, absent].view(np.uint32),
                                  before[:, absent].view(np.uint32))
    touched = np.unique(feats)
    assert (after[touched][:, present] != before[touched][:, present]).any()
    # and a feature no row holds keeps its whole block
    untouched = np.setdiff1d(np.arange(NFEAT), touched)
    assert untouched.size
    np.testing.assert_array_equal(after[untouched].view(np.uint32),
                                  before[untouched].view(np.uint32))


@pytest.mark.parametrize("fields", [
    [[0, 1, 2]], [[2, 2, 0]], [[4, 4, 4]], [[3, 0, 3], [1, 1, 2]],
    [[3, 0, 4, 1, 2, 0]]],
    ids=["distinct", "twice", "all_one", "two_rows", "all_and_one_again"])
def test_field_select_is_exact_and_its_transpose_adds(rng, fields):
    """``E[n, a, j, b]`` is bit for bit ``blk[n, a, j * stride +
    fields[n, b]]`` ([N, K, k, K]: component by component, never the k
    components last) and ``wv[n, a]`` the block's last column, and the
    gradient comes back summed over the slots of a field, the weight's
    in its column, 0.0 elsewhere."""
    cfg = _cfg("ffm")
    fields = np.asarray(fields, np.int32)
    N, K = fields.shape
    width = fm_mod._block_width(cfg)
    assert width == 128
    blk = rng.standard_normal((N, K, width)).astype(np.float32)
    (wv, E), back = jax.vjp(
        lambda b: fm_mod._select_fields(b, fields, cfg), jnp.asarray(blk))
    stride = fm_mod._block_stride(cfg)
    np.testing.assert_array_equal(np.asarray(wv).view(np.uint32),
                                  blk[:, :, -1].view(np.uint32))
    assert E.shape == (N, K, KDIM, K)
    want = np.empty(E.shape, np.float32)
    for n in range(N):
        for j in range(KDIM):
            want[n, :, j, :] = blk[n][:, j * stride + fields[n]]
    np.testing.assert_array_equal(np.asarray(E).view(np.uint32),
                                  want.view(np.uint32))

    def by_field(a):            # [N, K, width] -> [N, K, n_fields, k]
        return a[:, :, :KDIM * stride].reshape(N, K, KDIM, stride)[
            ..., :NFIELDS].transpose(0, 1, 3, 2)

    gE = rng.standard_normal(E.shape).astype(np.float32)
    gw = rng.standard_normal(wv.shape).astype(np.float32)
    (gblk,) = back((jnp.asarray(gw), jnp.asarray(gE)))
    want_g = np.zeros((N, K, NFIELDS, KDIM), np.float64)
    for n in range(N):
        for b in range(K):
            want_g[n, :, fields[n, b]] += gE[n, :, :, b]
    gblk = np.asarray(gblk)
    np.testing.assert_allclose(gblk[:, :, -1], gw, rtol=1e-6, atol=0)
    nonzero = np.count_nonzero(gblk) - gw.size
    gblk = by_field(gblk)
    assert np.count_nonzero(gblk) == nonzero    # the padding got 0.0
    np.testing.assert_allclose(gblk, want_g, rtol=1e-6, atol=0)
    lacking = np.ones((N, NFIELDS), bool)
    lacking[np.arange(N)[:, None], fields] = False
    assert (gblk.transpose(0, 2, 1, 3)[lacking] == 0.0).all()


@pytest.mark.parametrize("case", ["plain", "field_twice", "fields_absent",
                                  "padded_slots", "k_below_n_fields"])
def test_the_pairs_on_the_component_form_are_the_float64_score(rng, case):
    """``_score_from_slots``'s FFM branch reads ``E[n, a, j, b]``
    ([N, K, k, K]): what ``_select_fields`` hands it out of the blocks
    and what the row form gathers ([N, K, K, k]) after its one
    ``moveaxis`` are the same array, and either scores as the model's
    definition does in float64, pair by pair."""
    cfg = _cfg("ffm")
    feats, fields, vals, _ = _instances(rng, case)
    N, K = feats.shape
    w0, w, V = _start(cfg, rng)
    rows = fm_mod._slot_rows(jnp.asarray(feats), jnp.asarray(fields), cfg)
    by_row = np.asarray(V)[np.asarray(rows)]                # [N, K, K, k]
    assert by_row.shape == (N, K, K, KDIM)
    E = fm_mod._by_component(jnp.asarray(by_row), cfg)
    assert E.shape == (N, K, KDIM, K)
    # the block form of the same table gives the same E, bit for bit
    stride, width = fm_mod._block_stride(cfg), fm_mod._block_width(cfg)
    T = np.zeros((NFEAT, width), np.float32)
    for j in range(KDIM):
        T[:, j * stride:j * stride + NFIELDS] = np.asarray(V).reshape(
            NFEAT, NFIELDS, KDIM)[:, :, j]
    T[:, -1] = w
    wv, from_blocks = fm_mod._select_fields(jnp.asarray(T[feats]),
                                            jnp.asarray(fields), cfg)
    np.testing.assert_array_equal(np.asarray(from_blocks).view(np.uint32),
                                  np.asarray(E).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(wv), np.asarray(w)[feats])
    got = np.asarray(fm_mod._score_from_slots(
        jnp.float32(w0), wv, E, jnp.asarray(vals), cfg))
    want = np.full(N, np.float64(w0))
    V64, x = np.asarray(V, np.float64), vals.astype(np.float64)
    for n in range(N):
        want[n] += np.sum(np.asarray(w, np.float64)[feats[n]] * x[n])
        for a in range(K):
            for b in range(a + 1, K):
                ra = feats[n, a] * NFIELDS + fields[n, b]
                rb = feats[n, b] * NFIELDS + fields[n, a]
                want[n] += V64[ra] @ V64[rb] * x[n, a] * x[n, b]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("model", ["ffm", "fm"])
@pytest.mark.parametrize("n_shards", [1, 2])
def test_fit_stream_leaves_the_callers_table_and_returns_its_shape(
        rng, model, n_shards):
    """The step donates its own state, never what the caller passed in:
    every array given to ``fit_stream`` is still readable afterwards and
    unchanged, and what comes back is the public [n_rows, k] table."""
    cfg = _cfg(model)
    feats, fields, vals, _ = _instances(rng, "plain")
    y = rng.integers(0, 2, feats.shape[0]).astype(np.float32)
    tr = FMTrainer(cfg, mesh=make_mesh(n_shards), sparse_grads=True)
    start = _start(cfg, rng)
    given = tr._place_params(tuple(jnp.asarray(p) for p in start))
    out, losses = tr.fit_stream(
        ((feats, fields, vals, y) for _ in range(3)), params=given)
    assert losses.shape == (3,)
    for g, s in zip(given, start):
        assert not g.is_deleted()
        np.testing.assert_array_equal(np.asarray(g), s)
    assert out[2].shape == (tr.n_rows, cfg.k) == start[2].shape
    assert all(o is not g for o, g in zip(out, given))
    # the returned table serves and saves as it always did
    assert tr.full_table(out).shape == start[2].shape
    assert np.isfinite(tr.predict(out, feats, fields, vals)).all()
    # and trains on: a second stream from what the first returned
    again, _ = tr.fit_stream(iter([(feats, fields, vals, y)]), params=out)
    assert not out[2].is_deleted() and again[2].shape == out[2].shape


@pytest.mark.parametrize("model", ["ffm", "fm"])
def test_one_chunk_fed_e_times_is_fit_of_e_steps(rng, model):
    cfg = _cfg(model)
    feats, fields, vals, _ = _instances(rng, "field_twice", n=37)
    y = rng.integers(0, 2, 37).astype(np.float32)
    E = 4
    a = FMTrainer(cfg, mesh=make_mesh(2), sparse_grads=True)
    p_fit, l_fit = a.fit(feats, fields, vals, y, n_steps=E, seed=5)
    b = FMTrainer(cfg, mesh=make_mesh(2), sparse_grads=True)
    p_st, l_st = b.fit_stream(((feats, fields, vals, y) for _ in range(E)),
                              seed=5)
    np.testing.assert_array_equal(l_st, l_fit)
    for x, z in zip(p_fit, p_st):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(z))


@pytest.mark.parametrize("model,k,stride", [
    ("ffm", KDIM, 42), ("ffm", 4, 32), ("fm", KDIM, None)],
    ids=["ffm", "ffm_runs_fill_the_width", "fm"])
def test_table_round_trip_through_the_block_form_is_exact(rng, model, k,
                                                          stride):
    """``narrow(widen(params))`` returns ``w0``, ``w`` and ``V`` bit for
    bit (a -0.0 keeps its sign), with a last conversion block that
    starts early (77 features in blocks of 32)."""
    cfg = _cfg(model, k=k, n_features=77)
    tr = FMTrainer(cfg, mesh=make_mesh(2), sparse_grads=True)
    tr._CONVERT_ROWS = 32 * NFIELDS
    V = rng.standard_normal((tr.n_rows, k)).astype(np.float32)
    w = rng.standard_normal(77).astype(np.float32)
    V[::5, 0], w[::7] = -0.0, -0.0
    w0, T = tr._enter((np.float32(0.5), w, V))
    T = np.asarray(T)
    assert float(w0) == 0.5
    # the linear weight is the block's last column
    assert fm_mod._weight_column(cfg) == T.shape[1] - 1
    np.testing.assert_array_equal(T[:, -1].view(np.uint32),
                                  w.view(np.uint32))
    if model == "fm":
        assert T.shape == (77, k + 1)
        np.testing.assert_array_equal(T[:, :k], V)
    else:
        # a block is padded with zeros to whole 128-lane words; entry j
        # of the vector against field fl is column j * stride + fl
        assert T.shape == (77, 128)
        assert fm_mod._block_stride(cfg) == stride
        runs = T[:, :k * stride].reshape(77, k, stride)
        np.testing.assert_array_equal(
            runs[:, :, :NFIELDS],
            V.reshape(77, NFIELDS, k).transpose(0, 2, 1))
        # every column but the vectors' and the weight's is 0.0
        vectors = np.zeros(128, bool)
        vectors[:k * stride].reshape(k, stride)[:, :NFIELDS] = True
        assert vectors.sum() == NFIELDS * k and not vectors[-1]
        assert not T[:, ~vectors][:, :-1].any()
    back = tr._leave((w0, jnp.asarray(T)))
    for b, given in zip(back, (np.float32(0.5), w, V)):
        np.testing.assert_array_equal(
            np.asarray(b).view(np.uint32), np.asarray(given).view(np.uint32))


def test_early_stopping_returns_the_best_rounds_public_params(rng):
    """The step donates its state, so the best round's params are taken
    out when the round is seen to be the best."""
    cfg = _cfg("ffm", learning_rate=0.5)
    feats, fields, vals, _ = _instances(rng, "plain", n=64)
    y = rng.integers(0, 2, 64).astype(np.float32)       # noise
    va = (feats[:16], fields[:16], vals[:16],
          rng.integers(0, 2, 16).astype(np.float32))
    tr = FMTrainer(cfg, mesh=make_mesh(2), sparse_grads=True)
    params, losses = tr.fit(feats, fields, vals, y, n_steps=40, seed=1,
                            eval_set=va, early_stopping_rounds=2)
    assert len(losses) < 40
    best = int(np.argmin(tr.eval_history_))
    assert len(losses) == best + 1
    assert params[2].shape == (tr.n_rows, cfg.k)
    assert tr._eval_loss(params, tr._prep_eval(*va)) == pytest.approx(
        min(tr.eval_history_), rel=1e-6)
    # the same job with the dense step stops at the same round
    dense = FMTrainer(cfg, mesh=make_mesh(2))
    pd, ld = dense.fit(feats, fields, vals, y, n_steps=40, seed=1,
                       eval_set=va, early_stopping_rounds=2)
    assert len(ld) == len(losses)
    np.testing.assert_allclose(np.asarray(params[2]), np.asarray(pd[2]),
                               rtol=1e-4, atol=1e-6)


def test_a_table_of_another_shape_is_refused(rng):
    from ytk_mp4j_tpu.exceptions import Mp4jError
    cfg = _cfg("ffm")
    feats, fields, vals, _ = _instances(rng, "plain")
    y = np.zeros(feats.shape[0], np.float32)
    tr = FMTrainer(cfg, mesh=make_mesh(1), sparse_grads=True)
    w0, w, V = _start(cfg, rng)
    with pytest.raises(Mp4jError, match="n_rows"):
        tr.fit(feats, fields, vals, y, n_steps=1,
               params=(w0, w, V.reshape(NFEAT, NFIELDS * KDIM)))


def test_the_step_refuses_the_public_table(rng):
    """[n_rows, k] would index and compile as n_rows features of one
    k-wide block each: another model, silently."""
    from ytk_mp4j_tpu.exceptions import Mp4jError
    cfg = _cfg("ffm")
    tr = FMTrainer(cfg, mesh=make_mesh(1), sparse_grads=True)
    step = tr._build_step(8 * NNZ)
    slots = jax.ShapeDtypeStruct((1, 8, NNZ), jnp.float32)
    ids = jax.ShapeDtypeStruct((1, 8, NNZ), jnp.int32)
    row = jax.ShapeDtypeStruct((1, 8), jnp.float32)
    state = tr._state_avals()
    step.lower(state, ids, ids, slots, slots, row, row)
    table = jax.ShapeDtypeStruct((tr.n_rows, cfg.k), jnp.float32)
    w = jax.ShapeDtypeStruct((NFEAT,), jnp.float32)
    for public in [(state[0], table), (state[0], w, table)]:
        with pytest.raises(Mp4jError, match="by feature"):
            step.lower(public, ids, ids, slots, slots, row, row)
