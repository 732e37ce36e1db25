"""libffm's rule (``FMConfig(optimizer="adagrad")``) on the replicated
block table, at small sizes on the CPU, against the benchmark's plain
float64 reference (``benchmark/reference/ffm_adagrad.py``, which imports
nothing of the package): the chunk's summed gradient, duplicates merged
before the rule, ``l2`` and the accumulators only where a row looked,
the state in and out of ``fit`` / ``fit_stream``."""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.models import fm
from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer
from ytk_mp4j_tpu.ops import sparse as sparse_ops
from ytk_mp4j_tpu.parallel.mesh import make_mesh

NFEAT, NFIELDS, KDIM, ROWS = 48, 4, 2, 16
LR, L2, INIT = 0.2, 0.01, 1.0


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "reference",
        "ffm_adagrad.py")
    spec = importlib.util.spec_from_file_location("ffm_adagrad_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _reference()


def _cfg(**kw):
    args = dict(n_features=NFEAT, n_fields=NFIELDS, k=KDIM, max_nnz=NFIELDS,
                model="ffm", learning_rate=LR, l2=L2, optimizer="adagrad",
                adagrad_init=INIT)
    args.update(kw)
    return FMConfig(**args)


def _trainer(n_devices=1, **kw):
    return FMTrainer(_cfg(**kw), n_devices=n_devices, sparse_grads=True)


def _start(rng):
    """Public params with every array away from zero."""
    return (np.float32(0.05),
            (0.1 * rng.standard_normal(NFEAT)).astype(np.float32),
            rng.uniform(0, 0.5, (NFEAT * NFIELDS, KDIM)).astype(np.float32))


def _zipf_chunks(rng, n, rows=ROWS):
    """One feature a field, each field's ids its own range, skewed."""
    per = NFEAT // NFIELDS
    p = np.arange(1, per + 1, dtype=np.float64) ** -1.1
    p /= p.sum()
    fields = np.broadcast_to(np.arange(NFIELDS, dtype=np.int32),
                             (rows, NFIELDS)).copy()
    out = []
    for _ in range(n):
        feats = (rng.choice(per, (rows, NFIELDS), p=p)
                 + np.arange(NFIELDS) * per).astype(np.int32)
        vals = np.full((rows, NFIELDS), 0.5, np.float32)
        y = (rng.random(rows) < 0.5).astype(np.float32)
        out.append((feats, fields, vals, y))
    return out


def _fresh(params):
    return tuple(np.full(np.shape(p), INIT, np.float64) for p in params)


def _reference_step(params, opt, chunk, sw=None, lr=LR, l2=L2):
    """The reference on whole float64 tables: gathers what the chunk
    touches, steps, writes it back. Returns (loss, params, opt)."""
    feats, fields, vals, y = chunk[:4]
    sw = np.ones(len(y)) if sw is None else sw
    w0, w, V = (np.array(p, np.float64) for p in params)
    G0, Gw, GV = (np.array(g, np.float64) for g in opt)
    rows = feats[:, :, None] * NFIELDS + fields[:, None, :]
    loss, (w0, G0), new_rows, new_w = reference.step(
        V[rows], GV[rows], w[feats], Gw[feats], w0, G0, rows, feats, vals,
        y, sw, lr, l2)
    V[new_rows[0]], GV[new_rows[0]] = new_rows[1], new_rows[2]
    w[new_w[0]], Gw[new_w[0]] = new_w[1], new_w[2]
    return loss, (w0, w, V), (G0, Gw, GV)


def _assert_close(got, want, what):
    for g, w, name in zip(got, want, ("w0", "w", "V")):
        np.testing.assert_allclose(np.asarray(g), w, rtol=2e-5, atol=2e-6,
                                   err_msg=f"{what} {name}")


@pytest.fixture(scope="module")
def stream():
    """Three Zipf chunks through ``fit_stream`` on one device and on
    four, and through the reference."""
    rng = np.random.default_rng(3)
    params, chunks = _start(rng), _zipf_chunks(rng, 3)
    want_p, want_o, want_losses = params, _fresh(params), []
    for chunk in chunks:
        loss, want_p, want_o = _reference_step(want_p, want_o, chunk)
        want_losses.append(loss)
    got = {}
    for n in (1, 4):
        tr = _trainer(n)
        p, losses = tr.fit_stream(iter(chunks), params=params)
        got[n] = (p, tr.opt_state_, losses)
    return got, (want_p, want_o, want_losses)


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("what", ["params", "accumulators", "losses"])
def test_three_zipf_chunks_match_the_float64_reference(stream, n_devices,
                                                       what):
    got, want = stream
    i = ["params", "accumulators", "losses"].index(what)
    if what == "losses":
        np.testing.assert_allclose(got[n_devices][i], want[i], rtol=1e-5)
    else:
        _assert_close(got[n_devices][i], want[i], what)
        # the rule did something an f32 rounding could not hide
        assert np.abs(np.asarray(got[n_devices][i][2])
                      - (INIT if i else 0)).max() > 1e-3


def test_four_devices_give_what_one_device_gives(stream):
    """The same rows in the same order: the shards' slots are gathered
    before the merge, so a feature two shards saw is summed first."""
    got, _ = stream
    for i in (0, 1):
        for a, b in zip(got[1][i], got[4][i]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)


def _hot_chunk(rng):
    (chunk,) = _zipf_chunks(rng, 1)
    chunk[0][:, 0] = 5                  # one feature in every row
    return chunk


def test_a_feature_in_every_row_gets_one_merged_update():
    rng = np.random.default_rng(5)
    params, chunk = _start(rng), _hot_chunk(rng)
    tr = _trainer()
    got, _ = tr.fit_stream(iter([chunk]), params=params)
    _, want, want_o = _reference_step(params, _fresh(params), chunk)
    _assert_close(got, want, "merged")
    _assert_close(tr.opt_state_, want_o, "merged accumulators")
    # an update a row, each from the parameters the chunk began with,
    # is another answer: the rule is not linear in the gradient
    rows = 5 * NFIELDS + np.arange(1, NFIELDS)      # v[5, other fields]
    per_row = np.zeros((len(rows), KDIM))
    for n in range(ROWS):
        sw = np.zeros(ROWS)
        sw[n] = 1.0
        _, p, _ = _reference_step(params, _fresh(params), chunk, sw=sw)
        per_row += p[2][rows] - params[2][rows]
    merged = np.asarray(got[2])[rows] - params[2][rows]
    np.testing.assert_allclose(merged, want[2][rows] - params[2][rows],
                               rtol=1e-4, atol=1e-7)
    assert np.abs(per_row - merged).max() > 0.05 * np.abs(merged).max()


def test_what_no_row_reached_keeps_its_bits():
    rng = np.random.default_rng(7)
    params, (chunk,) = _start(rng), _zipf_chunks(rng, 1)
    tr = _trainer()
    got, _ = tr.fit_stream(iter([chunk]), params=params)
    feats, fields = chunk[:2]
    reached = np.zeros(NFEAT * NFIELDS, bool)
    pairs = feats[:, :, None] * NFIELDS + fields[:, None, :]
    reached[pairs[:, ~np.eye(NFIELDS, dtype=bool)].reshape(-1)] = True
    seen = np.zeros(NFEAT, bool)
    seen[feats.reshape(-1)] = True
    assert 0 < reached.sum() < reached.size and 0 < seen.sum() < NFEAT
    # a reached feature's vector against its own field is not reached
    assert not reached[feats[0, 0] * NFIELDS + fields[0, 0]]
    V, GV = np.asarray(got[2]), np.asarray(tr.opt_state_[2])
    w, Gw = np.asarray(got[1]), np.asarray(tr.opt_state_[1])
    assert np.array_equal(V[~reached], params[2][~reached])
    assert np.all(GV[~reached] == np.float32(INIT))
    assert np.array_equal(w[~seen], params[1][~seen])
    assert np.all(Gw[~seen] == np.float32(INIT))
    assert np.all(V[reached] != params[2][reached])
    assert np.all(GV[reached] > INIT) and np.all(Gw[seen] > INIT)


@pytest.mark.parametrize("how", ["sample_weight", "batch_rows", "value_0"])
def test_a_row_or_slot_that_does_not_count_touches_nothing(how):
    """``l2`` included: under lazy regularisation a zero-weight row that
    decayed its features would be another model."""
    rng = np.random.default_rng(11)
    params, (chunk,) = _start(rng), _zipf_chunks(rng, 1)
    feats, fields, vals, y = chunk
    lone = NFEAT // NFIELDS - 1         # the rarest id of field 0
    feats[feats == lone] = 0
    live = (feats, fields, vals, y)
    if how == "sample_weight":
        feats = feats.copy()
        feats[3, 0] = lone
        got_chunk = (feats, fields, vals, y,
                     np.where(np.arange(ROWS) == 3, 0.0, 1.0))
        # the row's other features are untouched by it too
        live = tuple(np.delete(a, 3, axis=0) for a in (feats, fields, vals, y))
        kw = {}
    elif how == "batch_rows":           # padding rows hold feature 0
        feats[feats == 0] = 1
        got_chunk, kw, lone = live, {"batch_rows": ROWS + 8}, 0
    else:
        feats, vals = feats.copy(), vals.copy()
        feats[3, 0], vals[3, 0] = lone, 0.0
        got_chunk = (feats, fields, vals, y)
        live = got_chunk
        kw = {}
    tr = _trainer()
    got, _ = tr.fit_stream(iter([got_chunk]), params=params, **kw)
    rows = lone * NFIELDS + np.arange(NFIELDS)
    assert np.array_equal(np.asarray(got[2])[rows], params[2][rows])
    assert np.asarray(got[1])[lone] == params[1][lone]
    assert np.all(np.asarray(tr.opt_state_[2])[rows] == np.float32(INIT))
    assert np.asarray(tr.opt_state_[1])[lone] == np.float32(INIT)
    if how != "value_0":
        # and the step is the step of the rows that count
        _, want, _ = _reference_step(params, _fresh(params), live)
        _assert_close(got, want, how)


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("rows,slots", [(128, 8), (1024, 1)],
                         ids=["128x8", "1024x1"])
def test_dead_rows_on_whole_1024s_change_no_bit(monkeypatch, rows, slots):
    """A chunk whose slots are a whole number of 1,024s is stepped with
    ``_DEAD_ROWS`` dead rows after it (``fm._with_dead_rows``). Their
    weight is 0, so their slots' keys are SENTINEL like every dead
    slot's, and the merge drops them after its sort
    (``fm._merge_slots``): every feature's parameters and accumulators
    come out as the step without the rows leaves them, to the bit, and
    feature 0, which the dead slots name and no live slot holds, is not
    reached. The loss and the bias's gradient are sums over the rows,
    the same terms and eight of 0.0 after them: the same but for the
    order the backend sums a vector that much longer in (f32; the bias's
    accumulator squares it). ``model="fm"`` has no AdaGrad."""
    rng = np.random.default_rng(17)
    feats = rng.integers(1, NFEAT, (rows, slots)).astype(np.int32)
    fields = rng.integers(0, NFIELDS, (rows, slots)).astype(np.int32)
    vals = (rng.random((rows, slots)) + 0.5).astype(np.float32)
    vals[::5, 0] = 0.0
    y = (rng.random(rows) < 0.5).astype(np.float32)
    sw = rng.integers(0, 3, rows).astype(np.float32)
    params = _start(rng)

    def one_step(dead):
        monkeypatch.setattr(fm, "_DEAD_ROWS", dead)
        tr = _trainer(max_nnz=slots)
        got, losses = tr.fit(feats, fields, vals, y, n_steps=1,
                             params=params, sample_weight=sw)
        return got, tr.opt_state_, losses

    assert fm._DEAD_ROWS == 8 and fm._dead_rows(rows * slots) == 8
    (got, got_o, got_l), (want, want_o, want_l) = one_step(8), one_step(0)
    np.testing.assert_allclose(got_l, want_l, rtol=1e-6, atol=0)
    for g, w in ((got[0], want[0]), (got_o[0], want_o[0])):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=0)
    for g, w in zip(got[1:] + tuple(got_o[1:]), want[1:] + tuple(want_o[1:])):
        assert np.array_equal(_bits(g), _bits(w))
    assert np.array_equal(_bits(got[2])[:NFIELDS], _bits(params[2])[:NFIELDS])
    assert np.asarray(got[1])[0] == params[1][0]
    assert np.all(np.asarray(got_o[2])[:NFIELDS] == np.float32(INIT))
    assert np.asarray(got_o[1])[0] == np.float32(INIT)
    assert np.all(np.asarray(got_o[1])[np.unique(feats[vals > 0])] > INIT)


def test_a_chunk_off_the_1024s_lowers_to_the_program_it_was(monkeypatch):
    """100 rows of 8 slots are no whole number of 1,024s: the step takes
    no dead row and lowers to the same text whatever ``_DEAD_ROWS`` says;
    128 rows of 8 do, and the step is traced on 136."""
    def lowered(rows, dead):
        monkeypatch.setattr(fm, "_DEAD_ROWS", dead)
        tr = _trainer(max_nnz=8)
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


@pytest.mark.parametrize("n_devices", [1, 2])
@pytest.mark.parametrize("entry", ["fit_stream", "fit"])
def test_two_calls_with_the_state_handed_over_are_one_call(entry, n_devices):
    rng = np.random.default_rng(13)
    params, chunks = _start(rng), _zipf_chunks(rng, 4)

    def run(tr, part, params, opt):
        if entry == "fit_stream":
            return tr.fit_stream(iter(part), params=params, opt_state=opt)
        return tr.fit(*chunks[0], n_steps=len(part), params=params,
                      opt_state=opt)

    whole = _trainer(n_devices)
    p_whole, l_whole = run(whole, chunks, params, None)
    halves = _trainer(n_devices)
    p, l1 = run(halves, chunks[:2], params, None)
    first = halves.opt_state_
    p, l2 = run(halves, chunks[2:], p, first)
    for a, b in zip(p_whole + whole.opt_state_, p + halves.opt_state_):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(l_whole, np.concatenate([l1, l2]))
    # without the state the second call starts its accumulators anew
    p_lost, _ = run(halves, chunks[2:], run(halves, chunks[:2], params,
                                            None)[0], None)
    assert not np.array_equal(np.asarray(p_lost[2]), np.asarray(p_whole[2]))


def test_one_chunk_e_times_is_fit_of_e_steps():
    rng = np.random.default_rng(17)
    params, (chunk,) = _start(rng), _zipf_chunks(rng, 1)
    a, b = _trainer(), _trainer()
    pa, la = a.fit(*chunk, n_steps=3, params=params)
    pb, lb = b.fit_stream(iter([chunk] * 3), params=params)
    for x, y in zip(pa + a.opt_state_, pb + b.opt_state_):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert np.array_equal(la, lb)


@pytest.fixture(scope="module")
def trained():
    rng = np.random.default_rng(19)
    params, chunks = _start(rng), _zipf_chunks(rng, 2)
    tr = _trainer()
    return tr, tr.fit_stream(iter(chunks), params=params)[0], chunks[0]


@pytest.mark.parametrize("what", ["predict", "save_params", "servable",
                                  "eval_loss"])
def test_what_scores_reads_parameters_only(trained, tmp_path, what):
    tr, params, (feats, fields, vals, y) = trained
    assert [np.shape(p) for p in params] == [
        (), (NFEAT,), (NFEAT * NFIELDS, KDIM)]
    plain = FMTrainer(_cfg(optimizer="sgd"), n_devices=1, sparse_grads=True)
    want = plain.predict(params, feats, fields, vals)
    if what == "predict":
        np.testing.assert_array_equal(
            tr.predict(params, feats, fields, vals), want)
    elif what == "save_params":
        path = str(tmp_path / "ffm.npz")
        tr.save_params(path, params)
        cfg, loaded = FMTrainer.load_params(path, FMConfig)
        assert cfg == tr.cfg and len(loaded) == 3
        np.testing.assert_array_equal(
            tr.predict(loaded, feats, fields, vals), want)
    elif what == "servable":
        sv = fm.servable(params, tr.cfg)
        reqs = [(feats[i], fields[i], vals[i]) for i in range(4)]
        rowmap = {int(f): r for f, r in zip(
            np.unique(feats[:4]), sv.rows(np.unique(feats[:4])))}
        got = np.concatenate(sv.predict_sharded(reqs, rowmap))
        np.testing.assert_allclose(got, want[:4], rtol=1e-5)
    else:
        # the held-out loss of the step's own state, accumulators and all
        state = tr._enter(params, tr.opt_state_)
        va = tr._prep_eval(feats, fields, vals, y)
        got = tr._eval_loss(state, va, fm._score_blocks)
        assert got == pytest.approx(tr._eval_loss(params, va), rel=1e-6)


@pytest.mark.parametrize("kw,says", [
    (dict(table_sharding="sharded"), "owner's side"),
    (dict(sparse_grads=False), "dense step"),
    (dict(sparse_capacity=8), "would drop features"),
], ids=["sharded", "dense", "capacity"])
def test_adagrad_raises_where_it_does_not_run(kw, says):
    args = dict(sparse_grads=True, n_devices=2)
    args.update(kw)
    with pytest.raises(Mp4jError, match=says):
        FMTrainer(_cfg(), **args)


@pytest.mark.parametrize("case", ["fm", "unknown", "state_for_sgd",
                                  "state_shapes"])
def test_adagrad_refuses_what_it_cannot_mean(case):
    rng = np.random.default_rng(23)
    params, (chunk,) = _start(rng), _zipf_chunks(rng, 1)
    if case == "fm":
        with pytest.raises(Mp4jError, match="field-aware"):
            _cfg(model="fm")
    elif case == "unknown":
        with pytest.raises(Mp4jError, match="optimizer must be"):
            _cfg(optimizer="adam")
    elif case == "state_for_sgd":
        tr = FMTrainer(_cfg(optimizer="sgd"), n_devices=1, sparse_grads=True)
        with pytest.raises(Mp4jError, match="carries none"):
            tr.fit_stream(iter([chunk]), params=params,
                          opt_state=_fresh(params))
    else:
        with pytest.raises(Mp4jError, match="shapes of"):
            _trainer().fit_stream(iter([chunk]), params=params,
                                  opt_state=_fresh(params)[:2] + (
                                      np.ones((3, KDIM)),))


@pytest.mark.parametrize("n_devices", [1, 4])
def test_the_merged_index_list_holds_no_duplicate(n_devices):
    """What the setting scatter is given: every live feature once,
    ascending, with the sum of its slots; sentinels after them."""
    rng = np.random.default_rng(29)
    S, width = 40, 5
    keys = rng.integers(0, 9, (n_devices, S)).astype(np.int32)
    keys[:, ::7] = sparse_ops.SENTINEL
    payload = rng.standard_normal((n_devices, S, width)).astype(np.float32)
    capacity = n_devices * S
    mesh = make_mesh(n_devices)
    axis = mesh.axis_names[0]

    def merge(k, v):
        return fm._merge_slots(k[0], v[0], capacity, axis)

    ui, uv = jax.jit(jax.shard_map(
        merge, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(),
        check_vma=False))(jnp.asarray(keys), jnp.asarray(payload))
    ui, uv = np.asarray(ui), np.asarray(uv)
    live = ui[ui != sparse_ops.SENTINEL]
    want = np.unique(keys[keys != sparse_ops.SENTINEL])
    assert np.array_equal(live, want)               # distinct, ascending
    assert np.all(ui[len(live):] == sparse_ops.SENTINEL)
    for f, row in zip(live, uv):
        np.testing.assert_allclose(row, payload[keys == f].sum(axis=0),
                                   rtol=1e-5, atol=1e-6)
    assert np.all(uv[len(live):] == 0)


def test_the_block_holds_every_accumulator_beside_its_parameter():
    cfg = FMConfig(n_features=8, n_fields=39, k=4, max_nnz=39, model="ffm",
                   optimizer="adagrad")
    assert (fm._block_width(cfg), fm._weights_width(cfg)) == (384, 192)
    assert fm._field_columns(cfg).sum() == 39 * 4 + 1
    assert fm._weight_column(cfg) == 191
    plain = FMConfig(n_features=8, n_fields=39, k=4, max_nnz=39, model="ffm")
    assert (fm._block_width(plain), fm._weights_width(plain),
            fm._weight_column(plain)) == (256, 256, 255)
    # widen lays fresh accumulators beside the live columns only
    tr = _trainer()
    rng = np.random.default_rng(31)
    w0, T, a0 = tr._enter(_start(rng))
    T, hw = np.asarray(T), fm._weights_width(tr.cfg)
    live = fm._field_columns(tr.cfg).any(axis=0)
    assert np.all(T[:, hw:][:, live] == np.float32(INIT)) and a0 == INIT
    assert np.all(T[:, hw:][:, ~live] == 0) and np.all(T[:, :hw][:, ~live] == 0)


# --------------------------------- the update loop over the live prefix
WIDE = 96       # features: capacity is then the chunk's 64 slots


def _chunk_of(rng, distinct):
    """16 rows x 4 slots that hold exactly ``distinct`` features between
    them (4 .. 64), each field's ids in its own range."""
    per = WIDE // NFIELDS
    feats = np.empty((ROWS, NFIELDS), np.int32)
    for f in range(NFIELDS):
        d = distinct // NFIELDS + (f < distinct % NFIELDS)
        ids = rng.choice(per, d, replace=False) + f * per
        feats[:, f] = np.concatenate([ids, rng.choice(ids, ROWS - d)])
    assert len(np.unique(feats)) == distinct
    fields = np.broadcast_to(np.arange(NFIELDS, dtype=np.int32),
                             (ROWS, NFIELDS)).copy()
    return (feats, fields, np.full((ROWS, NFIELDS), 0.5, np.float32),
            (rng.random(ROWS) < 0.5).astype(np.float32))


@pytest.mark.parametrize("n_devices,tile", [(1, 8), (4, 8), (1, 7)],
                         ids=["one_device", "four_devices",
                              "tile_not_dividing"])
@pytest.mark.parametrize("case", ["whole_tiles", "one_past_a_tile",
                                  "under_one_tile", "no_live_row",
                                  "every_slot_distinct"])
def test_the_update_loop_visits_what_the_chunk_holds(monkeypatch, case,
                                                     n_devices, tile):
    """The distinct features number a multiple of the tile, one more,
    fewer than a tile, none (every row's weight 0: no trip, every bit
    kept) and all 64 slots (every trip): each reached parameter gets its
    one update, whatever tile its feature falls into."""
    monkeypatch.setattr(fm, "_UPDATE_TILE", tile)
    distinct = {"whole_tiles": 2 * tile, "one_past_a_tile": 2 * tile + 1,
                "under_one_tile": tile - 3, "no_live_row": 2 * tile + 1,
                "every_slot_distinct": ROWS * NFIELDS}[case]
    rng = np.random.default_rng(37 + tile)
    params = (np.float32(0.05),
              (0.1 * rng.standard_normal(WIDE)).astype(np.float32),
              rng.uniform(0, 0.5, (WIDE * NFIELDS, KDIM)).astype(np.float32))
    chunk = _chunk_of(rng, distinct)
    tr = _trainer(n_devices, n_features=WIDE)
    assert fm._update_tile(ROWS * NFIELDS) == tile
    if case == "no_live_row":
        # the public entries refuse weights that sum to zero: the step
        # itself, on the chunk as they stage it
        (staged, slots), _ = tr._stage_stream_chunk(chunk, None)
        no_weight = jax.device_put(jnp.zeros_like(staged[5]),
                                   staged[5].sharding)
        state, loss = tr._build_step(slots)(
            tr._enter(params), *staged[:5], no_weight)
        for g, p in zip(tr._leave(state), params):
            assert np.array_equal(np.asarray(g), p)
        for G in tr.opt_state_:
            assert np.all(np.asarray(G) == np.float32(INIT))
        assert float(loss) == 0.0
        return
    got, losses = tr.fit_stream(iter([chunk]), params=params)
    loss, want, want_o = _reference_step(params, _fresh(params), chunk)
    _assert_close(got, want, case)
    _assert_close(tr.opt_state_, want_o, case + " accumulators")
    np.testing.assert_allclose(losses[0], loss, rtol=1e-5)
    # every feature the chunk holds moved, and nothing else did
    seen = np.zeros(WIDE, bool)
    seen[chunk[0].reshape(-1)] = True
    assert seen.sum() == distinct
    w, Gw = np.asarray(got[1]), np.asarray(tr.opt_state_[1])
    assert np.all(w[seen] != params[1][seen]) and np.all(Gw[seen] > INIT)
    assert np.array_equal(w[~seen], params[1][~seen])
    assert np.all(Gw[~seen] == np.float32(INIT))
    rows = np.repeat(~seen, NFIELDS)
    assert np.array_equal(np.asarray(got[2])[rows], params[2][rows])
    assert np.all(np.asarray(tr.opt_state_[2])[rows] == np.float32(INIT))
