"""``FMTrainer.predict`` on a replicated table (ISSUE 36): one jitted,
row-sharded program that reads the entered ``(w0, T)`` a block a (row,
slot), over instances that cross in pieces of rows, ids, fields and
values each on its own, and are scored in tiles as they crossed (ISSUE
52: no table of instances is built). Against a float64 numpy score and
against the old row form, across piece, tile and shard boundaries."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.models import fm
from ytk_mp4j_tpu.models.fm import EnteredModel, FMConfig, FMTrainer
from ytk_mp4j_tpu.obs import spans

V, NF, K, NNZ = 120, 5, 4, 7


def _cfg(model="ffm", optimizer="sgd"):
    return FMConfig(n_features=V, n_fields=NF, k=K, max_nnz=NNZ, model=model,
                    optimizer=optimizer, learning_rate=0.1)


def _params(tr, seed=0):
    """Public parameters with every term alive: bias, weights, vectors."""
    rng = np.random.default_rng(seed)
    return (jnp.float32(0.3),
            jnp.asarray(rng.uniform(-0.5, 0.5, V).astype(np.float32)),
            jnp.asarray(rng.uniform(-0.5, 0.5, (tr.n_rows, K)).astype(
                np.float32)))


def _instances(n, slots, seed=1, how="drawn"):
    """``n`` rows of ``slots`` slots: some slots empty, row 2 (where
    there is one) all zeros. ``how`` says which field a slot holds:
    ``drawn`` at random; ``shuffled`` another permutation of the fields
    a row (never ``0..slots-1`` in order throughout); ``twice`` the
    permutation with its first field again in the slot after it;
    ``missing`` fields 0 and 3 only; ``padded`` the permutation with
    every slot from the third on empty."""
    rng = np.random.default_rng(seed)
    feats = rng.integers(0, V, (n, slots)).astype(np.int32)
    if how in ("drawn", "missing"):
        fields = (rng.integers(0, NF, (n, slots)) if how == "drawn"
                  else rng.choice([0, 3], (n, slots))).astype(np.int32)
    else:
        assert slots <= NF
        fields = np.stack([rng.permutation(NF)[:slots]
                           for _ in range(n)]).astype(np.int32)
        assert (fields != np.arange(slots)).any(axis=1).sum() > n // 2
    vals = rng.standard_normal((n, slots)).astype(np.float32)
    vals[rng.random((n, slots)) < 0.2] = 0.0
    vals[2:3] = 0.0
    if how == "twice":
        fields[:, 1] = fields[:, 0]
    elif how == "padded":
        vals[:, 2:] = 0.0
    return feats, fields, vals


def _score64(params, feats, fields, vals, model):
    """The model's margin in float64, slot by slot and pair by pair."""
    w0, w, table = (np.asarray(p, np.float64) for p in params)
    z = np.full(feats.shape[0], w0)
    for n in range(feats.shape[0]):
        live = [a for a in range(feats.shape[1]) if vals[n, a] != 0]
        for a in live:
            z[n] += w[feats[n, a]] * vals[n, a]
        for i, a in enumerate(live):
            for b in live[i + 1:]:
                if model == "ffm":
                    va = table[feats[n, a] * NF + fields[n, b]]
                    vb = table[feats[n, b] * NF + fields[n, a]]
                else:
                    va, vb = table[feats[n, a]], table[feats[n, b]]
                z[n] += va @ vb * vals[n, a] * vals[n, b]
    return z


def _row_form(tr, params, feats, fields, vals):
    """What ``predict`` returned before it read blocks: the row form."""
    f, fl, v = tr._stage_instances(feats, fields, vals)
    z = fm._score(params, jnp.asarray(f), jnp.asarray(fl), jnp.asarray(v),
                  jnp.asarray(fm._live_mask(v)), tr.cfg)
    return np.asarray(jax.nn.sigmoid(z))


def _small_pieces(monkeypatch, chunk_rows, tile):
    """Staging chunks of ``chunk_rows`` rows a shard, tiles of ``tile``."""
    monkeypatch.setattr(FMTrainer, "_EACH_CHUNK_BYTES",
                        chunk_rows * 3 * NNZ * 4)
    monkeypatch.setattr(fm, "_SCORE_TILE", tile)


def _logit(p):
    p = np.asarray(p, np.float64)
    return np.log(p) - np.log1p(-p)


@pytest.mark.parametrize("model,optimizer,n_devices,rows,slots,how", [
    ("ffm", "sgd", 1, 37, 7, "drawn"),      # no whole tile, no whole chunk
    ("ffm", "sgd", 4, 37, 5, "drawn"),      # no whole shard; slots padded
    ("ffm", "sgd", 4, 64, 7, "drawn"),      # whole shards, chunks, tiles
    ("ffm", "adagrad", 1, 33, 6, "drawn"),  # accumulators must not enter
    ("ffm", "adagrad", 4, 41, 7, "drawn"),
    ("fm", "sgd", 1, 37, 7, "drawn"),
    ("fm", "sgd", 4, 30, 4, "drawn"),
    ("ffm", "sgd", 1, 1, 7, "drawn"),       # one row
    ("ffm", "sgd", 4, 3, 2, "drawn"),       # fewer rows than shards
    # the select is a contraction whatever field a slot holds: no row
    # here has fields 0..slots-1 in order
    ("ffm", "sgd", 1, 37, 5, "shuffled"),
    ("ffm", "sgd", 4, 37, 5, "shuffled"),
    ("ffm", "sgd", 1, 37, 5, "twice"),
    ("ffm", "sgd", 1, 37, 7, "missing"),
    ("ffm", "sgd", 4, 37, 4, "padded"),
    ("ffm", "adagrad", 1, 33, 5, "twice"),
])
def test_predict_is_the_float64_score_and_the_row_form(
        monkeypatch, model, optimizer, n_devices, rows, slots, how):
    _small_pieces(monkeypatch, chunk_rows=8, tile=3)
    cfg = _cfg(model, optimizer)
    blocks = optimizer == "adagrad"
    tr = FMTrainer(cfg, n_devices=n_devices, sparse_grads=blocks)
    params = _params(tr)
    feats, fields, vals = _instances(rows, slots, how=how)
    if blocks:
        # parameters that the rule itself made, accumulators beside them
        y = (np.arange(rows) % 2).astype(np.float32)
        params, _ = tr.fit(feats, fields, vals, y, n_steps=2, params=params)
        assert tr.opt_state_ is not None
    got = tr.predict(params, feats, fields, vals)
    assert got.shape == (rows,) and got.dtype == np.float32
    want = _score64(params, feats, fields, vals, model)
    assert np.abs(_logit(got) - want).max() < 2e-5
    np.testing.assert_allclose(got, _row_form(tr, params, feats, fields,
                                              vals), rtol=0, atol=5e-7)
    if rows > 2:
        # a row of zeros scores the bias alone
        assert _logit(got[2]) == pytest.approx(float(params[0]), abs=1e-6)


@pytest.mark.parametrize("model,n_devices,chunk_rows,tile", [
    ("ffm", 1, 8, 3), ("ffm", 1, 16, 16), ("ffm", 1, 5, 7), ("ffm", 4, 4, 3),
    ("ffm", 4, 3, 2), ("fm", 1, 8, 3), ("fm", 4, 4, 3),
])
def test_chunks_and_tiles_change_no_bit(monkeypatch, model, n_devices,
                                        chunk_rows, tile):
    """A file longer than a staging chunk, in tiles that divide neither
    the chunk nor its remainder: the same bits as one chunk, one tile."""
    tr = FMTrainer(_cfg(model), n_devices=n_devices)
    params = _params(tr)
    feats, fields, vals = _instances(53, NNZ)
    whole = tr.predict(params, feats, fields, vals)
    built = len(tr._score_programs)
    _small_pieces(monkeypatch, chunk_rows, tile)
    got = tr.predict(params, feats, fields, vals)
    assert len(tr._score_programs) > built      # other programs ran
    assert got.tobytes() == whole.tobytes()


@pytest.mark.parametrize("tile", [fm._SCORE_TILE - 128, fm._SCORE_TILE,
                                  fm._SCORE_TILE + 128])
def test_the_shipped_tile_and_its_neighbours_change_no_bit(monkeypatch,
                                                           tile):
    """The constant itself and the tiles a sweep tries beside it, on a
    call long enough for two whole tiles and a last one that starts
    early: the same bits as tiles of 64."""
    # whole 128s, so that the rows cross as one staging chunk
    feats, fields, vals = _instances(2 * tile + 256, NNZ)

    def run(t):
        monkeypatch.setattr(fm, "_SCORE_TILE", t)
        # a trainer a tile: programs are kept by shape, the tile is read
        # when one is built
        tr = FMTrainer(_cfg(), n_devices=1)
        cursor = spans.take_since(0)[0]
        got = tr.predict(_params(tr), feats, fields, vals)
        build, = [s[6] for s in spans.take_since(cursor)[1]
                  if s[0] == "mp4j.step.build"
                  and s[6].get("key") == "ffm_score"]
        return got, build

    want, _ = run(64)
    got, build = run(tile)
    assert (build["tile"], build["tiles"]) == (tile, 3)
    assert got.tobytes() == want.tobytes()


def test_no_rows_no_program():
    tr = FMTrainer(_cfg(), n_devices=4)
    got = tr.predict(_params(tr), *_instances(0, NNZ))
    assert got.shape == (0,) and got.dtype == np.float32
    assert tr._score_programs == {}


def _count(name, since):
    return sum(s[0] == name for s in spans.take_since(since)[1])


def test_a_second_predict_of_the_same_shape_builds_nothing(monkeypatch):
    _small_pieces(monkeypatch, chunk_rows=8, tile=3)
    tr = FMTrainer(_cfg(), n_devices=4)
    params = _params(tr)
    feats, fields, vals = _instances(53, NNZ)
    cursor = spans.take_since(0)[0]
    first = tr.predict(params, feats, fields, vals)
    # the conversion, a piece's program and the remainder's: no placer
    assert _count("mp4j.step.build", cursor) == 3
    cursor = spans.take_since(0)[0]
    again = tr.predict(params, *_instances(53, NNZ, seed=2))
    assert _count("mp4j.step.build", cursor) == 0
    assert again.shape == first.shape and (again != first).any()


def test_an_entered_model_converts_once():
    tr = FMTrainer(_cfg(), n_devices=4)
    params = _params(tr)
    feats, fields, vals = _instances(21, NNZ)
    cursor = spans.take_since(0)[0]
    model = tr.enter_model(params)
    assert isinstance(model, EnteredModel)
    assert model.T.shape == (V, 128) and model.w0.shape == ()
    a = tr.predict(model, feats, fields, vals)
    b = tr.predict(model, feats, fields, vals)
    assert _count("mp4j.ffm.score.enter", cursor) == 1
    assert a.tobytes() == b.tobytes()
    # the public params convert a call, to the same bits
    c = tr.predict(params, feats, fields, vals)
    assert _count("mp4j.ffm.score.enter", cursor) == 2
    assert c.tobytes() == a.tobytes()
    # the caller's arrays are theirs still
    assert np.asarray(params[2]).shape == (tr.n_rows, K)


@pytest.mark.parametrize("sparse_grads,optimizer,own", [
    (False, "sgd", True),       # the dense step has no converter
    (True, "sgd", False),       # the SGD block is the one scoring reads
    (True, "adagrad", True),    # 256 columns a row there, 128 here
])
def test_scoring_builds_a_converter_only_where_training_has_another(
        sparse_grads, optimizer, own):
    tr = FMTrainer(_cfg(optimizer=optimizer), n_devices=1,
                   sparse_grads=sparse_grads)
    model = tr.enter_model(_params(tr))
    assert model.T.shape == (V, 128)
    assert (tr._score_widen is not None) == own
    assert (tr._converters is not None) == (not own)
    if not own:
        # what training enters with is the same function
        state = tr._enter(_params(tr))
        assert np.array_equal(np.asarray(state[1]), np.asarray(model.T))


def test_the_job_leaves_its_spans(monkeypatch):
    _small_pieces(monkeypatch, chunk_rows=8, tile=3)
    tr = FMTrainer(_cfg(), n_devices=1)
    cursor = spans.take_since(0)[0]
    tr.predict(_params(tr), *_instances(20, NNZ))
    tr.predict(_params(tr), *_instances(20, NNZ))
    taken = spans.take_since(cursor)[1]
    names = [s[0] for s in taken]
    for name, times in [("mp4j.ffm.score.enter", 2),
                        ("mp4j.ffm.score.stage", 2),
                        ("mp4j.ffm.score.fetch", 2),
                        ("mp4j.ffm.score.dispatch", 6),
                        ("mp4j.put_sharded", 2), ("mp4j.stage.prep", 2),
                        ("mp4j.stage.send", 6), ("mp4j.stage.place", 0),
                        ("mp4j.stage.link_wait", 4)]:
        assert names.count(name) == times, name
    stage = [s[6] for s in taken if s[0] == "mp4j.ffm.score.stage"]
    assert [a["job"] for a in stage] == [0, 1] and stage[0]["rows"] == 20
    dispatch = [s[6] for s in taken if s[0] == "mp4j.ffm.score.dispatch"]
    # 20 rows in chunks of 8: the last starts at 12 and scores from 16 on
    assert [(a["start"], a["rows"]) for a in dispatch[:3]] == [
        (0, 8), (8, 8), (16, 4)]
    # what crosses is the three arrays as the host holds them
    sent = [s[6]["bytes"] for s in taken if s[0] == "mp4j.put_sharded"]
    assert sent == [20 * 3 * NNZ * 4] * 2
    chunks = [s[6] for s in taken if s[0] == "mp4j.stage.send"]
    assert chunks == [{"chunk": k, "bytes": 8 * 3 * NNZ * 4}
                      for k in (0, 1, 2)] * 2
    # and under put_sharded there is the pace and nothing else
    puts = [s for s in taken if s[0] == "mp4j.put_sharded"]
    under = [s for s in taken if s[0].startswith("mp4j.stage.")
             and any(p[2] <= s[2] and s[2] + s[3] <= p[2] + p[3]
                     for p in puts)]
    assert {s[0] for s in under} == {"mp4j.stage.send",
                                     "mp4j.stage.link_wait"}
    assert [s[6]["chunk"] for s in under
            if s[0] == "mp4j.stage.link_wait"] == [0, 1] * 2


@pytest.mark.parametrize("model", ["ffm", "fm"])
def test_the_program_names_its_scopes_and_gathers_a_block_a_slot(model):
    tr = FMTrainer(_cfg(model), n_devices=4)
    shape = (4, 10, NNZ)
    width = fm._block_width(tr._score_cfg)
    rows, rep = tr._row_sharding(), tr._place_replicated
    text = tr._build_score(shape, 10).lower(
        *(jnp.zeros(shape, dtype, device=rows)
          for dtype in (jnp.int32, jnp.int32, jnp.float32)),
        rep((jnp.float32(0), jnp.zeros((V, width), jnp.float32))),
        jnp.zeros((4, 25), jnp.float32, device=rows),
        np.int32(0)).as_text(debug_info=True)
    scopes = ["stage.place", "ffm.table_gather", "ffm.score.pairs"]
    if model == "ffm":
        scopes.append("ffm.score.select")   # FM's block is its vector
    for scope in scopes:
        assert re.search(rf'loc\("(?:[^"]*/)?{re.escape(scope)}[/"]', text), \
            scope
    # one gather, of whole blocks: [tile, slots] indices, no [.., 7, 7]
    gathers = re.findall(r'"stablehlo\.gather".*', text)
    assert len(gathers) == 1
    assert f"slice_sizes = array<i64: 1, {width}>" in gathers[0]
    assert "all_reduce" not in text and "all_gather" not in text
    assert "all_to_all" not in text and "collective_permute" not in text


def test_the_replicated_path_is_off_the_row_form():
    import inspect

    for fn in (fm.predict, fm.score_rows, FMTrainer._build_score,
               FMTrainer.enter_model):
        src = inspect.getsource(fn)
        assert "_slot_rows" not in src and "_gather_slots" not in src
    # the sharded table keeps it
    assert "_slot_rows" in inspect.getsource(FMTrainer._build_sharded_predict)


def test_what_predict_refuses():
    tr = FMTrainer(_cfg(), n_devices=1)
    params = _params(tr)
    feats, fields, vals = _instances(9, NNZ)
    with pytest.raises(Mp4jError, match="alike"):
        tr.predict(params, feats, fields[:, :3], vals)
    with pytest.raises(Mp4jError, match="feature id out of range"):
        tr.predict(params, np.where(feats == feats[8, 0], V, feats), fields,
                   vals)
    with pytest.raises(Mp4jError, match="field id out of range"):
        tr.predict(params, feats, fields + NF, vals)
    with pytest.raises(Mp4jError, match=r"feats must be \[N, K<=7\]"):
        tr.predict(params, *_instances(9, NNZ + 1))
    with pytest.raises(Mp4jError, match="a model is"):
        tr.enter_model((params[0], params[1], params[2][:-1]))
    sharded = FMTrainer(_cfg(), n_devices=4, sparse_grads=True,
                        table_sharding="sharded")
    with pytest.raises(Mp4jError, match="replicated table only"):
        sharded.enter_model(params)
    # and scores the params themselves, as before
    got = sharded.predict(sharded._stage_table(params), feats, fields, vals)
    np.testing.assert_allclose(got, tr.predict(params, feats, fields, vals),
                               rtol=0, atol=5e-7)


def test_instances_that_are_full_width_already_are_not_copied(monkeypatch):
    tr = FMTrainer(_cfg(), n_devices=1)
    feats, fields, vals = _instances(9, NNZ)
    f, fl, v = tr._stage_instances(feats, fields, vals)
    assert f is feats and fl is fields and v is vals
    # and what predict hands the cutting loop are views of them
    staged = []
    cuts = FMTrainer._array_cuts
    monkeypatch.setattr(
        FMTrainer, "_array_cuts",
        lambda self, a: staged.extend(a) or cuts(self, a))
    tr.predict(_params(tr), feats, fields, vals)
    assert [a.shape for a in staged] == [(1, 9, NNZ)] * 3
    assert all(np.shares_memory(a, b)
               for a, b in zip(staged, (feats, fields, vals)))
    # narrower ones are padded with empty slots
    f, fl, v = tr._stage_instances(*_instances(9, 3))
    assert f.shape == (9, NNZ) and (v[:, 3:] == 0).all()


@pytest.mark.parametrize("n_devices,per,chunk_rows", [
    (1, 20, 8), (4, 11, 4), (1, 256, 128), (4, 5, 64),
])
def test_a_tuple_of_arrays_crosses_each_on_its_own(monkeypatch, n_devices,
                                                   per, chunk_rows):
    """``_array_cuts`` with a tuple: every array crosses on its own, a
    piece of rows at a time and in its own type, and a piece is the
    tuple of them; no table, no placer, no padding words."""
    monkeypatch.setattr(FMTrainer, "_EACH_CHUNK_BYTES",
                        chunk_rows * 3 * NNZ * 4)
    tr = FMTrainer(_cfg(), n_devices=n_devices)
    rng = np.random.default_rng(3)
    shape = (n_devices, per, NNZ)
    a, b = (rng.integers(0, 2 ** 31 - 1, shape).astype(np.int32)
            for _ in range(2))
    c = rng.standard_normal(shape).astype(np.float32)
    seen, got = [], [np.zeros_like(x) for x in (a, b, c)]
    cursor = spans.take_since(0)[0]
    for k, piece, shard, start, stop, turns in tr._crossed(
            tr._array_cuts((a, b, c))):
        assert shard is None and len(piece) == 3 and k == len(seen)
        assert [p.dtype for p in piece] == [np.int32, np.int32, np.float32]
        seen.append((start, stop))
        for whole, p in zip(got, piece):
            assert p.sharding == tr._row_sharding()
            whole[:, start:stop] = np.asarray(p).reshape(n_devices, -1, NNZ)
    for whole, want in zip(got, (a, b, c)):
        assert np.array_equal(whole, want)
    rows = min(per, chunk_rows)
    assert seen[0] == (0, rows) and seen[-1] == (per - rows, per)
    assert len(seen) == -(-per // rows)
    assert _count("mp4j.step.build", cursor) == 0
    assert _count("mp4j.stage.send", cursor) == len(seen)
    assert not hasattr(fm, "packed_width") and tr._row_placers == {}


@pytest.mark.parametrize("n_devices", [1, 4])
def test_no_array_of_the_files_size_is_on_the_mesh(monkeypatch, n_devices):
    """While a file is scored in pieces the largest device array made
    since the call began is one array of a piece, the probabilities or
    the model: no table of instances, at any launch; and every row of a
    shard is scored exactly once."""
    from tests.helpers import watch_live_arrays

    _small_pieces(monkeypatch, chunk_rows=128, tile=40)
    tr = FMTrainer(_cfg(), n_devices=n_devices)
    model = tr.enter_model(_params(tr))
    feats, fields, vals = _instances(3000, NNZ)
    seen = watch_live_arrays(monkeypatch, tr)
    cursor = spans.take_since(0)[0]
    got = tr.predict(model, feats, fields, vals)
    per = 3000 // n_devices
    assert len(seen) == -(-per // 128)
    assert max(seen) == max(n_devices * 128 * NNZ * 4, n_devices * per * 4)
    assert max(seen) * 4 < feats.nbytes
    dispatch = [s[6] for s in spans.take_since(cursor)[1]
                if s[0] == "mp4j.ffm.score.dispatch"]
    assert [d["start"] for d in dispatch] == list(range(0, per, 128))
    assert sum(d["rows"] for d in dispatch) == per
    # and the bits are one piece's
    monkeypatch.setattr(FMTrainer, "_EACH_CHUNK_BYTES", 2 ** 27)
    whole = FMTrainer(_cfg(), n_devices=n_devices).predict(
        _params(tr), feats, fields, vals)
    assert got.tobytes() == whole.tobytes()
