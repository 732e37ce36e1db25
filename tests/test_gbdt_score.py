"""``GBDTTrainer.predict`` as a staged, sharded scoring program: held to
the plain float64 oracle (``check/_oracle.py: score_ensemble``) and to the
form it replaced (a ``lax.scan`` of ``predict_tree`` over the ensemble)
on seeded random ensembles at small sizes."""

import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from ytk_mp4j_tpu.check._oracle import score_ensemble
from ytk_mp4j_tpu.models import gbdt
from ytk_mp4j_tpu.models.gbdt import (GBDTConfig, GBDTTrainer, predict_tree,
                                      score_group_size, score_row_chunks)
from ytk_mp4j_tpu.obs import spans
from ytk_mp4j_tpu.parallel.mesh import make_mesh

# what the Bosch scoring cell states of a margin (its configuration's
# ``guarantees``): within 2^-18 of the sum of its terms' absolute values
MARGIN_REL_ERR = 2.0 ** -18
F, DEPTH, ROUNDS, ROWS = 19, 5, 37, 1003   # 37 rounds: three groups;
ROW_CHUNK = 256                            # 1003 rows: four chunks of 256


@pytest.fixture(autouse=True)
def small_row_chunks(monkeypatch):
    """Several chunks, the last one overlapping, at a test's size."""
    monkeypatch.setattr(gbdt, "_SCORE_ROW_CHUNK", ROW_CHUNK)


# (missing_bin, categorical features, n_bins)
TABLES = {"dense": (False, (), 256),
          "missing": (True, (), 256),
          "missing-cat": (True, (2, 11), 256),
          "cat": (False, (0, 18), 256),
          "missing-cat-512": (True, (2, 11), 512),
          "dense-512": (False, (), 512)}
LOSSES = ("logistic", "squared", "softmax")
CASES = [pytest.param(loss, table, n_devices,
                      id=f"{loss}-{table}-{n_devices}dev")
         for loss, table, n_devices in itertools.product(
             LOSSES, TABLES, (1, 4))]


def _cfg(loss, table):
    missing_bin, cats, n_bins = TABLES[table]
    return GBDTConfig(n_features=F, n_bins=n_bins, depth=DEPTH, loss=loss,
                      n_classes=3, missing_bin=missing_bin,
                      categorical_features=cats, learning_rate=0.1)


def _draw(cfg, seed=0, rounds=ROUNDS, rows=ROWS):
    """A seeded table and ensemble: bins over the whole range with a
    third of the cells in bin 0, a tenth of the nodes frozen at B - 1,
    thresholds that also hit bins 0 and B - 2, both directions."""
    rng = np.random.default_rng(seed)
    B, nodes = cfg.n_bins, 2 ** cfg.depth - 1
    bins = rng.integers(0, B, (rows, cfg.n_features)).astype(np.int32)
    bins[rng.random(bins.shape) < 0.33] = 0
    # a few columns of few values, so that equality splits are taken
    bins[:, :4] %= 5

    def tree():
        bin_ = rng.integers(0, B - 1, nodes).astype(np.int32)
        bin_[rng.random(nodes) < 0.4] %= 5
        bin_[rng.random(nodes) < 0.1] = B - 1
        return (rng.integers(0, cfg.n_features, nodes).astype(np.int32),
                bin_, rng.integers(0, 2, nodes).astype(np.int32),
                (0.1 * rng.standard_normal(nodes + 1)).astype(np.float32))

    if cfg.loss == "softmax":
        trees = [tuple(tree() for _ in range(cfg.n_classes))
                 for _ in range(rounds)]
    else:
        trees = [tree() for _ in range(rounds)]
    return bins, trees


def _want(cfg, bins, trees):
    return score_ensemble(
        trees, bins, depth=cfg.depth, learning_rate=cfg.learning_rate,
        n_bins=cfg.n_bins, missing_bin=cfg.missing_bin,
        categorical_features=cfg.categorical_features)


def _scan_form(cfg, bins, trees):
    """``predict`` as it was before the scoring program: the table as one
    array, ``lax.scan`` over the stacked ensemble around
    ``predict_tree``, which routes a level at a time."""
    softmax = cfg.loss == "softmax"
    if softmax:
        stacked = tuple(jnp.asarray(np.stack(
            [[cls[j] for cls in rnd] for rnd in trees])) for j in range(4))
    else:
        stacked = tuple(jnp.asarray(np.stack([t[j] for t in trees]))
                        for j in range(4))

    @jax.jit
    def run(bins, stacked):
        def body(out, tree):
            if softmax:
                delta = jnp.stack(
                    [predict_tree(bins, tuple(a[c] for a in tree), cfg)
                     for c in range(cfg.n_classes)], axis=1)
            else:
                delta = predict_tree(bins, tree, cfg)
            return out + cfg.learning_rate * delta, None

        shape = ((bins.shape[0], cfg.n_classes) if softmax
                 else (bins.shape[0],))
        return lax.scan(body, jnp.zeros(shape, jnp.float32), stacked)[0]

    return np.asarray(run(jnp.asarray(bins), stacked))


_scored = {}


def _score(loss, table, n_devices):
    """(cfg, bins, trees, predict's margins), scored once a case."""
    key = (loss, table, n_devices)
    if key not in _scored:
        cfg = _cfg(loss, table)
        bins, trees = _draw(cfg)
        got = GBDTTrainer(cfg, n_devices=n_devices).predict(bins, trees)
        _scored[key] = (cfg, bins, trees, got)
    return _scored[key]


@pytest.mark.parametrize("loss,table,n_devices", CASES)
def test_predict_equals_the_oracle(loss, table, n_devices):
    cfg, bins, trees, got = _score(loss, table, n_devices)
    want, terms = _want(cfg, bins, trees)
    assert got.dtype == np.float32 and got.shape == want.shape
    assert got.shape == ((ROWS, 3) if loss == "softmax" else (ROWS,))
    assert (np.abs(got - want) <= MARGIN_REL_ERR * terms).all()
    assert np.abs(got - want).max() > 0         # f32 against f64


@pytest.mark.parametrize("loss,table,n_devices", CASES)
def test_predict_equals_the_scan_form(loss, table, n_devices):
    """Same routing, same f32 sum in tree order: 1 ulp of slack for a
    multiply-add that one program fuses and the other does not."""
    cfg, bins, trees, got = _score(loss, table, n_devices)
    np.testing.assert_allclose(got, _scan_form(cfg, bins, trees),
                               rtol=0, atol=2e-7)


@pytest.mark.parametrize("loss", LOSSES)
def test_four_devices_equal_one(loss):
    one, four = (_score(loss, "missing-cat", n)[3] for n in (1, 4))
    np.testing.assert_array_equal(one, four)


@pytest.mark.parametrize("rounding", ["leaf", "sum"])
def test_bf16_misses_the_stated_precision(monkeypatch, rounding):
    """The control of the tolerance: a leaf held in bf16, or margins
    held in bf16 between groups, is not within 2^-18 of the terms."""
    score_group = gbdt._score_group

    def bf16(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    def rounded(digits, group, out, cfg):
        if rounding == "leaf":
            group = group[:3] + (bf16(group[3]),)
        out = score_group(digits, group, out, cfg)
        return bf16(out) if rounding == "sum" else out

    monkeypatch.setattr(gbdt, "_score_group", rounded)
    cfg = _cfg("logistic", "missing")
    bins, trees = _draw(cfg)
    got = GBDTTrainer(cfg, n_devices=1).predict(bins, trees)
    want, terms = _want(cfg, bins, trees)
    assert (np.abs(got - want) > MARGIN_REL_ERR * terms).mean() > 0.5


@pytest.mark.parametrize("depth,rounds,rows", [(1, 1, 7), (2, 17, 130),
                                               (6, 3, 1)])
def test_other_depths_and_sizes(depth, rounds, rows):
    cfg = GBDTConfig(n_features=F, n_bins=64, depth=depth, missing_bin=True)
    bins, trees = _draw(cfg, seed=depth, rounds=rounds, rows=rows)
    got = GBDTTrainer(cfg, n_devices=4).predict(bins, trees)
    want, terms = _want(cfg, bins, trees)
    assert got.shape == (rows,)
    assert (np.abs(got - want) <= MARGIN_REL_ERR * terms).all()


def test_a_non_finite_leaf_reaches_only_its_own_rows():
    cfg = _cfg("squared", "missing")
    bins, trees = _draw(cfg, rounds=3)
    trees[1][3][5] = np.nan
    got = GBDTTrainer(cfg, n_devices=1).predict(bins, trees)
    want, _ = _want(cfg, bins, trees)
    assert 0 < np.isnan(want).sum() < ROWS
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want))


@pytest.mark.parametrize("loss", LOSSES)
@pytest.mark.parametrize("rows", [200, 1, 129])
def test_a_chunk_off_the_lane_words_is_filled_to_the_same_bits(
        monkeypatch, loss, rows):
    """A chunk whose rows are not whole 128-lane words is scored with
    empty rows after it, whose margins are dropped (``_SCORE_LANES``): the
    bits are those of the chunk alone, and the select's operand is whole
    words."""
    cfg = _cfg(loss, "missing-cat")
    bins, trees = _draw(cfg, rows=rows)
    got = GBDTTrainer(cfg, n_devices=1).predict(bins, trees)
    monkeypatch.setattr(gbdt, "_SCORE_LANES", 1)
    np.testing.assert_array_equal(
        got, GBDTTrainer(cfg, n_devices=1).predict(bins, trees))
    monkeypatch.undo()
    monkeypatch.setattr(gbdt, "_SCORE_ROW_CHUNK", ROW_CHUNK)
    tr = GBDTTrainer(cfg, mesh=make_mesh(1))
    C = 3 if loss == "softmax" else 1
    stacked = tuple(
        jax.ShapeDtypeStruct((-(-ROUNDS // score_group_size(ROUNDS, C)),
                              2 ** DEPTH, score_group_size(ROUNDS, C), C), d)
        for d in (jnp.int32, jnp.int32, jnp.int32, jnp.float32))
    text = tr._build_score((1, rows, F), rows, ROUNDS).lower(
        jax.ShapeDtypeStruct((1, rows, F), jnp.int32), stacked,
        jax.ShapeDtypeStruct((1, C, rows), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    filled = -(-rows // 128) * 128
    assert f"tensor<{F}x{filled}xbf16>" in text
    assert f"tensor<{F}x{rows}xbf16>" not in text


def test_group_and_chunk_sizes():
    assert score_group_size(500) == 16 and -(-500 // 16) == 32
    assert score_group_size(37) == 13 and score_group_size(16) == 16
    assert score_group_size(17) == 9 and score_group_size(1) == 1
    assert score_group_size(37, n_classes=3) == 5       # 8 groups of <= 5
    assert score_group_size(4, n_classes=40) == 1
    assert score_row_chunks(200) == (200, 1)
    assert score_row_chunks(256) == (256, 1)
    assert score_row_chunks(1003) == (256, 4)
    assert score_row_chunks(1100) == (256, 5)           # 220 a chunk, in
    assert score_row_chunks(257) == (256, 2)            # whole lane words


def _in_pieces(monkeypatch, tr, piece_rows):
    """``tr`` sends its table in pieces of ``piece_rows`` rows a shard."""
    monkeypatch.setattr(tr, "_ONE_TRANSFER_BYTES", 1)
    monkeypatch.setattr(tr, "_EACH_CHUNK_BYTES", piece_rows * F * 4)
    return tr


# rows a piece, of 251 (four shards) or 1003 (one) rows a shard
PIECES = {"last-overlaps": 64, "a-row-past-a-piece": 250,
          "under-one-piece": 2000, "lane-words": 128, "a-row-a-piece": 1}


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("pieces", sorted(PIECES))
@pytest.mark.parametrize("loss", ["logistic", "softmax"])
def test_pieces_give_the_margins_of_one_transfer(monkeypatch, loss, pieces,
                                                 n_devices):
    """A table that crosses in pieces is scored a piece at a time, each
    as it crossed, and gives the margins of the table in one transfer to
    the bit; every row of a shard is scored exactly once (the last piece
    starts early and only its new rows are scored), and a second call
    builds nothing."""
    if pieces == "a-row-a-piece" and n_devices == 1:
        pytest.skip("a thousand programs of one row")
    cfg = _cfg(loss, "missing-cat")
    bins, trees = _draw(cfg)
    want = _score(loss, "missing-cat", n_devices)[3]
    tr = _in_pieces(monkeypatch, GBDTTrainer(cfg, n_devices=n_devices),
                    PIECES[pieces])
    spans.clear()
    np.testing.assert_array_equal(tr.predict(bins, trees), want)
    per = -(-ROWS // n_devices)
    dispatch = [s[-1] for s in _named("mp4j.gbdt.score.dispatch")]
    rows = min(per, PIECES[pieces])
    if rows >= 128:
        rows -= rows % 128
    assert [d["start"] for d in dispatch] == list(range(0, per, rows))
    assert sum(d["rows"] for d in dispatch) == per
    assert [d["rows"] for d in dispatch[:-1]] == [rows] * (len(dispatch) - 1)
    # one program for the pieces and one for what the last one adds
    assert len(tr._score_programs) == 1 + (per % rows > 0)
    built = len(_named("mp4j.step.build"))
    np.testing.assert_array_equal(tr.predict(bins, trees), want)
    assert len(_named("mp4j.step.build")) == built


def test_a_large_shard_is_scored_in_the_pieces_train_stages(monkeypatch):
    """predict sends its table as ``_put_sharded`` does: a shard at or
    over ``_ONE_TRANSFER_BYTES`` crosses in pieces of
    ``_EACH_CHUNK_BYTES``, each scored as it crossed by a program that
    takes the piece; ``train`` places the same pieces in its table."""
    cfg = _cfg("logistic", "missing")
    bins, trees = _draw(cfg)
    want = GBDTTrainer(cfg, n_devices=4).predict(bins, trees)
    tr = GBDTTrainer(cfg, n_devices=4)
    monkeypatch.setattr(tr, "_ONE_TRANSFER_BYTES", 4 * 1024)
    monkeypatch.setattr(tr, "_EACH_CHUNK_BYTES", 64 * F * 4)
    cut, placed = [], []
    cuts, put = tr._array_cuts, tr._put_in_row_chunks
    monkeypatch.setattr(tr, "_array_cuts",
                        lambda a: cut.append(a.shape) or cuts(a))
    monkeypatch.setattr(tr, "_put_in_row_chunks",
                        lambda a: placed.append(a.shape) or put(a))
    spans.clear()
    np.testing.assert_array_equal(tr.predict(bins, trees), want)
    assert cut == [(4, 251, F)] and placed == []
    assert _named("mp4j.stage.place") == [] and tr._row_placers == {}
    # a piece is scored as soon as it is on its way; the last one starts
    # early (251 rows in pieces of 64: at 187) and only its new rows
    # are scored, by a program of their own, from the same 64-row piece
    assert [s[-1]["start"] for s in _named("mp4j.gbdt.score.dispatch")] == \
        [0, 64, 128, 192]
    assert list(tr._score_programs) == [((4, 64, F), 251, 64, ROUNDS),
                                        ((4, 64, F), 251, 59, ROUNDS)]
    # and the same pieces are what train stages
    dbins = tr.shard_data(bins, np.zeros(ROWS, np.float32))[0]
    assert cut == [(4, 251, F)] * 2 and placed == [(4, 251, F)]
    assert len(_named("mp4j.stage.place")) == 4
    np.testing.assert_array_equal(
        np.asarray(dbins).reshape(-1, F)[:ROWS], bins)
    np.testing.assert_array_equal(
        np.asarray(tr.shard_bins(bins)), np.asarray(dbins))


def test_more_pieces_than_the_host_runs_ahead(monkeypatch):
    """The host waits for a piece to have crossed before it sends the
    one after the next, whoever takes the pieces, and for the device
    once ``_CHUNKS_AHEAD`` pieces wait for their turn: 16 pieces a shard
    pass both waits and give the margins of one transfer."""
    cfg = _cfg("logistic", "missing")
    bins, trees = _draw(cfg)
    want = GBDTTrainer(cfg, n_devices=4).predict(bins, trees)
    tr = GBDTTrainer(cfg, n_devices=4)
    monkeypatch.setattr(tr, "_ONE_TRANSFER_BYTES", 4 * 1024)
    monkeypatch.setattr(tr, "_EACH_CHUNK_BYTES", 16 * F * 4)    # 16 rows
    monkeypatch.setattr(tr, "_CHUNKS_AHEAD", 6)
    waited = []
    ready = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(np.shape(x)) or ready(x))
    spans.clear()
    np.testing.assert_array_equal(tr.predict(bins, trees), want)
    starts = [s[-1]["start"] for s in _named("mp4j.gbdt.score.dispatch")]
    assert starts == list(range(0, 256, 16))
    # pieces waited for as they crossed (all but the last), and the
    # scoring program's markers (a piece's first word of every shard)
    # once more than _CHUNKS_AHEAD were outstanding
    assert tr._CHUNKS_CROSSING == 2
    assert sorted(waited) == [(4,)] * (16 - 6) + [(4, 16, F)] * 15
    # staging alone keeps the same pace: ``train`` waits as ``predict``,
    # for the placer's scalars
    waited.clear()
    tr.shard_bins(bins)
    assert sorted(waited) == [()] * (16 - 6) + [(4, 16, F)] * 15


def test_no_array_of_the_tables_size_is_on_the_mesh(monkeypatch):
    """While a table is scored in pieces the largest device array made
    since the call began is a piece, the margins or the model: neither a
    table nor a copy of one, at any launch."""
    from tests.helpers import watch_live_arrays

    cfg = _cfg("logistic", "missing")
    bins, trees = _draw(cfg, rows=4000)
    for n_devices in (1, 4):
        tr = _in_pieces(monkeypatch, GBDTTrainer(cfg, n_devices=n_devices),
                        128)
        seen = watch_live_arrays(monkeypatch, tr)
        got = tr.predict(bins, trees)
        per = -(-4000 // n_devices)
        assert len(seen) == -(-per // 128) > 7
        model = max(a.nbytes for a in tr._stack_trees(trees))
        assert max(seen) == max(n_devices * 128 * F * 4, model,
                                n_devices * per * 4)
        assert max(seen) * 4 < bins.nbytes
        np.testing.assert_array_equal(
            got, GBDTTrainer(cfg, n_devices=n_devices).predict(bins, trees))


@pytest.mark.parametrize("n_devices", [1, 4])
def test_a_scoring_job_leaves_the_paces_spans_and_no_place(monkeypatch,
                                                           n_devices):
    """Under one ``mp4j.put_sharded`` of the table's bytes: a ``send`` a
    piece with its bytes, ``link_wait`` and ``device_wait`` by the
    piece they waited for, and nothing else; no placer is launched."""
    cfg = _cfg("logistic", "missing")
    bins, trees = _draw(cfg)
    tr = _in_pieces(monkeypatch, GBDTTrainer(cfg, n_devices=n_devices), 16)
    spans.clear()
    tr.predict(bins, trees)
    per = -(-ROWS // n_devices)
    pieces = -(-per // 16)
    (put,) = _named("mp4j.put_sharded")
    assert put[-1] == {"bytes": n_devices * per * F * 4}
    (stage,) = _named("mp4j.gbdt.score.stage")
    under = [s for s in spans.snapshot() if s[0].startswith(
        ("mp4j.stage.", "mp4j.stream.")) and s[0] != "mp4j.stage.prep"]
    assert all(put[2] <= s[2] and s[2] + s[3] <= put[2] + put[3]
               for s in under)
    assert stage[2] <= put[2] and put[2] + put[3] <= stage[2] + stage[3]
    assert {s[0] for s in under} == {
        "mp4j.stage.send", "mp4j.stage.link_wait", "mp4j.stage.device_wait"}
    assert [s[-1] for s in _named("mp4j.stage.send")] == [
        {"chunk": k, "bytes": n_devices * 16 * F * 4} for k in range(pieces)]
    assert [s[-1]["chunk"] for s in _named("mp4j.stage.link_wait")] == \
        list(range(pieces - 1))
    assert [s[-1]["chunk"] for s in _named("mp4j.stage.device_wait")] == \
        list(range(pieces - tr._CHUNKS_AHEAD))
    assert len(_named("mp4j.gbdt.score.dispatch")) == pieces


def _named(name):
    return [s for s in spans.snapshot() if s[0] == name]


def test_spans_once_a_job_and_one_build(monkeypatch):
    cfg = _cfg("logistic", "missing")
    bins, trees = _draw(cfg)
    tr = GBDTTrainer(cfg, n_devices=4)
    fetched = []
    device_get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: fetched.append(1) or device_get(x))
    spans.clear()
    device_trees = [tuple(jnp.asarray(a) for a in t) for t in trees]
    for _ in range(3):
        tr.predict(bins, device_trees)
    # the ensemble came to the host once, in one fetch
    assert len(fetched) == 1
    for name in ("stage", "dispatch", "fetch"):
        got = _named(f"mp4j.gbdt.score.{name}")
        assert [s[-1]["job"] for s in got] == [0, 1, 2], name
    assert [s[-1]["trees"] for s in _named("mp4j.gbdt.score.dispatch")] == \
        [ROUNDS] * 3
    builds = [s[-1] for s in _named("mp4j.step.build")
              if s[-1].get("key") == "gbdt_score"]
    assert builds == [{"key": "gbdt_score", "group": 13, "rows": 251,
                       "row_chunk": 251, "row_chunks": 1}]
    # a table under the limit is one piece, itself
    assert list(tr._score_programs) == [((4, 251, F), 251, 251, ROUNDS)]
    # another table shape or tree count is another program
    tr.predict(bins[:500], device_trees)
    tr.predict(bins, device_trees[:5])
    assert len([s for s in _named("mp4j.step.build")
                if s[-1].get("key") == "gbdt_score"]) == 3
    assert len(tr._score_programs) == 3


def test_scopes_of_the_scoring_program():
    """The program's operations sit under gbdt.score.select and
    gbdt.score.walk, and none under gbdt.route."""
    cfg = _cfg("logistic", "missing-cat")
    tr = GBDTTrainer(cfg, mesh=make_mesh(1))
    stacked = tuple(
        jax.ShapeDtypeStruct((3, 2 ** DEPTH, 13, 1), d)
        for d in (jnp.int32, jnp.int32, jnp.int32, jnp.float32))
    text = tr._build_score((1, ROWS, F), ROWS, ROUNDS).lower(
        jax.ShapeDtypeStruct((1, ROWS, F), jnp.int32), stacked,
        jax.ShapeDtypeStruct((1, 1, ROWS), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32)).as_text(debug_info=True)
    assert "gbdt.score.select" in text and "gbdt.score.walk" in text
    assert "gbdt.route" not in text
    assert "dot_general" in text and "gather" not in text


def test_no_trees_and_no_rows():
    cfg = _cfg("softmax", "dense")
    bins, trees = _draw(cfg, rounds=2, rows=10)
    tr = GBDTTrainer(cfg, n_devices=4)
    assert (tr.predict(bins, []) == np.zeros((10, 3), np.float32)).all()
    assert tr.predict(bins[:0], trees).shape == (0, 3)
    np.testing.assert_allclose(tr.predict(bins, [], proba=True), 1 / 3)
