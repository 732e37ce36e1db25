"""Scoring from floats (ISSUE 47): ``GBDTTrainer.predict_raw_chunks``
takes a float table with NaN in row chunks, bins a piece's rows on the
device it went to and scores them while the next ones cross, and lets
the piece go (ISSUE 52: no table of floats is built);
``predict_raw`` is the same entry point over row slices of one array.
Held to the benchmark's plain float64 reference
(``benchmark/reference/gbdt_score_raw.py``, which imports nothing of the
system) by the scoring cell's margin limit, and bit for bit to
``predict`` of the reference's bins, at small sizes on CPU devices."""

import itertools

import numpy as np
import pytest

import jax

from benchmark.reference import gbdt_raw, gbdt_score_raw as reference
from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.models import gbdt
from ytk_mp4j_tpu.models.binning import QuantileBinner
from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer
from ytk_mp4j_tpu.obs import spans
from ytk_mp4j_tpu.parallel.mesh import make_mesh

F, DEPTH, ROUNDS, ROWS, BINS = 12, 4, 23, 1003, 32   # 23 rounds: 2 groups


@pytest.fixture(autouse=True)
def small_row_chunks(monkeypatch):
    """Several chunks inside one call of the program, the last one
    overlapping, at a test's size."""
    monkeypatch.setattr(gbdt, "_SCORE_ROW_CHUNK", 256)


def _cfg(loss="logistic", missing=True, n_bins=BINS):
    return GBDTConfig(n_features=F, n_bins=n_bins, depth=DEPTH, loss=loss,
                      n_classes=3, missing_bin=missing, learning_rate=0.1)


def _table(rows=ROWS, seed=3):
    """Floats at one decimal, blockwise empty (three stations of four
    columns, about 60% NaN), with the troubles the transform must get
    right: heavy ties (column 1: a dozen levels, so edges repeat and
    values sit ON edges), infinities of both signs (column 2), a column
    empty through a whole stretch of rows (column 3, rows 200-500)."""
    rng = np.random.default_rng(seed)
    there = np.repeat(rng.random((rows, 3)) < [0.7, 0.3, 0.2], 4, axis=1)
    X = np.round(rng.standard_normal((rows, F)) * 2 + 0.5, 1)
    X = np.where(there, X, np.nan).astype(np.float32)
    X[:, 1] = np.round(X[:, 1])
    X[::5, 2], X[1::11, 2] = np.inf, -np.inf
    X[200:500, 3] = np.nan
    return X


def _binner(X, missing=True, n_bins=BINS):
    return QuantileBinner(n_bins, missing_bucket=missing).fit(X)


def _trees(cfg, seed=0, rounds=ROUNDS):
    rng = np.random.default_rng(seed)
    B, nodes = cfg.n_bins, 2 ** cfg.depth - 1

    def tree():
        bin_ = rng.integers(0, B - 1, nodes).astype(np.int32)
        bin_[rng.random(nodes) < 0.1] = B - 1           # frozen
        return (rng.integers(0, cfg.n_features, nodes).astype(np.int32),
                bin_, rng.integers(0, 2, nodes).astype(np.int32),
                (0.1 * rng.standard_normal(nodes + 1)).astype(np.float32))

    if cfg.loss == "softmax":
        return [tuple(tree() for _ in range(cfg.n_classes))
                for _ in range(rounds)]
    return [tree() for _ in range(rounds)]


def _slices(X, cuts):
    cuts = [0, *cuts, len(X)]
    return (X[a:b] for a, b in zip(cuts[:-1], cuts[1:]))


def _named(name):
    return [s for s in spans.snapshot() if s[0] == name]


def _reference_margins(cfg, trees, X, edges):
    """(margins [N] or [N, C] f64, their terms, the reference's bins)."""
    per_class = ([[rnd[c] for rnd in trees] for c in range(cfg.n_classes)]
                 if cfg.loss == "softmax" else [trees])
    got = [reference.score(t, X, edges, cfg.depth, cfg.learning_rate,
                           cfg.n_bins, cfg.missing_bin, cfg.missing_bin)
           for t in per_class]
    if cfg.loss != "softmax":
        return got[0]
    return (np.stack([g[0] for g in got], 1),
            np.stack([g[1] for g in got], 1), got[0][2])


CASES = [pytest.param(loss, missing, n, id=f"{loss}-"
                      f"{'missing' if missing else 'dense'}-{n}dev")
         for loss, missing, n in itertools.product(
             ("logistic", "squared", "softmax"), (True, False), (1, 4))]


@pytest.mark.parametrize("loss,missing,n_devices", CASES)
def test_margins_are_the_references_and_predicts_of_its_bins(
        loss, missing, n_devices):
    cfg = _cfg(loss, missing)
    X, trees = _table(), _trees(cfg)
    binner = _binner(X, missing)
    tr = GBDTTrainer(cfg, n_devices=n_devices)
    got = tr.predict_raw_chunks(_slices(X, (130, 131, 640, 997)), ROWS,
                                trees, binner=binner)
    want, terms, bins = _reference_margins(cfg, trees, X, binner.edges)
    assert got.shape == want.shape and got.dtype == np.float32
    assert reference.margin_error(got.ravel(), want.ravel(),
                                  terms.ravel()) <= reference.MARGIN_REL_ERR
    # the routing is exact: the accepted path on the reference's bins
    # gives these margins to the bit
    np.testing.assert_array_equal(got, tr.predict(bins, trees))
    # and the reference's bins are the plain compare-count's
    if missing:
        np.testing.assert_array_equal(bins, gbdt_raw.bins(X, binner.edges))
        assert ((bins == 0) == np.isnan(X)).all()


CUTS = {"one-chunk": (),
        "many": (100, 200, 300, 400, 500, 600, 700, 800, 900, 1000),
        "last-of-one-row": (500, ROWS - 1),
        "no-multiple-of-anything": (7, 20, 57, 251, 252, 641, 1001),
        "a-row-each-side-of-a-shard": (250, 251, 252, 502, 753)}


@pytest.fixture(scope="module")
def job():
    """One table, ensemble and binner, and the margins every chunking
    and every mesh must give: ``predict`` of the reference's bins."""
    cfg = _cfg()
    X, trees = _table(), _trees(cfg)
    binner = _binner(X)
    want = GBDTTrainer(cfg, n_devices=1).predict(
        reference.bins(X, binner.edges), trees)
    return cfg, X, trees, binner, want


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("cuts", sorted(CUTS))
def test_the_margins_do_not_depend_on_the_chunking(job, cuts, n_devices):
    cfg, X, trees, binner, want = job
    tr = GBDTTrainer(cfg, n_devices=n_devices)
    tr.binner_ = binner
    got = tr.predict_raw_chunks(_slices(X, CUTS[cuts]), ROWS, trees)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("cuts", sorted(CUTS))
@pytest.mark.parametrize("piece_rows", [64, 250])
def test_small_pieces_give_the_margins_of_one_transfer(job, cuts, n_devices,
                                                       piece_rows,
                                                       monkeypatch):
    """Pieces forced small: a chunk is cut under the cap and at shard
    ends (a chunk that spans two shards, a piece of one row past a
    boundary, a last shard that ends in a padding row), every piece is
    scored on the device it went to, and the margins are those of the
    bins in one transfer, to the bit; every row of the table is scored
    exactly once, and the padding never."""
    cfg, X, trees, binner, want = job
    tr = GBDTTrainer(cfg, n_devices=n_devices)
    monkeypatch.setattr(tr, "_EACH_CHUNK_BYTES", piece_rows * F * 4)
    spans.clear()
    got = tr.predict_raw_chunks(_slices(X, CUTS[cuts]), ROWS, trees,
                                binner=binner)
    np.testing.assert_array_equal(got, want)
    per = -(-ROWS // n_devices)
    dispatch = [s[-1] for s in _named("mp4j.gbdt.score.dispatch")]
    assert max(d["rows"] for d in dispatch) <= piece_rows
    # a shard's pieces follow each other, and a new shard starts at 0
    shard, at, rows = 0, 0, np.zeros(n_devices, np.int64)
    for d in dispatch:
        if d["start"] != at:
            assert d["start"] == 0 and at == per
            shard, at = shard + 1, 0
        at += d["rows"]
        rows[shard] += d["rows"]
    assert rows.tolist() == [per] * (n_devices - 1) + [
        ROWS - (n_devices - 1) * per]
    assert len(dispatch) == len(_named("mp4j.stage.send"))
    assert _named("mp4j.stage.place") == [] and tr._row_placers == {}


@pytest.mark.parametrize("n_devices", [1, 4])
def test_a_chunk_over_the_staging_size_crosses_and_is_scored_in_pieces(
        job, n_devices, monkeypatch):
    """A reader's chunk is cut into pieces of ``_EACH_CHUNK_BYTES`` at
    most and at shard ends; each piece is scored as it crosses by the
    device that holds its shard (on four: every shard's pieces, not the
    last one's alone), and the rows that pad the last shard are no
    piece."""
    cfg, X, trees, binner, want = job
    tr = GBDTTrainer(cfg, n_devices=n_devices)
    monkeypatch.setattr(tr, "_EACH_CHUNK_BYTES", 64 * F * 4)
    spans.clear()
    got = tr.predict_raw_chunks(_slices(X, (600,)), ROWS, trees,
                                binner=binner)
    np.testing.assert_array_equal(got, want)
    per = -(-ROWS // n_devices)
    dispatch = [s[-1] for s in _named("mp4j.gbdt.score.dispatch")]
    starts = [d["start"] for d in dispatch]
    assert sum(d["rows"] for d in dispatch) == ROWS
    if n_devices == 1:
        # 600 rows in ten pieces of 60, 403 in seven of 58 or 57
        assert starts == sorted(starts) and starts[0] == 0
        assert len(starts) == 17 and starts[1] == 60
    else:
        # four pieces a shard (251 rows; the chunks' cut at 600 makes a
        # fifth in the third), the last shard's 250 rows likewise
        assert [s for s in starts if s == 0] == [0] * 4
        assert len(starts) == 4 + 4 + 5 + 4
        assert dispatch[-1]["start"] + dispatch[-1]["rows"] == per - 1
    assert len(_named("mp4j.stage.send")) == len(starts)
    assert _named("mp4j.stage.place") == []
    # a float piece's programs say its binning in their key, and are one
    # device's own: a piece as it crossed, [rows, F] or [M, 128]
    assert all(k[4] == (BINS - 2, True) and len(k[0]) == 2
               for k in tr._score_programs)


def test_no_array_of_the_tables_size_is_on_the_mesh(monkeypatch):
    """While a float table is scored the largest device array made since
    the call began is a piece, a shard's margins or the model."""
    from tests.helpers import watch_live_arrays

    cfg = _cfg()
    X, trees = _table(rows=4000), _trees(cfg)
    binner = _binner(X)
    for n_devices in (1, 4):
        tr = GBDTTrainer(cfg, n_devices=n_devices)
        monkeypatch.setattr(tr, "_EACH_CHUNK_BYTES", 128 * F * 4)
        seen = watch_live_arrays(monkeypatch, tr)
        got = tr.predict_raw_chunks(_slices(X, (1500, 3000)), 4000, trees,
                                    binner=binner)
        per = 4000 // n_devices
        assert len(seen) >= 4000 // 128
        model = max(a.nbytes for a in (*tr._stack_trees(trees),
                                       binner.edges.astype(np.float32)))
        assert max(seen) <= max(128 * F * 4, model, per * 4)
        assert max(seen) * 4 < X.nbytes
        np.testing.assert_array_equal(
            got, tr.predict(reference.bins(X, binner.edges), trees))


@pytest.mark.parametrize("n_devices", [1, 4])
def test_a_job_leaves_the_paces_spans_and_no_place(job, n_devices,
                                                   monkeypatch):
    """Under one ``mp4j.put_sharded`` of the announced table's bytes: a
    ``stream.next`` a chunk and one more, a ``send`` a piece with its
    bytes, ``link_wait`` and ``device_wait`` by the piece they waited
    for, and nothing else."""
    cfg, X, trees, binner, want = job
    tr = GBDTTrainer(cfg, n_devices=n_devices)
    monkeypatch.setattr(tr, "_EACH_CHUNK_BYTES", 16 * F * 4)
    spans.clear()
    tr.predict_raw_chunks(_slices(X, (500,)), ROWS, trees, binner=binner)
    (put,) = _named("mp4j.put_sharded")
    assert put[-1] == {"bytes": 4 * ROWS * F}
    under = [s for s in spans.snapshot() if s[0].startswith(
        ("mp4j.stage.", "mp4j.stream."))]
    assert all(put[2] <= s[2] and s[2] + s[3] <= put[2] + put[3]
               for s in under)
    assert {s[0] for s in under} == {
        "mp4j.stream.next", "mp4j.stage.send", "mp4j.stage.link_wait",
        "mp4j.stage.device_wait"}
    assert [s[-1]["chunk"] for s in _named("mp4j.stream.next")] == [0, 1, 2]
    sends = [s[-1] for s in _named("mp4j.stage.send")]
    pieces = len(sends)
    assert [d["chunk"] for d in sends] == list(range(pieces))
    assert sum(d["bytes"] for d in sends) == 4 * ROWS * F
    assert max(d["bytes"] for d in sends) <= 16 * F * 4
    assert [s[-1]["chunk"] for s in _named("mp4j.stage.link_wait")] == \
        list(range(pieces - 1))
    assert [s[-1]["chunk"] for s in _named("mp4j.stage.device_wait")] == \
        list(range(pieces - tr._CHUNKS_AHEAD))
    assert len(_named("mp4j.gbdt.score.dispatch")) == pieces


@pytest.mark.parametrize("trouble", ["on-an-edge", "repeated-edges",
                                     "infinities", "a-column-of-nan"])
def test_the_transform_bins_by_the_rule(trouble):
    """A value equal to an edge lies above it, repeated edges count
    once each, +-inf are values like any other (and +inf edges are met
    by +inf alone), a column empty through a whole chunk is bin 0 and
    nothing else is."""
    cfg = _cfg()
    X, trees = _table(), _trees(cfg, seed=5)
    binner = _binner(X)
    edges = binner.edges
    if trouble == "on-an-edge":
        # every finite cell of four columns IS one of its column's edges
        for f in (0, 4, 5, 8):
            at = ~np.isnan(X[:, f])
            finite = edges[f][np.isfinite(edges[f])]
            X[at, f] = finite[np.arange(at.sum()) % len(finite)]
        assert (X[:, 0, None] == edges[0]).any(1).sum() > 500
    elif trouble == "repeated-edges":
        assert (edges[1, 1:] == edges[1, :-1]).sum() > 10
    elif trouble == "infinities":
        assert np.isposinf(edges[2]).any() and np.isneginf(X[:, 2]).any()
    else:
        assert np.isnan(X[200:500, 3]).all()
    tr = GBDTTrainer(cfg, n_devices=1)
    got = tr.predict_raw_chunks(_slices(X, (200, 500)), ROWS, trees,
                                binner=binner)
    bins = reference.bins(X, edges)
    np.testing.assert_array_equal(bins, gbdt_raw.bins(X, edges))
    np.testing.assert_array_equal(got, tr.predict(bins, trees))
    want, terms, _ = _reference_margins(cfg, trees, X, edges)
    assert reference.margin_error(got, want, terms) <= \
        reference.MARGIN_REL_ERR


def test_512_bins_search_nine_levels_and_take_two_digits():
    """512 bins: 510 edges are nine levels of the search, the last of
    two registers of nodes, and a bin takes two bf16 digits."""
    cfg = _cfg(n_bins=512)
    rng = np.random.default_rng(1)
    X = rng.standard_normal((ROWS, F)).astype(np.float32)
    X[rng.random(X.shape) < 0.5] = np.nan
    binner, trees = _binner(X, n_bins=512), _trees(cfg, rounds=5)
    assert binner.edges.shape == (F, 510)
    tr = GBDTTrainer(cfg, n_devices=4)
    got = tr.predict_raw_chunks(_slices(X, (400,)), ROWS, trees,
                                binner=binner)
    bins = reference.bins(X, binner.edges)
    assert bins.max() > 255
    np.testing.assert_array_equal(got, tr.predict(bins, trees))


@pytest.mark.parametrize("loss", ["logistic", "softmax"])
def test_proba_is_predicts(loss):
    cfg = _cfg(loss)
    X, trees = _table(), _trees(cfg)
    binner = _binner(X)
    tr = GBDTTrainer(cfg, n_devices=4)
    got = tr.predict_raw_chunks(_slices(X, (333,)), ROWS, trees,
                                proba=True, binner=binner)
    want = tr.predict(reference.bins(X, binner.edges), trees, proba=True)
    np.testing.assert_array_equal(got, want)
    assert got.shape == ((ROWS, 3) if loss == "softmax" else (ROWS,))
    assert (got >= 0).all() and (got <= 1).all()
    if loss == "softmax":
        np.testing.assert_allclose(got.sum(1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("loss", ["logistic", "softmax"])
def test_no_trees_and_no_rows(loss):
    cfg = _cfg(loss)
    X = _table()
    tr = GBDTTrainer(cfg, n_devices=4)
    tr.binner_ = _binner(X)
    tail = (3,) if loss == "softmax" else ()
    none = tr.predict_raw_chunks(_slices(X, (500,)), ROWS, [])
    assert none.shape == (ROWS,) + tail and not none.any()
    empty = tr.predict_raw_chunks(iter(()), 0, _trees(cfg))
    assert empty.shape == (0,) + tail
    assert tr.predict_raw(X[:0], _trees(cfg)).shape == (0,) + tail
    assert tr._score_programs == {}


def test_errors():
    cfg = _cfg()
    X, trees = _table(), _trees(cfg)
    tr = GBDTTrainer(cfg, n_devices=4)
    for call in (lambda: tr.predict_raw_chunks(_slices(X, ()), ROWS, trees),
                 lambda: tr.predict_raw(X, trees),
                 lambda: tr.predict_raw_chunks(
                     _slices(X, ()), ROWS, trees,
                     binner=QuantileBinner(BINS, missing_bucket=True))):
        with pytest.raises(Mp4jError, match="no fitted binner.*"
                                            "predict_raw_chunks"):
            call()
    tr.binner_ = _binner(X)
    with pytest.raises(Mp4jError, match=r"chunk 1 must be \[rows, 12\]"):
        tr.predict_raw_chunks(iter([X[:10], X[10:20, :5]]), ROWS, trees)
    with pytest.raises(Mp4jError, match="more than n_rows=1000"):
        tr.predict_raw_chunks(_slices(X, (500,)), 1000, trees)
    with pytest.raises(Mp4jError, match="hold 1003 rows, n_rows=1010"):
        tr.predict_raw_chunks(_slices(X, (500,)), 1010, trees)
    with pytest.raises(Mp4jError, match=r"X must be \[N, n_features=12\]"):
        tr.predict_raw(X[:, :5], trees)
    with pytest.raises(Mp4jError, match="edges for 5 features"):
        tr.predict_raw_chunks(_slices(X, ()), ROWS, trees,
                              binner=_binner(X[:, :5]))


@pytest.mark.parametrize("n_devices", [1, 4])
def test_predict_raw_is_predict_raw_chunks_over_its_slices(job, n_devices,
                                                           monkeypatch):
    cfg, X, trees, binner, want = job
    tr = GBDTTrainer(cfg, n_devices=n_devices)
    tr.binner_ = binner
    np.testing.assert_array_equal(tr.predict_raw(X, trees), want)
    # a staging chunk of 100 rows: eleven slices, the last of three rows
    monkeypatch.setattr(tr, "_EACH_CHUNK_BYTES", 100 * F * 4)
    handed = []
    chunks = tr.predict_raw_chunks
    monkeypatch.setattr(
        tr, "predict_raw_chunks", lambda c, n, t, p=False: chunks(
            (handed.append(len(x)) or x for x in c), n, t, p))
    np.testing.assert_array_equal(tr.predict_raw(X, trees), want)
    assert handed == [100] * 10 + [3]


def test_no_bin_crosses_the_link_in_either_direction(job, monkeypatch):
    """What crosses is the floats, once (one ``mp4j.put_sharded`` of
    their bytes), the edges and the ensemble up, the margins down; the
    host's ``transform`` is not called."""
    cfg, X, trees, binner, want = job
    tr = GBDTTrainer(cfg, n_devices=4)
    tr.binner_ = binner
    monkeypatch.setattr(QuantileBinner, "transform", lambda *a: pytest.fail(
        "the host's transform ran"))
    fetched = []
    to_host = GBDTTrainer._to_host
    monkeypatch.setattr(GBDTTrainer, "_to_host", staticmethod(
        lambda x: fetched.append((x.dtype, x.shape)) or to_host(x)))
    for call in (lambda: tr.predict_raw(X, trees),
                 lambda: tr.predict_raw_chunks(_slices(X, (77, 600)), ROWS,
                                               trees)):
        spans.clear()
        fetched.clear()
        np.testing.assert_array_equal(call(), want)
        assert [s[-1]["bytes"] for s in _named("mp4j.put_sharded")] == \
            [4 * ROWS * F]
        assert fetched == [(np.float32, (4, 1, 251))]
        for name in ("stage", "dispatch", "fetch"):
            assert {s[-1]["source"] for s in _named(
                f"mp4j.gbdt.score.{name}")} == {"floats"}, name
    spans.clear()
    tr.predict(reference.bins(X, binner.edges), trees)
    assert {s[-1]["source"] for s in _named("mp4j.gbdt.score.stage")} == \
        {"bins"}


def test_a_second_call_of_the_same_shape_builds_nothing(job):
    cfg, X, trees, binner, want = job
    tr = GBDTTrainer(cfg, n_devices=4)
    tr.binner_ = binner
    spans.clear()
    tr.predict_raw_chunks(_slices(X, (600,)), ROWS, trees)
    builds = [s[-1] for s in _named("mp4j.step.build")]
    scoring = [b for b in builds if b.get("key") == "gbdt_score_raw"]
    # one device's programs, by the piece: a whole shard (251 rows: the
    # first two), the third's 98 and 153 either side of the chunks' cut,
    # the last shard's 250; its padding row is no piece and no program
    said = {"key": "gbdt_score_raw", "edges": 30, "compares": 5,
            "bin_block_columns": 8, "bin_block_rows": 4096, "form": "bins",
            "group": 12, "row_chunks": 1}
    assert scoring == [{**said, "rows": r, "row_chunk": r}
                       for r in (251, 98, 153, 250)]
    assert list(tr._score_programs) == [
        ((r, F), 251, r, ROUNDS, (30, True)) for r in (251, 98, 153, 250)]
    for _ in range(2):
        np.testing.assert_array_equal(
            tr.predict_raw_chunks(_slices(X, (600,)), ROWS, trees), want)
    assert len(_named("mp4j.step.build")) == len(builds)
    assert [s[-1]["job"] for s in _named("mp4j.gbdt.score.stage")] == \
        [0, 1, 2]
    # the same table as bins is another program, kept beside these
    tr.predict(reference.bins(X, binner.edges), trees)
    assert ((4, 251, F), 251, 251, ROUNDS) in tr._score_programs
    assert len(tr._score_programs) == 5


def test_the_binner_rides_save_model_between_training_and_scoring(tmp_path):
    """Train from chunks, save, load, score from chunks."""
    X = _table()
    y = (np.nan_to_num(X[:, 0]) > 0.4).astype(np.float32)
    cfg = GBDTConfig(n_features=F, n_bins=BINS, depth=3, n_trees=3,
                     loss="logistic", missing_bin=True, hist_mode="matmul")
    tr = GBDTTrainer(cfg, n_devices=4)
    cuts = (300, 301, 800)
    trees, margins = tr.train_raw_chunks(
        zip(_slices(X, cuts), _slices(y, cuts)), ROWS)
    np.testing.assert_array_equal(
        tr.predict_raw_chunks(_slices(X, (500,)), ROWS, trees),
        margins[:ROWS])
    path = str(tmp_path / "model.npz")
    tr.save_model(path, trees)
    cfg2, trees2, binner2 = GBDTTrainer.load_model(path)
    served = GBDTTrainer(cfg2, n_devices=1)
    np.testing.assert_array_equal(
        served.predict_raw_chunks(_slices(X, (10, 999)), ROWS, trees2,
                                  binner=binner2), margins[:ROWS])


def test_scopes_of_the_float_scoring_program():
    """The transform sits under bin.transform inside the scoring
    program, the select and the walk under their own scopes."""
    cfg = _cfg()
    tr = GBDTTrainer(cfg, mesh=make_mesh(1))
    import jax.numpy as jnp
    stacked = tuple(
        jax.ShapeDtypeStruct((2, 2 ** DEPTH, 12, 1), d)
        for d in (jnp.int32, jnp.int32, jnp.int32, jnp.float32))
    text = tr._build_score((1, ROWS, F), ROWS, ROUNDS, (30, True)).lower(
        jax.ShapeDtypeStruct((1, ROWS, F), jnp.float32), stacked,
        jax.ShapeDtypeStruct((1, 1, ROWS), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((F, 30), jnp.float32)).as_text(debug_info=True)
    for scope in ("bin.transform", "gbdt.score.select", "gbdt.score.walk"):
        assert scope in text, scope
    # off the TPU the only gathers are the search's probes, one a level
    # of 30 edges (on it they are inside the kernel, tests/test_gbdt_aot)
    assert "gbdt.route" not in text
    assert text.count('"stablehlo.gather"(') == 5
