"""The raw front end (ISSUE 44): ``GBDTTrainer.train_raw_chunks`` takes a
float table with NaN in row chunks, fits the quantile edges and bins the
table where it rests on the mesh, and trains; ``train_raw`` is the same
front end over row slices of one array. Held to the benchmark's plain
float64 reference (``benchmark/reference/gbdt_raw.py``, which imports
nothing of the system) and to the host binner (``QuantileBinner.fit`` /
``transform``), at small sizes on CPU devices."""

import numpy as np
import pytest

import jax

from benchmark.reference import gbdt_raw as reference
from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.models.binning import QuantileBinner
from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer
from ytk_mp4j_tpu.obs import spans
from ytk_mp4j_tpu.parallel.mesh import make_mesh

F = 20


def _table(case: str, n: int = 1003, seed: int = 2):
    """Floats that are blockwise empty (five stations of four columns,
    about 80% NaN), three decimals, the named trouble in columns 1-3,
    and a label that leans on a value and on a station's missingness."""
    rng = np.random.default_rng(seed)
    visit = np.array([0.5, 0.1, 0.15, 0.1, 0.15])
    there = np.repeat(rng.random((n, 5)) < visit, 4, axis=1)
    X = np.round(rng.standard_normal((n, F)) * 2 + 0.5, 3)
    X = np.where(there, X, np.nan).astype(np.float32)
    if case == "inf_sentinels":
        X[::3, 1] = np.inf
        X[1::7, 1] = -np.inf
    elif case == "constant_column":
        X[~np.isnan(X[:, 1]), 1] = 0.125
    elif case == "heavy_ties":
        X[:, 1] = np.round(X[:, 1])         # a dozen levels
        X[:, 2] = np.where(np.isnan(X[:, 2]), np.nan,
                           rng.integers(0, 3, n) * 0.5)
    elif case == "three_finite_values":
        X[:, 3] = np.nan
        X[[5, 400, 900], 3] = [-1.5, 0.25, 7.0]
    y = ((np.nan_to_num(X[:, 0]) > 0.4) ^ np.isnan(X[:, 4])).astype(
        np.float32)
    return X, y


def _cfg(**kw):
    return GBDTConfig(**{**dict(n_features=F, n_bins=256, depth=3,
                                n_trees=3, loss="logistic",
                                missing_bin=True, hist_mode="matmul"), **kw})


def _reader(X, y, cuts):
    cuts = [0, *cuts, len(X)]
    return ((X[a:b], y[a:b]) for a, b in zip(cuts[:-1], cuts[1:]))


def _same_trees(a, b):
    assert len(a) == len(b)
    for t1, t2 in zip(a, b):
        for a1, a2 in zip(t1, t2):
            np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


CASES = ["nan_blocks", "inf_sentinels", "constant_column", "heavy_ties",
         "three_finite_values"]


@pytest.mark.parametrize("bin_sample", [1_000_000, 400],
                         ids=["all_rows", "sampled"])
@pytest.mark.parametrize("case", CASES)
def test_the_system_is_the_plain_reference(case, bin_sample):
    """Edges, bins, then trees: the edges are the float64 reference's
    inside its own limit (and ``QuantileBinner.fit``'s to the bit), the
    device's bins are the plain compare-count under them, bin 0 exactly
    the NaN cells, and trees and margins equal ``train()`` on the
    reference's bins."""
    X, y = _table(case)
    tr = GBDTTrainer(_cfg(), mesh=make_mesh(1))
    trees, margins = tr.train_raw_chunks(_reader(X, y, [300, 650]), len(X),
                                         seed=5, bin_sample=bin_sample)
    got = tr.binner_.edges
    assert got.shape == (F, 254) and got.dtype == np.float32
    want, lo, hi = reference.edges(X, 254, bin_sample, 5)
    finite = np.isfinite(want)
    np.testing.assert_array_equal(got[~finite], want[~finite])
    g = got.astype(np.float64)
    with np.errstate(invalid="ignore"):
        inside = (lo <= g) & (g <= hi)
        room = np.maximum(np.abs(lo), np.abs(hi))
        close = np.abs(g - want) <= 2.0 ** -22 * room
    assert inside[finite].all() and close[finite].all()
    np.testing.assert_array_equal(
        got, QuantileBinner(256, missing_bucket=True).fit(
            X, sample=bin_sample, seed=5).edges)
    # the bins the device makes, fetched (only a test does that)
    table, labels = tr.shard_raw_chunks(_reader(X, y, [1]), len(X))
    np.testing.assert_array_equal(labels, y)
    bins = np.asarray(tr.binner_.transform_staged(table)).reshape(-1, F)
    want_bins = reference.bins(X, got)
    np.testing.assert_array_equal(bins[:len(X)], want_bins)
    np.testing.assert_array_equal(bins[:len(X)] == 0, np.isnan(X))
    np.testing.assert_array_equal(tr.binner_.transform(X), want_bins)
    if case == "heavy_ties":            # ties: edges repeat
        assert (np.diff(got[2]) == 0).mean() > 0.9
    trees2, margins2 = GBDTTrainer(_cfg(), mesh=make_mesh(1)).train(
        want_bins, y, seed=5)
    np.testing.assert_array_equal(margins, margins2)
    _same_trees(trees, trees2)
    # and the trees are trees: they split on the label's two columns
    assert {0, 4} & {int(f) for t in trees for f in np.asarray(t[0])}


CUTS = {"one_chunk": [],
        "seven_uneven": [100, 101, 350, 600, 777, 990],
        "one_row_last": [501, 1002]}


@pytest.mark.parametrize("n_devices", [1, 4])
def test_the_chunking_is_nothing_to_the_result(n_devices):
    """One chunk, seven uneven ones and a last chunk of one row give the
    same edges, bins, trees and margins, bit for bit, on one device and
    on four (1003 rows: four do not divide them, and chunks span
    shards); ``train_raw(X, y)`` is ``train_raw_chunks`` over its own
    slices."""
    X, y = _table("heavy_ties")
    runs = {}
    for name, cuts in CUTS.items():
        tr = GBDTTrainer(_cfg(), mesh=make_mesh(n_devices))
        trees, margins = tr.train_raw_chunks(_reader(X, y, cuts), len(X),
                                             seed=3, bin_sample=700)
        table, _ = tr.shard_raw_chunks(_reader(X, y, cuts), len(X))
        bins = np.asarray(tr.binner_.transform_staged(table))
        assert bins.shape == (n_devices, -(-len(X) // n_devices), F)
        runs[name] = (tr.binner_.edges, bins, trees, margins)
    tr = GBDTTrainer(_cfg(), mesh=make_mesh(n_devices))
    tr._EACH_CHUNK_BYTES = 256 * F * 4  # train_raw's slices: 256 rows
    trees, margins = tr.train_raw(X, y, seed=3, bin_sample=700)
    runs["train_raw"] = (tr.binner_.edges, runs["one_chunk"][1], trees,
                         margins)
    edges, bins, trees, margins = runs.pop("one_chunk")
    for other in runs.values():
        np.testing.assert_array_equal(other[0], edges)
        np.testing.assert_array_equal(other[1], bins)
        _same_trees(other[2], trees)
        np.testing.assert_array_equal(other[3], margins)
    # the rows that pad the last shard are empty: bin 0, as train() pads
    flat = bins.reshape(-1, F)
    assert (flat[len(X):] == 0).all()
    np.testing.assert_array_equal(flat[:len(X)], reference.bins(X, edges))


def test_the_placer_carries_every_bit_and_pads_with_nan():
    """Pieces cut at shard ends and under the cap land bit for bit, NaN
    payloads and all; what no chunk brought is NaN."""
    X, _ = _table("nan_blocks", n=1000)
    X[3, 3] = np.frombuffer(np.uint32(0x7fc12345).tobytes(), np.float32)[0]
    for n_devices in (1, 4):
        tr = GBDTTrainer(_cfg(), mesh=make_mesh(n_devices))
        tr._EACH_CHUNK_BYTES = 64 * F * 4       # pieces of 64 rows at most
        spans.clear()
        table = tr._put_row_chunks(iter([X[:130], X[130:131], X[131:997]]),
                                   997, F)
        per = -(-997 // n_devices)
        assert table.shape == (n_devices, per, F)
        assert table.dtype == np.float32
        assert table.sharding == tr._row_sharding()
        flat = np.asarray(table).reshape(-1, F)
        np.testing.assert_array_equal(flat[:997].view(np.uint32),
                                      X[:997].view(np.uint32))
        assert np.isnan(flat[997:]).all()
        sends = [s for s in spans.snapshot() if s[0] == "mp4j.stage.send"]
        assert max(s[6]["bytes"] for s in sends) <= 64 * F * 4
        assert sum(s[6]["bytes"] for s in sends) == 997 * F * 4
        (put,) = [s for s in spans.snapshot() if s[0] == "mp4j.put_sharded"]
        assert put[6] == {"bytes": 997 * F * 4}


def test_what_is_wrong_with_the_chunks_is_said():
    X, y = _table("nan_blocks", n=200)
    tr = GBDTTrainer(_cfg(), mesh=make_mesh(2))
    with pytest.raises(Mp4jError, match=r"chunk 1 must be \[rows, 20\]"):
        tr.train_raw_chunks([(X[:100], y[:100]), (X[100:, :19], y[100:])],
                            200)
    with pytest.raises(Mp4jError, match="more than n_rows=150"):
        tr.train_raw_chunks(_reader(X, y, [100]), 150)
    with pytest.raises(Mp4jError, match="hold 200 rows, n_rows=250"):
        tr.train_raw_chunks(_reader(X, y, [100]), 250)
    with pytest.raises(Mp4jError, match="chunk 0 has 100 rows and 99"):
        tr.train_raw_chunks([(X[:100], y[:99])], 100)
    bad = X.copy()
    bad[:, 2] = np.nan
    bad[:, 5] = np.inf
    with pytest.raises(Mp4jError, match=r"features \[2, 5\] have no finite"):
        tr.train_raw_chunks(_reader(bad, y, [70]), 200)
    with pytest.raises(Mp4jError, match="exceeds"):
        tr.train_raw_chunks(_reader(X, y, []), 200,
                            binner=QuantileBinner(512, missing_bucket=True))
    with pytest.raises(Mp4jError, match="early_stopping_rounds requires"):
        tr.train_raw(X, y, early_stopping_rounds=2)


def test_the_binner_stays_for_predict_raw_and_save_model(tmp_path):
    X, y = _table("nan_blocks")
    tr = GBDTTrainer(_cfg(), mesh=make_mesh(2))
    trees, margins = tr.train_raw_chunks(_reader(X, y, [400, 800]), len(X))
    assert isinstance(tr.binner_.edges, np.ndarray)
    pred = tr.predict_raw(X, trees)
    np.testing.assert_allclose(pred, margins[:len(X)], rtol=1e-6, atol=1e-6)
    path = str(tmp_path / "raw_chunks.npz")
    tr.save_model(path, trees)              # the binner rides along
    cfg2, trees2, binner2 = GBDTTrainer.load_model(path)
    np.testing.assert_array_equal(binner2.edges, tr.binner_.edges)
    assert binner2.missing_bucket and binner2.n_bins == 256
    tr2 = GBDTTrainer(cfg2, mesh=make_mesh(2))
    tr2.binner_ = binner2
    np.testing.assert_array_equal(tr2.predict_raw(X, trees2), pred)
    # a fitted binner's edges are used as they are: nothing is fitted
    tr3 = GBDTTrainer(_cfg(), mesh=make_mesh(2))
    spans.clear()
    trees3, margins3 = tr3.train_raw_chunks(_reader(X, y, [1]), len(X),
                                            binner=binner2)
    assert not [s for s in spans.snapshot() if s[0] == "mp4j.bin.fit"]
    np.testing.assert_array_equal(margins3, margins)


@pytest.mark.parametrize("how", ["weighted", "eval_set"])
def test_train_raws_other_edges_meet_the_same_device_path(how):
    """With ``sample_weight`` the edges are the host's weighted ones and
    with ``eval_set`` the held-out floats are binned by the same
    transform; staging, transform and loop are the device's: what comes
    out is ``train()`` on the host binner's bins."""
    X, y = _table("nan_blocks")
    Xv, yv = _table("nan_blocks", n=300, seed=9)
    w = np.where(y > 0, 3.0, 1.0).astype(np.float32)
    kw = ({"sample_weight": w} if how == "weighted"
          else {"eval_set": (Xv, yv), "early_stopping_rounds": 2})
    tr = GBDTTrainer(_cfg(n_trees=5), mesh=make_mesh(2))
    spans.clear()
    trees, margins = tr.train_raw(X, y, seed=4, **kw)
    names = [s[0] for s in spans.snapshot()]
    assert "mp4j.bin.transform" in names
    assert ("mp4j.bin.fit" in names) == (how == "eval_set")
    host = QuantileBinner(256, missing_bucket=True).fit(
        X, seed=4, sample_weight=kw.get("sample_weight"))
    np.testing.assert_array_equal(tr.binner_.edges, host.edges)
    if how == "eval_set":
        kw["eval_set"] = (host.transform(Xv), yv)
    tr2 = GBDTTrainer(_cfg(n_trees=5), mesh=make_mesh(2))
    trees2, margins2 = tr2.train(host.transform(X), y, seed=4, **kw)
    _same_trees(trees, trees2)
    np.testing.assert_array_equal(margins, margins2)
    assert tr.eval_history_ == tr2.eval_history_


def test_the_front_ends_spans_and_what_crossed(monkeypatch):
    """A job's spans: ``mp4j.gbdt.raw.stage`` (round the chunk loop),
    ``mp4j.bin.fit`` (holding the wait for the picks) and
    ``mp4j.bin.transform``, in that order inside ``mp4j.gbdt.stage``;
    the ``mp4j.put_sharded`` bytes are the floats and the three row
    vectors; a second job builds nothing and the host transform is never
    called."""
    X, y = _table("nan_blocks", n=512)
    tr = GBDTTrainer(_cfg(depth=2, n_trees=1), mesh=make_mesh(2))
    monkeypatch.setattr(QuantileBinner, "transform", lambda *a: pytest.fail(
        "the host transform ran"))
    tr.train_raw_chunks(_reader(X, y, [200]), len(X))   # builds everything
    spans.clear()
    tr.train_raw_chunks(_reader(X, y, [200]), len(X))
    got = [s for s in spans.snapshot() if s[1] == "trainer"]

    def named(name):
        return [s for s in got if s[0] == name]

    def inside(a, b):
        return b[2] <= a[2] and a[2] + a[3] <= b[2] + b[3]

    assert named("mp4j.step.build") == []
    (stage,), (raw,), (fit,), (wait,), (transform,) = (
        named(n) for n in ("mp4j.gbdt.stage", "mp4j.gbdt.raw.stage",
                           "mp4j.bin.fit", "mp4j.bin.device_wait",
                           "mp4j.bin.transform"))
    assert all(inside(s, stage) for s in (raw, fit, transform))
    assert inside(wait, fit)
    assert raw[2] + raw[3] <= fit[2] and fit[2] + fit[3] <= transform[2]
    assert stage[6] == {"job": 1}
    assert raw[6] == {"job": 1, "rows": 512, "chunks": 2,
                      "bytes": 4 * 512 * (F + 1)}
    assert fit[6] == {"sample_rows": 512, "columns": F, "blocks": 3}
    # 254 edges a column: eight levels of the search a cell
    assert transform[6] == {"rows": 512, "columns": F, "compares": 8}
    puts = named("mp4j.put_sharded")
    assert [p[6]["bytes"] for p in puts] == [4 * 512 * F] + [4 * 512] * 3
    assert inside(puts[0], raw) and all(inside(p, stage) for p in puts)
    assert len(named("mp4j.stream.next")) == 3      # and the end
    assert len(named("mp4j.gbdt.dispatch")) == 1


@pytest.mark.parametrize("first", ["train", "train_raw_chunks"])
def test_one_step_serves_both_entries(first):
    """After ``train(host bins)`` and ``train_raw_chunks`` at the same
    shapes the step was built once and compiled once: the bins the
    transform leaves on the mesh have the shape, dtype and sharding
    ``shard_bins`` gives, so the seam costs ``train`` nothing."""
    X, y = _table("nan_blocks", n=600)
    tr = GBDTTrainer(_cfg(depth=2, n_trees=2), mesh=make_mesh(2))
    builds = []
    build_step = tr._build_step
    tr._build_step = lambda: builds.append(1) or build_step()
    jobs = [lambda: tr.train(np.zeros(X.shape, np.int32), y),
            lambda: tr.train_raw_chunks(_reader(X, y, [64]), len(X))]
    for job in jobs if first == "train" else jobs[::-1]:
        job()
    assert builds == [1] and tr._step._cache_size() == 1
    table, _ = tr.shard_raw_chunks(_reader(X, y, [64]), len(X))
    bins = tr.binner_.transform_staged(table)
    placed = tr.shard_bins(np.zeros(X.shape, np.int32))
    assert (bins.shape, bins.dtype, bins.sharding) == (
        placed.shape, placed.dtype, placed.sharding)


def test_1000_bins_count_alike(rng):
    """n_bins above 256: 998 edges are ten levels of the search, the
    deep ones of more than one register of nodes."""
    X = rng.standard_normal((400, 3)).astype(np.float32)
    X[::9, 1] = np.nan
    b = QuantileBinner(1000).fit(X, sample=None)
    want = np.stack([np.searchsorted(b.edges[f], X[:, f], side="right")
                     for f in range(3)], axis=1)
    np.testing.assert_array_equal(b.transform(X),
                                  np.where(np.isnan(X), 0, want))


@pytest.mark.parametrize("chunk_rows,dispatches", [(None, 1), (16, 4)])
def test_transform_at_968_columns_goes_by_bytes(monkeypatch, chunk_rows,
                                                dispatches):
    """968 columns and 254 edges: 50 rows are ONE dispatch (the guard
    against an unfused [rows, F, edges] intermediate cut them into
    chunks of 272 rows, and a million rows into 4,353); where the table
    is more than a chunk's bytes the last chunk starts early, so every
    chunk is one shape."""
    from ytk_mp4j_tpu.models import binning

    rng = np.random.default_rng(5)
    X = np.round(rng.standard_normal((50, 968)), 3).astype(np.float32)
    X[rng.random(X.shape) < 0.8] = np.nan
    X[0] = 0.5                          # no column is empty
    b = QuantileBinner(256, missing_bucket=True).fit(X, sample=None)
    if chunk_rows:
        monkeypatch.setattr(QuantileBinner, "_TRANSFORM_CHUNK_BYTES",
                            chunk_rows * 968 * 4)
    shapes = []
    program = binning._transform_program(True, 254)
    monkeypatch.setattr(
        binning, "_transform_program", lambda shift, n_edges: (
            lambda X, edges: shapes.append(X.shape) or program(X, edges)))
    bins = b.transform(X)
    assert len(shapes) == dispatches and len(set(shapes)) == 1
    np.testing.assert_array_equal(bins, reference.bins(X, b.edges))
    np.testing.assert_array_equal(bins == 0, np.isnan(X))


def test_there_is_one_compare_count():
    """``grep`` finds one compare-count program in ``models/binning.py``
    and one walk under it: ``transform`` (host arrays) and
    ``transform_staged`` (the raw path) both run ``_count_edges``, whose
    two backends both run ``bin_kernel.upper_bound``."""
    import inspect

    from ytk_mp4j_tpu.models import binning
    from ytk_mp4j_tpu.ops import bin_kernel

    source = inspect.getsource(binning)
    assert ">=" not in inspect.getsource(binning._count_edges)
    assert inspect.getsource(bin_kernel).count("x >= probe(") == 1
    assert source.count("def _count_edges") == 1
    assert "_count_edges(X, edges, shift)" in inspect.getsource(
        binning._transform_program)
    for method in (QuantileBinner.transform, QuantileBinner.transform_staged):
        assert "_transform_program(" in inspect.getsource(method)
