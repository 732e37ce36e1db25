"""The cell ``ffm-criteo-score.file-zipf`` end to end at a toy size
through ``run.main`` itself, on the CPU with the platform check stubbed
(by hand, like the rest of this directory): the contract's last line,
``correct`` true, the metrics the cell MUST report found by name (it may
report more: a later PR appends), and ``correct`` false when the table
is held in bf16 or the select runs at the default precision."""

import json
import os

import numpy as np
import pytest

from benchmark import arith, arith_ffm_score, cells, run
from benchmark.reference import ffm_score as reference

from conftest import ROOT

CELL = "ffm-criteo-score.file-zipf"
# ``compared``: each number the check compared beside its limit, last
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}
# what ISSUE 36 names; on the CPU the device trace's are left out
FROM_TRACE = {"rows_device_idle_share",
              "ffmscore_table_gather_ms_per_job",
              "ffmscore_select_ms_per_job", "ffmscore_pairs_ms_per_job",
              "ffmscore_roofline"}
FROM_WAITS = {"score_stage_device_wait_ms_per_job", "score_stage_gbps"}
FROM_HOST = {"ffmscore_stage_ms_per_job", "ffmscore_dispatch_ms_per_job",
             "ffmscore_fetch_wait_ms_per_job",
             "score_stage_link_wait_ms_per_job", "ffmscore_enter_s",
             "peak_hbm_gb", "compile_s", "compiles_in_window",
             "step_builds_in_window", "ffmscore_step_mfu"}


@pytest.fixture
def toy_root(tiny_root, monkeypatch):
    """``tiny_root`` with this cell's file and table cut to a toy, and
    the trainer's chunks and tiles with them: several chunks of several
    tiles, and a remainder."""
    from ytk_mp4j_tpu.models import fm

    for rel, cut in (("configs/ffm-criteo-score.json",
                      {"rows": 5003, "n_features": 39 * 64}),
                     ("traffic/score-file-zipf.json",
                      {"check_rows": 512, "check_edge_rows": 128})):
        path = os.path.join(tiny_root, "benchmark", rel)
        with open(path) as f:
            doc = json.load(f)
        doc.update(cut)
        with open(path, "w") as f:
            json.dump(doc, f)
    monkeypatch.setattr(fm, "_SCORE_TILE", 128)
    monkeypatch.setattr(fm.FMTrainer, "_EACH_CHUNK_BYTES", 1024 * 468)
    return tiny_root


def _run(capsys, root, trace, seed=3000000019):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.3", "--trace", str(trace)], root=root)
    return rc, capsys.readouterr().out.strip().splitlines()


def _window(lines):
    return json.loads(next(ln for ln in lines if ln.startswith("window: "))
                      [len("window: "):])


def test_the_cell_reports_rows_per_s_and_its_own_layer_metrics():
    cell = cells.load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.adapter_name == "ffm_score"
    assert [m["name"] for m in cell.end_to_end] == ["rows_per_s", "setup_s"]
    got = {m["name"] for m in cell.per_layer}
    assert FROM_TRACE | FROM_WAITS | FROM_HOST <= got
    for m in cell.per_layer:
        assert m["spec"]["name"] == m["name"]
        assert CELL in m["workloads"]
        for key in ("layer", "moves", "source"):
            assert m["spec"][key] == m[key], (m["name"], key)
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "readers", f"{m['spec']['reader']}.py"))
    # the deployment is the source's, the vocabulary alone cut
    c = cell.config
    assert (c["rows"], c["n_fields"], c["max_nnz"], c["k"]) == (
        6_042_135, 39, 39, 4)
    assert c["n_features"] == 2 ** 22 and list(c["reduced"]) == ["n_features"]
    assert c["architecture"] is None and c["table_sharding"] == "replicated"
    assert c["bias"] != 0 and c["w_uniform_half"] > 0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["configs"]
                 if e["name"] == "ffm-criteo-score")
    assert entry["reduced"] == ["n_features"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2


def test_the_accepted_cells_report_what_they_reported():
    for name in ("gbdt-higgs-11m.train", "ffm-criteo.stream-zipf",
                 "allreduce-4rank.hist-and-bulk", "gbdt-bosch-968.train",
                 "gbdt-bosch-score-500.batch",
                 "ffm-criteo-adagrad.stream-zipf"):
        got = {m["name"] for m in cells.load_cell(ROOT, name).per_layer}
        assert not any(n.startswith("ffmscore_") for n in got)


def test_untraced_run(capsys, toy_root):
    rc, lines = _run(capsys, toy_root, trace=0)
    assert rc == 0
    line = json.loads(lines[-1])
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    assert line["metrics"]["rows_per_s"]["unit"] == "rows/s"
    assert line["metrics"]["rows_per_s"]["value"] > 0
    window = _window(lines)
    assert window["compiles_in_window"] == 0
    counters, check = window["counters"], window["check"]
    assert counters["rows"] == 5003 * counters["jobs"]
    assert counters["enter_s"] > 0
    assert len(window["log"]["job_secs"]) == counters["jobs"]
    assert check["probs_shape"] == [5003] and check["all_inside_0_1"]
    # 512 sampled rows and both edges, less what the sample repeats
    assert 512 <= check["rows_checked"] <= 512 + 256
    assert check["margin_err_over_terms"] <= check["margin_err_bound"]
    assert check["margin_err_bound"] == 2.0 ** -16
    # every term is alive and no margin is in the sigmoid's flat ends
    assert check["terms_mean"] > 1 and 0.1 < check["margin_std"] < 1
    assert 0.01 < check["prob_min"] and check["prob_max"] < 0.99


def _in_bf16(x):
    import jax.numpy as jnp
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def test_a_table_held_in_bf16_is_not_correct(capsys, toy_root, monkeypatch):
    """The control: the same run with every gathered block rounded to
    bf16 misses the stated precision."""
    from ytk_mp4j_tpu.models import fm

    gather = fm._gather_blocks
    monkeypatch.setattr(fm, "_gather_blocks",
                        lambda T, feats: _in_bf16(gather(T, feats)))
    rc, lines = _run(capsys, toy_root, trace=0)
    assert rc == 0
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert _window(lines)["check"]["margin_err_over_terms"] > 2.0 ** -16


def test_a_select_at_the_default_precision_is_not_correct(
        capsys, toy_root, monkeypatch):
    """On the chip a matmul at the default precision takes its f32
    operands as bf16: here the select's output is rounded so."""
    from ytk_mp4j_tpu.models import fm

    select = fm._select_fields

    def rounded(blk, fields, cfg):
        wv, E = select(blk, fields, cfg)
        return _in_bf16(wv), _in_bf16(E)

    monkeypatch.setattr(fm, "_select_fields", rounded)
    rc, lines = _run(capsys, toy_root, trace=0)
    assert rc == 0
    assert json.loads(lines[-1])["correct"] is False
    assert _window(lines)["check"]["margin_err_over_terms"] > 2.0 ** -16


def test_traced_run(capsys, toy_root):
    rc, lines = _run(capsys, toy_root, trace=1)
    assert rc == 0
    line = json.loads(lines[-1])
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert line["correct"] is True and line["attempted"] == 1
    # the CPU's trace has no device plane: the trace readers find nothing
    # and their metrics are left out; counters and host spans are there
    assert FROM_HOST <= set(line["metrics"])
    assert not FROM_TRACE & set(line["metrics"])
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["metrics"]["step_builds_in_window"]["value"] == 0
    assert line["metrics"]["ffmscore_enter_s"]["value"] > 0


def test_a_checkout_without_enter_model_fails_at_once(tiny_root, monkeypatch):
    """What the parent of the PR that added the cell does with it: an
    error before the table and the file are made, not a hang and not a
    result."""
    from ytk_mp4j_tpu.models.fm import FMTrainer

    monkeypatch.delattr(FMTrainer, "enter_model")
    with pytest.raises(RuntimeError, match="no enter_model"):
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "0.3",
                  "--trace", "0"], root=tiny_root)


def test_same_seed_same_file_other_seed_other_file():
    from benchmark.adapters.ffm_score import zipf_file

    a = zipf_file(3000000019, 300_000, 39 * 1000, 39, 1.1, 39 ** -0.5)
    b = zipf_file(3000000019, 300_000, 39 * 1000, 39, 1.1, 39 ** -0.5)
    c = zipf_file(3000000020, 300_000, 39 * 1000, 39, 1.1, 39 ** -0.5)
    assert all((p == q).all() for p, q in zip(a, b))
    assert (a[0] != c[0]).any()
    feats, fields, vals = a
    assert feats.dtype == fields.dtype == np.int32
    assert vals.dtype == np.float32 and feats.shape == (300_000, 39)
    assert (fields == np.arange(39)).all()
    assert (vals == np.float32(39 ** -0.5)).all()
    # every field draws from its own 1,000 ids
    assert (feats // 1000 == np.arange(39)).all()
    # Zipf(1.1): the hottest id's share is 1 / H(1000, 1.1), the second's
    # 2^-1.1 of it; through a permutation, so not ids 0 and 1
    counts = np.sort(np.bincount(feats[:, 7] % 1000, minlength=1000))[::-1]
    h = (np.arange(1, 1001) ** -1.1).sum()
    assert counts[0] / 300_000 == pytest.approx(1 / h, rel=0.03)
    assert counts[1] / counts[0] == pytest.approx(2 ** -1.1, rel=0.05)
    assert counts[0] != np.bincount(feats[:, 7] % 1000)[0]


def test_the_reference_is_the_triple_loop():
    rng = np.random.default_rng(5)
    rows, slots, nf, k, n_features = 12, 6, 4, 3, 50
    w0 = 0.4
    w = rng.standard_normal(n_features)
    table = rng.standard_normal((n_features * nf, k))
    feats = rng.integers(0, n_features, (rows, slots))
    fields = rng.integers(0, nf, (rows, slots))
    vals = rng.standard_normal((rows, slots))
    vals[3] = 0.0
    vals[:, 4] = 0.0
    want = np.full(rows, w0)
    terms = np.full(rows, abs(w0))
    for n in range(rows):
        for a in range(slots):
            t = w[feats[n, a]] * vals[n, a]
            want[n] += t
            terms[n] += abs(t)
            for b in range(a + 1, slots):
                va = table[feats[n, a] * nf + fields[n, b]]
                vb = table[feats[n, b] * nf + fields[n, a]]
                t = sum(va[j] * vb[j] for j in range(k)) * vals[n, a] \
                    * vals[n, b]
                want[n] += t
                terms[n] += abs(t)
    got, got_terms = reference.score(w0, w, table, feats, fields, vals, nf)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(got_terms, terms, rtol=1e-12)
    assert got[3] == w0 and got_terms[3] == abs(w0)
    assert reference.margin_error(got + 1e-7 * terms, got, terms) == \
        pytest.approx(1e-7, rel=1e-6)
    p = 1 / (1 + np.exp(-got))
    np.testing.assert_allclose(reference.logit(p), got, atol=1e-12)


def test_arithmetic_of_a_job():
    nbytes = arith_ffm_score.score_min_bytes(6_042_135, 39, 39, 4)
    assert nbytes == 6_042_135 * 39 * (157 * 4 + 12) + 6_042_135 * 4
    flops = arith_ffm_score.score_flops(6_042_135, 39, 4)
    assert flops == 6_042_135 * (741 * 10 + 78)
    peaks = arith.peaks_for("TPU v5 lite")
    least, bound = arith.roofline_seconds(flops, nbytes, peaks)
    assert bound == "hbm" and 0.184 < least < 0.185
