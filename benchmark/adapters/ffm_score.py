"""Drives ``FMTrainer.predict()``: whole scoring jobs, back to back,
closed loop, one client.

A job is ``trainer.predict(model, feats, fields, vals)`` of the
configuration's whole host file by the model that was entered once in
the warm-up (``trainer.enter_model``): it stages the file, scores it and
ends with a probability a row on the host. Every job scores the same
file with the same model, both drawn from the seed. Only the trainer's
public surface is used: the constructor, its ``mesh``, ``enter_model()``
and ``predict()``.

The public parameters (2.62 GB) are alive on the device only while the
model enters; the check draws them again from the seed (the same
program, the same key) when it needs the rows the sampled instances
touch.
"""

from __future__ import annotations

import concurrent.futures
import time
import traceback

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.reference import ffm_score as reference
from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer
from ytk_mp4j_tpu.obs import spans as program_spans

DRAW_ROWS = 262_144         # rows of the file one task of the pool draws
DRAW_THREADS = 8
FETCH_FEATURES = 32_768     # features whose rows one gather fetches


def params_maker(config, sharding):
    """A jitted ``key -> (w0, w, table)``, every parameter drawn and none
    zero: the table uniform in [-table_uniform_half, +half) a field's
    block of rows at a time, written in place (temporaries: a block), the
    linear weights uniform in [-w_uniform_half, +half), the bias
    ``bias``; placed as ``enter_model`` takes them."""
    n_features, n_fields, k = (config["n_features"], config["n_fields"],
                               config["k"])
    half, w_half = config["table_uniform_half"], config["w_uniform_half"]

    def make_params(key):
        def block(i, table):
            rows = jax.random.uniform(
                jax.random.fold_in(key, i), (n_features, k), jnp.float32,
                -half, half)
            return lax.dynamic_update_slice(table, rows, (i * n_features, 0))
        table = lax.fori_loop(
            0, n_fields, block,
            jnp.zeros((n_features * n_fields, k), jnp.float32))
        w = jax.random.uniform(jax.random.fold_in(key, n_fields),
                               (n_features,), jnp.float32, -w_half, w_half)
        return jnp.full((), config["bias"], jnp.float32), w, table

    return jax.jit(make_params, out_shardings=(sharding,) * 3)


def _alias_tables(weights: np.ndarray):
    """Walker's alias tables (Vose's construction) of a discrete law:
    ``(prob f32 [n], alias int32 [n])`` such that drawing j uniform in
    [0, n) and u uniform in [0, 1) and taking ``j if u < prob[j] else
    alias[j]`` draws from ``weights / weights.sum()``."""
    n = weights.size
    scaled = (weights / weights.sum() * n).tolist()
    prob, alias = [1.0] * n, list(range(n))
    small = [i for i, p in enumerate(scaled) if p < 1.0]
    large = [i for i, p in enumerate(scaled) if p >= 1.0]
    while small and large:
        s, g = small.pop(), large.pop()
        prob[s], alias[s] = scaled[s], g
        scaled[g] -= 1.0 - scaled[s]
        (small if scaled[g] < 1.0 else large).append(g)
    return np.asarray(prob, np.float32), np.asarray(alias, np.int32)


def zipf_file(seed: int, rows: int, n_features: int, n_fields: int,
              exponent: float, value: float):
    """The file as ``predict`` takes it, ``(feats, fields, vals)``, each
    [rows, n_fields]: slot f holds one feature of field f, drawn from
    that field's own ``n_features // n_fields`` ids by a Zipf law of the
    given exponent through a fixed permutation (``traffic.py``'s
    ``zipf_chunk_pool`` law, drawn by the alias method: two uniforms and
    two look-ups an id, where the inverse of the distribution function
    is a search an id). Block b of ``DRAW_ROWS`` rows is drawn from
    ``default_rng([seed, b])``, so the file does not depend on how many
    threads draw it; every value is ``value``."""
    per_field = n_features // n_fields
    if per_field < 1:
        raise ValueError("fewer features than fields")
    prob, alias = _alias_tables(
        np.arange(1, per_field + 1, dtype=np.float64) ** -float(exponent))
    perm = np.random.default_rng(seed).permutation(per_field).astype(np.int32)
    base = np.arange(n_fields, dtype=np.int32) * per_field
    field_ids = np.arange(n_fields, dtype=np.int32)
    feats, fields = (np.empty((rows, n_fields), np.int32) for _ in range(2))
    vals = np.empty((rows, n_fields), np.float32)

    def draw(b: int):
        # np.take and the generator's draws let go of the interpreter's
        # lock, a[index] does not: blocks are drawn side by side
        rng = np.random.default_rng([seed, b])
        at = slice(b * DRAW_ROWS, (b + 1) * DRAW_ROWS)
        out = feats[at]
        j = rng.integers(0, per_field, out.shape, dtype=np.int32)
        u = rng.random(out.shape, dtype=np.float32)
        rank = np.where(u < np.take(prob, j), j, np.take(alias, j))
        np.add(np.take(perm, rank), base, out=out)
        fields[at] = field_ids
        vals[at] = value

    with concurrent.futures.ThreadPoolExecutor(DRAW_THREADS) as pool:
        list(pool.map(draw, range(-(-rows // DRAW_ROWS))))
    return feats, fields, vals


class Adapter:
    def __init__(self, config, traffic, seed, devices, spans):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.spans = devices, spans
        self.first_probs = None     # kept for the check

    def setup(self):
        c = self.config
        self.cfg = FMConfig(
            model=c["model"], n_features=c["n_features"],
            n_fields=c["n_fields"], k=c["k"], max_nnz=c["max_nnz"],
            loss=c["loss"])
        self.trainer = FMTrainer(
            self.cfg, n_devices=len(self.devices),
            table_sharding=c["table_sharding"])
        if not hasattr(self.trainer, "enter_model"):
            # before the table and the file are made: a checkout whose
            # predict() is not on the staged path cannot run this cell
            raise RuntimeError(
                "FMTrainer has no enter_model(): this checkout's predict() "
                "gathers a row a slot pair from the public table, eagerly, "
                "and holds [rows, 39, 39, 4] whole (147 GB for this file); "
                "the cell needs the staged scoring path")
        with self.spans.span("ffm.make_file"):
            self.file = zipf_file(
                self.seed, c["rows"], c["n_features"], c["n_fields"],
                self.traffic["zipf_exponent"],
                c["max_nnz"] ** -0.5 if c["instance_norm"] else 1.0)
        self._make_params = params_maker(
            c, NamedSharding(self.trainer.mesh, P()))
        with self.spans.span("ffm.make_table"):
            self._params = self._make_params(jax.random.key(self.seed))
            jax.block_until_ready(self._params)

    def warmup(self):
        """The model's entering, timed on the host clock, then one whole
        job: it compiles the conversion, the placer and the two scoring
        programs (a full chunk's and the last chunk's remainder)."""
        cursor = program_spans.take_since(0)[0]
        with self.spans.span("ffm.enter_model"):
            self.model = self.trainer.enter_model(self._params)
        self._params = None     # a deployment holds the entered model
        # the trainer's own span round the conversion
        self.enter_s = sum(s[3] for s in program_spans.take_since(cursor)[1]
                           if s[0] == "mp4j.ffm.score.enter")
        with self.spans.span("ffm.warmup_job"):
            self.trainer.predict(self.model, *self.file)

    def _job(self):
        with self.spans.span("ffm.score_job"):
            probs = self.trainer.predict(self.model, *self.file)
        if self.first_probs is None:
            self.first_probs = probs
        return probs.shape[0]

    def _jobs(self, keep_going) -> dict:
        attempted = failed = rows = 0
        job_secs = []
        cursor = program_spans.take_since(0)[0]
        t0 = time.perf_counter()
        while keep_going(attempted, time.perf_counter() - t0):
            attempted += 1
            s = time.perf_counter()
            try:
                rows += self._job()
            except Exception:       # the job is lost, the run reports it
                traceback.print_exc()
                failed += 1
                break
            job_secs.append(time.perf_counter() - s)
        elapsed = time.perf_counter() - t0
        jobs = attempted - failed
        # where a slow job went: the trainer's own spans, job by job
        taken = program_spans.take_since(cursor)[1]
        parts = {name: [s[3] for s in taken
                        if s[0] == f"mp4j.ffm.score.{name}"]
                 for name in ("stage", "fetch")}
        return {"attempted": attempted, "failed": failed,
                "metrics": {"rows_per_s": rows / elapsed},
                "counters": {"jobs": jobs, "rows": rows,
                             "elapsed_s": elapsed, "enter_s": self.enter_s},
                "log": {"job_secs": job_secs, "stage_secs": parts["stage"],
                        "fetch_secs": parts["fetch"]}}

    def window(self, seconds: float) -> dict:
        """Jobs back to back; a new one starts while ``seconds`` have not
        elapsed and the window closes at the end of the job in which they
        do."""
        return self._jobs(lambda done, elapsed: elapsed < seconds)

    def slice(self) -> dict:
        """The traced slice: one whole job."""
        return self._jobs(lambda done, elapsed: done < 1)

    def _touched_model(self, feats):
        """The public model cut to the features ``feats`` holds:
        ``(w0, w [U], V [U * n_fields, k], feats renumbered 0 .. U-1)``,
        the parameters drawn again from the seed and their rows fetched
        ``FETCH_FEATURES`` features a gather."""
        c = self.cfg
        w0, w, V = self._make_params(jax.random.key(self.seed))
        # a feature's n_fields rows, gathered as rows: a reshape of the
        # table to a feature a row would copy all of it (83.7 GB)
        own = jnp.arange(c.n_fields, dtype=jnp.int32)
        gather = jax.jit(lambda w, V, ids: (
            w[ids], V[ids[:, None] * c.n_fields + own]))
        uniq, renumbered = np.unique(feats, return_inverse=True)
        ws, blocks = [], []
        for lo in range(0, uniq.size, FETCH_FEATURES):
            ids = np.zeros(FETCH_FEATURES, np.int32)
            part = uniq[lo:lo + FETCH_FEATURES]
            ids[:part.size] = part
            gw, gV = gather(w, V, ids)
            ws.append(np.asarray(gw)[:part.size])
            blocks.append(np.asarray(gV)[:part.size])
        return (float(w0), np.concatenate(ws),
                np.concatenate(blocks).reshape(-1, c.k),
                renumbered.reshape(feats.shape))

    def check(self):
        """Against ``reference/ffm_score.py``: the first timed job's own
        output on a seeded sample of rows and on the file's first and
        last ``check_edge_rows`` (no shorter than a tile of the program),
        each margin (the logit of the returned probability, in float64)
        within the stated share of its own terms; every row of the file
        got one probability, finite and inside (0, 1)."""
        if self.first_probs is None:
            return False, {"error": "no job finished"}
        c, t = self.cfg, self.traffic
        probs = self.first_probs
        feats, fields, vals = self.file
        rows = feats.shape[0]
        edge = min(t["check_edge_rows"], rows)
        sample = np.unique(np.concatenate([
            np.random.default_rng(self.seed).choice(
                rows, min(t["check_rows"], rows), replace=False),
            np.arange(edge), np.arange(rows - edge, rows)]))
        shape_ok = probs.shape == (rows,) and probs.dtype == np.float32
        detail = {"probs_shape": list(probs.shape),
                  "margin_err_bound": reference.MARGIN_REL_ERR,
                  "rows_checked": int(sample.size)}
        if not shape_ok:
            detail["compared"] = {"probs_shape_ok": [0, 1]}
            return False, detail
        inside = bool(np.isfinite(probs).all() and (probs > 0).all()
                      and (probs < 1).all())
        w0, w, V, renumbered = self._touched_model(feats[sample])
        want, terms = reference.score(w0, w, V, renumbered, fields[sample],
                                      vals[sample], c.n_fields)
        got = reference.logit(probs[sample])
        err = reference.margin_error(got, want, terms)
        detail.update(
            margin_err_over_terms=err,
            margin_max_abs_err=float(np.abs(got - want).max()),
            terms_mean=float(terms.mean()),
            margin_mean=float(want.mean()), margin_std=float(want.std()),
            prob_min=float(probs.min()), prob_max=float(probs.max()),
            all_inside_0_1=inside,
            features_fetched=int(w.size),
            compared={
                "probs_shape_ok": [1, 1],
                "all_inside_0_1": [int(inside), 1],
                "margin_err_over_terms": [err, reference.MARGIN_REL_ERR]})
        return bool(inside and err <= reference.MARGIN_REL_ERR), detail
