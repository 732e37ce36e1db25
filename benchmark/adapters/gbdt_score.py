"""Drives ``GBDTTrainer.predict()``: whole scoring jobs, back to back,
closed loop, one client.

A job is ``trainer.predict(bins, trees)`` of the configuration's whole
host table through the whole ensemble: it stages the table, runs the
scoring program and ends with the margins on the host. Every job scores
the same table with the same ensemble, both drawn from the seed
(``drawn_ensemble``: routing is dense, so a drawn ensemble does the work
of a trained one). Only the trainer's public surface is used: the
constructor, ``predict()`` and ``shard_bins()``.
"""

from __future__ import annotations

import time
import traceback

import numpy as np

import jax

from benchmark import missing_table
from benchmark.reference import gbdt_score as reference
from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer
from ytk_mp4j_tpu.obs import spans as program_spans

CHECK_ROWS = 65_536         # rows the plain scorer is run on


def drawn_ensemble(seed: int, n_trees: int, n_features: int, n_bins: int,
                   depth: int, frozen_rate: float, leaf_scale: float):
    """``n_trees`` trees in the format ``train()`` returns, tree k from
    ``default_rng([seed, k])``: features uniform over the width,
    thresholds uniform in 1 .. n_bins - 2, directions Bernoulli(0.5), a
    share ``frozen_rate`` of the nodes frozen (bin n_bins - 1), leaves
    N(0, ``leaf_scale``) held in f32."""
    nodes = 2 ** depth - 1
    trees = []
    for k in range(n_trees):
        rng = np.random.default_rng([seed, k])
        bin_ = rng.integers(1, n_bins - 1, nodes).astype(np.int32)
        bin_[rng.random(nodes) < frozen_rate] = n_bins - 1
        trees.append((
            rng.integers(0, n_features, nodes).astype(np.int32), bin_,
            rng.integers(0, 2, nodes).astype(np.int32),
            (leaf_scale * rng.standard_normal(nodes + 1)).astype(np.float32)))
    return trees


class Adapter:
    def __init__(self, config, traffic, seed, devices, spans):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.spans = devices, spans
        self.first_margins = None   # kept for the check

    def setup(self):
        c = self.config
        cfg = GBDTConfig(
            n_features=c["n_features"], n_bins=c["n_bins"], depth=c["depth"],
            loss=c["loss"], learning_rate=c["learning_rate"],
            missing_bin=c["missing_bin"])
        self.trainer = GBDTTrainer(cfg, n_devices=len(self.devices))
        if not hasattr(self.trainer, "shard_bins"):
            # before the table is made: a checkout whose predict() is
            # not on the staged path cannot run this cell at all
            raise RuntimeError(
                "GBDTTrainer has no shard_bins(): this checkout's "
                "predict() places its table with one jnp.asarray and "
                "scores a level at a time; the cell needs the staged "
                "scoring path")
        with self.spans.span("gbdt.make_table"):
            self.bins, _ = missing_table.missing_binned_table(
                self.seed, c["rows"], c["n_features"], c["n_bins"],
                c["missing_rate"])
        with self.spans.span("gbdt.draw_ensemble"):
            self.trees = drawn_ensemble(
                self.seed, c["n_trees"], c["n_features"], c["n_bins"],
                c["depth"], c["frozen_rate"], c["leaf_scale"])

    def warmup(self):
        """One staging of the table timed on the host clock, then one
        whole job: it compiles the placer and the scoring program and
        fills the trainer's cache of the stacked ensemble."""
        with self.spans.span("gbdt.shard_bins"):
            jax.block_until_ready(self.trainer.shard_bins(self.bins))
        with self.spans.span("gbdt.warmup_job"):
            self.trainer.predict(self.bins, self.trees)

    def _job(self):
        with self.spans.span("gbdt.score_job"):
            margins = self.trainer.predict(self.bins, self.trees)
        if self.first_margins is None:
            self.first_margins = margins
        return margins.shape[0]

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
        parts = {name: [s[3] for s in program_spans.take_since(cursor)[1]
                        if s[0] == f"mp4j.gbdt.score.{name}"]
                 for name in ("stage", "fetch")}
        return {"attempted": attempted, "failed": failed,
                "metrics": {"rows_per_s": rows / elapsed},
                "counters": {"jobs": jobs, "rows": rows,
                             "trees": jobs * len(self.trees),
                             "elapsed_s": elapsed},
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

    def check(self):
        """Against ``reference/gbdt_score.py``: the first job's margins
        on a seeded sample of rows, every tree, each within the stated
        share of its own terms; every row of the table got a margin."""
        if self.first_margins is None:
            return False, {"error": "no job finished"}
        c = self.config
        margins = self.first_margins
        rows = self.bins.shape[0]
        sample = np.random.default_rng(self.seed).choice(
            rows, min(CHECK_ROWS, rows), replace=False)
        want, terms = reference.score_ensemble(
            self.trees, self.bins[sample], c["depth"], c["learning_rate"],
            c["n_bins"], c["missing_bin"])
        shape_ok = margins.shape == (rows,)
        err = (reference.margin_error(margins[sample], want, terms)
               if shape_ok else float("inf"))
        detail = {"margin_err_over_terms": err,
                  "margin_err_bound": reference.MARGIN_REL_ERR,
                  "margin_max_abs_err": float(
                      np.abs(margins[sample] - want).max())
                  if shape_ok else None,
                  "terms_mean": float(terms.mean()),
                  "margins_shape": list(margins.shape),
                  "rows_checked": int(sample.size),
                  "trees_checked": len(self.trees)}
        finite = bool(np.isfinite(margins).all())
        detail["compared"] = {
            "margins_shape_ok": [int(shape_ok), 1],
            "margins_finite": [int(finite), 1],
            "margin_err_over_terms": [err, reference.MARGIN_REL_ERR]}
        ok = (shape_ok and finite
              and err <= reference.MARGIN_REL_ERR)
        return bool(ok), detail
