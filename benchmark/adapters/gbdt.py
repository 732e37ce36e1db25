"""Drives ``GBDTTrainer.train()``: whole training jobs, back to back,
closed loop, one client.

Only the trainer's public surface is used: the constructor, ``train()``
and ``shard_data()``. A job is ``train(bins, y, n_trees=T)``: it stages
the table, queues T steps and ends in the fetch of the margins, which is
a real synchronisation. Every job trains the same T trees from the same
table, so a run does a fixed amount of work drawn from the seed.
"""

from __future__ import annotations

import time
import traceback

import numpy as np

import jax

from benchmark import traffic as traffic_gen
from benchmark.reference import gbdt as reference
from benchmark.reference import gbdt_rows_needed as needed
from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer

CHECK_ROWS = 65_536         # rows the plain router is run on
MARGIN_ATOL = 1e-5          # f32 sums of T leaf values against f64


class Adapter:
    def __init__(self, config, traffic, seed, devices, spans):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.spans = devices, spans
        self.first_job = None       # (trees, margins) kept for the check
        self.sliced = False         # a traced run: its readers want counts
        self.jobs_done = 0          # in the window or slice

    def setup(self):
        c = self.config
        with self.spans.span("gbdt.make_table"):
            self.bins, self.y = traffic_gen.binned_table(
                self.seed, c["rows"], c["n_features"], c["n_bins"])
        cfg = GBDTConfig(
            n_features=c["n_features"], n_bins=c["n_bins"], depth=c["depth"],
            loss=c["loss"], learning_rate=c["learning_rate"],
            reg_lambda=c["reg_lambda"], n_trees=c["n_trees"])
        self.trainer = GBDTTrainer(cfg, n_devices=len(self.devices))

    def warmup(self):
        """One job of one tree: compiles the step, the key programs and
        the fetch, with the table staged as every job stages it."""
        with self.spans.span("gbdt.warmup_job"):
            self.trainer.train(self.bins, self.y, n_trees=1)
        # what a job's staging costs on the host clock, outside any window
        with self.spans.span("gbdt.shard_data"):
            jax.block_until_ready(self.trainer.shard_data(self.bins, self.y))

    def _job(self):
        with self.spans.span("gbdt.train_job"):
            trees, margins = self.trainer.train(
                self.bins, self.y, n_trees=self.config["n_trees"])
        if self.first_job is None:
            self.first_job = ([tuple(np.asarray(a) for a in t)
                               for t in trees], margins)
        return len(trees)

    def _jobs(self, keep_going) -> dict:
        attempted = failed = trees = 0
        job_secs = []
        t0 = time.perf_counter()
        while keep_going(attempted, time.perf_counter() - t0):
            attempted += 1
            s = time.perf_counter()
            try:
                trees += self._job()
            except Exception:       # the job is lost, the run reports it
                traceback.print_exc()
                failed += 1
                break
            job_secs.append(time.perf_counter() - s)
        elapsed = time.perf_counter() - t0
        self.jobs_done = attempted - failed
        return {"attempted": attempted, "failed": failed,
                "metrics": {"trees_per_s": trees / elapsed},
                "counters": {"jobs": attempted - failed, "trees": trees,
                             "elapsed_s": elapsed},
                "log": {"job_secs": job_secs}}

    def window(self, seconds: float) -> dict:
        """Jobs back to back; a new one starts while ``seconds`` have not
        elapsed and the window closes at the end of the job in which they
        do."""
        return self._jobs(lambda done, elapsed: elapsed < seconds)

    def slice(self) -> dict:
        """The traced slice: one whole job."""
        self.sliced = True
        return self._jobs(lambda done, elapsed: done < 1)

    def _rows_needed(self, trees) -> list[int]:
        """Rows each tree's histograms had to be built from
        (``reference/gbdt_rows_needed.py``): the host's table routed
        through the returned tree, all rows for the root and the smaller
        child's under every node whose children may split."""
        c = self.config
        return needed.rows_needed_a_tree(
            trees, needed.binned(self.bins, bool(c.get("missing_bin"))),
            self.bins.shape[0], c["depth"])

    def _needed_work(self, trees) -> dict:
        """What ``check`` adds to its detail for the readers of the
        kernel's roofline and the step's share of the peak: the needed
        rows of the first timed job a tree, and of all the slice's jobs
        in all (every job trains the same trees from the same table and
        label, so the first job's count times the jobs is theirs).
        Counted outside the slice, and only in a traced run: nothing
        reads it in an untraced one, and 16 trees on 11M rows take 10 s
        to route."""
        if not self.sliced:
            return {}
        per_tree = self._rows_needed(trees)
        return {"rows_needed_a_tree": per_tree,
                "counters": {
                    "hist_rows_needed": int(sum(per_tree)) * self.jobs_done}}

    def check(self):
        """Against ``reference/gbdt.py``: the first tree's root split is
        the best (or ties the best) candidate of float64 bincount
        histograms; a plain router over the returned trees reproduces the
        returned margins on a seeded sample; logloss fell below ln 2.
        ``detail["counters"]`` (``_needed_work``) joins the counters the
        per-layer readers see."""
        if self.first_job is None:
            return False, {"error": "no job finished"}
        trees, margins = self.first_job
        c = self.config
        gain = reference.root_gains(self.bins, self.y, c["n_bins"],
                                    c["reg_lambda"])
        feat, bin_ = int(trees[0][0][0]), int(trees[0][1][0])
        root_ok = reference.root_split_ok(gain, feat, bin_)
        rows = np.random.default_rng(self.seed).choice(
            self.bins.shape[0], min(CHECK_ROWS, self.bins.shape[0]),
            replace=False)
        want = reference.route_margins(trees, self.bins[rows], c["depth"],
                                       c["learning_rate"])
        got = margins[: self.bins.shape[0]][rows]
        margin_err = float(np.abs(got - want).max())
        loss = reference.logloss(margins[: self.bins.shape[0]], self.y)
        detail = {"root_split": [feat, bin_], "root_ok": root_ok,
                  "best_candidate": [int(v) for v in np.unravel_index(
                      np.argmax(gain), gain.shape)],
                  "margin_max_abs_err": margin_err, "logloss": loss,
                  "trees_checked": len(trees), **self._needed_work(trees),
                  "compared": {
                      "root_ok": [int(root_ok), 1],
                      "margin_max_abs_err": [margin_err, MARGIN_ATOL],
                      "logloss": [loss, float(np.log(2.0))]}}
        ok = (root_ok and margin_err <= MARGIN_ATOL
              and np.isfinite(loss) and loss < np.log(2.0))
        return bool(ok), detail
