"""Drives ``GBDTTrainer.predict_raw_chunks()`` on a wide table of floats
in which most cells are empty, handed over in row chunks as a file's
reader hands them: whole scoring jobs from floats, back to back, closed
loop, one client.

A job is ``trainer.predict_raw_chunks(reader, n_rows, trees)``: the
reader's chunks cross the host link into one float table, each piece's
rows are binned and scored by the whole ensemble as soon as they are in
place, and the job ends with the margins on the host. Every job scores
the same table (``benchmark/raw_table.py``: f32 with NaN, blockwise) by
the same ensemble (``adapters/gbdt_score.py: drawn_ensemble``) under the
same edges, fitted once at set-up on this table's own floats
(``QuantileBinner.fit_staged``: the configuration's ``assumed`` says why).
The window uses the trainer's public surface alone: the constructor,
``binner_`` and ``predict_raw_chunks()``.

The check, outside the window, of what the LAST timed job produced, on a
seeded sample of rows and the table's first and last ``END_ROWS`` (the
last piece goes through a program of its own): (i) its margins against
``reference/gbdt_score_raw.py`` (float64 bins by binary search, the
plain walk, a float64 sum) by the configuration's margin limit; (ii) the
same margins, bit for bit, against ``trainer.predict`` of the
reference's bins of those rows; (iii) every row got a finite margin;
(iv) what the job's ``mp4j.put_sharded`` spans say crossed the link: the
floats, once; (v) no ``mp4j.step.build`` inside the window.

A program that has no ``predict_raw_chunks`` (the parent of the PR that
added it) cannot run this cell: ``setup`` looks the method up before
anything is drawn and stops with the ``AttributeError``.
"""

from __future__ import annotations

import time
import traceback

import numpy as np

from benchmark import arith_raw, raw_table
from benchmark.adapters.gbdt_score import CHECK_ROWS, drawn_ensemble
from benchmark.reference import gbdt_score_raw as reference
from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer
from ytk_mp4j_tpu.obs import spans as program_spans

END_ROWS = 4_096            # the first and the last rows, besides a sample
# the host's phases of a job, by the program's own spans (host clock)
PHASES = {"stage": "mp4j.gbdt.score.stage",
          "dispatch": "mp4j.gbdt.score.dispatch",
          "fetch": "mp4j.gbdt.score.fetch",
          "link_wait": "mp4j.stage.link_wait",
          "device_wait": "mp4j.stage.device_wait",
          "put_sharded": "mp4j.put_sharded"}


def _now() -> int:
    """The span ring's cursor: what is recorded from here on."""
    return program_spans.take_since(1 << 62)[0]


class Adapter:
    def __init__(self, config, traffic, seed, devices, spans):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.spans = devices, spans
        self.last_job = None        # (margins, span cursor at its start)
        self.compares = None        # a cell's, as the program's build says

    def setup(self):
        # before the 4.58 GB table is drawn: a checkout without the
        # entry point stops here, at once, with the AttributeError
        self.entry_point = GBDTTrainer.predict_raw_chunks
        from ytk_mp4j_tpu.models.binning import QuantileBinner

        c = self.config
        with self.spans.span("gbdt.make_table"):
            self.X, labels = raw_table.raw_table(
                self.seed, c["rows"], c["n_features"], c["missing_rate"])
        cfg = GBDTConfig(
            n_features=c["n_features"], n_bins=c["n_bins"], depth=c["depth"],
            loss=c["loss"], learning_rate=c["learning_rate"],
            missing_bin=c["missing_bin"])
        self.trainer = GBDTTrainer(cfg, n_devices=len(self.devices))
        with self.spans.span("gbdt.draw_ensemble"):
            self.trees = drawn_ensemble(
                self.seed, c["n_trees"], c["n_features"], c["n_bins"],
                c["depth"], c["frozen_rate"], c["leaf_scale"])
        with self.spans.span("gbdt.fit_edges"):
            # the deployment's binner comes from the training file; here
            # from this table's own floats, staged once and let go
            step = c["chunk_rows"]
            table, _ = self.trainer.shard_raw_chunks(
                ((self.X[s:s + step], labels[s:s + step])
                 for s in range(0, len(self.X), step)), len(self.X))
            binner = QuantileBinner(c["n_bins"],
                                    missing_bucket=c["missing_bin"])
            binner.fit_staged(table, len(self.X), sample=c["bin_sample"],
                              seed=c["bin_seed"])
            del table
        self.trainer.binner_ = binner
        self.edges = np.array(binner.edges)

    def _reader(self):
        """Row slices of the host table in file order, ``chunk_rows``
        each and a shorter last one: what a CSV's reader yields."""
        step = self.config["chunk_rows"]
        for start in range(0, len(self.X), step):
            yield self.X[start:start + step]

    def warmup(self):
        """One whole job: it compiles the placers and both scoring
        programs (a whole piece's and the last chunk's) and fills the
        trainer's cache of the stacked ensemble."""
        cursor = _now()
        with self.spans.span("gbdt.warmup_job"):
            self.trainer.predict_raw_chunks(self._reader(), len(self.X),
                                            self.trees)
        # what the program says it issues a cell, beside the roofline's
        # bytes, which do not depend on it (``adapters/gbdt_raw.py``
        # reads the same argument off ``mp4j.bin.transform``)
        self.compares = next(
            (s[6]["compares"] for s in program_spans.take_since(cursor)[1]
             if s[0] == "mp4j.step.build"
             and s[6].get("key") == "gbdt_score_raw"), None)

    def _job(self):
        cursor = _now()
        with self.spans.span("gbdt.score_raw_job"):
            margins = self.trainer.predict_raw_chunks(
                self._reader(), len(self.X), self.trees)
        self.last_job = (margins, cursor)
        return margins.shape[0]

    def _jobs(self, keep_going) -> dict:
        attempted = failed = rows = 0
        job_secs = []
        cursor = _now()
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
        c = self.config
        recorded = program_spans.take_since(cursor)[1]
        # where a job's time went on the host, by the trainer's own
        # spans, summed over the window and divided by its jobs
        host_ms = {phase: 1e3 * sum(s[3] for s in recorded if s[0] == name)
                   / max(jobs, 1) for phase, name in PHASES.items()}
        self.builds_in_window = sum(s[0] == "mp4j.step.build"
                                    for s in recorded)
        counters = {
            "jobs": jobs, "rows": rows,
            "chunks": jobs * -(-len(self.X) // c["chunk_rows"]),
            "trees": jobs * len(self.trees), "elapsed_s": elapsed,
            "step_builds_in_window": self.builds_in_window,
            "transform_least_bytes_per_job":
                arith_raw.transform_least_bytes(c["rows"], c["n_features"])}
        if self.compares is not None:
            counters["transform_compares_per_job"] = (
                arith_raw.transform_compares(c["rows"], c["n_features"],
                                             self.compares))
        return {"attempted": attempted, "failed": failed,
                "metrics": {"rows_per_s": rows / elapsed},
                "counters": counters,
                "log": {"job_secs": job_secs, "host_ms_per_job": host_ms}}

    def window(self, seconds: float) -> dict:
        """Jobs back to back; a new one starts while ``seconds`` have not
        elapsed and the window closes at the end of the job in which they
        do."""
        return self._jobs(lambda done, elapsed: elapsed < seconds)

    def slice(self) -> dict:
        """The traced slice: one whole job."""
        return self._jobs(lambda done, elapsed: done < 1)

    def check(self):
        """(i) to (v) of the module docstring."""
        if self.last_job is None:
            return False, {"error": "no job finished"}
        c = self.config
        margins, cursor = self.last_job
        clock = [time.perf_counter()]

        def lap() -> float:
            clock.append(time.perf_counter())
            return round(clock[-1] - clock[-2], 3)

        # (iv) the last job's own spans, before the check adds any
        job_spans = program_spans.take_since(cursor)[1]
        rows = len(self.X)
        shape_ok = margins.shape == (rows,)
        sample = np.random.default_rng(self.seed).choice(
            rows, min(CHECK_ROWS, rows), replace=False)
        at = np.unique(np.r_[sample, :min(END_ROWS, rows),
                             max(0, rows - END_ROWS):rows])
        want, terms, bins = reference.score(
            self.trees, self.X[at], self.edges, c["depth"],
            c["learning_rate"], c["n_bins"], c["missing_bin"],
            c["missing_bin"])
        secs = {"reference": lap()}
        got = margins[at] if shape_ok else None
        err = (reference.margin_error(got, want, terms) if shape_ok
               else float("inf"))
        # (ii) the accepted scoring path on the reference's bins
        theirs = self.trainer.predict(bins, self.trees)
        secs["predict_of_reference_bins"] = lap()
        off = int((got != theirs).sum()) if shape_ok else int(at.size)
        crossed = [s[6]["bytes"] for s in job_spans
                   if s[0] == "mp4j.put_sharded"]
        floats = 4 * rows * c["n_features"]
        detail = {
            "margin_err_over_terms": err,
            "margin_err_bound": reference.MARGIN_REL_ERR,
            "margin_max_abs_err": float(np.abs(got - want).max())
            if shape_ok else None,
            "terms_mean": float(terms.mean()),
            "rows_off_predict_of_reference_bins": off,
            "bin0_share_of_checked_cells": float((bins == 0).mean()),
            "margins_shape": list(margins.shape),
            "margins_finite": bool(np.isfinite(margins).all()),
            "rows_checked": int(at.size),
            "trees_checked": len(self.trees),
            "edges_a_column": int(self.edges.shape[1]),
            "job_put_sharded_bytes": int(sum(crossed)),
            "job_put_sharded_bytes_expected": floats,
            "job_put_sharded_spans": len(crossed),
            "step_builds_in_window": int(self.builds_in_window),
            "check_secs": secs}
        detail["compared"] = {
            "margins_shape_ok": [int(shape_ok), 1],
            "margins_finite": [int(detail["margins_finite"]), 1],
            "margin_err_over_terms": [err, reference.MARGIN_REL_ERR],
            "rows_off_predict_of_reference_bins": [off, 0],
            "job_put_sharded_bytes": [int(sum(crossed)), floats],
            "job_put_sharded_spans": [len(crossed), 1],
            "step_builds_in_window": [int(self.builds_in_window), 0]}
        ok = (shape_ok and detail["margins_finite"]
              and err <= reference.MARGIN_REL_ERR and off == 0
              and crossed == [floats] and not self.builds_in_window)
        return bool(ok), detail
