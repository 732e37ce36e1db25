"""Drives ``GBDTTrainer.train_raw_chunks()`` on a wide table of floats in
which most cells are empty, handed over in row chunks as a file's reader
hands them: whole training jobs from floats, back to back, closed loop,
one client.

The job loop, its counters (``jobs``, ``trees``, ``elapsed_s``) and the
window rule are ``adapters/gbdt.py``'s, by inheritance through
``gbdt_missing``; what differs is the table (``benchmark/raw_table.py``:
f32 with NaN), the job (``train_raw_chunks`` over a reader of row
slices, a fresh binner every time: the crossing, the sketch, the
transform and the trees are all inside it) and the check. The window
uses the trainer's public surface alone: the constructor and
``train_raw_chunks()``.

The check, outside the window, of what the first timed job produced:
(i) its edges against ``reference/gbdt_raw.py``'s float64 ones on the
same sampled rows; (ii) the DEVICE's binned table (the job's own
staging and transform run once more under the job's edges,
``shard_raw_chunks`` and ``transform_staged``, and fetched) against the
plain compare-count; (iii) ``gbdt_missing``'s check of the job's trees
and margins on those bins; (iv) what the job's ``mp4j.put_sharded``
spans say crossed the link.

A program that has no ``train_raw_chunks`` (the parent of the PR that
added it) cannot run this cell: ``setup`` says so before anything is
drawn.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import jax

from benchmark import arith_raw, raw_table
from benchmark.adapters import gbdt as dense
from benchmark.adapters import gbdt_missing as missing
from benchmark.reference import gbdt_raw as reference
from benchmark.reference import gbdt_rows_needed as needed
from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer
from ytk_mp4j_tpu.obs import spans as program_spans

# What the configuration guarantees of an edge: inside the two order
# statistics of the sample that bracket its quantile, and within 2**-22
# (two f32 ulps) of the larger of them in magnitude of the float64
# reference on the same sampled rows. The one place the two may differ
# is the interpolation: numpy subtracts the two f32 neighbours in f32
# before it steps in float64, which the program does too, and the
# reference works in float64 throughout. A wrong sample, a position
# computed in f32, a coarser sketch or bf16 values miss by a step of the
# three-decimal data, thousands of times the limit (PERF.md section 6).
EDGE_REL = 2.0 ** -22
END_ROWS = 4_096            # the first and the last rows, besides a sample
FETCH_ROWS = 65_536         # of the device's bins a transfer (254 MB)


class Adapter(missing.Adapter):
    def setup(self):
        if not hasattr(GBDTTrainer, "train_raw_chunks"):
            raise RuntimeError(
                "GBDTTrainer has no train_raw_chunks(): this cell's job is "
                "that entry point (a float table handed over in row "
                "chunks), and this program cannot run it")
        c = self.config
        with self.spans.span("gbdt.make_table"):
            self.X, self.y = raw_table.raw_table(
                self.seed, c["rows"], c["n_features"], c["missing_rate"])
        cfg = GBDTConfig(
            n_features=c["n_features"], n_bins=c["n_bins"], depth=c["depth"],
            loss=c["loss"], learning_rate=c["learning_rate"],
            reg_lambda=c["reg_lambda"], n_trees=c["n_trees"],
            missing_bin=c["missing_bin"])
        self.trainer = GBDTTrainer(cfg, n_devices=len(self.devices))
        self.edges = None           # the first job's, kept for the check
        self.job_spans = []         # the first job's own spans

    def _reader(self):
        """Row slices of the host table in file order, ``chunk_rows``
        each and a shorter last one: what a CSV's reader yields."""
        step = self.config["chunk_rows"]
        for start in range(0, len(self.X), step):
            yield self.X[start:start + step], self.y[start:start + step]

    def _train(self, n_trees: int):
        return self.trainer.train_raw_chunks(
            self._reader(), n_rows=len(self.X), n_trees=n_trees,
            bin_sample=self.config["bin_sample"])

    def warmup(self):
        """One whole job of one tree: compiles the placers, the sketch,
        the transform, the step, the key programs and the fetch, with
        the table staged and binned as every job stages and bins it."""
        with self.spans.span("gbdt.warmup_job"):
            self._train(1)

    def _job(self):
        first = self.first_job is None
        if first:
            cursor = program_spans.take_since(1 << 62)[0]
        with self.spans.span("gbdt.train_job"):
            trees, margins = self._train(self.config["n_trees"])
        if first:
            # the trees stay where the job left them and ``check``
            # fetches them: fetched here, one small array at a time,
            # they made the first job of a window longer than the others
            # by another amount every run (ledger notes of PR 43)
            self.first_job = (trees, margins)
            self.edges = np.array(self.trainer.binner_.edges)
            self.job_spans = program_spans.take_since(cursor)[1]
        return len(trees)

    def _jobs(self, keep_going) -> dict:
        result = super()._jobs(keep_going)
        c = self.config
        edges = next((s[6]["compares"] for s in self.job_spans
                      if s[0] == "mp4j.bin.transform"), None)
        if edges is not None:
            # what the program says it issues, beside the roofline's
            # bytes, which do not depend on it
            result["counters"]["transform_compares_per_job"] = (
                arith_raw.transform_compares(c["rows"], c["n_features"],
                                             edges))
            result["counters"]["transform_least_bytes_per_job"] = (
                arith_raw.transform_least_bytes(c["rows"], c["n_features"]))
        return result

    def _device_bins(self) -> tuple[np.ndarray, int]:
        """The binned table as the device makes it from the staged
        floats under the first job's edges (the job's own staging and
        transform), fetched once, ``FETCH_ROWS`` rows a transfer, and
        its largest bin, taken there. 256 bins fit a byte, and a
        transfer of bytes is a quarter of one of int32 (the link carries
        4.58 GB of them at a third of a GB/s: 15 s); the largest bin is
        read from the int32 table, so nothing can wrap unseen."""
        from ytk_mp4j_tpu.models.binning import QuantileBinner

        c = self.config
        binner = QuantileBinner(c["n_bins"], missing_bucket=c["missing_bin"])
        binner.edges = self.edges
        table, _ = self.trainer.shard_raw_chunks(self._reader(), c["rows"])
        table = binner.transform_staged(table)
        largest = int(jax.jit(lambda t: t.max())(table))
        wire = np.uint8 if largest < 256 else np.int32
        rows = min(FETCH_ROWS, c["rows"])
        piece = jax.jit(lambda t, start: jax.lax.dynamic_slice_in_dim(
            t.reshape(-1, c["n_features"]), start, rows).astype(wire))
        out = np.empty((c["rows"], c["n_features"]), np.int32)
        for start in range(0, c["rows"], rows):
            start = min(start, c["rows"] - rows)
            out[start:start + rows] = np.asarray(piece(table, np.int32(start)))
        return out, largest

    def _edges_check(self) -> tuple[bool, dict, np.ndarray]:
        """(i): every column's edges (the label's five, the emptiest and
        the fullest among them) against the float64 reference on the
        same sampled rows: inside the bracketing order statistics,
        within ``EDGE_REL`` of the larger of them, +inf where the
        reference has +inf (or -inf: equal where it is infinite). Returns (ok, detail, the reference's
        edges)."""
        c = self.config
        want, lo, hi = reference.edges(self.X, self.edges.shape[1],
                                       c["bin_sample"], 0)
        got = self.edges.astype(np.float64)
        infinite = np.isinf(want)
        with np.errstate(invalid="ignore"):
            outside = ~infinite & ((got < lo) | (got > hi))
            room = np.maximum(np.abs(lo), np.abs(hi))
            apart = np.where(infinite | (room == 0), 0.0,
                             np.abs(got - want) / np.where(room == 0, 1,
                                                           room))
        detail = {
            "edge_columns_checked": int(self.edges.shape[0]),
            "edges_outside_their_order_statistics": int(outside.sum()),
            "edges_max_rel_err": float(apart.max()),
            "edges_rel_err_limit": EDGE_REL,
            # where the reference is infinite, or both neighbours are
            # zero, there is no room: the edge is the reference's
            "edges_exact_mismatches": int(
                (infinite & (self.edges != want)).sum()
                + (~infinite & (room == 0) & (got != want)).sum()),
            "edges_equal_share": float((self.edges == want).mean()),
            "edges_repeated_share": float(
                (want[:, 1:] == want[:, :-1]).mean()),
        }
        ok = (not detail["edges_outside_their_order_statistics"]
              and detail["edges_max_rel_err"] <= EDGE_REL
              and not detail["edges_exact_mismatches"])
        return ok, detail, want

    def _bins_check(self, bins: np.ndarray, largest: int,
                    want_edges: np.ndarray):
        """(ii): on a seeded sample of rows and the first and last
        ``END_ROWS``, all columns: the device's bins equal the plain
        compare-count under the program's own edges, cell for cell, and
        equal the reference's bins except in cells whose value lies
        between the two versions of one edge; and in the whole table bin
        0 holds the NaN cells and nothing else."""
        rows = len(self.X)
        sample = np.random.default_rng(self.seed).choice(
            rows, min(dense.CHECK_ROWS, rows), replace=False)
        at = np.unique(np.r_[sample, :min(END_ROWS, rows),
                             max(0, rows - END_ROWS):rows])
        x, got = self.X[at], bins[at]
        own = reference.bins(x, self.edges)
        # the same function of the same edges gives the same bins
        theirs = (own if np.array_equal(self.edges, want_edges)
                  else reference.bins(x, want_edges))
        differ = got != theirs
        explained = differ & reference.between_versions(x, self.edges,
                                                        want_edges)
        step = 16_384

        def zero_wrong(s: int) -> int:
            return int(((bins[s:s + step] == 0)
                        != np.isnan(self.X[s:s + step])).sum())

        with ThreadPoolExecutor(8) as pool:
            zero_wrong = sum(pool.map(zero_wrong, range(0, rows, step)))
        detail = {
            "bin_rows_checked": int(len(at)),
            "bins_off_own_edges": int((got != own).sum()),
            "bins_off_reference": int(differ.sum()),
            "bins_off_reference_unexplained": int(
                (differ & ~explained).sum()),
            "bin0_is_not_nan_cells": zero_wrong,
            "bins_max": largest,
        }
        ok = (not detail["bins_off_own_edges"]
              and not detail["bins_off_reference_unexplained"]
              and not zero_wrong and detail["bins_max"] < self.config["n_bins"])
        return ok, detail

    def _link_check(self):
        """(iv): the ``bytes`` of the first job's ``mp4j.put_sharded``
        spans are the float table and the three row vectors (labels,
        margins, weights), no more: no binned cell crossed."""
        c = self.config
        n = len(self.devices)
        padded = -(-c["rows"] // n) * n
        want = 4 * c["rows"] * c["n_features"] + 3 * 4 * padded
        crossed = sum(s[6]["bytes"] for s in self.job_spans
                      if s[0] == "mp4j.put_sharded")
        return crossed == want, {"job_put_sharded_bytes": int(crossed),
                                 "job_put_sharded_bytes_expected": want}

    def _rows_needed(self, trees) -> list[int]:
        """The host's table is floats: ``check`` routes it through the
        trees under the reference's own edges before it lets the floats
        go (no binned table of the program's enters the count)."""
        return self.rows_needed

    def check(self):
        """(i) to (iv) of the module docstring; (iii) is
        ``gbdt_missing``'s check (root, every split of the second tree,
        the kernel's deepest-level sums, the margins by a plain router,
        logloss under ln 2, a node that learned "missing goes right")
        handed the device's bins."""
        if self.first_job is None:
            return False, {"error": "no job finished"}
        trees, margins = self.first_job
        self.first_job = ([tuple(np.asarray(a) for a in t) for t in trees],
                          margins)
        clock = [time.perf_counter()]

        def lap() -> float:
            clock.append(time.perf_counter())
            return round(clock[-1] - clock[-2], 3)

        link_ok, link = self._link_check()
        edges_ok, edges, want_edges = self._edges_check()
        secs = {"edges": lap()}
        if self.sliced:             # ``_needed_work``: a traced run's
            self.rows_needed = needed.rows_needed_a_tree(
                self.first_job[0], needed.raw(self.X, want_edges),
                len(self.X), self.config["depth"])
            secs["rows_needed"] = lap()
        self.bins, largest = self._device_bins()
        secs["device_bins"] = lap()
        bins_ok, bins = self._bins_check(self.bins, largest, want_edges)
        secs["bins"] = lap()
        del self.X                  # the trees are checked on the bins
        trees_ok, trees_detail = super().check()
        secs["trees"] = lap()
        own = {
            "edges_outside_their_order_statistics": 0,
            "edges_max_rel_err": EDGE_REL, "edges_exact_mismatches": 0,
            "bins_off_own_edges": 0, "bins_off_reference_unexplained": 0,
            "bin0_is_not_nan_cells": 0,
            "bins_max": self.config["n_bins"] - 1,
            "job_put_sharded_bytes": link["job_put_sharded_bytes_expected"]}
        detail = {**edges, **bins, **trees_detail, **link,
                  "check_secs": secs}
        detail["compared"] = {
            **{name: [detail[name], limit] for name, limit in own.items()},
            **trees_detail.get("compared", {})}
        return link_ok and edges_ok and bins_ok and trees_ok, detail
