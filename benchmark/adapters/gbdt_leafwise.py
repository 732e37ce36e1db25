"""Drives ``GBDTTrainer.train()`` with ``grow_policy="loss"`` on the wide,
mostly-missing table of ``gbdt_missing``: trees grown leaf by leaf
(best-first) under a budget of leaves and a cap on depth; whole training
jobs, back to back, closed loop, one client.

The table, the job loop and its counters are ``adapters/gbdt_missing.py``'s,
by inheritance. What differs is the trainer's configuration, which is
built *before* the table is drawn, so that a program without the policy
fails in ``setup`` at once with its own error; the labels, which the
traffic mix draws anew a job (``label_draws``: job i of a window trains
on draw i mod that many of the same table, ``missing_table.label_draw``;
draw 0 is the table's own label): which near-ties a tree's rounded
histograms break one way or the other is a draw's own, moves a job's
time by up to 1.8%, and averages over a window's draws; the window,
which closes at the end of the first job after ``--seconds`` at which
every draw has had as many jobs as every other, so that the mix of
draws a run times does not depend on how long a job takes; the counters
the trainer keeps of its growth (``grow_stats_``: ``grow_splits``,
``grow_rows_built``, and their share of the rows its passes read,
``grow_rows_read_``); and the check. It holds EVERY job of the window
to its own draw's label by routing and a count alone
(``reference/gbdt_rows_needed.py``, no histogram): the trainer's two
counts are the reference's over all the job's trees, the returned
margins are those of the returned trees on every row, logloss fell
under ln 2. And it replays best-first growth on float64 histograms of
every node of the first job's first two trees and its last
(``reference/gbdt_leafwise.py``).
"""

from __future__ import annotations

import collections
import contextlib
import time

import numpy as np

import jax

from benchmark import missing_table
from benchmark.adapters import gbdt as dense
from benchmark.adapters import gbdt_missing
from benchmark.reference import gbdt_leafwise as reference
from benchmark.reference import gbdt_missing as missing
from benchmark.reference import gbdt_rows_needed as needed
from ytk_mp4j_tpu.models.gbdt import (GBDTConfig, GBDTTrainer,
                                      build_histograms)


class Adapter(gbdt_missing.Adapter):
    def __init__(self, *args):
        super().__init__(*args)
        # every timed job: (draw, trees, margins, grow_stats_)
        self.jobs_kept = []
        self.grown = {"splits": 0, "rows_built": 0, "rows_read": 0}

    def setup(self):
        c = self.config
        # first, so that a program that lacks the policy stops here
        cfg = GBDTConfig(
            n_features=c["n_features"], n_bins=c["n_bins"], depth=c["depth"],
            loss=c["loss"], learning_rate=c["learning_rate"],
            reg_lambda=c["reg_lambda"], n_trees=c["n_trees"],
            missing_bin=c["missing_bin"], grow_policy=c["grow_policy"],
            max_leaves=c["max_leaves"])
        with self.spans.span("gbdt.make_table"):
            self.bins, self.y = missing_table.missing_binned_table(
                self.seed, c["rows"], c["n_features"], c["n_bins"],
                c["missing_rate"])
            self.labels = [self.y] + [
                missing_table.label_draw(self.bins, c["n_bins"], self.seed,
                                         draw)
                for draw in range(1, self.traffic.get("label_draws", 1))]
        self.trainer = GBDTTrainer(cfg, n_devices=len(self.devices))

    def _job(self):
        """Job i of a window or slice trains on draw i mod
        ``label_draws``; all of it is kept for the check, the trees as
        they are returned (on the device: fetching 96 small arrays is a
        round trip each, which is the check's to spend, not a job's)."""
        draw = len(self.jobs_kept) % len(self.labels)
        with self.spans.span("gbdt.train_job"):
            trees, margins = self.trainer.train(
                self.bins, self.labels[draw], n_trees=self.config["n_trees"])
        stats = dict(self.trainer.grow_stats_)
        self.jobs_kept.append((draw, trees, margins, stats))
        # what the job's passes read is the trainer's own count beside
        # the two (``grow_rows_read_``, the fetch span's ``rows_read``);
        # a program without it reports no share
        stats = {**stats,
                 "rows_read": getattr(self.trainer, "grow_rows_read_", 0)}
        for key in self.grown:
            self.grown[key] += stats[key]
        return len(trees)

    def _jobs(self, keep_going) -> dict:
        result = super()._jobs(keep_going)
        result["counters"].update(
            grow_splits=self.grown["splits"],
            grow_rows_built=self.grown["rows_built"],
            grow_rows_read=self.grown["rows_read"])
        result["log"]["job_draws"] = [job[0] for job in self.jobs_kept]
        if self.grown["rows_read"]:
            result["counters"]["grow_rows_built_share"] = (
                100.0 * self.grown["rows_built"] / self.grown["rows_read"])
        return result

    def window(self, seconds: float) -> dict:
        """Jobs back to back; a new one starts while ``seconds`` have not
        elapsed or some draw has had fewer jobs than another, so a window
        holds a whole multiple of ``label_draws`` jobs however long a job
        takes."""
        draws = len(self.labels)
        return self._jobs(lambda done, elapsed:
                          elapsed < seconds or done % draws != 0)

    def _routed(self, trees, before_tree=None) -> tuple[dict, np.ndarray]:
        """One job's trees by routing and a count, no histogram: the
        rows the trainer has to say it built a tree (the smaller child's
        under every split, the children at the cap on depth too:
        ``rows_needed(leaves_built=True)``), how many of them under the
        cap, the splits, and the float64 margins of every row.
        ``before_tree(i, tree, margins so far)`` is called ahead of tree
        i."""
        c = self.config
        rows = self.bins.shape[0]
        goes_right = needed.binned(self.bins, True)
        margins = np.zeros(rows)
        built, at_the_cap, splits = [], 0, 0
        for i, tree in enumerate(trees):
            if before_tree is not None:
                before_tree(i, tree, margins)
            deepest = needed.deepest_leaf(tree, goes_right, rows, c["depth"])
            built.append(needed.rows_needed(deepest, c["depth"],
                                            leaves_built=True))
            at_the_cap += built[-1] - needed.rows_needed(deepest, c["depth"])
            splits += len(reference.grown(tree, c["n_bins"])[0])
            margins += c["learning_rate"] * np.asarray(
                tree[3])[deepest].astype(np.float64)
        return ({"rows_built_a_tree": built, "splits": splits,
                 "rows_built": int(sum(built)),
                 "rows_built_at_the_cap": int(at_the_cap)}, margins)

    def _replayed(self, tree, g, h) -> tuple[dict, tuple]:
        """One tree against the float64 histograms of all its nodes from
        gradients ``g``, ``h`` (f64 values that f32 holds, so that both
        sides sum the same terms): every split the best candidate of its
        node within the stated precision, and best-first growth replayed
        on those gains. Returns (what to print, what ``_kernel_sums``
        asks the kernel for: the rows and the reference's histogram of
        the largest leaf whose histogram the grower built from rows)."""
        c = self.config
        split, leaves = reference.grown(tree, c["n_bins"])
        hists, rows, leaf_of_row = reference.tree_histograms(
            tree, self.bins, g, h, c["depth"], c["n_bins"])
        built = reference.built_from_rows(split, rows)
        errs = reference.histogram_errors(split, hists, built)
        best, bad = reference.node_gains(tree, hists, errs, c["reg_lambda"],
                                         split)
        order, broken = reference.replay(split, best, c["depth"],
                                         c["max_leaves"])
        leaf = max((k for k in leaves if built[k]), key=rows.get)
        return ({"leaves": len(leaves), "bad_nodes": bad,
                 "replay_order": order, "replay_broken": broken[:5],
                 "deepest_leaf": max(map(reference.level_of, leaves)),
                 "rows_built": reference.rows_built(split, rows),
                 "probed_leaf": [leaf, rows[leaf]]},
                (leaf_of_row == leaf, hists[leaf]))

    def _kernel_sums(self, g32, h32, in_leaf, want) -> float:
        """The kernel itself, asked through ``build_histograms`` with
        the trainer's configuration, on the table as ``shard_data``
        stages it, for one node the way the grower asks: the rows of one
        leaf that the grower built from rows, every other row on the
        sentinel id, all columns. Its sums over bins are held to the
        stated precision."""
        dbins = self.trainer.shard_data(self.bins, self.y)[0]
        cfg = self.trainer.cfg
        got_g, got_h = jax.jit(lambda b, g, h, i: build_histograms(
            b[0], g, h, i, 1, cfg))(
                dbins, g32, h32, np.where(in_leaf, 0, 1).astype(np.int32))
        want_g, want_h, want_abs = want
        return max(
            missing.prefix_sum_error(np.asarray(got_g)[0], want_g, want_abs),
            missing.prefix_sum_error(np.asarray(got_h)[0], want_h, want_h))

    def check(self):
        """Every job of the window against its own draw's label, by
        ``_routed``: the splits and the rows the trainer says it built
        histograms from are the reference's over all the job's trees
        (``reference/gbdt_rows_needed.py``, which is
        ``gbdt_leafwise.rows_built`` on the replayed trees); a plain
        router that sends missing cells by each node's stored direction
        reproduces the returned margins on every row; logloss fell below
        ln 2; some node learned "missing goes right"; every tree has the
        heap's shape. The first job, which is on the table's own label,
        also against ``reference/gbdt_missing.py`` what still holds of
        its cell's check (the first tree's root (feature, bin,
        direction) is the best candidate of float64 histograms or ties
        it within the stated histogram precision; the kernel's own sums
        for one built node keep the stated precision: ``_kernel_sums``,
        on the last tree's gradients) and against
        ``reference/gbdt_leafwise.py``, for its first two trees and its
        last, the replay (``_replayed``; a later tree's gradients from
        the float64 margins of every tree before it, by routing alone):
        every split the best candidate of its node, the order
        best-first, the budget of leaves met, no leaf under the cap."""
        if not self.jobs_kept:
            return False, {"error": "no job finished"}
        c = self.config
        rows = self.bins.shape[0]
        secs = collections.defaultdict(float)

        @contextlib.contextmanager
        def timed(part):
            t0 = time.perf_counter()
            yield
            secs[part] += time.perf_counter() - t0

        fetched = [[tuple(np.asarray(a) for a in tree) for tree in job[1]]
                   for job in self.jobs_kept]
        trees = fetched[0]
        with timed("root"):
            hist_g, hist_h = missing.root_histograms(self.bins, self.y,
                                                     c["n_bins"])
            gain = missing.split_gains(hist_g, hist_h, c["reg_lambda"])
            root = tuple(int(trees[0][k][0]) for k in range(3))
            root_ok = missing.root_split_ok(gain, hist_g, hist_h,
                                            c["reg_lambda"], *root)
        replayed, probe = [], {}

        def replay(i, tree, margin_so_far):
            if i not in (0, 1, len(trees) - 1):
                return
            with timed(f"replay_{i}"):
                g, h = missing.gradients(margin_so_far, self.y)
                g32, h32 = g.astype(np.float32), h.astype(np.float32)
                out, (in_leaf, want) = self._replayed(
                    tree, g32.astype(np.float64), h32.astype(np.float64))
                replayed.append({"tree": i, **out})
                probe.update(g32=g32, h32=h32, in_leaf=in_leaf, want=want)

        nodes = 2 ** c["depth"] - 1
        jobs = []
        for at, (draw, _, margins, stats) in enumerate(self.jobs_kept):
            job_trees = fetched[at]
            t0 = time.perf_counter()
            want, want_margins = self._routed(
                job_trees, replay if at == 0 else None)
            jobs.append({
                "draw": draw, **want, "grow_stats": stats,
                "counts_ok": all(stats.get(key) == want[key]
                                 for key in ("splits", "rows_built")),
                "heap_shaped": all(
                    [len(a) for a in tree] == [nodes] * 3 + [nodes + 1]
                    for tree in job_trees),
                "margin_max_abs_err": float(
                    np.abs(margins[:rows] - want_margins).max()),
                "logloss": missing.logloss(margins[:rows],
                                           self.labels[draw]),
                "missing_right_nodes": int(sum(
                    (tree[2] > 0).sum() for tree in job_trees))})
            secs["jobs_routed"] += time.perf_counter() - t0
        secs["jobs_routed"] -= sum(
            v for k, v in secs.items() if k.startswith("replay_"))
        kernel_err = 0.0
        if len(trees) > 1:      # gradients that are no longer +-0.5
            with timed("kernel_sums"):
                kernel_err = self._kernel_sums(**probe)
        first = jobs[0]
        stats_ok = all(job["counts_ok"] for job in jobs)
        shaped = all(job["heap_shaped"] for job in jobs)
        margin_err = max(job["margin_max_abs_err"] for job in jobs)
        loss = max(job["logloss"] for job in jobs)
        right = min(job["missing_right_nodes"] for job in jobs)
        rows_needed = sum(job["rows_built"] for job in jobs)
        detail = {"root_split": list(root), "root_ok": root_ok,
                  "best_candidate": [int(v) for v in np.unravel_index(
                      np.argmax(gain), gain.shape)],
                  "trees_replayed": replayed,
                  "hist_prefix_sum_err": kernel_err,
                  "hist_prefix_sum_err_bound": reference.HIST_REL_ERR,
                  "jobs_checked": jobs,
                  "grow_stats_ok": stats_ok,
                  "counters": {"hist_rows_needed": rows_needed},
                  "heap_shaped": shaped,
                  "margin_max_abs_err": margin_err, "logloss": loss,
                  "missing_right_nodes": right,
                  "trees_checked": sum(map(len, fetched)),
                  "check_secs": dict(secs),
                  "compared": {
                      "root_ok": [int(root_ok), 1],
                      "heap_shaped": [int(shaped), 1],
                      "jobs_with_counts_off": [
                          sum(not job["counts_ok"] for job in jobs), 0],
                      "grow_splits": [
                          sum(job["grow_stats"].get("splits", 0)
                              for job in jobs),
                          sum(job["splits"] for job in jobs)],
                      "grow_rows_built": [
                          sum(job["grow_stats"].get("rows_built", 0)
                              for job in jobs), rows_needed],
                      "replayed_bad_nodes": [
                          sum(len(r["bad_nodes"]) for r in replayed), 0],
                      "replayed_steps_broken": [
                          sum(len(r["replay_broken"]) for r in replayed), 0],
                      "replayed_leaves_most": [
                          max(r["leaves"] for r in replayed),
                          c["max_leaves"]],
                      "replayed_leaf_deepest": [
                          max(r["deepest_leaf"] for r in replayed),
                          c["depth"]],
                      "hist_prefix_sum_err": [kernel_err,
                                              reference.HIST_REL_ERR],
                      "margin_max_abs_err": [margin_err, dense.MARGIN_ATOL],
                      "logloss": [loss, float(np.log(2.0))],
                      "missing_right_nodes_at_least": [right, 1]}}
        ok = (root_ok and shaped and stats_ok
              and all(not r["bad_nodes"] and not r["replay_broken"]
                      and r["leaves"] <= c["max_leaves"]
                      and r["deepest_leaf"] <= c["depth"]
                      and r["rows_built"]
                      == first["rows_built_a_tree"][r["tree"]]
                      for r in replayed)
              and kernel_err <= reference.HIST_REL_ERR
              and margin_err <= dense.MARGIN_ATOL
              and np.isfinite(loss) and loss < np.log(2.0) and right >= 1)
        return bool(ok), detail
