"""Drives ``GBDTTrainer.train()`` with ``grow_policy="loss"`` on the wide,
mostly-missing table of ``gbdt_missing``: trees grown leaf by leaf
(best-first) under a budget of leaves and a cap on depth; whole training
jobs, back to back, closed loop, one client.

The table, the job loop, its counters and the window rule are
``adapters/gbdt_missing.py``'s, by inheritance. What differs is the
trainer's configuration, which is built *before* the table is drawn, so
that a program without the policy fails in ``setup`` at once with its
own error; the counters the trainer keeps of its growth
(``grow_stats_``: ``grow_splits``, ``grow_rows_built`` and their share
of the rows the passes read); and the check, which replays best-first
growth on float64 histograms of every node of the job's two trees
(``reference/gbdt_leafwise.py``).
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

import jax

from benchmark import missing_table
from benchmark.adapters import gbdt as dense
from benchmark.adapters import gbdt_missing
from benchmark.reference import gbdt_leafwise as reference
from benchmark.reference import gbdt_missing as missing
from ytk_mp4j_tpu.models.gbdt import (GBDTConfig, GBDTTrainer,
                                      build_histograms)


class Adapter(gbdt_missing.Adapter):
    def __init__(self, *args):
        super().__init__(*args)
        self.first_stats = None     # the first timed job's grow_stats_
        self.grown = {"splits": 0, "rows_built": 0}

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
        self.trainer = GBDTTrainer(cfg, n_devices=len(self.devices))

    def _job(self):
        trees = super()._job()
        stats = self.trainer.grow_stats_
        if self.first_stats is None:
            self.first_stats = dict(stats)
        for key in self.grown:
            self.grown[key] += stats[key]
        return trees

    def _jobs(self, keep_going) -> dict:
        self.grown = dict.fromkeys(self.grown, 0)
        result = super()._jobs(keep_going)
        # rows the passes of the jobs' trees read: every pass (the root's
        # and one a split) reads the whole table
        passes = self.grown["splits"] + result["counters"]["trees"]
        result["counters"].update(
            grow_splits=self.grown["splits"],
            grow_rows_built=self.grown["rows_built"],
            grow_rows_built_share=(
                100.0 * self.grown["rows_built"]
                / (passes * self.bins.shape[0]) if passes else 0.0))
        return result

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
        """Against ``reference/gbdt_missing.py`` what still holds of its
        cell's check: the first tree's root (feature, bin, direction) is
        the best candidate of float64 histograms or ties it within the
        stated histogram precision; a plain router that sends missing
        cells by each node's stored direction reproduces the returned
        margins on a seeded sample; logloss fell below ln 2; some node
        learned "missing goes right"; the kernel's own sums for one
        built node keep the stated precision (``_kernel_sums``). And
        against ``reference/gbdt_leafwise.py``, for every tree of the
        job (two), the replay (``_replayed``): every split the best
        candidate of its node, the order best-first, the budget of
        leaves met, no leaf under the cap, and the rows the trainer says
        it built histograms from the reference's count."""
        if self.first_job is None:
            return False, {"error": "no job finished"}
        trees, margins = self.first_job
        c = self.config
        rows = self.bins.shape[0]
        secs = {}

        @contextlib.contextmanager
        def timed(part):
            t0 = time.perf_counter()
            yield
            secs[part] = time.perf_counter() - t0

        with timed("root"):
            hist_g, hist_h = missing.root_histograms(self.bins, self.y,
                                                     c["n_bins"])
            gain = missing.split_gains(hist_g, hist_h, c["reg_lambda"])
            root = tuple(int(trees[0][k][0]) for k in range(3))
            root_ok = missing.root_split_ok(gain, hist_g, hist_h,
                                            c["reg_lambda"], *root)
        nodes = 2 ** c["depth"] - 1
        shaped = all([len(a) for a in tree] == [nodes] * 3 + [nodes + 1]
                     for tree in trees)
        replayed, kernel_err = [], 0.0
        margin_so_far = np.zeros(rows)
        for i, tree in enumerate(trees):
            with timed(f"replay_{i}"):
                g, h = missing.gradients(margin_so_far, self.y)
                g32, h32 = g.astype(np.float32), h.astype(np.float32)
                out, probe = self._replayed(
                    tree, g32.astype(np.float64), h32.astype(np.float64))
                replayed.append(out)
                margin_so_far += missing.route_margins(
                    [tree], self.bins, c["depth"], c["learning_rate"])
        if len(trees) > 1:      # gradients that are no longer +-0.5
            with timed("kernel_sums"):
                kernel_err = self._kernel_sums(g32, h32, *probe)
        with timed("margins"):
            sample = np.random.default_rng(self.seed).choice(
                rows, min(dense.CHECK_ROWS, rows), replace=False)
            want = missing.route_margins(trees, self.bins[sample],
                                         c["depth"], c["learning_rate"])
            margin_err = float(np.abs(margins[:rows][sample] - want).max())
            loss = missing.logloss(margins[:rows], self.y)
        right = int(sum((tree[2] > 0).sum() for tree in trees))
        stats_ok = self.first_stats == {
            "splits": sum(r["leaves"] - 1 for r in replayed),
            "rows_built": sum(r["rows_built"] for r in replayed)}
        detail = {"root_split": list(root), "root_ok": root_ok,
                  "best_candidate": [int(v) for v in np.unravel_index(
                      np.argmax(gain), gain.shape)],
                  "trees_replayed": replayed,
                  "hist_prefix_sum_err": kernel_err,
                  "hist_prefix_sum_err_bound": reference.HIST_REL_ERR,
                  "grow_stats": self.first_stats,
                  "grow_stats_ok": stats_ok,
                  "heap_shaped": shaped,
                  "margin_max_abs_err": margin_err, "logloss": loss,
                  "missing_right_nodes": right,
                  "trees_checked": len(trees), "check_secs": secs}
        ok = (root_ok and shaped and stats_ok
              and all(not r["bad_nodes"] and not r["replay_broken"]
                      and r["leaves"] <= c["max_leaves"]
                      and r["deepest_leaf"] <= c["depth"] for r in replayed)
              and kernel_err <= reference.HIST_REL_ERR
              and margin_err <= dense.MARGIN_ATOL
              and np.isfinite(loss) and loss < np.log(2.0) and right >= 1)
        return bool(ok), detail
