"""Drives ``GBDTTrainer.train()`` with ``missing_bin=True`` on a wide
table in which most cells are missing: whole training jobs, back to
back, closed loop, one client.

The job loop, its counters (``jobs``, ``trees``, ``elapsed_s``) and the
window rule are ``adapters/gbdt.py``'s, by inheritance; what differs is
the table (``benchmark/missing_table.py``: bin 0 is the reserved missing
bucket), the trainer's configuration and the check, which holds the
trainer to the stored default directions and its histograms to the
stated precision (``reference/gbdt_missing.py``). The window uses the
trainer's public surface alone: the constructor and ``train()``, with
the trainer's default ``hist_mode``; the check, outside the window, also
asks ``build_histograms`` for one level's sums on the staged table.
"""

from __future__ import annotations

import numpy as np

import jax

from benchmark import missing_table
from benchmark.adapters import gbdt as dense
from benchmark.reference import gbdt_missing as reference
from ytk_mp4j_tpu.models.gbdt import (GBDTConfig, GBDTTrainer,
                                      build_histograms)


class Adapter(dense.Adapter):
    def setup(self):
        c = self.config
        with self.spans.span("gbdt.make_table"):
            self.bins, self.y = missing_table.missing_binned_table(
                self.seed, c["rows"], c["n_features"], c["n_bins"],
                c["missing_rate"])
        cfg = GBDTConfig(
            n_features=c["n_features"], n_bins=c["n_bins"], depth=c["depth"],
            loss=c["loss"], learning_rate=c["learning_rate"],
            reg_lambda=c["reg_lambda"], n_trees=c["n_trees"],
            missing_bin=c["missing_bin"])
        self.trainer = GBDTTrainer(cfg, n_devices=len(self.devices))

    def warmup(self):
        """One job of one tree: compiles the step, the key programs and
        the fetch, with the table staged as every job stages it."""
        with self.spans.span("gbdt.warmup_job"):
            self.trainer.train(self.bins, self.y, n_trees=1)

    def _second_tree(self, trees) -> dict:
        """The second tree, whose gradients are no longer +-0.5: the
        reference routes the first tree in float64, works g and h out
        (held in f32, so that both sides sum the same terms) and builds
        the histograms of every node of the second tree in one pass.
        Every split of the tree is held to them; and the kernel itself
        is asked, through ``build_histograms`` with the trainer's
        configuration on the table as ``shard_data`` stages it, for the
        histograms of the deepest level as the trainer builds them (the
        left children, every other row on the sentinel id), all 968
        columns, and its sums over bins are held to the stated
        precision."""
        c = self.config
        g, h = reference.gradients(reference.route_margins(
            trees[:1], self.bins, c["depth"], c["learning_rate"]), self.y)
        g32, h32 = g.astype(np.float32), h.astype(np.float32)
        levels, deepest = reference.tree_level_histograms(
            trees[1], self.bins, g32.astype(np.float64),
            h32.astype(np.float64), c["depth"], c["n_bins"])
        bad, checked = reference.tree_splits_ok(trees[1], levels,
                                                c["reg_lambda"])
        out = {"second_tree_nodes_checked": checked,
               "second_tree_bad_nodes": bad,
               "second_tree_features": sorted({
                   int(f) for f, b in zip(*trees[1][:2])
                   if b < c["n_bins"] - 1})}
        if c["depth"] > 1:
            n_left = 2 ** (c["depth"] - 2)
            ids = np.where(deepest % 2 == 0, deepest // 2, n_left)
            dbins = self.trainer.shard_data(self.bins, self.y)[0]
            cfg = self.trainer.cfg
            got_g, got_h = jax.jit(lambda b, g, h, i: build_histograms(
                b[0], g, h, i, n_left, cfg))(
                    dbins, g32, h32, ids.astype(np.int32))
            want_g, want_h, want_abs = (a[-1][0::2] for a in levels)
            out["hist_prefix_sum_err"] = max(
                reference.prefix_sum_error(np.asarray(got_g), want_g,
                                           want_abs),
                reference.prefix_sum_error(np.asarray(got_h), want_h,
                                           want_h))
        return out

    def check(self):
        """Against ``reference/gbdt_missing.py``: the first tree's root
        (feature, bin, direction) is the best candidate of float64
        histograms or ties it within the stated histogram precision; so
        is every split of the second tree, and the kernel's own sums at
        its deepest level keep that precision (``_second_tree``); a
        plain router that sends missing cells by each node's stored
        direction reproduces the returned margins on a seeded sample;
        logloss fell below ln 2; some node learned "missing goes
        right"."""
        if self.first_job is None:
            return False, {"error": "no job finished"}
        trees, margins = self.first_job
        c = self.config
        rows = self.bins.shape[0]
        hist_g, hist_h = reference.root_histograms(self.bins, self.y,
                                                   c["n_bins"])
        gain = reference.split_gains(hist_g, hist_h, c["reg_lambda"])
        root = tuple(int(trees[0][k][0]) for k in range(3))
        root_ok = reference.root_split_ok(gain, hist_g, hist_h,
                                          c["reg_lambda"], *root)
        later = self._second_tree(trees) if len(trees) > 1 else {}
        sample = np.random.default_rng(self.seed).choice(
            rows, min(dense.CHECK_ROWS, rows), replace=False)
        want = reference.route_margins(trees, self.bins[sample], c["depth"],
                                       c["learning_rate"])
        margin_err = float(np.abs(margins[:rows][sample] - want).max())
        loss = reference.logloss(margins[:rows], self.y)
        right = int(sum((t[2] > 0).sum() for t in trees))
        detail = {"root_split": list(root), "root_ok": root_ok,
                  "best_candidate": [int(v) for v in np.unravel_index(
                      np.argmax(gain), gain.shape)],
                  **later,
                  "margin_max_abs_err": margin_err, "logloss": loss,
                  "missing_right_nodes": right,
                  "missing_share": float(hist_h[0, 0] / hist_h[0].sum()),
                  "trees_checked": len(trees), **self._needed_work(trees),
                  "compared": {
                      "root_ok": [int(root_ok), 1],
                      "second_tree_bad_nodes": [
                          len(later.get("second_tree_bad_nodes", [])), 0],
                      "hist_prefix_sum_err": [
                          later.get("hist_prefix_sum_err", 0.0),
                          reference.HIST_REL_ERR],
                      "margin_max_abs_err": [margin_err, dense.MARGIN_ATOL],
                      "logloss": [loss, float(np.log(2.0))],
                      "missing_right_nodes_at_least": [right, 1]}}
        ok = (root_ok and not later.get("second_tree_bad_nodes")
              and later.get("hist_prefix_sum_err", 0.0)
              <= reference.HIST_REL_ERR
              and margin_err <= dense.MARGIN_ATOL
              and np.isfinite(loss) and loss < np.log(2.0) and right >= 1)
        return bool(ok), detail
