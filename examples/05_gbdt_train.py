"""End-to-end consumer: distributed GBDT (the north-star workload).
Continuous features cross to the mesh once, in row chunks, and are
sketched and quantile-binned on device, samples shard over
the mesh, each boosting round is ONE jitted shard_map step whose
histogram allreduce is a psum, and ensemble predict runs in one jit,
from bins or (predict_raw_chunks / predict_raw) from floats that are
binned on device as they cross."""
import os
import tempfile

import numpy as np

from ytk_mp4j_tpu.models.binning import QuantileBinner
from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer

rng = np.random.default_rng(0)
N, F, B = 20_000, 8, 32
X = rng.standard_normal((N, F)).astype(np.float32)
y = ((X[:, 0] > 0).astype(np.float32)
     + 0.1 * rng.standard_normal(N).astype(np.float32))

# The one-call consumer path (ytk-learn shape): RAW continuous
# features in, the trainer quantile-bins internally (train_raw) and
# keeps the fitted binner for serving. On a multi-process job, pass
# ``comm=`` and the binner fits DISTRIBUTED (each rank sketches its
# own shard, one allgather merges — check/checkdist.py runs that).
# 32 bins are too few for the compiled Pallas kernel (n_bins % 128 == 0),
# so the XLA matmul strategy is chosen explicitly
cfg = GBDTConfig(n_features=F, n_bins=B, depth=4, n_trees=5,
                 learning_rate=0.3, hist_mode="matmul")
trainer = GBDTTrainer(cfg)  # all available devices, data-parallel
trees, train_preds = trainer.train_raw(X, y)

preds = trainer.predict_raw(X, trees)           # ensemble inference
mse0 = float(np.mean(y ** 2))
mse = float(np.mean((preds - y) ** 2))
print(f"mse: {mse0:.4f} -> {mse:.4f} after {len(trees)} trees")
assert mse < mse0

# trees grown leaf by leaf, best first (LightGBM's policy, XGBoost's
# lossguide): grow_policy="loss" and a budget of leaves; depth caps any
# leaf's depth. Same entry points, same trees downstream.
leafwise = GBDTTrainer(GBDTConfig(
    n_features=F, n_bins=B, depth=6, grow_policy="loss", max_leaves=12,
    n_trees=5, learning_rate=0.3, hist_mode="matmul"))
leaf_trees, _ = leafwise.train_raw(X, y)
leaf_mse = float(np.mean((leafwise.predict_raw(X, leaf_trees) - y) ** 2))
print(f"leaf-wise, 12 leaves a tree: mse {leaf_mse:.4f}, "
      f"{leafwise.grow_stats_['splits']} splits")
assert leaf_mse < mse0 and leafwise.grow_stats_["splits"] <= 5 * 11

# a table that arrives in pieces (a CSV read a block of rows at a time):
# any iterable of (X [m, F] float32 with NaN for empty cells, y [m]) and
# the total. The floats cross to the mesh once, the quantile sketch and
# the binning run there; the trees do not depend on where the chunks
# were cut (train_raw is this front end over row slices of one array).
reader = ((X[s:s + 3_000], y[s:s + 3_000]) for s in range(0, N, 3_000))
chunked = GBDTTrainer(cfg)
chunked_trees, chunked_preds = chunked.train_raw_chunks(reader, N)
assert np.array_equal(chunked_preds, train_preds)

# ... and is scored the same way: train from chunks, save (the fitted
# binner's edges ride with the model), load, score from chunks of floats
# (no labels). Each chunk is binned on the mesh where it lands, inside
# the scoring program, while the next ones cross; predict_raw is this
# entry point over row slices of one array.
path = os.path.join(tempfile.mkdtemp(), "gbdt.npz")
chunked.save_model(path, chunked_trees)
cfg2, trees2, binner2 = GBDTTrainer.load_model(path)
served = GBDTTrainer(cfg2).predict_raw_chunks(
    (X[s:s + 7_000] for s in range(0, N, 7_000)), N, trees2, binner=binner2)
assert np.array_equal(served, preds)

# the manual wiring underneath: the sketch/merge pair is what
# fit_distributed runs per rank on a multi-host job (edges are the
# merge's 2/Q-approximation of train_raw's exact local fit)
binner = QuantileBinner(B)
sketches = [binner.local_sketch(s) for s in np.array_split(X, 4)]
binner.merge_sketches(np.stack([s.values for s in sketches]),
                      np.stack([s.counts for s in sketches]))
bins = binner.transform(X)
manual_preds = GBDTTrainer(cfg).train(bins, y)[1]
assert np.isfinite(manual_preds).all()
