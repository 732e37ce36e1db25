"""FFM consumer: field-aware factorization machine with the sparse
embedding-gradient allreduce (the Criteo-shaped workload of
BASELINE.json configs[4]); train, persist, and serve."""
import numpy as np

from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer

rng = np.random.default_rng(0)
N, NF, NFIELDS, K = 20_000, 1000, 4, 6
feats = rng.integers(0, NF, (N, K)).astype(np.int32)
fields = rng.integers(0, NFIELDS, (N, K)).astype(np.int32)
vals = np.ones((N, K), np.float32)
y = (feats.min(1) < NF // 10).astype(np.float32)

cfg = FMConfig(model="ffm", n_features=NF, n_fields=NFIELDS, k=4,
               max_nnz=K, learning_rate=0.5)
trainer = FMTrainer(cfg, sparse_grads=True)  # device sparse allreduce
params, losses = trainer.fit(feats, fields, vals, y, n_steps=100)
print(f"logloss: {losses[0]:.4f} -> {losses[-1]:.4f}")
assert losses[-1] < losses[0]

trainer.save_params("/tmp/ffm_model.npz", params)
cfg2, params2 = FMTrainer.load_params("/tmp/ffm_model.npz", FMConfig)
serve = FMTrainer(cfg2)
p = serve.predict(params2, feats[:5], fields[:5], vals[:5])
print("served probs:", np.round(p, 3))
