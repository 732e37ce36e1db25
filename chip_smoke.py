#!/usr/bin/env python3
"""The quickest proof that ytk-mp4j-tpu still starts on the chip.

One process drives the main path once, through the entry points a user
calls, on every device jax reports, without ``jax_enable_x64``:

1. GBDT, the flagship: ``GBDTTrainer.train_raw`` on Higgs-like synthetic
   data at full width (1,000,000 x 28, 256 bins, depth 6), then
   ``predict_raw``. The compiled step must contain the Mosaic histogram
   kernel, the placed data must span every device, the kernel must agree
   with the XLA matmul strategy inside ``shard_map``, the scanned
   predict must agree with the numpy host router, and with more than one
   device the ensemble must agree with a one-device run.
2. The FFM sparse step at the bench shape (8192 x 8 nnz, 100,000
   features x 8 fields, k=8), plus sparse-against-dense at a small shape.
3. The driver surface: ``TpuCommCluster`` dense collectives and one
   sparse map allreduce against the numpy / dict oracles.

A phase that raises ends the run: nothing here catches a failure and
carries on. The phases are plain functions of their sizes, so tier-1
calls them tiny on the CPU. ``main()`` chooses no platform; it refuses
to run on anything but a TPU. The last line of stdout is the JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with exactly those keys; the line before it is the JSON summary of the
versions, the phases and the machine facts, ending ``"claim": null``.

Besides the phases it records three facts about the installation (not
metrics): the scalar host<->device round trip, whether
``jax.block_until_ready`` blocks, and the compile-cache directory.
"""

from __future__ import annotations

import importlib.metadata
import json
import math
import statistics
import sys
import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ytk_mp4j_tpu import meta
from ytk_mp4j_tpu.check._oracle import expected_reduce, rank_data
from ytk_mp4j_tpu.comm.tpu_comm import TpuCommCluster
from ytk_mp4j_tpu.models import gbdt
from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer
from ytk_mp4j_tpu.operands import Operands
from ytk_mp4j_tpu.operators import Operators
from ytk_mp4j_tpu.utils.compile_cache import enable_compilation_cache

REQUIRED_PLATFORM = "tpu"


def _logloss(p: np.ndarray, y: np.ndarray) -> float:
    p = np.clip(p.astype(np.float64), 1e-12, 1 - 1e-12)
    return float(-np.mean(y * np.log(p) + (1 - y) * np.log1p(-p)))


def higgs_like(n_rows: int, n_features: int, seed: int):
    """Continuous features with a nonlinear, noisy binary target."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_rows, n_features), dtype=np.float32)
    z = X[:, 0] * X[:, 1] + 0.8 * X[:, 2] - 0.5 * X[:, 3] ** 2 + 0.5
    noise = 0.5 * rng.standard_normal(n_rows, dtype=np.float32)
    return X, (z + noise > 0).astype(np.float32)


def _root_split(tree) -> tuple[int, int]:
    """(feature, bin) of a tree's root node."""
    return int(tree[0][0]), int(tree[1][0])


def _hist_kernel_against_matmul(trainer, dbins, dy, dw) -> float:
    """The configured histogram strategy against the XLA matmul strategy
    on the placed shards, inside ``shard_map`` with ``check_vma`` on (the
    step's own context). Node ids run one past ``n_nodes`` so the
    out-of-range sentinel of the sibling-subtraction path is exercised.
    Returns the largest absolute difference; the tolerance is the one
    tests/test_hist_kernel.py holds the kernel to."""
    cfg, axes = trainer.cfg, trainer.axes
    spec = P(axes)
    n_nodes = 4
    interpret = trainer.mesh.devices.flat[0].platform != "tpu"

    @jax.jit
    @partial(jax.shard_map, mesh=trainer.mesh,
             in_specs=(spec, spec, spec), out_specs=(spec, spec))
    def both(bins, y, w):
        b, g = bins[0], (0.5 - y[0]) * w[0]
        h = 0.25 * w[0]
        nid = b[:, 0] % (n_nodes + 1)
        got = gbdt.build_histograms(b, g, h, nid, n_nodes, cfg,
                                    interpret=interpret)
        want = gbdt._build_histograms_matmul(b, g, h, nid, n_nodes, cfg)
        return (jnp.stack(got)[None], jnp.stack(want)[None])

    lowered = both.lower(dbins, dy, dw)
    assert ("tpu_custom_call" in lowered.as_text()) == (not interpret)
    got, want = (np.asarray(a) for a in lowered.compile()(dbins, dy, dw))
    assert got.shape == (trainer.n_shards, 2, n_nodes, cfg.n_features,
                         cfg.n_bins), got.shape
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert float(np.abs(want).max()) > 0.0
    return float(np.abs(got - want).max())


def _block_until_ready_blocks(trainer, dbins, dy, dpreds, dw, kd) -> dict:
    """Dispatch one already-compiled GBDT step, then time
    ``jax.block_until_ready`` against the ``np.asarray`` that follows.
    Where the first really waits for the device the second finds the
    value ready and is the shorter of the two."""
    out = trainer._step(dbins, dy, dpreds, dw, kd)
    t0 = time.perf_counter()
    jax.block_until_ready(out)
    t1 = time.perf_counter()
    np.asarray(out[1][3])
    t2 = time.perf_counter()
    return {"block_secs": t1 - t0, "fetch_secs": t2 - t1,
            "blocks": (t1 - t0) > (t2 - t1)}


def phase_gbdt(n_rows: int = 1_000_000, n_features: int = 28,
               n_bins: int = 256, depth: int = 6, n_trees: int = 3,
               predict_rows: int = 65536, seed: int = 0) -> dict:
    X, y = higgs_like(n_rows, n_features, seed)
    cfg = gbdt.GBDTConfig(n_features=n_features, n_bins=n_bins,
                          depth=depth, loss="logistic")
    trainer = gbdt.GBDTTrainer(cfg)
    trees, _ = trainer.train_raw(X, y, n_trees=n_trees)
    assert len(trees) == n_trees
    bins = trainer.binner_.transform(X)

    Xp, yp = X[:predict_rows], y[:predict_rows]
    p_first = trainer.predict_raw(Xp, trees[:1], proba=True)
    p_last = trainer.predict_raw(Xp, trees, proba=True)
    assert p_last.shape == (len(Xp),), p_last.shape
    losses = [_logloss(p_first, yp), _logloss(p_last, yp)]
    assert all(math.isfinite(v) for v in losses), losses
    assert losses[1] < losses[0] < math.log(2.0), losses

    # the scanned device predict against the numpy host router
    margins = trainer.predict_raw(Xp, trees)
    host = gbdt.servable(trees, cfg).partial_margins(
        bins[:predict_rows], 0, 1)[:, 0]
    np.testing.assert_allclose(margins, host, rtol=0, atol=1e-5)

    dbins, dy, dpreds, dw = trainer.shard_data(bins, y)
    spanned = len(dbins.sharding.device_set)
    assert spanned == jax.device_count(), (spanned, jax.device_count())

    # on a TPU mesh the step holds the Mosaic kernel; anywhere else the
    # kernel is interpreted and no custom call may appear
    on_tpu = trainer.mesh.devices.flat[0].platform == "tpu"
    kd = jax.random.key_data(jax.random.key(0))
    text = trainer._build_step().lower(dbins, dy, dpreds, dw,
                                       kd).as_text()
    assert ("tpu_custom_call" in text) == on_tpu, (
        f"Mosaic custom call present={'tpu_custom_call' in text} on a "
        f"{trainer.mesh.devices.flat[0].platform} mesh")

    out = {
        "rows": n_rows, "trees": n_trees, "devices_spanned": spanned,
        "logloss_tree1": losses[0], f"logloss_tree{n_trees}": losses[1],
        "root_split": _root_split(trees[0]),
        "mosaic_custom_call": on_tpu,
        "hist_kernel_vs_matmul_max_abs": _hist_kernel_against_matmul(
            trainer, dbins, dy, dw),
        "block_until_ready": _block_until_ready_blocks(
            trainer, dbins, dy, dpreds, dw, kd),
    }

    if jax.device_count() > 1:
        # The same trees on a one-device mesh. Per-shard partial sums
        # meeting in a psum add in another order than one device's
        # running sum, which moves a histogram entry by ~1e-6 relative;
        # leaf values -G/(H+lambda) and so margins inherit that, far
        # inside 1e-4 in probability. A split whose two best candidates
        # tie within that noise may flip and move that node's rows
        # only, hence the 99th percentile and not the maximum. Data
        # left on one device, or a psum that does not sum, moves every
        # row by orders of magnitude more.
        single = gbdt.GBDTTrainer(cfg, n_devices=1)
        trees1, _ = single.train_raw(X, y, n_trees=n_trees)
        roots = [_root_split(t) for t in trees]
        roots1 = [_root_split(t) for t in trees1]
        assert roots == roots1, (roots, roots1)
        dp = np.abs(p_last - single.predict_raw(Xp, trees1, proba=True))
        q99 = float(np.quantile(dp, 0.99))
        assert q99 <= 1e-4, (q99, float(dp.max()))
        out["one_device_dp_q99"] = q99
        out["one_device_dp_max"] = float(dp.max())
    return out


def phase_ffm(n_rows: int = 8192, n_features: int = 100_000,
              n_fields: int = 8, k: int = 8, max_nnz: int = 8,
              n_steps: int = 3, seed: int = 3) -> dict:
    rng = np.random.default_rng(seed)
    feats = rng.integers(0, n_features, (n_rows, max_nnz)).astype(np.int32)
    fields = rng.integers(0, n_fields, (n_rows, max_nnz)).astype(np.int32)
    vals = np.ones((n_rows, max_nnz), np.float32)
    y = (rng.random(n_rows) > 0.5).astype(np.float32)
    cfg = FMConfig(model="ffm", n_features=n_features, n_fields=n_fields,
                   k=k, max_nnz=max_nnz, learning_rate=0.05)
    _, losses = FMTrainer(cfg, sparse_grads=True).fit(
        feats, fields, vals, y, n_steps=n_steps)
    assert losses.shape == (n_steps,), losses.shape
    assert np.isfinite(losses).all(), losses
    # init_scale is 0.01, so the first loss sits at ln 2; full-batch
    # descent on a fixed batch then lowers it
    assert abs(float(losses[0]) - math.log(2.0)) < 0.05, losses
    assert losses[-1] < losses[0], losses

    # the sparse (row, grad) allreduce against the dense psum at a small
    # shape, to the tolerance tests/test_fm.py uses
    small = FMConfig(model="ffm", n_features=64, n_fields=4, k=4,
                     max_nnz=4, learning_rate=0.3, init_scale=0.1)
    n = 96
    sf = rng.integers(0, 64, (n, 4)).astype(np.int32)
    sd = rng.integers(0, 4, (n, 4)).astype(np.int32)
    sv = rng.random((n, 4)).astype(np.float32)
    sy = (sf[:, 0] % 2).astype(np.float32)
    fits = [FMTrainer(small, sparse_grads=sparse).fit(
        sf, sd, sv, sy, n_steps=n_steps, seed=3) for sparse in (False, True)]
    for a, b in zip(fits[0][0], fits[1][0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
    return {"rows": n_rows, "steps": n_steps,
            "losses": [float(v) for v in losses]}


def phase_driver(length: int = 1_000_000, n_keys: int = 50_000,
                 seed: int = 4200) -> dict:
    cluster = TpuCommCluster()
    n = cluster.n
    alls = [rank_data(r, length, Operands.FLOAT, seed) for r in range(n)]
    for op_name in ("SUM", "MAX", "MIN", "PROD"):
        arrs = [a.copy() for a in alls]
        cluster.allreduce_array(arrs, Operands.FLOAT,
                                Operators.by_name(op_name))
        want = expected_reduce(alls, op_name)
        for a in arrs:
            if op_name in ("MAX", "MIN"):
                np.testing.assert_array_equal(a, want)
            else:   # float summation / product order differs
                np.testing.assert_allclose(a, want, rtol=1e-5, atol=1e-5)

    ranges = meta.partition_range(0, length, n)
    want = expected_reduce(alls, "SUM")
    arrs = [a.copy() for a in alls]
    cluster.reduce_scatter_array(arrs, Operands.FLOAT, Operators.SUM)
    for a, (s, e) in zip(arrs, ranges):
        np.testing.assert_allclose(a[s:e], want[s:e], rtol=1e-5, atol=1e-5)

    want = np.concatenate([alls[r][s:e] for r, (s, e) in enumerate(ranges)])
    arrs = [a.copy() for a in alls]
    cluster.allgather_array(arrs, Operands.FLOAT)
    for a in arrs:
        np.testing.assert_array_equal(a, want)

    root = 1 % n
    arrs = [a.copy() for a in alls]
    cluster.broadcast_array(arrs, Operands.FLOAT, root=root)
    for a in arrs:
        np.testing.assert_array_equal(a, alls[root])

    # int keys, each rank's range half-overlapping the next rank's;
    # small-integer values keep the float32 sums exact
    maps = [{key: np.float32(key % 97 + r + 1)
             for key in range(r * n_keys // 2, r * n_keys // 2 + n_keys)}
            for r in range(n)]
    oracle: dict = {}
    for m in maps:
        for key, v in m.items():
            oracle[key] = oracle.get(key, np.float32(0)) + v
    cluster.allreduce_map(maps, Operands.FLOAT, Operators.SUM)
    for m in maps:
        assert m.keys() == oracle.keys(), (len(m), len(oracle))
        assert all(m[key] == v for key, v in oracle.items())
    return {"ranks": n, "length": length, "map_keys": n_keys,
            "map_union": len(oracle)}


def scalar_round_trip(reps: int = 50) -> float:
    """Median seconds to dispatch a scalar program and fetch its result."""
    bump = jax.jit(lambda v: v + 1)
    x = jax.device_put(np.float32(0))
    float(bump(x))
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(bump(x))
        samples.append(time.perf_counter() - t0)
    return statistics.median(samples)


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling (or reading the
    persistent cache instead), from ``jax.monitoring``'s own events."""

    def __init__(self):
        self.secs = 0.0
        self.cache_hits = 0

    def on_duration(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.secs += secs

    def on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


PHASES = (("gbdt", phase_gbdt), ("ffm", phase_ffm),
          ("driver", phase_driver))


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def main() -> int:
    cache_dir = enable_compilation_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != REQUIRED_PLATFORM:
        print(f"chip_smoke: needs a {REQUIRED_PLATFORM} device, jax found "
              f"platform {dev.platform!r} ({dev.device_kind}, "
              f"{len(devices)} device(s))", file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    versions = {d: _version(d) for d in ("jax", "jaxlib", "libtpu")}
    print(f"platform: {dev.platform}  device_kind: {dev.device_kind}  "
          f"devices: {len(devices)}")
    print("  ".join(f"{d} {v}" for d, v in versions.items()))
    print(f"compile cache: {cache_dir}")

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock.on_duration)
    jax.monitoring.register_event_listener(clock.on_event)
    phases = {}
    try:
        for name, fn in PHASES:
            c0, h0, t0 = clock.secs, clock.cache_hits, time.perf_counter()
            result = fn()
            wall = time.perf_counter() - t0
            compile_secs = clock.secs - c0
            phases[name] = {"compile_secs": round(compile_secs, 3),
                            "run_secs": round(wall - compile_secs, 3),
                            "cache_hits": clock.cache_hits - h0, **result}
            print(f"phase {name}: compile {compile_secs:.1f} s, run "
                  f"{wall - compile_secs:.1f} s, cache hits "
                  f"{clock.cache_hits - h0}: {json.dumps(result)}")
        rtt = scalar_round_trip()
    finally:
        jax.monitoring.unregister_event_duration_listener(
            clock.on_duration)
        jax.monitoring.unregister_event_listener(clock.on_event)

    bur = phases["gbdt"]["block_until_ready"]
    machine = {"scalar_round_trip_secs": rtt,
               "block_until_ready_blocks": bur["blocks"],
               "compile_cache_dir": cache_dir}
    print(f"scalar round trip: median {rtt * 1e3:.3f} ms; "
          f"block_until_ready took {bur['block_secs'] * 1e3:.1f} ms, the "
          f"np.asarray after it {bur['fetch_secs'] * 1e3:.3f} ms "
          f"(blocks: {bur['blocks']})")
    print(json.dumps({"versions": versions, "phases": phases,
                      "machine": machine, "claim": None}))
    # the contract line: these two keys and no other
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
