"""Distributed correctness check program — multi-host (DCN) level.

Third check family: one ``main()`` per PROCESS joins a
``jax.distributed`` job (the TPU-native rendezvous replacing the
reference's master, SURVEY.md section 3a), then checks

1. the host-level :class:`DistributedComm` slave API (dense + map
   collectives against the numpy oracle), and
2. the perf path: a jitted ``shard_map`` psum over a GLOBAL mesh built
   from every process's devices — host-local data placed with
   ``jax.make_array_from_process_local_data``, the cross-host allreduce
   staged by XLA over ICI/DCN.

Launch (2 processes x 2 CPU devices each, loopback coordinator):

    for i in 0 1; do
        python -m ytk_mp4j_tpu.check.checkdist \
            --coordinator localhost:9876 --num-processes 2 \
            --process-id $i --local-devices 2 &
    done
"""

from __future__ import annotations

import argparse
import sys
import traceback

import numpy as np


def check(comm, length: int = 97) -> int:
    from ytk_mp4j_tpu import meta
    from ytk_mp4j_tpu.check._oracle import expected_reduce, rank_data
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    n, r = comm.slave_num, comm.rank
    fails = 0

    def expect(name, ok):
        nonlocal fails
        if not ok:
            fails += 1
            comm.error(f"{name} MISMATCH")

    for operand in (Operands.DOUBLE, Operands.FLOAT, Operands.INT):
        exact = operand.dtype.kind != "f"
        alls = [rank_data(q, length, operand, 3000) for q in range(n)]
        ranges = meta.partition_range(0, length, n)
        for op_name in ("SUM", "MAX", "MIN", "PROD"):
            op = Operators.by_name(op_name)
            want = expected_reduce(alls, op_name)
            arr = alls[r].copy()
            comm.allreduce_array(arr, operand, op)
            ok = (np.array_equal(arr, want) if exact
                  else np.allclose(arr, want, rtol=1e-5, atol=1e-6))
            expect(f"allreduce/{operand.name}/{op_name}", ok)
        # rooted + segment family
        want = expected_reduce(alls, "SUM")
        arr = alls[r].copy()
        comm.reduce_array(arr, operand, Operators.SUM, root=0)
        if r == 0:
            expect(f"reduce/{operand.name}",
                   np.allclose(arr, want, rtol=1e-5))
        arr = alls[r].copy()
        comm.broadcast_array(arr, operand, root=n - 1)
        expect(f"broadcast/{operand.name}", np.array_equal(arr, alls[n - 1]))
        arr = alls[r].copy()
        comm.reduce_scatter_array(arr, operand, Operators.SUM)
        s, e = ranges[r]
        expect(f"reduce_scatter/{operand.name}",
               np.allclose(arr[s:e], want[s:e], rtol=1e-5))
        arr = alls[r].copy()
        comm.allgather_array(arr, operand)
        want_g = np.concatenate(
            [alls[q][s:e] for q, (s, e) in enumerate(ranges)])
        expect(f"allgather/{operand.name}", np.array_equal(arr, want_g))
        arr = alls[r].copy()
        comm.scatter_array(arr, operand, root=0)
        s, e = ranges[r]
        expect(f"scatter/{operand.name}",
               np.array_equal(arr[s:e], alls[0][s:e]))
        comm.barrier()

    # map collectives over the pickled-object path
    maps = [{f"k{(q + j) % (n + 1)}": float(q * 10 + j) for j in range(3)}
            for q in range(n)]
    want_merged: dict = {}
    for m in maps:
        for k, v in m.items():
            want_merged[k] = want_merged.get(k, 0.0) + v
    d = dict(maps[r])
    comm.allreduce_map(d, Operands.DOUBLE, Operators.SUM)
    expect("allreduce_map", d == want_merged)
    d = {f"r{r}": float(r)}
    comm.allgather_map(d, Operands.DOUBLE)
    expect("allgather_map", d == {f"r{q}": float(q) for q in range(n)})
    d = dict(maps[r])
    comm.reduce_scatter_map(d, Operands.DOUBLE, Operators.SUM)
    expect("reduce_scatter_map",
           d == {k: v for k, v in want_merged.items()
                 if meta.key_partition(k, n) == r})
    # int-keyed maps with a DRIFTING vocabulary: the device plane's
    # synchronized codecs must keep codes identical across processes
    # while only novel keys ride the pickled exchange
    for step in range(3):
        imaps = [{int(q * 5 + j + 3 * step): float(q * 10 + j)
                  for j in range(4)} for q in range(n)]
        want: dict = {}
        for m in imaps:
            for k, v in m.items():
                want[k] = want.get(k, 0.0) + v
        d = dict(imaps[r])
        comm.allreduce_map(d, Operands.DOUBLE, Operators.SUM)
        expect(f"allreduce_map_int/{step}", d == want)
        d = dict(imaps[r])
        comm.reduce_scatter_map(d, Operands.DOUBLE, Operators.SUM)
        expect(f"reduce_scatter_map_int/{step}",
               d == {k: v for k, v in want.items()
                     if meta.key_partition(k, n) == r})
    # rooted reduce on the map device plane: only root's dict merges
    d = dict(maps[r])
    comm.reduce_map(d, Operands.DOUBLE, Operators.SUM, root=n - 1)
    expect("reduce_map", d == (want_merged if r == n - 1 else maps[r]))
    # MAX on the map device plane (segment reducers, not all-reduce HLO)
    d = dict(maps[r])
    want_max: dict = {}
    for m in maps:
        for k, v in m.items():
            want_max[k] = max(want_max.get(k, -np.inf), v)
    comm.allreduce_map(d, Operands.DOUBLE, Operators.MAX)
    expect("allreduce_map_max", d == want_max)
    # vocabulary reset is collective: every rank resets at the same
    # point, then the next call resynchronizes from live keys
    comm.reset_map_vocabularies()
    d = dict(maps[r])
    comm.allreduce_map(d, Operands.DOUBLE, Operators.SUM)
    expect("allreduce_map_after_reset", d == want_merged)
    # a HOST-ONLY custom operator (python truthiness — untraceable)
    # must route numeric maps onto the pickled plane, not crash in jit
    from ytk_mp4j_tpu.operators import Operator
    absmax = Operator.custom(
        "ABSMAX_HOST", lambda a, b: a if abs(a) > abs(b) else b, 0.0)
    d = {k: (1.0 + v) * (-1.0 if r % 2 else 1.0)
         for k, v in maps[r].items()}
    plus = [{k: (1.0 + v) * (-1.0 if q % 2 else 1.0)
             for k, v in maps[q].items()} for q in range(n)]
    want_abs: dict = {}
    for m in plus:
        for k, v in m.items():
            want_abs[k] = (v if k not in want_abs
                           or abs(v) > abs(want_abs[k]) else want_abs[k])
    comm.allreduce_map(d, Operands.DOUBLE, absmax)
    expect("allreduce_map_custom_host", d == want_abs)
    return fails


def check_global_mesh(comm) -> int:
    """The perf path: jitted psum over a global (all-process) mesh."""
    import jax
    from functools import partial
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ytk_mp4j_tpu.comm.distributed import global_mesh, hier_global_mesh
    from ytk_mp4j_tpu.operators import Operators
    from ytk_mp4j_tpu.ops import collectives as coll

    fails = 0
    for mesh, axes in ((global_mesh(), "mp4j"),
                       (hier_global_mesh(), ("inter", "intra"))):
        D = mesh.size
        L = jax.local_device_count()
        spec = P(axes if isinstance(axes, str) else axes)
        # host-local rows -> one global [D, 8] array sharded over ranks
        local = np.stack([
            np.full(8, comm.rank * L + j, np.float32) for j in range(L)])
        garr = jax.make_array_from_process_local_data(
            NamedSharding(mesh, spec), local, (D, 8))

        @partial(jax.shard_map, mesh=mesh, in_specs=spec, out_specs=spec)
        def f(x):
            return coll.allreduce(x, Operators.SUM, axes)

        out = jax.jit(f)(garr)
        # row q is constant q; psum over ranks puts sum(range(D)) in
        # every slot
        want = float(sum(range(D)))
        got = np.asarray(
            [s.data for s in out.addressable_shards][0]).reshape(-1)[0]
        if not np.isclose(got, want):
            comm.error(f"global-mesh psum MISMATCH: {got} != {want}")
            fails += 1
    return fails


def check_gbdt_global_mesh(comm) -> int:
    """Consumer end-to-end at DCN scale: distributed GBDT training over
    the global (all-process) mesh must match a single-device reference
    computed locally on each process from the same seeded data."""
    import jax

    from ytk_mp4j_tpu.comm.distributed import global_mesh
    from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer
    from ytk_mp4j_tpu.parallel import make_mesh

    fails = 0
    rng = np.random.default_rng(1234)           # same data everywhere
    N, F, B = 512, 4, 16
    bins = rng.integers(0, B, (N, F)).astype(np.int32)
    y = (np.sin(bins[:, 1]) + 0.1 * rng.standard_normal(N)).astype(
        np.float32)
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=3, learning_rate=0.3,
                     n_trees=2)

    dist = GBDTTrainer(cfg, mesh=global_mesh())
    # eval_set exercises the multi-process per-round evaluation path
    # (trees from the global mesh consumed by a local jit)
    trees_d, preds_d = dist.train(bins, y, eval_set=(bins[:64], y[:64]))
    if len(dist.eval_history_) != cfg.n_trees or not all(
            np.isfinite(m) for m in dist.eval_history_):
        comm.error("gbdt eval history MISMATCH")
        fails += 1

    local = GBDTTrainer(
        cfg, mesh=make_mesh(1, devices=jax.local_devices()[:1]))
    trees_s, preds_s = local.train(bins, y)
    # order-insensitive comparison: the distributed psum and the
    # single-device scan reduce histograms in different float orders
    # (~5e-6 rel), so a near-tied split gain may legitimately flip
    # argmax and move individual predictions by whole leaf deltas; the
    # training MSE is robust to that (both trees are near-optimal)
    # while still catching real collective bugs (wrong sums -> wrong
    # splits everywhere -> MSE collapses toward var(y))
    mse_d = float(np.mean((preds_d[:N] - y) ** 2))
    mse_s = float(np.mean((preds_s[:N] - y) ** 2))
    var = float(np.var(y))
    if not (mse_d < 0.5 * var
            and abs(mse_d - mse_s) <= max(0.1 * mse_s, 1e-3)):
        comm.error(f"gbdt global-mesh MISMATCH: mse_d={mse_d:.5f} "
                   f"mse_s={mse_s:.5f} var={var:.5f}")
        fails += 1
    return fails


def check_ffm_global_mesh(comm) -> int:
    """The sparse-gradient consumer at DCN scale: FFM with the
    gathered-row sparse allreduce (check_vma=False collective over a
    multi-process mesh) must train to the same loss as a local dense
    run on identical seeded data."""
    import jax

    from ytk_mp4j_tpu.comm.distributed import global_mesh
    from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer
    from ytk_mp4j_tpu.parallel import make_mesh

    fails = 0
    rng = np.random.default_rng(77)             # same data everywhere
    N, K, nf, k, F = 256, 3, 3, 3, 500
    feats = rng.integers(0, F, (N, K)).astype(np.int32)
    fields = rng.integers(0, nf, (N, K)).astype(np.int32)
    vals = rng.random((N, K)).astype(np.float32)
    y = (rng.random(N) > 0.5).astype(np.float32)
    cfg = FMConfig(model="ffm", n_features=F, n_fields=nf, k=k,
                   max_nnz=K, learning_rate=0.2, l2=1e-4,
                   init_scale=0.1)

    sparse = FMTrainer(cfg, mesh=global_mesh(), sparse_grads=True)
    _, losses_d = sparse.fit(feats, fields, vals, y, n_steps=6, seed=5)
    dense = FMTrainer(
        cfg, mesh=make_mesh(1, devices=jax.local_devices()[:1]),
        sparse_grads=False)
    _, losses_s = dense.fit(feats, fields, vals, y, n_steps=6, seed=5)
    # NaN-proof form (like check_gbdt_global_mesh): any non-finite loss
    # on EITHER side, or a divergence, must count as failure —
    # `abs(x - nan) > tol` is False and would otherwise pass silently
    ok = (all(np.isfinite(m) for m in losses_d)
          and np.isfinite(losses_s[-1])
          and abs(losses_d[-1] - losses_s[-1]) <= 1e-3)
    if not ok:
        comm.error(f"ffm global-mesh MISMATCH: sparse {losses_d}"
                   f" vs dense-local {losses_s[-1]}")
        fails += 1
    return fails


def check_ffm_round4_global_mesh(comm) -> int:
    """Round-4 FFM surfaces at DCN scale: the mesh-SHARDED embedding
    table and the streaming fit must both train to the replicated
    full-batch losses over the global (all-process) mesh."""
    from ytk_mp4j_tpu.comm.distributed import global_mesh
    from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer

    fails = 0
    rng = np.random.default_rng(42)             # same data everywhere
    N, K, nf, k, F = 192, 3, 3, 3, 300
    feats = rng.integers(0, F, (N, K)).astype(np.int32)
    fields = rng.integers(0, nf, (N, K)).astype(np.int32)
    vals = rng.random((N, K)).astype(np.float32)
    y = (rng.random(N) > 0.5).astype(np.float32)
    cfg = FMConfig(model="ffm", n_features=F, n_fields=nf, k=k,
                   max_nnz=K, learning_rate=0.2, init_scale=0.1)

    rep = FMTrainer(cfg, mesh=global_mesh(), sparse_grads=True)
    _, l_rep = rep.fit(feats, fields, vals, y, n_steps=3, seed=11)
    sh = FMTrainer(cfg, mesh=global_mesh(), sparse_grads=True,
                   table_sharding="sharded")
    p_sh, l_sh = sh.fit(feats, fields, vals, y, n_steps=3, seed=11)
    if not (all(np.isfinite(m) for m in l_sh)
            and np.allclose(l_sh, l_rep, rtol=1e-4, atol=1e-6)):
        comm.error(f"sharded-table global-mesh MISMATCH: {l_sh} "
                   f"vs {l_rep}")
        fails += 1
    # sharded SERVE over the multi-process mesh (a collective: every
    # process calls predict together; the output fetch is a
    # process_allgather) vs a local dense scorer on the gathered table
    import jax

    from ytk_mp4j_tpu.parallel import make_mesh

    got = sh.predict(p_sh, feats, fields, vals)
    local = FMTrainer(cfg, mesh=make_mesh(
        1, devices=jax.local_devices()[:1]))
    want = local.predict(
        (sh._to_host(p_sh[0]), sh._to_host(p_sh[1]),
         sh.full_table(p_sh)), feats, fields, vals)
    if not np.allclose(got, want, rtol=1e-4, atol=1e-5):
        comm.error("sharded predict global-mesh MISMATCH")
        fails += 1

    # reuse rep: same cfg/mesh/slots -> same compiled step; fit_stream
    # with params=None re-inits from the seed, no state carryover
    _, l_stream = rep.fit_stream(
        ((feats, fields, vals, y) for _ in range(3)), seed=11)
    if not np.allclose(l_stream, l_rep, rtol=1e-5, atol=1e-7):
        comm.error(f"fit_stream global-mesh MISMATCH: {l_stream} "
                   f"vs {l_rep}")
        fails += 1
    # configs[4] COMPOSED at DCN scale: streamed chunks into the
    # mesh-SHARDED table (reuses sh's compiled step; double-buffered
    # dispatch path)
    _, l_shs = sh.fit_stream(
        ((feats, fields, vals, y) for _ in range(3)), seed=11)
    if not np.allclose(l_shs, l_rep, rtol=1e-5, atol=1e-7):
        comm.error(f"sharded fit_stream global-mesh MISMATCH: {l_shs} "
                   f"vs {l_rep}")
        fails += 1
    return fails


def check_binning_dist(comm) -> int:
    """Distributed quantile binning at DCN scale: each process sketches
    its own shard, ONE allgather merges the sketches, and every rank
    must end with (a) identical edges and (b) edges within 2/Q of the
    exact quantile positions of the pooled data (the merge's documented
    tolerance, tests/test_binning.py)."""
    from ytk_mp4j_tpu.models.binning import QuantileBinner
    from ytk_mp4j_tpu.operands import Operands

    fails = 0
    rng = np.random.default_rng(99)             # same data everywhere
    N, F, B = 6_000, 3, 16
    X = np.stack([rng.standard_normal(N),
                  rng.lognormal(0.0, 1.0, N),
                  rng.uniform(-2, 9, N)], axis=1).astype(np.float32)
    shards = np.array_split(X, comm.slave_num)
    binner = QuantileBinner(B).fit_distributed(
        shards[comm.rank], comm, sample=None)

    flat = binner.edges.ravel().astype(np.float32)
    buf = np.zeros(comm.slave_num * flat.size, np.float32)
    buf[comm.rank * flat.size: (comm.rank + 1) * flat.size] = flat
    comm.allgather_array(buf, Operands.FLOAT)
    rows = buf.reshape(comm.slave_num, flat.size)
    if not all(np.array_equal(rows[0], r) for r in rows[1:]):
        comm.error("binning edges DIFFER across ranks")
        fails += 1

    qs = np.arange(1, B) / B
    err = 0.0
    for f in range(F):
        col = np.sort(X[:, f])
        pos = np.searchsorted(col, binner.edges[f], side="right") / N
        err = max(err, float(np.abs(pos - qs).max()))
    if err > 2.0 / B:
        comm.error(f"binning quantile error {err:.4f} > {2.0 / B:.4f}")
        fails += 1

    # distributed binning FROM INSIDE the trainer (round-5 consumer
    # path): every rank calls train_raw(comm=...) together; the binner
    # fits via fit_distributed on each rank's own rows and the edges +
    # predictions must agree across ranks
    from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer
    from ytk_mp4j_tpu.parallel import make_mesh
    import jax

    Xr = shards[comm.rank]
    yr = (Xr[:, 0] > 0).astype(np.float32)
    # WEIGHTED rows: the weights flow into the distributed sketch
    # (weighted CDF mass over the allgather) AND the boosting
    # gradients; rank-dependent data with job-identical edges is the
    # invariant under test
    wr = 1.0 + (np.arange(Xr.shape[0]) % 3).astype(np.float64)
    cfg = GBDTConfig(n_features=F, n_bins=B, depth=2, n_trees=2,
                     learning_rate=0.5)
    tr = GBDTTrainer(cfg, mesh=make_mesh(
        1, devices=jax.local_devices()[:1]))
    trees, _ = tr.train_raw(Xr, yr, seed=4, comm=comm,
                            sample_weight=wr)
    # per-rank data -> per-rank trees; the BINNER must still be
    # job-identical (the distributed sketch merge) and must equal a
    # standalone WEIGHTED fit_distributed with the same inputs (below
    # — weighted edges differ from the unweighted binner at the top)
    seg = tr.binner_.edges.ravel().astype(np.float32)
    buf2 = np.zeros(comm.slave_num * seg.size, np.float32)
    buf2[comm.rank * seg.size:(comm.rank + 1) * seg.size] = seg
    comm.allgather_array(buf2, Operands.FLOAT)
    rows2 = buf2.reshape(comm.slave_num, seg.size)
    if not all(np.array_equal(rows2[0], r) for r in rows2[1:]):
        comm.error("train_raw distributed binning DIFFERS across ranks")
        fails += 1
    standalone = QuantileBinner(B).fit_distributed(
        Xr, comm, sample=1_000_000, seed=4, sample_weight=wr)
    if not np.array_equal(tr.binner_.edges, standalone.edges):
        comm.error("train_raw binner != standalone weighted "
                   "fit_distributed")
        fails += 1
    if not np.isfinite(tr.predict_raw(X[:64], trees)).all():
        comm.error("train_raw predict_raw produced non-finite values")
        fails += 1
    return fails


def check_dense_plane_timing(comm, elems: int = 1 << 20) -> int:
    """A/B the dense data plane: device psum vs the host
    allgather+loop formulation on the same buffer. Correctness is
    asserted; the timing is logged only (loopback CPU timings are
    noisy and say nothing about a device)."""
    import time

    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    rng = np.random.default_rng(7 + comm.rank)
    base = rng.standard_normal(elems).astype(np.float32)
    reps = 3

    # warm both paths first: the device path jit-compiles on first use
    comm.allreduce_array(base.copy(), Operands.FLOAT, Operators.SUM)
    comm._reduce_rows(comm._allgather_rows(base.copy()), Operators.SUM)

    dev = None
    comm.barrier()
    t0 = time.perf_counter()
    for _ in range(reps):
        dev = base.copy()
        comm.allreduce_array(dev, Operands.FLOAT, Operators.SUM)
    t_dev = (time.perf_counter() - t0) / reps

    host = None
    comm.barrier()
    t0 = time.perf_counter()
    for _ in range(reps):
        rows = comm._allgather_rows(base.copy())
        host = comm._reduce_rows(rows, Operators.SUM)
    t_host = (time.perf_counter() - t0) / reps

    fails = 0
    if not np.allclose(dev, host, rtol=1e-5, atol=1e-5):
        comm.error("dense-plane device vs host MISMATCH")
        fails += 1
    comm.info(f"dense plane {elems} f32 x {comm.slave_num} ranks: "
              f"device {t_dev * 1e3:.1f} ms, host-allgather "
              f"{t_host * 1e3:.1f} ms ({t_host / max(t_dev, 1e-9):.2f}x)")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", required=True, help="host:port")
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--local-devices", type=int, default=2)
    ap.add_argument("--length", type=int, default=97)
    args = ap.parse_args(argv)

    # CPU multi-process job: each process contributes --local-devices
    # virtual devices (the "multi-node without a cluster" pattern,
    # SURVEY.md section 4). Workers are pinned to the CPU on purpose:
    # a chip belongs to one process, and N workers that each tried to
    # take it would fail or hang.
    import jax

    from ytk_mp4j_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", args.local_devices)
    # DOUBLE/LONG operands round-trip through the devices; without x64
    # they would be silently downcast (the backend raises instead)
    jax.config.update("jax_enable_x64", True)

    from ytk_mp4j_tpu.comm.distributed import init_distributed

    comm = init_distributed(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id)
    try:
        fails = check(comm, args.length)
        fails += check_global_mesh(comm)
        fails += check_gbdt_global_mesh(comm)
        fails += check_ffm_global_mesh(comm)
        fails += check_ffm_round4_global_mesh(comm)
        fails += check_binning_dist(comm)
        fails += check_dense_plane_timing(comm)
        comm.info(f"checkdist done: {fails} failures")
        comm.close(0 if fails == 0 else 1)
        # job-wide verdict: root-only checks fail on rank 0 alone, so
        # every process must report the aggregate, not its local count
        return comm.final_code
    except Exception:
        traceback.print_exc()
        return 2


if __name__ == "__main__":
    sys.exit(main())
