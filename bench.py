#!/usr/bin/env python
"""North-star benchmark: GBDT histogram allreduce.

Measures the flagship workload — per-tree-level (node x feature x bin)
gradient/hessian histogram build + allreduce (ytk-learn GBDT shape:
F=28 features, 256 bins, depth-6 trees, Higgs-like synthetic data) — on:

1. the TPU path: one jitted shard_map step per tree over the available
   chip(s) (one-hot MXU matmul histograms + psum allreduce);
2. the CPU socket baseline: the same tree build with numpy histograms
   and the histogram allreduce over real loopback TCP via
   ProcessCommSlave ring collectives (the reference's architecture).

Timing: every timed device region is closed by ``np.asarray`` of a
device value, which waits for the device and copies the result to the
host. ``main()`` refuses to print device figures when the platform is
not a TPU.

Metric (GB/s/chip): bytes of training data scanned per histogram pass
(depth levels x N x (F bin-bytes + 8 grad/hess bytes)) per second per
chip — a rate, so the two paths may use different N. vs_baseline is the
TPU rate over the socket rate.

TPU context (see models/gbdt.py + ops/hist_kernel.py): scatter
histograms are bound by the chip's serial scatter unit; the "matmul"
strategy routes the build onto the MXU instead (tiled one-hot matmul,
hi/lo bf16 split); the default "pallas" strategy fuses the one-hot
generation and the matmul in VMEM — near the VPU floor of the one-hot
generation itself. The collective (psum over ICI vs Kryo-socket rounds,
socket allreduce GB/s in extras) additionally scales with chips while
the socket ring does not. The timed loop chains ``trees`` steps per
host sync; whether that still pays on the present machine (scalar round
trip under 1 ms) is not measured.

Prints exactly one JSON line.
"""

import collections
import json
import os
import queue as pyqueue
import sys
import threading
import time

import numpy as np


def make_data(n, f, b, seed=0):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, b, (n, f)).astype(np.int32)
    y = (bins[:, 0] / b + 0.1 * rng.standard_normal(n)).astype(np.float32)
    return bins, y


def scanned_bytes(n, f, depth):
    # per level the trainer scans every sample's F bin bytes + g/h floats
    return depth * n * (f + 8)


# ----------------------------------------------------------------------
def _aot_compile(jitted, *args):
    """Compile ``jitted`` for ``args`` ONCE (AOT), returning
    (callable, flops): the executable serves both the timed loop and
    the MFU numerator, instead of paying the jit compile AND a second
    lower().compile() just for cost analysis. A compile error
    propagates: a leg that cannot compile has no figure. (Scatter BYTE
    costs from this analysis are fantasy-magnitude, but the flop count
    is the standard MFU numerator.)"""
    compiled = jitted.lower(*args).compile()
    c = compiled.cost_analysis()
    if isinstance(c, (list, tuple)):
        c = c[0]
    fl = float((c or {}).get("flops", 0.0))
    return compiled, (fl if fl > 0 else None)


# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``
# (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16). A device
# that is not listed is an error, never a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_bf16_flops(device_kind: str) -> float:
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no published peak for device_kind {device_kind!r}; add it "
            f"to PEAK_BF16_FLOPS with its source") from None


def require_tpu():
    """The device a device figure is measured on, or an error: a
    measurement path that finds no chip fails, it does not fall back to
    the CPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py prints device figures only for a TPU; jax found "
            f"platform {dev.platform!r} ({dev.device_kind})")
    return dev


def gbdt_hist_mxu_flops(n, f, b, depth):
    """Analytic MXU flops of the fused Pallas histogram matmuls per
    tree. XLA's cost_analysis cannot see inside the Pallas custom call,
    so the cost-analysis MFU is only the XLA-visible remainder; this is
    the kernel's own arithmetic: level 0 histograms 1 node, levels
    d >= 1 histogram 2**(d-1) LEFT children (sibling subtraction,
    models/gbdt.py), and per level the kernel contracts the
    [tile, 4*n_nodes] hi/lo-split operand with the per-feature
    [tile, B] one-hot — 2 * N * 4*n_nodes * B * F flops."""
    nodes = 1 + sum(2 ** (d - 1) for d in range(1, depth))
    return 2.0 * n * 4 * nodes * b * f


def bench_tpu(n=1_000_000, f=28, b=256, depth=6, trees=10):
    import jax
    from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer

    cfg = GBDTConfig(n_features=f, n_bins=b, depth=depth,
                     learning_rate=0.1, n_trees=trees)
    tr = GBDTTrainer(cfg)  # all available real devices
    bins, y = make_data(n, f, b)
    dbins, dy, dpreds, dw = tr.shard_data(bins, y)
    kd = jax.random.key_data(jax.random.key(0))
    step, flops = _aot_compile(tr._build_step(), dbins, dy, dpreds, dw,
                               kd)
    # warmup; np.asarray forces a real host round-trip
    dpreds, tree = step(dbins, dy, dpreds, dw, kd)
    np.asarray(tree[0])
    t0 = time.perf_counter()
    for _ in range(trees):
        dpreds, tree = step(dbins, dy, dpreds, dw, kd)
    np.asarray(tree[0])  # sync: steps chain on device
    dt = (time.perf_counter() - t0) / trees
    n_chips = jax.device_count()
    gbs_per_chip = scanned_bytes(n, f, depth) / dt / 1e9 / n_chips
    flops_per_sec = None if flops is None else flops / dt / n_chips
    hist_fps = gbdt_hist_mxu_flops(n, f, b, depth) / dt / n_chips
    return gbs_per_chip, 1.0 / dt, n_chips, flops_per_sec, hist_fps


# ----------------------------------------------------------------------
def _numpy_histograms(bins, g, h, node_ids, n_nodes, f, b):
    hg = np.zeros((n_nodes, f, b), np.float32)
    hh = np.zeros((n_nodes, f, b), np.float32)
    base = node_ids.astype(np.int64) * (f * b)
    for j in range(f):
        ids = base + j * b + bins[:, j]
        hg.reshape(-1)[:] += np.bincount(ids, weights=g,
                                         minlength=n_nodes * f * b)
        hh.reshape(-1)[:] += np.bincount(ids, weights=h,
                                         minlength=n_nodes * f * b)
    return hg, hh


def _refuse_fork_with_live_accelerator():
    """One process for each chip: a parent whose jax backend holds an
    accelerator must not fork. The children inherit the device
    runtime's threads and fds, which is not fork-safe, and a child that
    needs the chip fails or hangs. A CPU-only backend holds no chip
    (tier-1 runs the socket legs after CPU device tests in one
    process), so it passes."""
    import jax
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"refusing to fork socket workers: this process already "
            f"holds the {backend} backend; run the socket legs before "
            f"any device leg (see main)")


def _run_socket_job(procs, body, native_transport, join_timeout=300.0,
                    master_kwargs=None, **slave_kwargs):
    """Master + ``procs`` slave worker PROCESSES; ``body(slave, rank)``
    returns a per-rank result. Returns ``(results, stats)`` where
    ``stats`` is the merged cross-rank ``comm.stats()`` snapshot of the
    whole job (emitted in the BENCH extra so every socket workload's
    wire/reduce/serialize budget is tracked across rounds). Raises the
    first worker error, or a RuntimeError naming the hung ranks if any
    worker missed the join deadline without raising. ``slave_kwargs``
    forward to every ProcessCommSlave (e.g. ``map_columnar=False`` for
    the pickled-plane A/B leg).

    Real OS processes (fork), matching the reference's unit of
    parallelism — N slave JVMs on one host (SURVEY.md section 4). A
    thread-based harness would share the GIL, understating the baseline
    (pickle framing holds the GIL); fork also lets ``body`` closures
    capture the benchmark data without pickling. Socket benches must
    run BEFORE any TPU client exists in this process (see main); the
    job refuses to fork otherwise."""
    import multiprocessing as mp

    from ytk_mp4j_tpu.comm.master import Master
    from ytk_mp4j_tpu.comm.process_comm import ProcessCommSlave

    _refuse_fork_with_live_accelerator()
    ctx = mp.get_context("fork")
    # frozen legs pin MP4J_ELASTIC=off, the nonblocking scheduler off
    # and the health plane off (the shm/audit/sink precedent):
    # historical figures stay comparable whatever the caller's env
    # says; the async/health legs opt back in explicitly. autoscale
    # joins the pin list (ISSUE 13): a frozen figure must not move
    # because an operator exported MP4J_AUTOSCALE=act
    mk = {"elastic": "off", "health": False, "autoscale": "off",
          "tuner": "off"}
    mk.update(master_kwargs or {})
    master = Master(procs, timeout=60.0, **mk).serve_in_thread()
    q = ctx.Queue()
    slave_kwargs.setdefault("elastic", "off")
    slave_kwargs.setdefault("async_collectives", False)
    slave_kwargs.setdefault("health", False)
    # frozen figures must not move because an operator exported
    # MP4J_TUNER=act (ISSUE 15): the tuner's own A/B leg opts back in
    slave_kwargs.setdefault("tuner", "off")

    def worker():
        try:
            # child-only pin (after fork, parent env untouched): frozen
            # figures must not move because an operator exported
            # MP4J_OVERLAP=1 (ISSUE 17) — the trainer-overlap leg opts
            # back in explicitly via StepStatsExchanger(overlap=True)
            os.environ["MP4J_OVERLAP"] = "0"
            slave = ProcessCommSlave("127.0.0.1", master.port, timeout=60.0,
                                     native_transport=native_transport,
                                     **slave_kwargs)
            res = body(slave, slave.rank)
            snap = slave.stats()
            slave.close(0)
            q.put(("ok", slave.rank, (res, snap)))
        except Exception as e:  # pragma: no cover
            q.put(("err", -1, repr(e)))

    ps = [ctx.Process(target=worker, daemon=True) for _ in range(procs)]
    for p in ps:
        p.start()
    results = [None] * procs
    deadline = time.monotonic() + join_timeout
    got = 0
    while got < procs:
        try:
            kind, rank, payload = q.get(timeout=1.0)
        except pyqueue.Empty:
            # fail fast on a child killed by a signal (segfault / OOM):
            # it can never report, so waiting out the deadline would
            # misdiagnose the crash as a hang
            dead = [p.exitcode for p in ps
                    if not p.is_alive() and p.exitcode not in (0, None)]
            if dead:
                for p in ps:
                    p.terminate()
                raise RuntimeError(
                    f"socket benchmark worker died without reporting "
                    f"(exit codes {dead})")
            if time.monotonic() > deadline:
                break
            continue
        if kind == "err":
            for p in ps:
                p.terminate()
            raise RuntimeError(f"socket benchmark worker failed: {payload}")
        results[rank] = payload
        got += 1
    for p in ps:
        p.join(max(0.1, deadline - time.monotonic()))
        if p.is_alive():
            p.terminate()
    if any(r is None for r in results):
        hung = [i for i, r in enumerate(results) if r is None]
        raise RuntimeError(
            f"socket benchmark workers hung past the join timeout: "
            f"ranks {hung}")
    from ytk_mp4j_tpu.utils.stats import merge_snapshots

    stats = merge_snapshots(*(snap for _, snap in results))
    return [res for res, _ in results], _round_stats(stats)


def _round_stats(stats):
    """Snapshot floats trimmed for the one-line BENCH JSON."""
    return {name: {k: (round(v, 6) if isinstance(v, float) else v)
                   for k, v in entry.items()}
            for name, entry in stats.items()}


def bench_socket(n=200_000, f=28, b=256, depth=6, procs=4,
                 native_transport=False):
    """The reference-architecture baseline: numpy histogram build + ring
    allreduce of the histogram buffers over loopback TCP. Also returns
    the pure collective rate (allreduce GB/s of the histogram buffers).

    ``native_transport=False`` is the FROZEN baseline: the fully framed
    per-message path mirroring the reference's Kryo-framed JVM sockets.
    True measures our native C++ raw data plane (reported in extras,
    not used as the comparison baseline)."""
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    bins, y = make_data(n, f, b, seed=1)
    per = n // procs

    def body(slave, r):
        lb = bins[r * per:(r + 1) * per]
        ly = y[r * per:(r + 1) * per]
        g = ly.copy()          # preds=0 -> g = -y up to sign; fine
        h = np.ones_like(g)
        node_ids = np.zeros(per, np.int32)
        slave.barrier()
        t0 = time.perf_counter()
        lam = 1.0
        cbytes = 0
        csecs = 0.0
        for d in range(depth):
            n_nodes = 2 ** d
            hg, hh = _numpy_histograms(lb, g, h, node_ids, n_nodes, f, b)
            flat = np.concatenate([hg.reshape(-1), hh.reshape(-1)])
            c0 = time.perf_counter()
            slave.allreduce_array(flat, Operands.FLOAT, Operators.SUM)
            csecs += time.perf_counter() - c0
            cbytes += flat.nbytes
            hg = flat[:hg.size].reshape(n_nodes, f, b)
            hh = flat[hg.size:].reshape(n_nodes, f, b)
            # split finding + routing (numpy mirror of the TPU path)
            cg, ch = np.cumsum(hg, -1), np.cumsum(hh, -1)
            Gt, Ht = cg[..., -1:], ch[..., -1:]
            gain = (cg ** 2 / (ch + lam)
                    + (Gt - cg) ** 2 / (Ht - ch + lam)
                    - Gt ** 2 / (Ht + lam))
            gain[..., -1] = -np.inf
            best = gain.reshape(n_nodes, -1).argmax(-1)
            feat, bin_ = best // b, best % b
            v = np.take_along_axis(lb, feat[node_ids][:, None],
                                   axis=1)[:, 0]
            node_ids = node_ids * 2 + (v > bin_[node_ids])
        return time.perf_counter() - t0, cbytes, csecs

    # frozen baseline legs stay all-TCP: MP4J_SHM now defaults on,
    # and the reference figures must keep measuring the socket wire
    # (audit="off" likewise pins the pre-ISSUE-8 wire figure — the
    # audit tax has its own A/B leg, see bench_audit_overhead)
    results, stats = _run_socket_job(procs, body, native_transport,
                                     shm=False, audit="off",
                                     sink_dir="")
    dt = max(res[0] for res in results)
    _, cbytes, csecs = results[0]
    # the socket job scanned n samples total across `procs` workers on
    # one host: rate per "chip" = whole-job rate (one machine)
    return (scanned_bytes(n, f, depth) / dt / 1e9, cbytes / csecs / 1e9,
            stats)


def bench_socket_collective(f=28, b=256, depth=6, procs=4, reps=3,
                            native_transport=True, shm=False,
                            algo="auto", audit="off", sink_dir="",
                            health=False):
    """Allreduce rate alone over the tree-level histogram buffer shapes
    (no numpy histogram/split work — used for the native-transport
    extras figure without re-running the whole socket workload).

    ``shm=False`` pins the all-TCP plane (the headline
    ``socket_collective_gbs`` figure bench-diff gates for continuity);
    ``audit="off"`` likewise pins the pre-ISSUE-8 figure — the audit
    plane's cost is measured by its own interleaved A/B
    (``bench_audit_overhead``), not smeared into every frozen leg;
    ``shm=True`` negotiates the intra-host shared-memory transport
    (ISSUE 7 — the 4 forked slaves share this host, so every pair
    rides it). ``algo`` forwards to every allreduce (``"twolevel"``
    forces the topology-aware schedule; on this single-host roster
    that is the binomial reduce+broadcast over shm with a no-op
    leader leg — the intra-host half of the two-level figure).

    Bench-host caveat (measured, ISSUE 7): this virtualized 1-core
    host's loopback TCP is itself a same-kernel memcpy with
    first-class scheduler wakeups, so the shm figure lands at TCP
    PARITY here rather than above it — the acceptance anchor is the
    r05 TCP figure (0.041 GB/s), which shm clears >=3x. The ring's
    syscall-free bulk path is the structural win on real multi-core
    hosts. Two environment findings are load-bearing for anyone
    re-tuning this: (a) mappings of files from the mounted /dev/shm
    tmpfs degraded ALL socket ops in the mapping process ~20x (hence
    the memfd segment backing); (b) every user-space wait discipline
    (spin, yield, select-parked doorbells) lost ms-scale scheduler
    tails to the kernel's recv wakeup on this oversubscribed host
    (hence the carrier sync-byte protocol)."""
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    sizes = [2 * (2 ** d) * f * b for d in range(depth)]

    def body(slave, r):
        bufs = [np.ones(s, np.float32) for s in sizes]
        slave.barrier()
        t0 = time.perf_counter()
        nbytes = 0
        for _ in range(reps):
            for buf in bufs:
                slave.allreduce_array(buf, Operands.FLOAT,
                                      Operators.SUM, algo=algo)
                nbytes += buf.nbytes
        return nbytes / (time.perf_counter() - t0)

    rates, stats = _run_socket_job(procs, body, native_transport,
                                   join_timeout=120.0, shm=shm,
                                   audit=audit, sink_dir=sink_dir,
                                   health=health,
                                   master_kwargs={"health": health})
    return min(rates) / 1e9, stats


def bench_socket_allreduce_sweep(procs=4, reps=8, native_transport=True):
    """Size sweep grounding the ``algo="auto"`` thresholds: per-size,
    per-algo allreduce GB/s over the default (native raw) data plane,
    emitted in the JSON ``extra`` so the thresholds stay data-grounded
    and tracked across rounds. Sizes bracket the latency-bound ->
    bandwidth-bound transition (4 KiB ... 8 MiB payloads)."""
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    sizes = [1024, 16_384, 65_536, 262_144, 1_048_576, 2_097_152]  # f32
    algos = ("tree", "rhd", "ring", "auto")

    def _reps(size):
        # latency-bound sizes are the noisiest on a shared host and the
        # cheapest to repeat: 4x reps below 256 KiB
        return reps * 4 if size * 4 < 262_144 else reps

    def body(slave, r):
        out = {(s, a): [] for s in sizes for a in algos}
        for size in sizes:
            buf = np.ones(size, np.float32)
            # interleave algos per rep so system-load drift spreads
            # evenly instead of biasing whole blocks
            for _ in range(_reps(size)):
                for algo in algos:
                    slave.barrier()
                    t0 = time.perf_counter()
                    slave.allreduce_array(buf, Operands.FLOAT,
                                          Operators.SUM, algo=algo)
                    out[(size, algo)].append(time.perf_counter() - t0)
        return out

    # all-TCP: this sweep grounds the MP4J_ALGO_* thresholds for
    # the inter-host (TCP) regime the auto rule serves
    rates, stats = _run_socket_job(procs, body, native_transport,
                                   join_timeout=600.0, shm=False,
                                   audit="off", sink_dir="")
    sweep = {}
    for size in sizes:
        row = {}
        for algo in algos:
            # per rep: the slowest rank defines the collective's time;
            # across reps: the best rep (min) is the standard
            # noise-robust microbenchmark statistic on a shared host
            dt = min(max(res[(size, algo)][k] for res in rates)
                     for k in range(_reps(size)))
            row[algo] = round(size * 4 / dt / 1e9, 4)
        sweep[f"{size * 4}B"] = row
    return sweep, stats


def bench_socket_async_overlap(procs=4, k=4, size=262_144, reps=8):
    """ISSUE 11 figures: ``socket_async_overlap_gbs`` — k outstanding
    1 MB ``iallreduce`` futures driven by the helper-thread scheduler
    (the native leg-graph driver: every leg of every outstanding
    collective in ONE C++ poll loop) — against
    ``socket_async_sequential_gbs``, the same k collectives as
    sequential blocking calls. Isolated leg, all-TCP, audit/sink off
    (the frozen-leg precedent); the k sequential leg runs with the
    scheduler pinned off (``async_collectives=False``) so it is the
    exact pre-ISSUE-11 path.

    MEASURED REALITY on this bench host (documented like PR 7's
    shm-parity caveat): this is a ONE-core Firecracker guest, and the
    sequential blocking path already saturates the core — its loopback
    wire runs at the kernel-TCP CPU ceiling (~1.4 GB/s aggregate
    duplex, measured) with 0% idle, so there is no latency to hide:
    overlap cannot create CPU cycles, and every scheduling layer adds
    some. The async figure lands BELOW sequential here (~0.6-0.7x;
    rusage shows the delta is scheduler CPU + extra context switches,
    the same class of 1-core scheduler-tail cost PR 7 measured for
    user-space shm waits). The structural win of k outstanding
    collectives — per-exchange wakeups and rounds amortized k-fold,
    wire idle time on real multi-core/NIC hosts filled with other
    collectives' work — needs a host where the wire is not the same
    CPU the ranks compute on. The figure the async plane DOES win on
    this host is ``socket_coalesce_keys_per_sec`` (fixed-cost
    amortization, ~2.5x — see bench_socket_coalesce); bench-diff gates
    both async figures so neither regresses further."""
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    def body_seq(slave, r):
        bufs = [np.ones(size, np.float32) for _ in range(k)]
        slave.barrier()
        t0 = time.perf_counter()
        n = 0
        for _ in range(reps):
            for b in bufs:
                slave.allreduce_array(b, Operands.FLOAT,
                                      Operators.SUM)
                n += b.nbytes
        return n / (time.perf_counter() - t0)

    def body_async(slave, r):
        bufs = [np.ones(size, np.float32) for _ in range(k)]
        slave.barrier()
        t0 = time.perf_counter()
        n = 0
        for _ in range(reps):
            futs = [slave.iallreduce(b, Operands.FLOAT,
                                     Operators.SUM) for b in bufs]
            slave.wait_all()
            n += sum(b.nbytes for b in bufs)
        return n / (time.perf_counter() - t0)

    seq, _ = _run_socket_job(procs, body_seq, True, shm=False,
                             audit="off", sink_dir="",
                             async_collectives=False)
    asy, stats = _run_socket_job(procs, body_async, True, shm=False,
                                 audit="off", sink_dir="",
                                 async_collectives=True)
    return {"async": min(asy) / 1e9, "sequential": min(seq) / 1e9,
            "stats": stats}


def bench_socket_coalesce(procs=4, maps=400, keys=16, window_us=500):
    """ISSUE 11 coalescing figure: ``maps`` tiny ``iallreduce_map``
    submissions (``keys`` int keys each) under the
    ``MP4J_COALESCE_USECS`` window vs the same stream with coalescing
    off (each map its own negotiation + tree walk). Fusion ships the
    whole backlog as ONE vocabulary sync + columnar frame train per
    negotiated batch, so the per-collective fixed cost (two tree walks
    of small pickled frames, their syscalls and scheduler wakeups)
    amortizes across the batch — measured ~2.5x keys/s at this config
    on the bench host. Frozen legs elsewhere pin async off per the
    shm/audit/sink precedent; this leg IS the async plane's figure."""
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    def body(slave, r):
        ds = [{key + 1000 * i: np.float64((r + 1) * (key + 1))
               for key in range(keys)} for i in range(maps)]
        slave.barrier()
        t0 = time.perf_counter()
        for d in ds:
            slave.iallreduce_map(d, Operands.DOUBLE, Operators.SUM)
        slave.wait_all()
        return maps * keys / (time.perf_counter() - t0)

    prior = os.environ.get("MP4J_COALESCE_USECS")
    try:
        os.environ["MP4J_COALESCE_USECS"] = str(window_us)
        on, stats = _run_socket_job(procs, body, True, shm=False,
                                    audit="off", sink_dir="",
                                    async_collectives=True)
        os.environ["MP4J_COALESCE_USECS"] = "0"
        off, _ = _run_socket_job(procs, body, True, shm=False,
                                 audit="off", sink_dir="",
                                 async_collectives=True)
    finally:
        if prior is None:
            os.environ.pop("MP4J_COALESCE_USECS", None)
        else:
            os.environ["MP4J_COALESCE_USECS"] = prior
    return {"on": min(on), "off": min(off), "stats": stats}


def bench_socket_coalesce_array(procs=4, arrays=400, size=256,
                                window_us=500):
    """ISSUE 17 dense-coalescing figure: ``arrays`` tiny ``iallreduce``
    submissions (``size`` float32 elems each, tree-schedule payloads)
    under the ``MP4J_COALESCE_USECS`` window vs the same stream with
    the window off (each array its own negotiation + tree walk). The
    array twin of ``bench_socket_coalesce``: consecutive same-signature
    submissions fuse into ONE count-negotiated multi-exchange
    (``allreduce_array_multi``), so the per-collective fixed cost
    amortizes across the backlog — acceptance is >= 2x elems/s over
    the sequential ``i*`` stream on this host. Needs procs >= 3: the
    fused walk is pinned to the tree schedule and ``algo="auto"`` only
    selects tree at n >= 3 (at n=2 RHD degenerates to the optimal
    pairwise exchange)."""
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    def body(slave, r):
        bufs = [np.full(size, float(r + 1) * (i + 1), np.float32)
                for i in range(arrays)]
        slave.barrier()
        t0 = time.perf_counter()
        for b in bufs:
            slave.iallreduce(b, Operands.FLOAT, Operators.SUM)
        slave.wait_all()
        return arrays * size / (time.perf_counter() - t0)

    prior = os.environ.get("MP4J_COALESCE_USECS")
    try:
        os.environ["MP4J_COALESCE_USECS"] = str(window_us)
        on, stats = _run_socket_job(procs, body, True, shm=False,
                                    audit="off", sink_dir="",
                                    async_collectives=True)
        os.environ["MP4J_COALESCE_USECS"] = "0"
        off, _ = _run_socket_job(procs, body, True, shm=False,
                                 audit="off", sink_dir="",
                                 async_collectives=True)
    finally:
        if prior is None:
            os.environ.pop("MP4J_COALESCE_USECS", None)
        else:
            os.environ["MP4J_COALESCE_USECS"] = prior
    return {"on": min(on), "off": min(off), "stats": stats}


def bench_trainer_overlap(procs=2, steps=30, grad_elems=65_536,
                          matmul_dim=192, matmul_reps=6):
    """ISSUE 17 trainer-overlap A/B: a trainer-shaped epoch loop —
    per step, a device-compute stand-in (BLAS matmuls, GIL released)
    plus a dense per-step gradient/statistics exchange through
    ``StepStatsExchanger`` — run with overlap ON (``iallreduce``
    posted, step k's wire rides the progression thread under step
    k+1's compute, ``drain()`` at the epoch boundary) vs OFF (today's
    blocking ``allreduce_array`` per step). Identical collectives in
    identical submit order; only the wait point moves.

    MULTI-CORE ONLY: ``len(os.sched_getaffinity(0))`` decides. On a
    1-core host (this bench rig) the wire and the compute time-share
    the same CPU, so overlap cannot create cycles — the leg records a
    ``skipped_1core`` marker INSTEAD of a bogus figure (the
    ``socket_async_overlap_gbs`` lesson, measured and documented in
    that leg's docstring: dense overlap lands BELOW sequential at 1
    core). When nproc > 1 the gate is >= 1.3x steps/s; a miss is
    reported in the ``gate`` field and the frozen ratio is bench-diff
    budgeted so it cannot silently regress between rounds."""
    nproc = len(os.sched_getaffinity(0))
    if nproc < 2:
        return {"skipped_1core": True, "nproc": nproc}

    from ytk_mp4j_tpu.models._base import StepStatsExchanger

    def make_body(overlap):
        def body(slave, r):
            rng = np.random.default_rng(r)
            a = rng.standard_normal((matmul_dim, matmul_dim),
                                    np.float32)
            grads = [np.full(grad_elems, float(r + 1) * (k + 1),
                             np.float64) for k in range(steps)]
            ex = StepStatsExchanger(slave, overlap=overlap)
            slave.barrier()
            t0 = time.perf_counter()
            for g in grads:
                ex.submit(g)
                # step k+1's independent compute: overlap mode drives
                # step k's wire under it, blocking mode already paid
                for _ in range(matmul_reps):
                    a = np.tanh(a @ a) + 0.1
            ex.drain()
            return steps / (time.perf_counter() - t0)
        return body

    blk, _ = _run_socket_job(procs, make_body(False), True, shm=False,
                             audit="off", sink_dir="",
                             async_collectives=True)
    ovl, stats = _run_socket_job(procs, make_body(True), True,
                                 shm=False, audit="off", sink_dir="",
                                 async_collectives=True)
    ratio = min(ovl) / min(blk)
    return {"overlap": min(ovl), "blocking": min(blk),
            "ratio": ratio, "nproc": nproc, "gate_min": 1.3,
            "gate": "ok" if ratio >= 1.3 else
                    f"MISS: {ratio:.2f}x < 1.3x dense-overlap gate",
            "stats": stats}


def bench_socket_tuner_act(procs=4, size=400_000, reps=6,
                           warmup_secs=3.0):
    """mp4j-tuner acceptance A/B (ISSUE 15): a compressed-operand
    allreduce stream, ``MP4J_TUNER=off`` vs ``act`` on the same host.

    The static policy zlib-compresses every frame (the operand says
    so); the tuner's probe/measure cycle observes that the link's
    plain payload rate beats the zlib-bound compressed rate by an
    order of magnitude on this loopback host and commits
    ``compress=False`` per link at a collective boundary. The act
    figure must be the measured net win bench-diff gates
    (``socket_tuner_act_gbs``); the ``tuner`` extra records the
    decisions the act leg actually converged to, so the win is
    attributable, not anecdotal. Both legs pay the same warmup wall
    time (the act leg needs ~SUSTAIN_WINDOWS decision windows to
    converge; the off leg idles the same period for thermal parity).
    All-TCP (``shm=False``): loopback TCP is this host's
    wire-vs-zlib contrast; the shm rings would only widen it."""
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    comp = Operands.compressed(Operands.DOUBLE)

    def body(slave, r):
        rng = np.random.default_rng(5)
        arr = rng.integers(0, 3, size).astype(np.float64)
        # convergence warmup: decision windows fold on the heartbeat
        # cadence, so the act leg needs WALL time and boundaries (the
        # off leg runs the same loop — parity, and a stronger static
        # baseline via warm channels). The exit is AGREED through a
        # MIN allreduce: a wall-clock-local break would leave ranks a
        # collective apart and deadlock the schedule (R1's lesson)
        deadline = time.monotonic() + warmup_secs
        flag = np.zeros(1)
        while True:
            a = arr.copy()
            slave.allreduce_array(a, comp, Operators.SUM)
            flag[0] = 1.0 if time.monotonic() >= deadline else 0.0
            slave.allreduce_array(flag, Operands.DOUBLE,
                                  Operators.MIN)
            if flag[0] == 1.0:
                break
        slave.barrier()
        t0 = time.perf_counter()
        nbytes = 0
        for _ in range(reps):
            a = arr.copy()
            slave.allreduce_array(a, comp, Operators.SUM)
            nbytes += arr.nbytes
        rate = nbytes / (time.perf_counter() - t0)
        st = slave.tuner_status()
        return rate, st

    out = {}
    decisions = None
    prior = {k: os.environ.get(k)
             for k in ("MP4J_TUNER_WINDOW_SECS", "MP4J_HEARTBEAT_SECS")}
    os.environ["MP4J_TUNER_WINDOW_SECS"] = "0.3"
    os.environ["MP4J_HEARTBEAT_SECS"] = "0.1"
    try:
        for mode in ("off", "act"):
            rates_status, _ = _run_socket_job(
                procs, body, True, join_timeout=180.0, shm=False,
                audit="off", sink_dir="", tuner=mode,
                master_kwargs={"tuner": mode})
            out[mode] = min(rate for rate, _ in rates_status) / 1e9
            if mode == "act":
                decisions = {i: st for i, (_, st)
                             in enumerate(rates_status)}
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    out["decisions"] = decisions
    return out


def bench_socket_recovery_latency(procs=4, reps=9, size=262_144):
    """ISSUE 5 acceptance workload: inject ONE connection reset into a
    ``reps``-iteration allreduce loop and report (a) the recovery
    latency — the faulted iteration's wall time over the healthy
    median, i.e. what one epoch-fenced abort/retry round costs end to
    end — and (b) the steady-state decomposition of the resilience
    layer, same loop, no faults:

    - ``failstop_gbs`` (``max_retries=0``): the EPOCH FENCE ALONE —
      fence polls, control thread, recovery wrapper and the
      (rank, epoch) peer handshake all stay active; only retry and
      its input-preservation snapshot are off. Measured
      indistinguishable from a snapshot-suppressed default run, i.e.
      the fence's steady-state cost is ~0 (it is a flag check).
    - ``default_gbs`` (``MP4J_MAX_RETRIES`` default): adds the
      input-preservation snapshot — ONE pooled memcpy pass of the
      payload per mutating collective, the irreducible price of
      re-runnable in-place merges (a retry needs the original bytes;
      staging the result instead costs the same pass at commit time,
      so the pass is conserved, not an implementation accident). On a
      real NIC that pass vanishes next to wire time; on THIS bench
      host the "wire" is loopback — itself memcpy through the kernel
      on one shared core — so the snapshot shows as a visible slice
      and ``failstop_gbs`` is the fence-only figure comparable with
      BENCH history.

    Returns ``(summary, stats)`` where stats is the FAULTED leg's
    merged snapshot — its nonzero ``retries``/``aborts_seen`` prove
    the fault actually fired (a silent no-op fault would report a
    flattering zero latency)."""
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    fault_at = reps // 2 + 1    # collective ordinal of the faulted rep

    def body(slave, r):
        buf = np.ones(size, np.float32)
        times = []
        for _ in range(reps):
            # lockstep per iteration (outside the timed window):
            # recovery is per-collective, so the faulted call must not
            # find ranks a whole collective apart on a loaded host
            slave.barrier()
            t0 = time.perf_counter()
            slave.allreduce_array(buf, Operands.FLOAT, Operators.SUM)
            times.append(time.perf_counter() - t0)
        return times

    res, stats = _run_socket_job(
        procs, body, True, fault_plan=f"reset:rank=1:nth={fault_at}",
        dead_rank_secs=30.0, shm=False, audit="off", sink_dir="")
    # per iteration the slowest rank defines the collective's time
    per_iter = [max(res[r][k] for r in range(procs))
                for k in range(reps)]
    healthy = sorted(per_iter[:fault_at - 1] + per_iter[fault_at:])
    median = healthy[len(healthy) // 2]
    recovery_latency = per_iter[fault_at - 1] - median
    retries = sum(e.get("retries", 0) for e in stats.values())
    if retries < 1:
        raise RuntimeError(
            "recovery bench: the injected reset never fired "
            "(0 retries recorded) — latency figure would be bogus")

    def steady_gbs(**kw):
        r2, _ = _run_socket_job(procs, body, True, shm=False,
                                audit="off", sink_dir="", **kw)
        dt = max(sum(ts) for ts in r2)
        return size * 4 * reps / dt / 1e9

    summary = {
        "recovery_latency_ms": round(recovery_latency * 1e3, 3),
        "healthy_iter_ms": round(median * 1e3, 3),
        "retries": int(retries),
        "steady_state": {
            "default_gbs": round(steady_gbs(), 4),
            "failstop_gbs": round(steady_gbs(max_retries=0), 4),
        },
    }
    return summary, stats


def _run_elastic_job(procs, body, fault_plan, elastic, spare_body=None,
                     join_timeout=120.0, master_kwargs=None,
                     trigger=None, **slave_kwargs):
    """Master + ``procs`` worker PROCESSES under an elastic mode, plus
    one warm-spare process when ``spare_body`` is given (ISSUE 10).
    Workers that die to an injected kill report ``("killed", rank)``;
    workers released by a planned eviction (ISSUE 13) report
    ``("evicted", rank)``; the spare reports under its adopted rank.
    ``trigger(master)`` (ISSUE 13) runs on a daemon thread after the
    master starts — the planned-evict leg drives the actuation from
    it. Returns ``(results, killed_ranks)`` with results keyed by
    FINAL rank (evicted ranks count in ``killed`` — they left the
    roster either way)."""
    import multiprocessing as mp

    from ytk_mp4j_tpu.comm.master import Master
    from ytk_mp4j_tpu.comm.process_comm import ProcessCommSlave
    from ytk_mp4j_tpu.exceptions import Mp4jEvicted
    from ytk_mp4j_tpu.resilience.faults import FaultKill

    _refuse_fork_with_live_accelerator()
    ctx = mp.get_context("fork")
    # frozen-leg pin (the shm/audit/sink/async precedent): the
    # replacement/shrink latency figures predate the health plane and
    # the autoscaler, and must not drift with MP4J_HEALTH or
    # MP4J_AUTOSCALE; the evict/grow legs opt back in via
    # master_kwargs
    mk = {"health": False, "autoscale": "off"}
    mk.update(master_kwargs or {})
    master = Master(procs, timeout=60.0, elastic=elastic,
                    spares=1 if spare_body is not None else 0,
                    adopt_secs=15.0, **mk).serve_in_thread()
    q = ctx.Queue()
    slave_kwargs.setdefault("health", False)

    def worker():
        try:
            slave = ProcessCommSlave(
                "127.0.0.1", master.port, timeout=60.0,
                fault_plan=fault_plan, elastic=elastic,
                dead_rank_secs=60.0, **slave_kwargs)
            start_rank = slave.rank
            try:
                res = body(slave, slave.rank)
            except FaultKill:
                q.put(("killed", start_rank, None))
                return
            except Mp4jEvicted:
                slave.close(0)
                q.put(("evicted", start_rank, None))
                return
            q.put(("ok", slave.rank, res))
            slave.close(0)
        except Exception as e:  # pragma: no cover
            q.put(("err", -1, repr(e)))

    def spare_worker():
        try:
            sp = ProcessCommSlave(
                "127.0.0.1", master.port, timeout=60.0, spare=True,
                elastic=elastic, dead_rank_secs=60.0, **slave_kwargs)
            res = spare_body(sp)
            q.put(("ok", sp.rank, res))
            sp.close(0)
        except Exception as e:  # pragma: no cover
            q.put(("err", -1, repr(e)))

    ps = [ctx.Process(target=worker, daemon=True)
          for _ in range(procs)]
    if spare_body is not None:
        ps.append(ctx.Process(target=spare_worker, daemon=True))
    for p in ps:
        p.start()
    if trigger is not None:
        threading.Thread(target=trigger, args=(master,),
                         daemon=True).start()
    expected = len(ps)
    results: dict[int, object] = {}
    killed: list[int] = []
    deadline = time.monotonic() + join_timeout
    got = 0
    while got < expected:
        try:
            kind, rank, payload = q.get(timeout=1.0)
        except pyqueue.Empty:
            dead = [p.exitcode for p in ps
                    if not p.is_alive() and p.exitcode not in (0, None)]
            if dead or time.monotonic() > deadline:
                for p in ps:
                    p.terminate()
                raise RuntimeError(
                    f"elastic benchmark stalled (exit codes {dead}, "
                    f"{got}/{expected} reported)")
            continue
        if kind == "err":
            for p in ps:
                p.terminate()
            raise RuntimeError(f"elastic benchmark worker: {payload}")
        if kind in ("killed", "evicted"):
            killed.append(rank)
        else:
            results[rank] = payload
        got += 1
    for p in ps:
        p.join(10.0)
    master.join(10.0)
    return results, killed


def _timed_elastic_loop(reps):
    """The shared per-iteration-timed allreduce loop of both elastic
    latency legs, plus the spare's resume half (skips the barrier of
    the iteration it resumes INTO — that generation completed before
    the kill could fire, see README 'Elastic membership'). The kill
    point lives ONLY in the caller's fault-plan string."""
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    size = 262_144

    def body(slave, r):
        buf = np.ones(size, np.float32)
        times = []
        for _ in range(reps):
            slave.barrier()
            t0 = time.perf_counter()
            slave.allreduce_array(buf, Operands.FLOAT, Operators.SUM)
            times.append(time.perf_counter() - t0)
        return times

    def spare_body(sp):
        buf = np.ones(size, np.float32)
        times = []
        for k in range(sp.resume_seq + 1, reps + 1):
            if not (k == sp.resume_seq + 1
                    and sp.resume_barrier_gen > sp.resume_seq):
                sp.barrier()
            t0 = time.perf_counter()
            sp.allreduce_array(buf, Operands.FLOAT, Operators.SUM)
            times.append(time.perf_counter() - t0)
        return times

    return body, spare_body


def bench_socket_replacement_latency(procs=4, reps=9):
    """ISSUE 10 acceptance workload (replace): ``kill -9`` one rank
    mid-loop with a warm spare registered and measure kill -> adopted
    spare -> first completed collective, as the faulted iteration's
    wall time over the healthy median on the SURVIVORS (the spare's
    first collective completes inside that same window — survivors
    cannot finish the retry without its contribution). Asserts the
    replacement actually happened (a silently-fatal run would report
    garbage)."""
    fault_at = reps // 2 + 1
    body, spare_body = _timed_elastic_loop(reps)
    results, killed = _run_elastic_job(
        procs, body, f"kill:rank=1:nth={fault_at}", "replace",
        spare_body=spare_body, shm=False, audit="off", sink_dir="")
    if killed != [1] or len(results) != procs:
        raise RuntimeError(
            f"replacement bench: expected rank 1 killed + {procs} "
            f"finishers, got killed={killed} results={sorted(results)}")
    survivors = [r for r in range(procs) if r != 1]
    per_iter = [max(results[r][k] for r in survivors)
                for k in range(reps)]
    healthy = sorted(per_iter[:fault_at - 1] + per_iter[fault_at:])
    median = healthy[len(healthy) // 2]
    return {
        "replacement_latency_ms": round(
            (per_iter[fault_at - 1] - median) * 1e3, 3),
        "healthy_iter_ms": round(median * 1e3, 3),
        "spare_iters": len(results[1]),
    }


def bench_socket_planned_evict_ms(procs=4, reps=11):
    """ISSUE 13 actuation workload: mid-loop, the master is asked to
    PLANNED-EVICT live rank 1 (the autoscaler's actuation API,
    detection excluded — detection latency is a pure function of
    MP4J_HEALTH_DOMINATOR_ORDINALS x iteration time, a config choice,
    not a protocol cost). Measured: the boundary fence + abort round
    + manifest + spare adoption + first post-adoption collective, as
    the worst faulted iteration's wall time over the healthy median
    on the survivors. Asserts the eviction actually landed."""
    body, spare_body = _timed_elastic_loop(reps)

    def trigger(master):
        # fire as soon as the request is accepted (rendezvous seated,
        # spare pooled): the boundary fence quiesces at whichever
        # iteration comes next — the figure is the actuation cost,
        # independent of WHICH iteration pays it (argmax below)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if master.request_planned_evict(1, "bench actuation"):
                return
            time.sleep(0.002)

    results, killed = _run_elastic_job(
        procs, body, None, "replace", spare_body=spare_body,
        trigger=trigger, shm=False, audit="off", sink_dir="")
    if killed != [1] or len(results) != procs:
        raise RuntimeError(
            f"planned-evict bench: expected rank 1 evicted + {procs} "
            f"finishers, got evicted={killed} results={sorted(results)}")
    survivors = [r for r in range(procs) if r != 1]
    per_iter = [max(results[r][k] for r in survivors)
                for k in range(reps)]
    ordered = sorted(per_iter)
    median = ordered[len(ordered) // 2]
    worst = max(per_iter)
    return {
        "planned_evict_ms": round((worst - median) * 1e3, 3),
        "healthy_iter_ms": round(median * 1e3, 3),
        "spare_iters": len(results[1]),
    }


def bench_socket_grow_latency_ms(procs=2, reps=9):
    """ISSUE 13 grow workload: mid-loop every rank hits
    ``resize_point()`` with one registered spare and
    MP4J_ELASTIC=grow + MP4J_AUTOSCALE=act — the roster EXPANDS to
    procs+1 and the loop continues at the new n. Measured: the
    resize_point wall time itself (fence-free quiesce + adoption +
    roster release), max over the pre-existing ranks."""
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    size = 262_144
    grow_at = reps // 2 + 1

    def body(slave, r):
        buf = np.ones(size, np.float32)
        out = {"iters": [], "resize_ms": None, "grown_n": None}
        for k in range(reps):
            if k == grow_at:
                t0 = time.perf_counter()
                roster = slave.resize_point()
                out["resize_ms"] = (time.perf_counter() - t0) * 1e3
                out["grown_n"] = len(roster)
            slave.barrier()
            t0 = time.perf_counter()
            slave.allreduce_array(buf, Operands.FLOAT, Operators.SUM)
            out["iters"].append(time.perf_counter() - t0)
        return out

    def spare_body(sp):
        buf = np.ones(size, np.float32)
        for k in range(sp.resume_seq, reps):
            sp.barrier()
            sp.allreduce_array(buf, Operands.FLOAT, Operators.SUM)
        return {"iters": [], "resize_ms": None,
                "grown_n": sp.slave_num}

    results, killed = _run_elastic_job(
        procs, body, None, "grow", spare_body=spare_body,
        master_kwargs={"autoscale": "act", "autoscale_cooldown": 0.0},
        shm=False, audit="off", sink_dir="")
    if killed or len(results) != procs + 1:
        raise RuntimeError(
            f"grow bench: expected {procs + 1} finishers, got "
            f"killed={killed} results={sorted(results)}")
    grown = [results[r]["grown_n"] for r in range(procs)]
    if any(g != procs + 1 for g in grown):
        raise RuntimeError(f"grow bench: roster did not grow: {grown}")
    return {
        "grow_latency_ms": round(
            max(results[r]["resize_ms"] for r in range(procs)), 3),
        "grown_n": procs + 1,
    }


def bench_socket_shrink_latency(procs=4, reps=9):
    """ISSUE 10 acceptance workload (shrink): same kill, no spare —
    survivors renumber to n-1 and continue; the figure is the faulted
    iteration's wall time over the healthy median."""
    fault_at = reps // 2 + 1
    body, _ = _timed_elastic_loop(reps)
    results, killed = _run_elastic_job(
        procs, body, f"kill:rank=1:nth={fault_at}", "shrink",
        shm=False, audit="off", sink_dir="")
    if killed != [1] or len(results) != procs - 1:
        raise RuntimeError(
            f"shrink bench: expected rank 1 killed + {procs - 1} "
            f"finishers, got killed={killed} results={sorted(results)}")
    per_iter = [max(results[r][k] for r in results)
                for k in range(reps)]
    healthy = sorted(per_iter[:fault_at - 1] + per_iter[fault_at:])
    median = healthy[len(healthy) // 2]
    return {
        "shrink_latency_ms": round(
            (per_iter[fault_at - 1] - median) * 1e3, 3),
        "healthy_iter_ms": round(median * 1e3, 3),
        "final_ranks": len(results),
    }


def bench_audit_overhead(rounds=2):
    """ISSUE 8 acceptance workload: interleaved A/B of the audit plane
    on the isolated headline collective leg — ``off`` vs ``digest``
    (the production default) vs ``verify`` (the diagnostic mode),
    best-of-``rounds`` per mode with modes interleaved per round so
    system-load drift spreads evenly (the ``metrics_overhead``
    precedent).

    Cost anatomy, measured on the bench host: ``digest`` adds 2
    payload-hash passes per rank per collective (block-xor at 21-35
    GB/s, obs/audit.py); ``verify`` adds zlib.crc32 folds over every
    wire byte (~1 GB/s — the diagnostic mode you arm when you need
    cross-rank proof, not a default). 1-CORE CAVEAT (the PR 5/7
    pattern): this host serializes all 4 ranks' digest passes onto the
    one core the collective also runs on, so the printed overhead is
    ~4x what a host with a core per rank pays — the per-rank digest
    cost on this leg is 2 passes x payload/24GB/s ~= 2% of the wire
    time, within the <=3% budget; the printed figure is that times the
    rank count sharing the core."""
    rates = {m: 0.0 for m in ("off", "digest", "verify")}
    for _ in range(rounds):
        for mode in rates:
            gbs, _ = bench_socket_collective(native_transport=True,
                                             audit=mode)
            rates[mode] = max(rates[mode], gbs)
    off = rates["off"]
    return {
        "socket_collective_gbs_audit_off": round(off, 4),
        "socket_collective_gbs_audit_digest": round(rates["digest"], 4),
        "socket_collective_gbs_audit_verify": round(rates["verify"], 4),
        "digest_overhead_pct": round((off - rates["digest"]) / off * 100,
                                     2) if off else None,
        "verify_overhead_pct": round((off - rates["verify"]) / off * 100,
                                     2) if off else None,
        "core_sharing_note": (
            "1-core host: 4 ranks' digest passes serialize onto the "
            "collective's core, overstating the per-rank tax ~4x "
            "(see bench_audit_overhead docstring)"),
    }


def bench_lint_runtime(reps=3):
    """ISSUE 14 + 16: mp4j-lint's own runtime over this repo — the
    per-file pass, the v2 two-pass run (per-file rules + the R19-R21
    lock-model pass) and the v3 run (adds the R23 lockset / R24-R25
    resource whole-program passes). The full mode rides the tier-1
    gate on every CI run, so its cost is tracked like any other
    figure; budgets: the full run stays <= 2x the per-file pass, and
    v3 stays <= 1.5x v2 (the race/resource models reuse v2's parsed
    index, call graph and lock summaries — their marginal cost is the
    fixpoint over already-built structures, not a re-parse). Engine
    caches are cleared between timed legs so every leg pays the full
    parse it would pay on a cold CI run."""
    import time as _time

    from ytk_mp4j_tpu.analysis.engine import Engine, ProgramRule
    from ytk_mp4j_tpu.analysis.rules import get_rules

    pkg = os.path.dirname(os.path.abspath(
        __import__("ytk_mp4j_tpu").__file__))
    v2_ids = ("R19", "R20", "R21")
    per_file = inf = float("inf")
    full = v2 = inf
    for _ in range(reps):
        rules = [r for r in get_rules()
                 if not isinstance(r, ProgramRule)]
        eng = Engine(rules=rules)
        eng.clear_caches()
        t0 = _time.perf_counter()
        eng.lint_paths([pkg])
        per_file = min(per_file, _time.perf_counter() - t0)
        rules = [r for r in get_rules()
                 if not isinstance(r, ProgramRule)
                 or r.rule_id in v2_ids]
        eng = Engine(rules=rules)
        eng.clear_caches()
        t0 = _time.perf_counter()
        eng.lint_paths([pkg])
        v2 = min(v2, _time.perf_counter() - t0)
        eng = Engine()
        eng.clear_caches()
        t0 = _time.perf_counter()
        eng.lint_paths([pkg])
        full = min(full, _time.perf_counter() - t0)
    return {
        "lint_runtime_secs": round(full, 3),
        "lint_perfile_secs": round(per_file, 3),
        "lint_wholeprogram_ratio": round(full / per_file, 3),
        "lint_v2_secs": round(v2, 3),
        "lint_v3_over_v2_ratio": round(full / v2, 3),
    }


def bench_sink_overhead(rounds=2):
    """ISSUE 9 acceptance workload: interleaved A/B of the durable
    telemetry sink on the isolated headline collective leg — sink off
    vs armed (segments under a throwaway dir, default flush cadence),
    best-of-``rounds`` per mode with modes interleaved per round so
    system-load drift spreads evenly (the ``metrics_overhead`` /
    ``bench_audit_overhead`` precedent). Budget: <= 3%.

    Cost anatomy: the collective hot path pays NOTHING new (the ring
    appends it drains were already booked by ISSUES 3/6/8); the sink
    adds one background thread per rank that wakes each flush
    interval, diffs snapshots and issues one unbuffered write —
    amortized over every collective in the interval. On this shared
    1-core host the drain thread time-shares the collective's core,
    so the printed delta carries the usual ~10% run-to-run noise
    floor; the per-rank steady-state cost is the snapshot diff
    (~100 us) once per second."""
    import shutil
    import tempfile

    rates = {m: 0.0 for m in ("off", "on")}
    for _ in range(rounds):
        for mode in rates:
            d = tempfile.mkdtemp(prefix="mp4j_sink_bench_") \
                if mode == "on" else ""
            try:
                gbs, _ = bench_socket_collective(native_transport=True,
                                                 sink_dir=d)
                rates[mode] = max(rates[mode], gbs)
            finally:
                if d:
                    shutil.rmtree(d, ignore_errors=True)
    off = rates["off"]
    return {
        "socket_collective_gbs_sink_off": round(off, 4),
        "socket_collective_gbs_sink_on": round(rates["on"], 4),
        "sink_overhead_pct": round((off - rates["on"]) / off * 100, 2)
        if off else None,
    }


def bench_health_overhead(rounds=2):
    """ISSUE 12 acceptance workload: interleaved A/B of the streaming
    health plane on the isolated headline collective leg — health off
    (the frozen-leg pin) vs armed on BOTH sides (slaves fold + ship
    per-ordinal span cells on each heartbeat; the master runs the
    detector set and online dominator attribution per fold),
    best-of-``rounds`` per mode with modes interleaved per round so
    system-load drift spreads evenly (the ``metrics_overhead`` /
    ``bench_audit_overhead`` / ``bench_sink_overhead`` precedent).
    Budget: <= 3%.

    Cost anatomy: the collective hot path pays NOTHING new (the span
    appends the folder reads were already booked by ISSUE 3); the
    slave side adds one O(delta) span-ring fold per heartbeat
    (~0.5 s), the master side a handful of dict updates plus one
    ``critpath.attribute`` call per completed ordinal — all on
    control-plane threads. On this shared 1-core host those threads
    time-share the collective's core, so the printed delta carries
    the usual ~10% run-to-run noise floor."""
    rates = {m: 0.0 for m in ("off", "on")}
    for _ in range(rounds):
        for mode in rates:
            gbs, _ = bench_socket_collective(native_transport=True,
                                             health=(mode == "on"))
            rates[mode] = max(rates[mode], gbs)
    off = rates["off"]
    return {
        "socket_collective_gbs_health_off": round(off, 4),
        "socket_collective_gbs_health_on": round(rates["on"], 4),
        "health_overhead_pct": round((off - rates["on"]) / off * 100, 2)
        if off else None,
    }


def bench_fleet_scrape(procs=4, sweeps=60, size=65_536):
    """ISSUE 18 observability figure: one full ``FleetPoller`` sweep
    (fetch ``/metrics.json`` + ``/health.json``, fold the job summary,
    rebuild the fleet model, run contention detection) against a LIVE
    ``procs``-rank job running an allreduce loop in this process —
    p50/p99 sweep latency plus the scrape loop's CPU share at the
    default poll cadence. The poller rides HTTP out of band, so no
    frozen socket leg arms it; this leg is the fleet plane's own
    figure, gated via ``fleet_scrape_p99_ms`` so a fold/detector
    regression (an accidental O(n^2) pass, an unbounded fetch) cannot
    creep in silently.

    CPU share is ``time.thread_time`` over the sweep loop (the fetches
    block off-GIL, so the thread clock charges only the poller's own
    fold work) divided by the default poll period — what one idle-free
    sweep costs per cadence slot. The p99 on this shared 1-core host
    carries the worker ranks' GIL interference; that contention IS the
    deployment reality for an in-host scraper, so it stays in the
    figure. Worker exit is agreed through a MIN allreduce (the R1
    lesson: a rank-local break leaves ranks a collective apart)."""
    from ytk_mp4j_tpu.comm.master import Master
    from ytk_mp4j_tpu.comm.process_comm import ProcessCommSlave
    from ytk_mp4j_tpu.obs.fleet import FleetPoller
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators
    from ytk_mp4j_tpu.utils import tuning

    master = Master(procs, timeout=60.0, metrics_port=0, elastic="off",
                    health=False, autoscale="off",
                    tuner="off").serve_in_thread()
    stop = threading.Event()
    errs = []

    def worker():
        try:
            slave = ProcessCommSlave(
                "127.0.0.1", master.port, timeout=60.0, elastic="off",
                async_collectives=False, health=False, tuner="off",
                shm=False, audit="off", sink_dir="")
            buf = np.ones(size, np.float32)
            flag = np.zeros(1)
            while True:
                slave.allreduce_array(buf, Operands.FLOAT,
                                      Operators.SUM)
                flag[0] = 1.0 if stop.is_set() else 0.0
                slave.allreduce_array(flag, Operands.DOUBLE,
                                      Operators.MIN)
                if flag[0] == 1.0:
                    break
            slave.close(0)
        except Exception as e:  # pragma: no cover
            errs.append(repr(e))

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(procs)]
    for t in threads:
        t.start()
    url = f"http://127.0.0.1:{master.metrics_port}"
    poller = FleetPoller([url], poll_secs=0.05, stale_secs=30.0)
    try:
        poller.poll_once()      # warmup: connection + lazy-path setup
        lat = []
        w0 = time.perf_counter()
        c0 = time.thread_time()
        for _ in range(sweeps):
            t0 = time.perf_counter()
            poller.poll_once()
            lat.append(time.perf_counter() - t0)
        cpu = time.thread_time() - c0
        wall = time.perf_counter() - w0
        st = poller.model()["jobs"][url]
    finally:
        stop.set()
        for t in threads:
            t.join(30.0)
        master.join(10.0)
    if errs:
        raise RuntimeError(f"fleet scrape bench worker failed: {errs}")
    if st["state"] != "LIVE" or st["summary"] is None:
        raise RuntimeError(
            f"fleet scrape bench: job never scraped LIVE "
            f"(state={st['state']}) — latency figures would be bogus")
    lat.sort()
    p50 = lat[len(lat) // 2]
    p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))]
    return {
        "fleet_scrape_p50_ms": round(p50 * 1e3, 3),
        "fleet_scrape_p99_ms": round(p99 * 1e3, 3),
        "fleet_scrape_cpu_ms_per_sweep": round(cpu / sweeps * 1e3, 3),
        "fleet_scrape_cpu_share_at_default_cadence": round(
            cpu / sweeps / tuning.fleet_poll_secs(), 4),
        "sweeps": sweeps,
        "wall_secs": round(wall, 3),
        "ranks_reporting": st["summary"]["ranks_reporting"],
    }


def _serve_fm_servable(n_features=4096, k=8, seed=7):
    """A synthesized (numpy-only) FM servable for the serve legs: the
    serve plane never trains, it pulls rows — random parameters
    exercise exactly the same dispatch/caching/scoring paths as a
    trained table, without touching the device runtime (the chaos leg
    forks, so nothing here may initialize a backend)."""
    from ytk_mp4j_tpu.models.fm import FMConfig, FMServable

    rng = np.random.default_rng(seed)
    cfg = FMConfig(n_features=n_features, k=k, model="fm")
    w0 = np.float32(0.1)
    w = rng.standard_normal(n_features).astype(np.float32)
    V = (0.05 * rng.standard_normal((n_features, k))).astype(
        np.float32)
    return FMServable((w0, w, V), cfg)


def _serve_gbdt_servable(n_features=16, n_bins=16, depth=4,
                         n_trees=32, seed=5):
    """A synthesized (numpy-only) GBDT servable: random level-ordered
    trees in the trainer's component layout — the reduce dispatch
    cares about routing + margin reduction, not about the split
    quality, and synthesizing keeps the fork-safety of this block."""
    from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTServable

    rng = np.random.default_rng(seed)
    cfg = GBDTConfig(n_features=n_features, n_bins=n_bins,
                     depth=depth, n_trees=n_trees, loss="logistic",
                     hist_mode="flat")
    n_internal = 2 ** depth - 1
    trees = []
    for _ in range(n_trees):
        trees.append((
            rng.integers(0, n_features, n_internal).astype(np.int32),
            rng.integers(1, n_bins - 1, n_internal).astype(np.int32),
            np.zeros(n_internal, np.int32),
            (0.1 * rng.standard_normal(2 ** depth)).astype(
                np.float32)))
    return GBDTServable(trees, cfg)


def _serve_fm_requests(n_reqs, n_features, nnz=16, seed=11):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, n_features, nnz).astype(np.int64),
             np.zeros(nnz, np.int32),
             rng.standard_normal(nnz).astype(np.float32))
            for _ in range(n_reqs)]


def _serve_threads_job(procs, servable, frontend_body, max_batch,
                       deadline_ms=2.0, cache_rows=0):
    """One live serve job on threads (master + ``procs`` slave
    threads, no fork — the bench_fleet_scrape harness shape): the
    rank-0 thread builds the :class:`ServeFrontend` and runs
    ``frontend_body(fe, slave)``; every other rank answers rounds in
    :func:`serve_worker` until the frontend's STOP. Returns the
    frontend body's result."""
    from ytk_mp4j_tpu.comm.master import Master
    from ytk_mp4j_tpu.comm.process_comm import ProcessCommSlave
    from ytk_mp4j_tpu.serve import ServeFrontend, serve_worker

    master = Master(procs, timeout=60.0, elastic="off", health=False,
                    autoscale="off", tuner="off").serve_in_thread()
    out = {}
    errs = []

    def worker():
        try:
            slave = ProcessCommSlave(
                "127.0.0.1", master.port, timeout=60.0, elastic="off",
                async_collectives=False, health=False, tuner="off",
                shm=False, audit="off", sink_dir="")
            if slave.rank == 0:
                fe = ServeFrontend(slave, servable,
                                   deadline_ms=deadline_ms,
                                   max_batch=max_batch,
                                   cache_rows=cache_rows)
                try:
                    out["result"] = frontend_body(fe, slave)
                finally:
                    fe.close()
            else:
                serve_worker(slave, servable, max_batch=max_batch)
            slave.close(0)
        except Exception as e:  # pragma: no cover
            errs.append(repr(e))

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(procs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120.0)
    master.join(10.0)
    if errs:
        raise RuntimeError(f"serve bench job failed: {errs}")
    if any(t.is_alive() for t in threads) or "result" not in out:
        raise RuntimeError("serve bench job hung")
    return out["result"]


def bench_serve_latency_qps(procs=4, reqs=512):
    """ISSUE 19 acceptance workload: the micro-batching A/B. Three
    full serve jobs over the same synthesized FM servable and the
    same request stream, cache OFF for the first two so every batch
    pays the pull round (the amortization is the figure, not the
    cache):

    - **batched** (``max_batch=32``, open loop): per-request latency
      (enqueue -> resolve, the batcher's own ``on_latency`` hook) is
      the p50/p99 figure; QPS is requests over wall.
    - **unbatched** (``max_batch=1``, same open loop): one pull round
      per REQUEST — the latency-optimal, throughput-terrible corner
      the batcher exists to escape. The batched/unbatched QPS ratio
      is ``serve_speedup`` (acceptance: >= 3x at bit-exact results —
      bitwise equality itself is tier-1's job, tests/test_serve.py).
    - **warm cache** (``max_batch=32``, cache sized to the table):
      pass 1 fills, pass 2 replays the stream — the hit-rate and the
      zero-collective warm QPS figure.
    """
    servable = _serve_fm_servable()
    requests = _serve_fm_requests(reqs, servable.n_rows)

    def open_loop(fe, _slave):
        # bounded in-flight window: deep enough to keep full batches
        # forming, shallow enough that the latency series measures
        # the serve plane, not the submitter's own queue
        window = 64
        lats = []
        orig = fe._batcher._on_latency
        fe._batcher._on_latency = \
            lambda s: (lats.append(s), orig(s))
        t0 = time.perf_counter()
        futs = collections.deque()
        for r in requests:
            futs.append(fe.submit(r))
            if len(futs) >= window:
                futs.popleft().wait(120.0)
        while futs:
            futs.popleft().wait(120.0)
        wall = time.perf_counter() - t0
        return {"wall": wall, "lats": lats,
                "batches": fe._batcher.batches}

    def warm_loop(fe, _slave):
        for f in [fe.submit(r) for r in requests]:
            f.wait(120.0)
        cold = fe.cache_stats()
        t0 = time.perf_counter()
        for f in [fe.submit(r) for r in requests]:
            f.wait(120.0)
        wall = time.perf_counter() - t0
        warm = fe.cache_stats()
        return {"wall": wall, "cold": cold, "warm": warm}

    batched = _serve_threads_job(procs, servable, open_loop,
                                 max_batch=32)
    unbatched = _serve_threads_job(procs, servable, open_loop,
                                   max_batch=1)
    cached = _serve_threads_job(procs, servable, warm_loop,
                                max_batch=32,
                                cache_rows=servable.n_rows)
    lat = sorted(batched["lats"])
    if len(lat) != reqs:
        raise RuntimeError(
            f"serve bench: {len(lat)} latencies for {reqs} requests")
    qps_b = reqs / batched["wall"]
    qps_u = reqs / unbatched["wall"]
    speedup = qps_b / qps_u
    if speedup < 1.5:
        # the batched plane not clearly beating one-round-per-request
        # means the amortization is structurally broken (an extra
        # collective crept into the batch path), not host noise
        raise RuntimeError(
            f"serve bench: batched {qps_b:.0f} QPS vs unbatched "
            f"{qps_u:.0f} QPS (x{speedup:.2f}) — batching is not "
            "amortizing the pull round")
    d = {k: cached["warm"][k] - cached["cold"][k]
         for k in ("hits", "misses")}
    warm_lookups = d["hits"] + d["misses"]
    return {
        "serve_batched_qps": round(qps_b, 1),
        "serve_unbatched_qps": round(qps_u, 1),
        "serve_speedup": round(speedup, 2),
        "serve_p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
        "serve_p99_ms": round(
            lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3, 3),
        "serve_batches": batched["batches"],
        "serve_warm_qps": round(reqs / cached["wall"], 1),
        "serve_warm_hit_rate": round(
            d["hits"] / warm_lookups, 4) if warm_lookups else 1.0,
        "serve_cold_hit_rate": round(
            cached["cold"]["hit_rate"], 4),
        "reqs": reqs,
        "procs": procs,
    }


def bench_serve_chaos(procs=3, reqs=48):
    """ISSUE 19 chaos leg: kill a serving rank mid-stream with a warm
    spare registered (the PR 10 replace machinery) and measure the
    blip a CALLER sees. GBDT reduce dispatch — every round is one
    fixed-shape allreduce, so the adopted spare just joins the next
    round and the batch the dead rank could not score is DELIVERED
    degraded (bitmap gap), never hung. ``max_batch=1`` so every
    request dispatches immediately ("full") and the per-request
    latency series brackets the recovery window exactly; the p99 over
    the stream IS the blip."""
    servable = _serve_gbdt_servable()
    rng = np.random.default_rng(3)
    requests = [rng.integers(0, 16, 16).astype(np.int64)
                for _ in range(reqs)]

    def body(slave, _r):
        from ytk_mp4j_tpu.serve import ServeFrontend, serve_worker
        if slave.rank == 0:
            fe = ServeFrontend(slave, servable, deadline_ms=5.0,
                               max_batch=1)
            lats = []
            for req in requests:
                t0 = time.perf_counter()
                fe.predict(req, timeout=60.0)
                lats.append(time.perf_counter() - t0)
            degraded = fe.degraded_batches
            fe.close()
            return {"lats": lats, "degraded": degraded}
        return serve_worker(slave, servable, max_batch=1)

    def spare_body(sp):
        from ytk_mp4j_tpu.serve import serve_worker
        return serve_worker(sp, servable, max_batch=1)

    # rank 1 answers ~2 serve rounds per request (announce + flush):
    # nth=reqs lands the kill mid-stream
    results, killed = _run_elastic_job(
        procs, body, f"kill:rank=1:nth={reqs}", "replace",
        spare_body=spare_body, shm=False, audit="off", sink_dir="")
    if killed != [1] or len(results) != procs:
        raise RuntimeError(
            f"serve chaos bench: expected rank 1 killed + {procs} "
            f"finishers, got killed={killed} "
            f"results={sorted(results)}")
    fe_out = results[0]
    spare_out = results[1]        # the spare reports under rank 1
    if spare_out.get("rounds", 0) < 1:
        raise RuntimeError(
            "serve chaos bench: adopted spare answered no serve "
            "rounds — the recovery never reached the serve plane")
    lats = fe_out["lats"]
    if len(lats) != reqs:
        raise RuntimeError(
            f"serve chaos bench: frontend delivered {len(lats)} of "
            f"{reqs} predictions")
    s = sorted(lats)
    median = s[len(s) // 2]
    return {
        "serve_chaos_p99_ms": round(
            s[min(len(s) - 1, int(len(s) * 0.99))] * 1e3, 3),
        "serve_chaos_healthy_p50_ms": round(median * 1e3, 3),
        "serve_chaos_blip_ms": round((max(lats) - median) * 1e3, 3),
        "serve_chaos_degraded_batches": fe_out["degraded"],
        "serve_chaos_spare_rounds": spare_out["rounds"],
        "reqs": reqs,
        "procs": procs,
    }


def bench_ffm_tpu(n=8192, n_features=100_000, n_fields=8, k=8,
                  max_nnz=8, steps=10):
    """FFM sparse embedding-gradient allreduce workload (BASELINE.json
    configs[4], Criteo-shaped synthetic minibatch): steps/sec of the
    full jitted sparse train step (score + grads + device-native sparse
    allreduce + update) on the available chip(s)."""
    import jax
    from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer

    rng = np.random.default_rng(3)
    feats = rng.integers(0, n_features, (n, max_nnz)).astype(np.int32)
    fields = rng.integers(0, n_fields, (n, max_nnz)).astype(np.int32)
    vals = np.ones((n, max_nnz), np.float32)
    y = (rng.random(n) > 0.5).astype(np.float32)
    cfg = FMConfig(model="ffm", n_features=n_features, n_fields=n_fields,
                   k=k, max_nnz=max_nnz, learning_rate=0.05)
    tr = FMTrainer(cfg, sparse_grads=True)
    params, _ = tr.fit(feats, fields, vals, y, n_steps=1)  # builds _step
    sharded = tr.shard_data(feats, fields, vals, y)
    # the step carries (and donates) its own state: the table by feature
    params = tr._enter(params)
    step, flops = _aot_compile(tr._step, params, *sharded)
    # warm with the SAME arrays the timed loop uses — a fresh
    # shard_data product can trigger a silent recompile that would
    # otherwise land inside the timed region (measured: 6.9 s)
    params, loss = step(params, *sharded)
    np.asarray(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, loss = step(params, *sharded)
    np.asarray(loss)
    dt = (time.perf_counter() - t0) / steps
    # same per-chip normalization as bench_tpu (cost_analysis flops are
    # whole-program — verified on a 4-device mesh; both steps are SPMD
    # over all devices)
    n_chips = jax.device_count()
    return 1.0 / dt, None if flops is None else flops / dt / n_chips


def bench_ffm_stream(chunks=6, rows=8192, max_in_flight=2):
    """configs[4] ingestion: rows/sec through ``fit_stream`` — chunk
    staging + padding + one sparse FFM step per chunk (the out-of-core
    path a Criteo-scale run must ride; chunk synthesis stands in for
    the file reader). ``max_in_flight=0`` serializes host staging with
    device compute (the round-4 behavior) — the A/B denominator for
    the double-buffering win."""
    from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer

    rng = np.random.default_rng(3)
    cfg = FMConfig(model="ffm", n_features=100_000, n_fields=8, k=8,
                   max_nnz=8, learning_rate=0.05)
    tr = FMTrainer(cfg, sparse_grads=True)

    def gen(n):
        for _ in range(n):
            feats = rng.integers(0, cfg.n_features,
                                 (rows, 8)).astype(np.int32)
            fields = rng.integers(0, 8, (rows, 8)).astype(np.int32)
            vals = np.ones((rows, 8), np.float32)
            y = (rng.random(rows) > 0.5).astype(np.float32)
            yield feats, fields, vals, y

    params, _ = tr.fit_stream(gen(1), batch_rows=rows)  # compile once
    t0 = time.perf_counter()
    params, _ = tr.fit_stream(gen(chunks), params=params,
                              batch_rows=rows,
                              max_in_flight=max_in_flight)
    return chunks * rows / (time.perf_counter() - t0)


def _make_ffm_lines(rows, n_features=100_000, n_fields=8, max_nnz=8,
                    seed=3):
    rng = np.random.default_rng(seed)
    feats = rng.integers(0, n_features, (rows, max_nnz))
    vals = rng.random((rows, max_nnz))
    y = (rng.random(rows) > 0.5).astype(np.int32)
    return [
        f"{y[i]} " + " ".join(
            f"{j % n_fields}:{feats[i, j]}:{vals[i, j]:.4f}"
            for j in range(max_nnz))
        for i in range(rows)
    ]


def bench_libsvm_reader(rows=100_000, chunk_rows=8192):
    """Reader alone: rows/sec through ``read_libsvm`` (the native
    csrc/mp4j_parse.cpp scanner) on Criteo-shaped libffm text held in
    memory — no training, no device."""
    from ytk_mp4j_tpu.utils.libsvm import read_libsvm

    lines = _make_ffm_lines(rows)
    t0 = time.perf_counter()
    got = sum(c[3].size
              for c in read_libsvm(iter(lines), chunk_rows=chunk_rows,
                                   max_nnz=8))
    assert got == rows
    return rows / (time.perf_counter() - t0)


def bench_ffm_stream_text(chunks=6, rows=8192, max_in_flight=2):
    """configs[4] END-TO-END: libffm TEXT -> native chunk parse ->
    pad/stage -> double-buffered sparse FFM steps; rows/sec with the
    reader INCLUDED (the figure round 4's bench excluded)."""
    from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer
    from ytk_mp4j_tpu.utils.libsvm import read_libsvm

    cfg = FMConfig(model="ffm", n_features=100_000, n_fields=8, k=8,
                   max_nnz=8, learning_rate=0.05)
    tr = FMTrainer(cfg, sparse_grads=True)
    lines = _make_ffm_lines(chunks * rows)
    params, _ = tr.fit_stream(            # compile once
        read_libsvm(iter(lines[:rows]), chunk_rows=rows, max_nnz=8),
        batch_rows=rows)
    t0 = time.perf_counter()
    params, _ = tr.fit_stream(
        read_libsvm(iter(lines), chunk_rows=rows, max_nnz=8),
        params=params, batch_rows=rows, max_in_flight=max_in_flight)
    return chunks * rows / (time.perf_counter() - t0)


def bench_device_map(keys=50_000, reps=5):
    """configs[2] on the DEVICE path: merged keys/sec for an int-keyed
    map allreduce on the default backend (n=1 driver, union == map —
    the host encode/decode + one device round-trip per call is the
    measured quantity; the union merge itself rides the device at any
    n). This extra pins the headline size every round."""
    from ytk_mp4j_tpu.comm.tpu_comm import TpuCommCluster
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    cl = TpuCommCluster(1)
    base = {i: float(i) for i in range(keys)}
    cl.allreduce_map([dict(base)], Operands.FLOAT, Operators.SUM)  # warm
    per_call = [[dict(base)] for _ in range(reps)]
    t0 = time.perf_counter()
    nk = 0
    for ms in per_call:
        cl.allreduce_map(ms, Operands.FLOAT, Operators.SUM)
        nk += len(ms[0])
    return nk / (time.perf_counter() - t0)


def bench_device_map_chained(keys=50_000, chain=8):
    """configs[2] STEADY-STATE: ``chain`` map allreduces dispatched per
    host resolution (``allreduce_map_async`` + deferred ``result()``),
    so the host encode, the dispatch and the fetch of consecutive calls
    overlap. The sync variant (``bench_device_map``) pays them in
    sequence on every call."""
    from ytk_mp4j_tpu.comm.tpu_comm import TpuCommCluster
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    cl = TpuCommCluster(1)
    base = {i: float(i) for i in range(keys)}
    cl.allreduce_map([dict(base)], Operands.FLOAT, Operators.SUM)  # warm
    batches = [[dict(base)] for _ in range(chain)]
    t0 = time.perf_counter()
    handles = [cl.allreduce_map_async(ms, Operands.FLOAT, Operators.SUM)
               for ms in batches]
    for h in handles:
        h.result()
    return chain * keys / (time.perf_counter() - t0)


def bench_socket_map(procs=4, keys=20_000, reps=3, int_keys=False,
                     columnar=None, join_timeout=120.0, shm=False):
    """Map<String,Double> sparse-grad allreduce over loopback TCP
    (BASELINE.json configs[2]). Returns merged keys/sec on the job's
    DEFAULT map plane — since ISSUE 4, the columnar (codes, values)
    data plane; ``columnar=False`` forces the pickled-dict reference
    path (the pre-ISSUE-4 Kryo-analogue figure) for the A/B.

    ``int_keys=True`` uses {feature id -> value} integer keys — the
    actual ytk-learn sparse-gradient shape. One UNTIMED warmup call
    precedes the loop: a sparse-gradient stream's vocabulary is
    near-persistent, so the steady-state rate (codec warm, novelty
    exchange empty) is the honest per-call figure; the warmup is a
    no-op for the pickled plane, which keeps no per-call state."""
    from ytk_mp4j_tpu.operands import Operands
    from ytk_mp4j_tpu.operators import Operators

    def body(slave, r):
        # 50% overlap across ranks, like sparse gradient updates; one
        # dict per rep (allreduce_map merges in place), built OUTSIDE
        # the timed region so only the collective is measured
        def key(i):
            c = (r * keys // 2 + i) % (procs * keys)
            return c if int_keys else f"w{c}"
        dicts = [
            {key(i): float(i) for i in range(keys)}
            for _ in range(reps + 1)
        ]
        slave.allreduce_map(dicts.pop(), Operands.DOUBLE,
                            Operators.SUM)     # untimed codec warmup
        slave.barrier()
        t0 = time.perf_counter()
        nkeys = 0
        for d in dicts:
            slave.allreduce_map(d, Operands.DOUBLE, Operators.SUM)
            nkeys += len(d)   # post-merge union size = keys merged
        return nkeys / (time.perf_counter() - t0)

    # all-TCP by default for figure continuity: the map keys/sec rows
    # are bench-diff-gated against pre-shm rounds; ``shm=True`` is the
    # ISSUE 15 leg (socket_map_shm_keys_s) — co-located pairs ride the
    # rings, and the frame-level routing carries the column frames
    rates, stats = _run_socket_job(procs, body, native_transport=False,
                                   join_timeout=join_timeout,
                                   map_columnar=columnar, shm=shm,
                                   audit="off", sink_dir="")
    return min(rates), stats


def bench_socket_map_sweep(procs=4,
                           sizes=(1_000, 10_000, 100_000, 500_000),
                           reps=3):
    """Columnar-vs-pickle A/B over map sizes, int AND str keys — the
    honest re-run of the old ``_merge_maps`` packed-merge measurement
    (which paid a per-call union sort + Python pack the grow-only
    codec amortizes away). Emitted in the BENCH ``extra`` so the
    crossover threshold is data-grounded, not guessed. Returns
    ``({"<keys>": {"int"|"str": {"columnar"|"pickle": keys/s}}},
    merged_stats)``."""
    from ytk_mp4j_tpu.utils.stats import merge_snapshots

    sweep = {}
    snaps = []
    for keys in sizes:
        # big unions are slow on the pickled leg and the least noisy;
        # repeat the cheap latency-bound sizes instead
        r = reps if keys <= 10_000 else 1
        row = {}
        for kind, int_keys in (("int", True), ("str", False)):
            cell = {}
            for plane, columnar in (("columnar", True),
                                    ("pickle", False)):
                rate, stats = bench_socket_map(
                    procs=procs, keys=keys, reps=r, int_keys=int_keys,
                    columnar=columnar, join_timeout=600.0)
                cell[plane] = round(rate, 0)
                snaps.append(stats)
            row[kind] = cell
        sweep[str(keys)] = row
    return sweep, _round_stats(merge_snapshots(*snaps))


def main():
    from ytk_mp4j_tpu.utils import tuning
    from ytk_mp4j_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    # MP4J_BENCH_N=11e6 runs the full Higgs-scale config (BASELINE.json
    # configs[3]); the default 1e6 keeps driver runs fast (the rate is a
    # per-byte measure).
    n_tpu = int(float(os.environ.get("MP4J_BENCH_N", "1e6")))
    # socket benches FIRST: they fork real slave processes, and forking
    # after the TPU client exists is not fork-safe (the children would
    # inherit live device-runtime threads/fds)
    sock_gbs, sock_workload_coll_gbs, sock_stats = bench_socket()
    # socket_collective_gbs: the DEFAULT socket data plane (native raw
    # + algo="auto" + pipelined chunked engine) over the tree-level
    # histogram buffer shapes, isolated from the workload's compute
    # skew. The pre-PR2 figure under this key was the framed in-GBDT
    # csecs rate, now kept as socket_collective_in_workload_gbs.
    sock_coll_gbs, sock_coll_stats = bench_socket_collective(
        native_transport=True)
    # ISSUE 7: the same isolated collective leg over the intra-host
    # shared-memory rings (the 4 forked slaves co-locate, so rendezvous
    # negotiates shm for every pair), and with the topology-aware
    # two-level schedule forced (on this single-host roster: binomial
    # reduce+broadcast over shm, leader leg a no-op)
    sock_shm_coll_gbs, sock_shm_coll_stats = bench_socket_collective(
        native_transport=True, shm=True)
    sock_twolevel_gbs, sock_twolevel_stats = bench_socket_collective(
        native_transport=True, shm=True, algo="twolevel")
    # audit-plane overhead A/B (ISSUE 8): off vs digest vs verify,
    # interleaved, on the isolated headline leg (frozen legs above pin
    # audit="off" so historical figures stay comparable)
    audit_overhead = bench_audit_overhead()
    # durable-sink overhead A/B (ISSUE 9): the same isolated headline
    # leg with segments streaming to a throwaway dir (frozen legs pin
    # sink_dir="" the way they pin shm=False / audit="off")
    sink_overhead = bench_sink_overhead()
    health_overhead = bench_health_overhead()
    lint_runtime = bench_lint_runtime()
    # metrics-plane overhead A/B (ISSUE 6 acceptance: <= 3% on the
    # headline leg): the same isolated collective leg with
    # MP4J_METRICS=0 — histogram observes become flag checks, the
    # heartbeat ships empty metric deltas. The default-on figure is
    # sock_coll_gbs itself (every socket figure in this file carries
    # the full metrics tax); forked slaves inherit the env toggle.
    prior_metrics = os.environ.get("MP4J_METRICS")
    os.environ["MP4J_METRICS"] = "0"
    try:
        sock_coll_gbs_nometrics, _ = bench_socket_collective(
            native_transport=True)
    finally:
        # restore, don't delete: a caller-exported MP4J_METRICS must
        # keep governing every later leg (and the A/B note below is
        # only honest when the ON leg really ran with metrics on)
        if prior_metrics is None:
            del os.environ["MP4J_METRICS"]
        else:
            os.environ["MP4J_METRICS"] = prior_metrics
    sock_framed_coll_gbs, sock_framed_coll_stats = bench_socket_collective(
        native_transport=False)
    sweep, sweep_stats = bench_socket_allreduce_sweep()
    map_keys, map_stats = bench_socket_map()
    map_int_keys, map_int_stats = bench_socket_map(int_keys=True)
    # columnar-vs-pickle A/B at the headline config (the pickle legs
    # are the pre-ISSUE-4 reference figures) + the size sweep that
    # grounds the crossover claim
    map_pickle_keys, _ = bench_socket_map(columnar=False)
    map_int_pickle_keys, _ = bench_socket_map(int_keys=True,
                                              columnar=False)
    map_sweep, map_sweep_stats = bench_socket_map_sweep()
    # ISSUE 11: the nonblocking-collective figures — k outstanding
    # iallreduces vs k sequential blocking calls (isolated leg; see
    # bench_socket_async_overlap's 1-core caveat) and the tiny-map
    # coalescing A/B (window on vs off)
    async_overlap = bench_socket_async_overlap()
    coalesce = bench_socket_coalesce()
    # ISSUE 17 (mp4j-overlap): the dense small-array coalescing A/B
    # (the array twin of the map figure above) and the trainer-shaped
    # overlap epoch — multi-core only, records skipped_1core on this
    # 1-core rig instead of a bogus figure (see the leg docstring)
    coalesce_array = bench_socket_coalesce_array()
    trainer_overlap = bench_trainer_overlap()
    # ISSUE 15 (mp4j-tuner): the framed + columnar-map planes over the
    # shm rings (frame-level routing — these bytes were carrier-bound
    # before), and the tuner act-vs-off A/B on a compressed-operand
    # stream (frozen legs everywhere else pin MP4J_TUNER=off)
    framed_shm_gbs, framed_shm_stats = bench_socket_collective(
        native_transport=False, shm=True)
    map_shm_keys, map_shm_stats = bench_socket_map(shm=True)
    tuner_ab = bench_socket_tuner_act()
    recovery, recovery_stats = bench_socket_recovery_latency()
    replacement = bench_socket_replacement_latency()
    shrinkage = bench_socket_shrink_latency()
    planned_evict = bench_socket_planned_evict_ms()
    grow = bench_socket_grow_latency_ms()
    # ISSUE 18 (mp4j-fleet): FleetPoller sweep latency + CPU share
    # against a live 4-rank job in this process (threads, no fork —
    # safe at any point in the socket block; the poller scrapes HTTP
    # out of band so no frozen leg changes)
    fleet_scrape = bench_fleet_scrape()
    # ISSUE 19 (mp4j-serve): the inference plane. The A/B leg runs on
    # threads; the chaos leg forks worker processes, so both stay in
    # this socket block ahead of any device-runtime init (the
    # servables are synthesized numpy-only for exactly that reason)
    serve_ab = bench_serve_latency_qps()
    serve_chaos = bench_serve_chaos()
    # device legs from here on: no TPU, or a chip with no published
    # peak, is an error and not a CPU figure under a device name
    device = require_tpu()
    peak = peak_bf16_flops(device.device_kind)
    (tpu_gbs, trees_per_sec, n_chips, gbdt_fps,
     gbdt_hist_fps) = bench_tpu(n=n_tpu)
    ffm_steps, ffm_fps = bench_ffm_tpu()
    ffm_stream_rows = bench_ffm_stream()
    ffm_stream_rows_serial = bench_ffm_stream(max_in_flight=0)
    reader_rows = bench_libsvm_reader()
    ffm_text_rows = bench_ffm_stream_text()
    dev_map_keys = bench_device_map()
    dev_map_keys_chained = bench_device_map_chained()
    print(json.dumps({
        "metric": "gbdt-histogram-allreduce GB/s/chip",
        "value": round(tpu_gbs, 4),
        "unit": "GB/s/chip",
        "vs_baseline": round(tpu_gbs / sock_gbs, 2),
        "extra": {
            "trees_per_sec": round(trees_per_sec, 4),
            "socket_baseline_gbs": round(sock_gbs, 4),
            "socket_collective_gbs": round(sock_coll_gbs, 4),
            "socket_framed_collective_gbs": round(sock_framed_coll_gbs, 4),
            "socket_collective_in_workload_gbs": round(
                sock_workload_coll_gbs, 4),
            # continuity alias: previous rounds tracked the native rate
            # under this key (socket_collective_gbs now measures it)
            "socket_native_collective_gbs": round(sock_coll_gbs, 4),
            # ISSUE 7: the same collective leg with the data plane on
            # the intra-host shared-memory rings (acceptance: >= 3x
            # the TCP socket_collective_gbs figure), and with the
            # two-level schedule forced (single-host: the intra half)
            "socket_shm_collective_gbs": round(sock_shm_coll_gbs, 4),
            "socket_twolevel_gbs": round(sock_twolevel_gbs, 4),
            "socket_allreduce_sweep": sweep,
            "ffm_sparse_steps_per_sec": round(ffm_steps, 3),
            "ffm_stream_rows_per_sec": round(ffm_stream_rows, 0),
            "ffm_stream_rows_per_sec_serialized": round(
                ffm_stream_rows_serial, 0),
            "libsvm_reader_rows_per_sec": round(reader_rows, 0),
            "ffm_stream_text_rows_per_sec": round(ffm_text_rows, 0),
            "vs_baseline_derate_caveat": (
                "this host has ONE core, so the 4 socket-baseline "
                "slaves time-share it; on a realistic 4-core host the "
                "socket denominator rises up to ~4x and the honest "
                "ratio lands near vs_baseline/4 — "
                "still clearing the >=10x north star, but vs_baseline "
                "as printed is environment-specific"),
            # headline map figures ride the DEFAULT socket map plane —
            # columnar (codes, values) since ISSUE 4; the *_pickle_*
            # keys are the frozen pickled-dict reference legs of the
            # same config, and socket_map_allreduce_sweep carries the
            # full columnar-vs-pickle A/B over 1k..500k keys x
            # {int, str} so the crossover is measured, not guessed
            "socket_map_allreduce_keys_per_sec": round(map_keys, 0),
            "socket_map_int_allreduce_keys_per_sec": round(map_int_keys, 0),
            "socket_map_pickle_keys_per_sec": round(map_pickle_keys, 0),
            "socket_map_int_pickle_keys_per_sec": round(
                map_int_pickle_keys, 0),
            "socket_map_allreduce_sweep": map_sweep,
            # ISSUE 11 (mp4j-async): k outstanding iallreduces on the
            # helper-thread scheduler vs the same k as sequential
            # blocking calls, plus the coalescing A/B. On this 1-core
            # host the sequential path saturates the core at the
            # kernel-TCP CPU ceiling, so overlap has no idle to fill
            # and the dense async figure lands BELOW sequential (the
            # measured, documented reality — see the leg docstring);
            # the coalescing figure is the async plane's honest win
            # here (~2.5x, fixed-cost amortization)
            "socket_async_overlap_gbs": round(async_overlap["async"], 4),
            "socket_async_sequential_gbs": round(
                async_overlap["sequential"], 4),
            "socket_async_overlap_ratio": round(
                async_overlap["async"] / async_overlap["sequential"],
                3),
            "socket_coalesce_keys_per_sec": round(coalesce["on"], 0),
            "socket_coalesce_off_keys_per_sec": round(
                coalesce["off"], 0),
            "socket_coalesce_ratio": round(
                coalesce["on"] / coalesce["off"], 3),
            # ISSUE 17 (mp4j-overlap): the dense small-array fused
            # plane (count-negotiated allreduce_array_multi) vs the
            # same stream as sequential i* submissions — acceptance
            # >= 2x elems/s — and the trainer-overlap epoch A/B. The
            # trainer leg is multi-core only: on this 1-core rig the
            # dict records skipped_1core and NO ratio figure is
            # emitted (bench-diff skips missing metrics, so the gate
            # arms itself the first time the bench runs on a
            # multi-core host)
            "socket_coalesce_array_elems_per_sec": round(
                coalesce_array["on"], 0),
            "socket_coalesce_array_off_elems_per_sec": round(
                coalesce_array["off"], 0),
            "socket_coalesce_array_ratio": round(
                coalesce_array["on"] / coalesce_array["off"], 3),
            "socket_trainer_overlap": {
                k: v for k, v in trainer_overlap.items()
                if k != "stats"},
            **({"socket_trainer_overlap_ratio": round(
                    trainer_overlap["ratio"], 3),
                "socket_trainer_overlap_steps_per_sec": round(
                    trainer_overlap["overlap"], 2),
                "socket_trainer_blocking_steps_per_sec": round(
                    trainer_overlap["blocking"], 2)}
               if "ratio" in trainer_overlap else {}),
            # ISSUE 15 (mp4j-tuner): the framed/columnar-map planes
            # over the shm rings (frame-level routing — previously
            # carrier-bound even intra-host), and the tuner A/B: act
            # must be a net win over off on this compressed-operand
            # leg (the probe discovers the loopback link outruns the
            # zlib bound and disables per-link compression); the
            # `tuner` extra records the converged decisions
            "socket_framed_shm_gbs": round(framed_shm_gbs, 4),
            "socket_map_shm_keys_s": round(map_shm_keys, 0),
            "socket_tuner_act_gbs": round(tuner_ab["act"], 4),
            "socket_tuner_off_gbs": round(tuner_ab["off"], 4),
            "socket_tuner_ratio": round(
                tuner_ab["act"] / tuner_ab["off"], 3),
            "tuner": tuner_ab["decisions"],
            # mp4j-resilience (ISSUE 5): one injected connection reset
            # in a 4-rank allreduce loop; recovery_latency_ms is the
            # full epoch-fenced abort/retry round end to end.
            # steady_state decomposes the no-fault cost: failstop_gbs
            # (max_retries=0) carries the epoch fence alone (~0, a
            # flag check — the figure comparable with BENCH history);
            # default_gbs adds the input-preservation snapshot, one
            # pooled memcpy pass per mutating collective, which this
            # 1-core loopback host amplifies because its "wire" is
            # itself memcpy (see bench_socket_recovery_latency doc)
            "socket_recovery": recovery,
            # scalar alias for bench-diff gating (lower is better)
            "socket_recovery_latency_ms": recovery[
                "recovery_latency_ms"],
            # mp4j-elastic (ISSUE 10): kill -> adopted spare (or n-1
            # shrink) -> first completed collective, measured as the
            # faulted iteration's wall time over the healthy median;
            # frozen legs elsewhere pin MP4J_ELASTIC=off so these are
            # the ONLY figures that pay the membership machinery
            "socket_replacement_latency_ms": replacement[
                "replacement_latency_ms"],
            "socket_shrink_latency_ms": shrinkage[
                "shrink_latency_ms"],
            # ISSUE 13: actuation latencies — planned evict (fence ->
            # round -> adoption -> first post-adoption collective,
            # detection excluded by design) and grow (resize_point
            # wall time). Frozen legs elsewhere pin MP4J_AUTOSCALE=off
            "socket_planned_evict_ms": planned_evict[
                "planned_evict_ms"],
            "socket_grow_latency_ms": grow["grow_latency_ms"],
            # ISSUE 18 (mp4j-fleet): one full fleet sweep (both
            # endpoint fetches + fold + contention detection) against
            # a live 4-rank job; the p99 row is bench-diff-gated
            # (lower is better) so a fold/detector regression cannot
            # creep in silently
            "fleet_scrape": fleet_scrape,
            "fleet_scrape_p99_ms": fleet_scrape[
                "fleet_scrape_p99_ms"],
            "serve": serve_ab,
            "serve_chaos": serve_chaos,
            "serve_batched_qps": serve_ab["serve_batched_qps"],
            "serve_unbatched_qps": serve_ab["serve_unbatched_qps"],
            "serve_speedup": serve_ab["serve_speedup"],
            "serve_p50_ms": serve_ab["serve_p50_ms"],
            "serve_p99_ms": serve_ab["serve_p99_ms"],
            "serve_chaos_p99_ms": serve_chaos["serve_chaos_p99_ms"],
            "socket_elastic": {"replace": replacement,
                               "shrink": shrinkage,
                               "planned_evict": planned_evict,
                               "grow": grow},
            # merged cross-rank comm.stats() snapshot per socket
            # workload: where the wire/reduce/serialize budget actually
            # went (schema: ytk_mp4j_tpu/utils/stats.py)
            "socket_stats": {
                "gbdt_workload": sock_stats,
                "collective_native": sock_coll_stats,
                "collective_shm": sock_shm_coll_stats,
                "collective_twolevel": sock_twolevel_stats,
                "collective_framed": sock_framed_coll_stats,
                "collective_framed_shm": framed_shm_stats,
                "map_shm": map_shm_stats,
                "allreduce_sweep": sweep_stats,
                "map_allreduce": map_stats,
                "map_int_allreduce": map_int_stats,
                "map_sweep": map_sweep_stats,
                "recovery": recovery_stats,
            },
            # telemetry overhead (ISSUE 3 acceptance, qualitative): the
            # spans + heartbeats are DEFAULT-ON in every socket figure
            # in this file, so socket_collective_gbs already carries
            # the full observability tax. A heartbeat is one ~300 B
            # control frame per rank per 0.5 s riding the master
            # channel (never the data plane); a span is one
            # bounded-deque append per chunk/phase. Measured A/B on
            # the bench host (on vs MP4J_SPAN_RING=0 +
            # MP4J_HEARTBEAT_SECS=0, interleaved rounds): the delta is
            # noise-dominated (run-to-run spread ~10% on this shared
            # 1-core host; the telemetry-ON median came out FASTER),
            # with best-of-N within the <2% target.
            "telemetry": {
                "heartbeat_secs": tuning.heartbeat_secs(),
                "span_ring_capacity": tuning.span_ring_capacity(),
                "default_on": True,
            },
            # metrics-plane overhead (ISSUE 6 acceptance: <= 3% on the
            # headline socket_collective_gbs leg). Same leg, metrics
            # on (the default — sock_coll_gbs itself) vs MP4J_METRICS=0
            # (observes become one flag check; heartbeats ship empty
            # metric deltas). Positive overhead_pct = metrics cost;
            # run-to-run spread on this shared 1-core host is ~10%, so
            # small negatives are noise, not a speedup.
            # audit-plane overhead (ISSUE 8): interleaved off/digest/
            # verify A/B on the headline leg; the digest figure is
            # bench-diff-gated (socket_collective_gbs_audit_digest).
            # The printed pct carries the 1-core x4 serialization
            # amplification — per-rank cost ~2%, see the leg docstring
            "audit_overhead": audit_overhead,
            "socket_collective_gbs_audit_digest":
                audit_overhead["socket_collective_gbs_audit_digest"],
            # durable-sink overhead (ISSUE 9 acceptance: <= 3% on the
            # headline leg, inside this host's ~10% noise floor); the
            # armed figure is bench-diff-gated so the sink tax cannot
            # silently creep
            "sink_overhead": sink_overhead,
            "socket_collective_gbs_sink_on":
                sink_overhead["socket_collective_gbs_sink_on"],
            # health-plane overhead (ISSUE 12 acceptance: <= 3% on the
            # isolated headline leg, inside this host's ~10% noise
            # floor); the armed figure is bench-diff-gated so the
            # detector tax cannot silently creep
            "health_overhead": health_overhead,
            "socket_collective_gbs_health_on":
                health_overhead["socket_collective_gbs_health_on"],
            # mp4j-lint runtime (ISSUE 14): the whole-program R19-R21
            # pass rides the tier-1 gate, so its cost is a tracked
            # figure — full two-pass run vs the per-file pass alone
            # (budget: <= 2x)
            "lint_runtime": lint_runtime,
            "lint_runtime_secs": lint_runtime["lint_runtime_secs"],
            # ISSUE 16: v3 (R23-R25 lockset/resource passes) over v2
            # (R19-R21) — flattened so bench-diff gates it (<= 1.5x)
            "lint_v3_over_v2_ratio":
                lint_runtime["lint_v3_over_v2_ratio"],
            "metrics_overhead": {
                # False means the caller exported MP4J_METRICS=0 and
                # the "on" leg really ran off — overhead_pct is then
                # an off-vs-off null, not a measurement
                "default_on": tuning.metrics_enabled(),
                "socket_collective_gbs_metrics_on": round(
                    sock_coll_gbs, 4),
                "socket_collective_gbs_metrics_off": round(
                    sock_coll_gbs_nometrics, 4),
                "overhead_pct": round(
                    (sock_coll_gbs_nometrics - sock_coll_gbs)
                    / sock_coll_gbs_nometrics * 100, 2),
            },
            "device_map_int_allreduce_keys_per_sec": round(dev_map_keys, 0),
            "device_map_chained_keys_per_sec": round(
                dev_map_keys_chained, 0),
            # MFU vs the chip's published bf16 MXU peak
            # (PEAK_BF16_FLOPS; the *_v5e_* key names date from when
            # v5e was the only entry).
            # gbdt_hist_mxu_* is the ANALYTIC flop count of the fused
            # Pallas histogram matmuls (cost_analysis cannot see inside
            # the custom call; gbdt_step_* below is the XLA-visible
            # remainder only — routing, splits, leaf math). The
            # histogram's one-hot GENERATION is VPU-bound (a
            # dtype-invariant floor), so MXU utilization
            # is structurally capped well below peak — the number
            # grounds "fast" against the hardware ceiling, not a claim
            # of matmul saturation; the FFM sparse step is gather/
            # scatter-unit-bound, lower still.
            "gbdt_hist_mxu_tflops_per_sec_per_chip": round(
                gbdt_hist_fps / 1e12, 3),
            "gbdt_hist_mxu_mfu_vs_v5e_bf16_peak": round(
                gbdt_hist_fps / peak, 4),
            "gbdt_step_xla_visible_tflops_per_sec_per_chip": (
                None if gbdt_fps is None else round(gbdt_fps / 1e12, 3)),
            "ffm_step_tflops_per_sec_per_chip": (
                None if ffm_fps is None else round(ffm_fps / 1e12, 4)),
            "ffm_step_mfu_vs_v5e_bf16_peak": (
                None if ffm_fps is None
                else round(ffm_fps / peak, 6)),
            "n_chips": n_chips,
            "platform": device.platform,
            "device_kind": device.device_kind,
            "config": f"Higgs-like synthetic, F=28, B=256, depth=6, "
                      f"N_tpu={n_tpu:.0e}, N_socket=2e5/4 procs; 10 "
                      "chained trees per host sync; timing closed by "
                      "np.asarray of a device value; "
                      "socket_collective_gbs = the default socket data "
                      "plane (native raw, algo=auto, chunked engine) "
                      "isolated over the tree-level buffer shapes — "
                      "the framed in-workload figure previous rounds "
                      "tracked under that key is "
                      "socket_collective_in_workload_gbs",
        },
    }))


if __name__ == "__main__":
    sys.exit(main())
