"""bench.py must stay runnable — the driver executes it at round end,
so an API drift that breaks it would lose the round's headline number.
Toy-sized smoke runs on the CPU test rig."""

import numpy as np

import bench


def test_bench_tpu_smoke():
    gbs, tps, n_chips, fps, hist_fps = bench.bench_tpu(
        n=512, f=4, b=256, depth=2, trees=1)
    assert np.isfinite(gbs) and gbs > 0
    assert np.isfinite(tps) and tps > 0
    assert n_chips >= 1
    assert fps is None or fps > 0          # MFU numerator (best-effort)
    assert np.isfinite(hist_fps) and hist_fps > 0
    # analytic count: level 0 full + sibling-subtracted level 1
    assert bench.gbdt_hist_mxu_flops(512, 4, 256, 2) == (
        2.0 * 512 * 4 * (1 + 1) * 256 * 4)


def test_bench_device_paths_smoke():
    steps, fps = bench.bench_ffm_tpu(n=64, n_features=128, n_fields=2,
                                     k=2, max_nnz=2, steps=1)
    assert np.isfinite(steps) and steps > 0
    assert fps is None or fps > 0
    rate = bench.bench_device_map_chained(keys=64, chain=2)
    assert np.isfinite(rate) and rate > 0
    rows = bench.bench_libsvm_reader(rows=256, chunk_rows=128)
    assert np.isfinite(rows) and rows > 0
    e2e = bench.bench_ffm_stream_text(chunks=2, rows=64)
    assert np.isfinite(e2e) and e2e > 0


def _check_socket_stats(stats):
    """Every socket workload emits the merged cross-rank comm.stats()
    snapshot; it must be JSON-ready and carry real wire traffic."""
    import json

    assert stats and json.dumps(stats)
    total_wire = sum(e.get("bytes_sent", 0) + e.get("bytes_recv", 0)
                     for e in stats.values())
    assert total_wire > 0


def test_bench_socket_smoke():
    gbs, coll, stats = bench.bench_socket(n=400, f=4, b=8, depth=2,
                                          procs=2)
    assert np.isfinite(gbs) and gbs > 0
    assert np.isfinite(coll) and coll > 0
    _check_socket_stats(stats)
    assert "allreduce_array" in stats


def test_bench_socket_collective_smoke():
    rate, stats = bench.bench_socket_collective(f=4, b=8, depth=2,
                                                procs=2, reps=1)
    assert np.isfinite(rate) and rate > 0
    _check_socket_stats(stats)


def test_bench_socket_map_smoke():
    rate, stats = bench.bench_socket_map(procs=2, keys=50, reps=1)
    assert np.isfinite(rate) and rate > 0
    _check_socket_stats(stats)
    assert "allreduce_map" in stats


def test_bench_socket_allreduce_sweep_smoke():
    sweep, stats = bench.bench_socket_allreduce_sweep(procs=2, reps=1)
    assert sweep, "sweep must report at least one size"
    for row in sweep.values():
        assert set(row) == {"tree", "rhd", "ring", "auto"}
        for rate in row.values():
            assert np.isfinite(rate) and rate > 0
    _check_socket_stats(stats)


def test_bench_socket_map_sweep_smoke():
    sweep, stats = bench.bench_socket_map_sweep(procs=2, sizes=(40,),
                                                reps=1)
    assert set(sweep) == {"40"}
    for kind in ("int", "str"):
        cell = sweep["40"][kind]
        assert set(cell) == {"columnar", "pickle"}
        for rate in cell.values():
            assert np.isfinite(rate) and rate > 0
    _check_socket_stats(stats)


def test_bench_socket_map_pickle_leg_smoke():
    rate, stats = bench.bench_socket_map(procs=2, keys=50, reps=1,
                                         columnar=False)
    assert np.isfinite(rate) and rate > 0
    # the forced-pickle leg must not touch the columnar encoder
    assert all(e.get("keys", 0) == 0 for e in stats.values())


def test_bench_socket_recovery_latency_smoke():
    summary, stats = bench.bench_socket_recovery_latency(
        procs=2, reps=5, size=4096)
    assert summary["retries"] >= 1          # the reset actually fired
    assert np.isfinite(summary["recovery_latency_ms"])
    ss = summary["steady_state"]
    assert ss["default_gbs"] > 0 and ss["failstop_gbs"] > 0
    _check_socket_stats(stats)


def test_bench_socket_framed_shm_smoke(monkeypatch):
    # the ISSUE 15 frame-routing leg: framed plane over the shm
    # rings. The smoke's tiny frames sit below the default
    # MP4J_SHM_FRAME_MIN, so lower it — the assertion must prove the
    # bytes rode the RINGS (wire_bytes_shm alone also counts the shm
    # pair's carrier traffic and would pass with routing broken)
    monkeypatch.setenv("MP4J_SHM_FRAME_MIN", "64")
    rate, stats = bench.bench_socket_collective(f=4, b=8, depth=2,
                                                procs=2, reps=1,
                                                native_transport=False,
                                                shm=True)
    assert np.isfinite(rate) and rate > 0
    _check_socket_stats(stats)
    assert sum(e["wire_bytes_shm"] for e in stats.values()) > 0
    assert sum(e["wire_bytes_shm_ring"] for e in stats.values()) > 0


def test_bench_socket_map_shm_smoke(monkeypatch):
    monkeypatch.setenv("MP4J_SHM_FRAME_MIN", "64")
    rate, stats = bench.bench_socket_map(procs=2, keys=50, reps=1,
                                         shm=True)
    assert np.isfinite(rate) and rate > 0
    assert sum(e["wire_bytes_shm"] for e in stats.values()) > 0
    assert sum(e["wire_bytes_shm_ring"] for e in stats.values()) > 0


def test_bench_socket_coalesce_array_smoke():
    # procs=3: the fused array plane is pinned to the tree schedule
    # and algo=auto only selects tree at n >= 3 (leg docstring)
    out = bench.bench_socket_coalesce_array(procs=3, arrays=40,
                                            size=64)
    assert np.isfinite(out["on"]) and out["on"] > 0
    assert np.isfinite(out["off"]) and out["off"] > 0
    # the window leg actually fused: coalesced_elems books the
    # count-negotiated multi-exchange totals
    assert sum(e.get("coalesced_elems", 0)
               for e in out["stats"].values()) > 0


def test_bench_trainer_overlap_skips_or_measures():
    import os

    out = bench.bench_trainer_overlap(procs=2, steps=3,
                                      grad_elems=512, matmul_dim=32,
                                      matmul_reps=1)
    nproc = len(os.sched_getaffinity(0))
    if nproc < 2:
        # the 1-core contract: a recorded marker, never a bogus figure
        assert out == {"skipped_1core": True, "nproc": nproc}
    else:
        assert np.isfinite(out["ratio"]) and out["ratio"] > 0
        assert out["overlap"] > 0 and out["blocking"] > 0
        assert out["gate_min"] == 1.3 and "gate" in out


def test_bench_socket_tuner_act_smoke():
    out = bench.bench_socket_tuner_act(procs=2, size=60_000, reps=2,
                                       warmup_secs=1.3)
    assert np.isfinite(out["off"]) and out["off"] > 0
    assert np.isfinite(out["act"]) and out["act"] > 0
    # the act leg's slaves report their tuner documents (the `tuner`
    # extra); the win itself is asserted by bench-diff on real runs,
    # not by this smoke (2-rank tiny payloads are noise-dominated)
    assert out["decisions"] and all(
        st is not None and st["mode"] == "act"
        for st in out["decisions"].values())


def test_socket_job_refuses_to_fork_under_an_accelerator(monkeypatch):
    """One process for each chip: once this process holds an
    accelerator backend the socket legs refuse to fork (no master, no
    child is started). A CPU-only backend holds no chip and passes —
    the smokes above fork after CPU device tests in this very process."""
    import jax
    import pytest

    jax.devices()                           # a backend is up
    bench._refuse_fork_with_live_accelerator()          # cpu: passes
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for job in (
            lambda: bench._run_socket_job(2, lambda s, r: r, False),
            lambda: bench._run_elastic_job(2, lambda s, r: r, "", "off")):
        with pytest.raises(RuntimeError, match="refusing to fork"):
            job()


def test_device_figures_need_a_tpu_and_a_known_peak():
    """A measurement path that finds no chip fails, and a chip with no
    published peak is an error, not a v5e default."""
    import pytest

    with pytest.raises(RuntimeError, match="platform 'cpu'"):
        bench.require_tpu()
    assert bench.peak_bf16_flops("TPU v5 lite") == 197e12
    with pytest.raises(RuntimeError, match="no published peak"):
        bench.peak_bf16_flops("cpu")


def test_aot_compile_lets_a_compile_error_out():
    import jax
    import pytest

    def bad(x):
        raise ValueError("does not trace")

    with pytest.raises(ValueError, match="does not trace"):
        bench._aot_compile(jax.jit(bad), np.zeros(2, np.float32))
