"""Device-path correctness battery — runs on the DEFAULT jax backend.

The device-path analogue of ``checkprocess``/``checkthread`` (the
reference's check-program strategy, SURVEY.md section 4): exercises
every collective x operator on BOTH device backends —

- ``TpuCommCluster`` (driver mode, host buffers in/out), and
- ``ops.collectives`` / ``ops.sparse`` inside a jitted ``shard_map``
  (the perf path),

against numpy oracles, on whatever devices the default backend exposes.
On a TPU host it proves that the emitted all_reduce / all_gather /
reduce_scatter / collective_permute HLO compiles and executes on the
chips present, and then executes the compiled Pallas ring kernels
(``ring_kernel_hw``). With one chip every collective is an identity
(the artifact says so); cross-chip semantics need two or more.

    python -m ytk_mp4j_tpu.check.checktpu [--out artifact.json]

Exit code 0 iff every check passes; the artifact records platform,
device count and per-family pass/fail counts. With ``--out`` the
artifact is written after each section, so a section that hangs leaves
the earlier sections' results on disk.
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ytk_mp4j_tpu import meta
from ytk_mp4j_tpu.comm.tpu_comm import TpuCommCluster
from ytk_mp4j_tpu.check._oracle import expected_reduce, rank_data
from ytk_mp4j_tpu.operands import Operands
from ytk_mp4j_tpu.operators import Operator, Operators
from ytk_mp4j_tpu.ops import collectives as coll
from ytk_mp4j_tpu.ops import ring
from ytk_mp4j_tpu.ops import sparse as sparse_ops
from ytk_mp4j_tpu.parallel import make_mesh
from ytk_mp4j_tpu.utils.compile_cache import enable_compilation_cache

SEED_BASE = 4200
OPS = ("SUM", "MAX", "MIN", "PROD")


class Tally:
    def __init__(self):
        self.passed = 0
        self.failures: list[str] = []

    def expect(self, name: str, got, want, exact: bool):
        ok = (np.array_equal(got, want) if exact
              else np.allclose(got, want, rtol=1e-4, atol=1e-5))
        if ok:
            self.passed += 1
        else:
            self.failures.append(name)
            print(f"FAIL {name}", file=sys.stderr)


def _operands():
    """Device-eligible operands for this backend (64-bit needs x64).
    SHORT/BYTE ride the device path too — int16/int8 collectives
    compile and execute on the real chip and AOT-compile for v5e-8
    (probed round 3), with numpy/Java wraparound semantics."""
    ops = [Operands.FLOAT, Operands.INT, Operands.SHORT, Operands.BYTE]
    if jax.config.jax_enable_x64:
        ops += [Operands.DOUBLE, Operands.LONG]
    return ops


def check_cluster(t: Tally, n: int, length: int = 192, devices=None):
    """Driver mode: all 7 dense collectives x operators + map family."""
    cluster = TpuCommCluster(mesh=make_mesh(n, devices=devices))
    for operand in _operands():
        exact = operand.dtype.kind != "f"
        alls = [rank_data(r, length, operand, SEED_BASE) for r in range(n)]
        for op_name in OPS:
            op = Operators.by_name(op_name)
            arrs = [a.copy() for a in alls]
            cluster.allreduce_array(arrs, operand, op)
            want = expected_reduce(alls, op_name)
            for r in range(n):
                t.expect(f"cluster/allreduce/{operand.name}/{op_name}",
                         arrs[r], want, exact)
            arrs = [a.copy() for a in alls]
            cluster.reduce_array(arrs, operand, op, root=n - 1)
            t.expect(f"cluster/reduce/{operand.name}/{op_name}",
                     arrs[n - 1], want, exact)
            arrs = [a.copy() for a in alls]
            cluster.reduce_scatter_array(arrs, operand, op)
            for r, (s, e) in enumerate(meta.partition_range(0, length, n)):
                t.expect(f"cluster/reduce_scatter/{operand.name}/{op_name}",
                         arrs[r][s:e], want[s:e], exact)
        root = 1 % n
        arrs = [a.copy() for a in alls]
        cluster.broadcast_array(arrs, operand, root=root)
        for r in range(n):
            t.expect(f"cluster/broadcast/{operand.name}", arrs[r],
                     alls[root], True)
        ranges = meta.partition_range(0, length, n)
        want_cat = np.concatenate(
            [alls[q][s:e] for q, (s, e) in enumerate(ranges)])
        arrs = [a.copy() for a in alls]
        cluster.allgather_array(arrs, operand)
        for r in range(n):
            t.expect(f"cluster/allgather/{operand.name}", arrs[r],
                     want_cat, True)
        arrs = [a.copy() for a in alls]
        cluster.gather_array(arrs, operand, root=0)
        t.expect(f"cluster/gather/{operand.name}", arrs[0], want_cat, True)
        arrs = [a.copy() for a in alls]
        cluster.scatter_array(arrs, operand, root=0)
        for r, (s, e) in enumerate(ranges):
            t.expect(f"cluster/scatter/{operand.name}", arrs[r][s:e],
                     alls[0][s:e], True)
    # sparse map family (values ride the device)
    for op_name in OPS:
        op = Operators.by_name(op_name)
        maps = [{f"k{j}": float(r + j + 1) for j in range(r + 1)}
                for r in range(n)]
        want: dict = {}
        for m in maps:
            for k, v in m.items():
                want[k] = op.np_fn(want[k], v) if k in want else v
        cluster.allreduce_map(maps, Operands.FLOAT, op)
        for m in maps:
            t.expect(f"cluster/allreduce_map/{op_name}",
                     np.array([m.get(k, np.nan) for k in sorted(want)]),
                     np.array([want[k] for k in sorted(want)]), False)
    cluster.barrier()


def check_functional(t: Tally, n: int, length: int = 64, devices=None):
    """The perf path: collectives inside one jitted shard_map program."""
    length = ((length + n - 1) // n) * n  # reduce_scatter/ring need n | L
    mesh = make_mesh(n, devices=devices)
    axis = mesh.axis_names[0]
    alls = [np.random.default_rng(SEED_BASE + r)
            .standard_normal(length).astype(np.float32) for r in range(n)]
    stacked = np.stack(alls)  # [n, L]
    custom = Operator.custom("ABSMAX",
                             lambda a, b: jnp.maximum(jnp.abs(a), jnp.abs(b)),
                             0.0)

    cases = {
        "allreduce_sum": (lambda x: coll.allreduce(x, Operators.SUM, axis),
                          lambda: expected_reduce(alls, "SUM")[None]
                          .repeat(n, 0)),
        "allreduce_max": (lambda x: coll.allreduce(x, Operators.MAX, axis),
                          lambda: expected_reduce(alls, "MAX")[None]
                          .repeat(n, 0)),
        "allreduce_min": (lambda x: coll.allreduce(x, Operators.MIN, axis),
                          lambda: expected_reduce(alls, "MIN")[None]
                          .repeat(n, 0)),
        "allreduce_prod": (lambda x: coll.allreduce(x, Operators.PROD, axis),
                           lambda: expected_reduce(alls, "PROD")[None]
                           .repeat(n, 0)),
        # singleton reduction applies the binary op n-1 = 0 times, so a
        # non-idempotent custom op returns the input unchanged at n=1
        # (same as the socket path's merge loop)
        "allreduce_custom": (lambda x: coll.allreduce(x, custom, axis),
                             lambda: (stacked if n == 1 else
                                      np.abs(stacked).max(0)[None]
                                      .repeat(n, 0))),
        "broadcast": (lambda x: coll.broadcast(x, 0, axis),
                      lambda: stacked[0][None].repeat(n, 0)),
        "reduce_scatter": (
            lambda x: coll.reduce_scatter(x[0], Operators.SUM, axis)[None],
            lambda: expected_reduce(alls, "SUM").reshape(n, -1)),
        "ring_allreduce": (
            lambda x: ring.ring_allreduce(x[0], Operators.SUM, axis)[None],
            lambda: expected_reduce(alls, "SUM")[None].repeat(n, 0)),
    }
    for name, (body, want) in cases.items():
        f = jax.jit(partial(
            jax.shard_map, mesh=mesh, check_vma=False,
            in_specs=P(axis), out_specs=P(axis))(body))
        got = np.asarray(f(stacked)).reshape(n, -1)
        t.expect(f"functional/{name}", got, want().reshape(n, -1), False)
    # allgather replicates: output spec P(None)
    f = jax.jit(partial(
        jax.shard_map, mesh=mesh, check_vma=False,
        in_specs=P(axis), out_specs=P(None, None))(
        lambda x: coll.allgather(x, axis, tiled=True)))
    t.expect("functional/allgather", np.asarray(f(stacked)), stacked, False)
    # sparse allreduce on device
    idx = np.stack([np.array([r, n + r], np.int32) for r in range(n)])
    val = np.stack([np.array([1.0, 2.0], np.float32) for r in range(n)])
    f = jax.jit(partial(
        jax.shard_map, mesh=mesh, check_vma=False,
        in_specs=(P(axis), P(axis)), out_specs=(P(None), P(None)))(
        lambda i, v: sparse_ops.sparse_allreduce(
            i[0], v[0], 2 * n, Operators.SUM, axis)))
    oi, ov = f(idx, val)
    got = {int(i): float(v) for i, v in zip(np.asarray(oi), np.asarray(ov))
           if i != sparse_ops.SENTINEL}
    want = {r: 1.0 for r in range(n)}
    want.update({n + r: 2.0 for r in range(n)})
    t.expect("functional/sparse_allreduce",
             np.array(sorted(got.items())), np.array(sorted(want.items())),
             False)
    # sparse reduce-scatter: each member keeps its block-owned share
    size = 2 * n
    f = jax.jit(partial(
        jax.shard_map, mesh=mesh, check_vma=False,
        in_specs=(P(axis), P(axis)), out_specs=(P(axis), P(axis)))(
        lambda i, v: tuple(
            x[None] for x in sparse_ops.sparse_reduce_scatter(
                i[0], v[0], 2 * n, size, Operators.SUM, axis))))
    oi, ov = f(idx, val)
    oi, ov = np.asarray(oi), np.asarray(ov)
    got_rs = {}
    for r in range(n):
        for i, v in zip(oi[r], ov[r]):
            if i != sparse_ops.SENTINEL:
                t.expect("functional/sparse_reduce_scatter/owner",
                         meta.owner_of(int(i), 0, size, n), r, True)
                got_rs[int(i)] = float(v)
    t.expect("functional/sparse_reduce_scatter",
             np.array(sorted(got_rs.items())),
             np.array(sorted(want.items())), False)
    # sparse allgather: disjoint-union pairs, sorted, duplicates kept
    f = jax.jit(partial(
        jax.shard_map, mesh=mesh, check_vma=False,
        in_specs=(P(axis), P(axis)), out_specs=(P(None), P(None)))(
        lambda i, v: sparse_ops.sparse_allgather(i[0], v[0], axis)))
    oi, ov = map(np.asarray, f(idx, val))
    live = oi != sparse_ops.SENTINEL
    t.expect("functional/sparse_allgather",
             np.array(sorted(zip(oi[live], ov[live]))),
             np.array(sorted((int(i), float(v))
                             for row_i, row_v in zip(idx, val)
                             for i, v in zip(row_i, row_v))), False)


def check_ring_kernels_hw(t: Tally, n: int, devices=None):
    """Execute the Pallas ring RDMA kernels — all three collectives,
    uni AND bidirectional — COMPILED (not interpreted) on the current
    backend. On one chip zero ring steps run, but Mosaic codegen, VMEM
    slot allocation, DMA/REGULAR semaphore allocation and the
    collective_id entry barrier all execute on real hardware
    (``force_kernel=True`` bypasses the n==1 identity fast path);
    with n > 1 chips the same code proves full ring semantics."""
    from ytk_mp4j_tpu.ops import ring_kernel as rk

    mesh = make_mesh(n, devices=devices)
    axis = mesh.axis_names[0]
    c = rk.min_chunk_elems(np.float32)
    L = 2 * c * n
    alls = [np.random.default_rng(SEED_BASE + 77 + r)
            .standard_normal(L).astype(np.float32) for r in range(n)]
    stacked = np.stack(alls)
    want_sum = expected_reduce(alls, "SUM")
    shards = stacked[:, : L // n]        # per-member allgather input

    def smap(body):
        return jax.jit(partial(
            jax.shard_map, mesh=mesh, check_vma=False,
            in_specs=P(axis), out_specs=P(axis))(body))

    for bidir in (False, True):
        tag = "bidir" if bidir else "uni"
        got = np.asarray(smap(
            lambda x, b=bidir: rk.ring_allreduce_kernel(
                x[0], Operators.SUM, axis, bidirectional=b,
                force_kernel=True)[None])(stacked))
        t.expect(f"ring_kernel_hw/allreduce/{tag}", got,
                 want_sum[None].repeat(n, 0), False)
        got = np.asarray(smap(
            lambda x, b=bidir: rk.ring_reduce_scatter_kernel(
                x[0], Operators.SUM, axis, bidirectional=b,
                force_kernel=True)[None])(stacked))
        t.expect(f"ring_kernel_hw/reduce_scatter/{tag}",
                 got.reshape(-1), want_sum, False)
        got = np.asarray(smap(
            lambda x, b=bidir: rk.ring_allgather_kernel(
                x[0], axis, bidirectional=b,
                force_kernel=True)[None])(shards))
        t.expect(f"ring_kernel_hw/allgather/{tag}", got,
                 shards.reshape(-1)[None].repeat(n, 0), False)


def _run_battery(n: int, devices=None) -> dict:
    t = Tally()
    section: dict = {"n_devices_used": n}
    try:
        check_cluster(t, n, devices=devices)
        check_functional(t, n, devices=devices)
        section["error"] = None
    except Exception:
        traceback.print_exc()
        section["error"] = traceback.format_exc(limit=3)
    section["passed"] = t.passed
    section["failures"] = t.failures
    section["ok"] = section["error"] is None and not t.failures
    return section


def _emit(result: dict, out: str | None) -> str:
    line = json.dumps(result)
    if out:
        with open(out, "w") as f:
            f.write(line + "\n")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write JSON artifact here")
    ap.add_argument("--n", type=int, default=None,
                    help="ranks (default: all devices)")
    ap.add_argument("--cpu-mesh-n", type=int, default=8,
                    help="ranks for the CPU-mesh execution section "
                         "(0 disables)")
    args = ap.parse_args(argv)
    enable_compilation_cache()
    # must happen before the first device query initializes backends:
    # the second section executes the SAME battery on an n>=8 CPU mesh
    # so real-HLO truth and multi-member execution semantics sit side
    # by side in one artifact
    if args.cpu_mesh_n:
        jax.config.update("jax_num_cpu_devices", args.cpu_mesh_n)
    devs = jax.devices()
    n = args.n or len(devs)
    result = {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "n_devices_used": n,
    }
    if n == 1:
        result["identity_caveat"] = (
            "every collective over a 1-member axis is an identity; this "
            "section proves the emitted HLO compiles and executes on the "
            "real device, NOT cross-member semantics — see the cpu_mesh "
            "section for executed n>1 semantics")
    result.update(_run_battery(n, devices=devs[:n]))

    if devs[0].platform == "tpu":
        # compiled Pallas ring kernels on the real chip (interpret mode
        # and AOT cover CPU meshes and pod topologies; this is the one
        # place Mosaic codegen + semaphore/DMA allocation EXECUTE on
        # hardware). Remote DMA can hang: leave what has passed so far
        # on disk first.
        result["ring_kernel_hw"] = {"started": True, "ok": False}
        _emit(result, args.out)
        hw = Tally()
        sec: dict = {"n_devices_used": n, "caveat": (
            "n=1 runs ZERO ring steps: this proves Mosaic codegen, "
            "VMEM/semaphore allocation and the collective_id entry "
            "barrier execute on the chip, NOT cross-chip DMA "
            "semantics — those are covered by the interpreted n=8 "
            "mesh and the 8/16/64-chip AOT artifacts" if n == 1
            else None)}
        try:
            check_ring_kernels_hw(hw, n, devices=devs[:n])
            sec["error"] = None
        except Exception:
            traceback.print_exc()
            sec["error"] = traceback.format_exc(limit=3)
        sec["passed"] = hw.passed
        sec["failures"] = hw.failures
        sec["ok"] = sec["error"] is None and not hw.failures
        result["ring_kernel_hw"] = sec
        result["ok"] = result["ok"] and sec["ok"]

    if args.cpu_mesh_n and (devs[0].platform == "cpu"
                            and n >= args.cpu_mesh_n):
        # the main section already executed this battery on a CPU mesh
        # of sufficient width — re-running it would double the runtime
        # for a duplicate result
        result["cpu_mesh"] = {"skipped": True,
                              "reason": "main section ran on cpu"}
    elif args.cpu_mesh_n:
        cpu_devs = jax.devices("cpu")
        section = _run_battery(args.cpu_mesh_n,
                               devices=cpu_devs[: args.cpu_mesh_n])
        section["platform"] = "cpu"
        result["cpu_mesh"] = section

    cm = result.get("cpu_mesh")
    result["ok"] = result["ok"] and (
        cm is None or cm.get("skipped", False) or cm["ok"])
    print(_emit(result, args.out))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
