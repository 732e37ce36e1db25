"""AOT multi-chip compile proof against a real TPU topology.

``dryrun_multichip`` (driver entry) proves SEMANTICS on a virtual CPU
mesh; this program proves the other half of the north-star claim
(SURVEY.md section 6: ">=10x ... on a TPU pod"): that XLA + Mosaic will
actually COMPILE every multi-chip program — the GBDT train step (with
the Pallas histogram kernel), the FFM sparse-gradient step, every dense
collective x operator, the sparse allreduce, the ppermute ring, and the
Pallas RDMA ring kernel — for a real multi-chip TPU topology, using the
JAX AOT topology API (``jax.experimental.topologies.get_topology_desc``
+ ``jit(...).lower(...).compile()``), no chips required.

    python -m ytk_mp4j_tpu.check.checkaot [--topology v5e:2x4] [--out f]

Exit code 0 iff every program compiles; the artifact records per-program
status plus compiler cost analysis (flops / bytes accessed) where
available.
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
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ytk_mp4j_tpu.operators import Operator, Operators
from ytk_mp4j_tpu.ops import collectives as coll
from ytk_mp4j_tpu.ops import ring
from ytk_mp4j_tpu.ops import ring_kernel
from ytk_mp4j_tpu.ops import sparse as sparse_ops
from ytk_mp4j_tpu.utils.compile_cache import enable_compilation_cache

AXIS = "mp4j"


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _compile(name: str, results: dict, jitted, *avals) -> None:
    """Lower + compile one program for the topology; record the outcome
    and the compiler's own cost analysis (proof the executable exists)."""
    try:
        compiled = jitted.lower(*avals).compile()
        cost = {}
        try:
            ca = compiled.cost_analysis() or {}
            cost = {k: ca[k] for k in ("flops", "bytes accessed")
                    if k in ca}
        except Exception:
            pass
        results[name] = {"ok": True, "cost": cost}
        print(f"ok   {name} {cost}")
    except Exception as e:
        results[name] = {"ok": False,
                         "error": traceback.format_exc(limit=3)}
        print(f"FAIL {name}: {str(e)[:300]}", file=sys.stderr)


def _hier_mesh(devices, n: int) -> Mesh:
    """The one inter x intra topology every hier program compiles for
    (n//2 x 2, row-major ranks) — shared so the hier_rs evidence and
    the gbdt hier train step measure the same topology."""
    return Mesh(np.asarray(devices[:n]).reshape(n // 2, 2),
                ("inter", "intra"))


def _shard_mapped(mesh, body, in_specs, out_specs):
    return jax.jit(partial(
        jax.shard_map, mesh=mesh, check_vma=False,
        in_specs=in_specs, out_specs=out_specs)(body))


def check_collectives(results: dict, mesh: Mesh, n: int, L: int = 4096):
    """Every dense collective x operator in one program per operator
    family, plus the rooted/topology-shaped ones."""
    custom = Operator.custom(
        "ABSMAX", lambda a, b: jnp.maximum(jnp.abs(a), jnp.abs(b)), 0.0)

    for op in (Operators.SUM, Operators.MAX, Operators.MIN,
               Operators.PROD, custom):
        def body(x, _op=op):
            v = x[0]                                   # per-shard [L]
            ar = coll.allreduce(v, _op, AXIS)
            rs = coll.reduce_scatter(v, _op, AXIS)
            rd = coll.reduce(v, _op, root=0, axis_name=AXIS)
            return ar[None], rs[None], rd[None]
        _compile(f"collectives/{op.name}", results,
                 _shard_mapped(mesh, body, P(AXIS), (P(AXIS),) * 3),
                 _f32(n, L))

    def rooted(x):
        v = x[0]
        bc = coll.broadcast(v, 0, AXIS)
        ag = coll.allgather(v, AXIS)
        ga = coll.gather(v, 0, AXIS)
        sc = coll.scatter(v, 0, AXIS)
        tok = coll.barrier(AXIS)
        return bc[None], ag, ga, sc[None], tok[None]
    _compile("collectives/rooted", results,
             _shard_mapped(mesh, rooted, P(AXIS),
                           (P(AXIS), P(None), P(None), P(AXIS), P(AXIS))),
             _f32(n, L))
    # the exchange by owner: member i's j-th slice to member j
    _compile("collectives/all_to_all", results,
             _shard_mapped(mesh,
                           lambda x: coll.all_to_all(x[0], AXIS)[None],
                           P(AXIS), P(AXIS)),
             _f32(n, n, L))


def check_rings(results: dict, mesh: Mesh, n: int, L: int | None = None):
    """The hand-scheduled ppermute ring and the Pallas RDMA kernels
    (compiled path: entry barrier + credit backpressure included).
    ``L`` scales with the topology: the reduce-scatter kernel splits a
    shard into n chunks and each chunk must be a full Mosaic tile
    (min_chunk_elems) — a fixed 8192 under-fills at n = 16."""
    if L is None:
        L = max(8192, n * ring_kernel.min_chunk_elems(jnp.float32))
    _compile("ring/ppermute_allreduce", results,
             _shard_mapped(
                 mesh, lambda x: ring.ring_allreduce(
                     x[0], Operators.SUM, AXIS)[None],
                 P(AXIS), P(AXIS)),
             _f32(n, L))
    for op in (Operators.SUM, Operators.MAX):
        _compile(f"ring/rdma_allreduce_{op.name}", results,
                 _shard_mapped(
                     mesh, lambda x, _op=op:
                     ring_kernel.ring_allreduce_kernel(
                         x[0], _op, AXIS)[None],
                     P(AXIS), P(AXIS)),
                 _f32(n, L))
    _compile("ring/rdma_allreduce_bidir", results,
             _shard_mapped(
                 mesh, lambda x: ring_kernel.ring_allreduce_kernel(
                     x[0], Operators.SUM, AXIS, bidirectional=True)[None],
                 P(AXIS), P(AXIS)),
             _f32(n, L))
    # unpadded length: exercises the internal identity padding
    _compile("ring/rdma_allreduce_unaligned", results,
             _shard_mapped(
                 mesh, lambda x: ring_kernel.ring_allreduce_kernel(
                     x[0], Operators.SUM, AXIS)[None],
                 P(AXIS), P(AXIS)),
             _f32(n, L + 7))
    for bidir in (False, True):
        tag = "_bidir" if bidir else ""
        _compile(f"ring/rdma_reduce_scatter{tag}", results,
                 _shard_mapped(
                     mesh, lambda x, b=bidir:
                     ring_kernel.ring_reduce_scatter_kernel(
                         x[0], Operators.SUM, AXIS,
                         bidirectional=b)[None],
                     P(AXIS), P(AXIS)),
                 _f32(n, L))
        _compile(f"ring/rdma_allgather{tag}", results,
                 _shard_mapped(
                     mesh, lambda x, b=bidir:
                     ring_kernel.ring_allgather_kernel(
                         x[0], AXIS, bidirectional=b)[None],
                     P(AXIS), P(AXIS)),
                 _f32(n, L))


def _rooted_reduce_rs_collect(v, n: int, root: int = 0):
    """Hand-built rooted reduce: psum_scatter, then n-1 ppermutes each
    delivering one reduced block to root (the many-to-one collect the
    coll.reduce docstring prices at (n-1)/n concentrated on root's
    links). Only root's output is meaningful."""
    block = lax.psum_scatter(v, AXIS, scatter_dimension=0, tiled=True)
    B = v.shape[0] // n
    out = jnp.zeros_like(v)
    out = lax.dynamic_update_slice_in_dim(
        out, block, coll.flat_index(AXIS) * B, 0)
    for i in range(1, n):
        src = (root + i) % n
        recv = lax.ppermute(block, AXIS, [(src, root)])
        out = lax.dynamic_update_slice_in_dim(out, recv, src * B, 0)
    return out


def _rooted_reduce_binomial(v, n: int):
    """Hand-built rooted reduce: binomial combining tree to rank 0 —
    log2(n) ppermute rounds each moving the FULL buffer (|x| * log n
    wire, the docstring's strictly-worse case for n >= 4)."""
    acc = v
    k = 1
    while k < n:
        pairs = [(r, r - k) for r in range(k, n, 2 * k)]
        recv = lax.ppermute(acc, AXIS, pairs)  # non-addressed get zeros
        acc = acc + recv
        k *= 2
    return acc


def _rooted_gather_sequential(v, n: int, root: int = 0):
    """Hand-built rooted gather: n-1 ppermutes each delivering one
    member's buffer to root (many-to-one serialization)."""
    out = jnp.zeros((n,) + v.shape, v.dtype)
    out = lax.dynamic_update_slice(
        out, v[None], (coll.flat_index(AXIS),) + (0,) * v.ndim)
    for i in range(1, n):
        src = (root + i) % n
        recv = lax.ppermute(v, AXIS, [(src, root)])
        out = lax.dynamic_update_slice(
            out, recv[None], (src,) + (0,) * v.ndim)
    return out


def _rooted_scatter_sequential(x, n: int, root: int = 0):
    """Hand-built rooted scatter: root sends block i to rank i, one
    ppermute per destination ((n-1) * B wire vs the broadcast+slice
    lowering's full-buffer psum)."""
    B = x.shape[0] // n
    idx = coll.flat_index(AXIS)
    own = lax.dynamic_slice_in_dim(x, idx * B, B, axis=0)
    out = jnp.where(idx == root, own, jnp.zeros_like(own))
    for i in range(1, n):
        dst = (root + i) % n
        blk = lax.dynamic_slice_in_dim(x, dst * B, B, axis=0)
        recv = lax.ppermute(blk, AXIS, [(root, dst)])
        out = jnp.where(idx == dst, recv, out)
    return out


def check_rooted_lowerings(results: dict, mesh: Mesh, n: int,
                           L: int = 1 << 20):
    """Turn the rooted-collective docstring
    arithmetic (ops/collectives.py reduce/gather/scatter) into compiler
    artifacts — the current allreduce/allgather/broadcast lowerings
    side by side with faithful hand-built rooted variants, so the cost
    analysis is on record next to the prose."""
    progs = {
        "rooted/reduce_current_allreduce":
            lambda x: coll.reduce(x[0], Operators.SUM, 0, AXIS)[None],
        "rooted/reduce_rs_collect":
            lambda x: _rooted_reduce_rs_collect(x[0], n)[None],
        "rooted/reduce_binomial":
            lambda x: _rooted_reduce_binomial(x[0], n)[None],
        "rooted/gather_current_allgather":
            lambda x: coll.gather(x[0], 0, AXIS)[None],
        "rooted/gather_sequential":
            lambda x: _rooted_gather_sequential(x[0], n)[None],
        "rooted/scatter_current_bcast_slice":
            lambda x: coll.scatter(x[0], 0, AXIS)[None],
        "rooted/scatter_sequential":
            lambda x: _rooted_scatter_sequential(x[0], n)[None],
    }
    for name, body in progs.items():
        _compile(name, results,
                 _shard_mapped(mesh, body, P(AXIS), P(AXIS)), _f32(n, L))


def check_hier_reduce_scatter(results: dict, devices, n: int,
                              L: int = 1 << 20):
    """Round-3 measured decision: tuple-axis reduce_scatter stays
    allreduce+slice because XLA's tuple psum is already hierarchical.
    These three programs keep the evidence on record:
    the current lowering vs the two hand-staged psum_scatter cascades
    (outer-first needs no permute; inner-first shrinks the buffer
    before the DCN stage but pays a block permutation)."""
    if n % 2:
        return
    mesh = _hier_mesh(devices, n)
    axes = ("inter", "intra")

    def current(x):
        return coll.reduce_scatter(x[0], Operators.SUM, axes)[None]

    def outer_first(x):
        out = lax.psum_scatter(x[0], "inter", scatter_dimension=0,
                               tiled=True)
        return lax.psum_scatter(out, "intra", scatter_dimension=0,
                                tiled=True)[None]

    def inner_first(x):
        v = x[0]
        grid = v.reshape(n // 2, 2, -1)
        out = grid.transpose(1, 0, 2).reshape(-1)
        out = lax.psum_scatter(out, "intra", scatter_dimension=0,
                               tiled=True)
        return lax.psum_scatter(out, "inter", scatter_dimension=0,
                                tiled=True)[None]

    for name, body in (("hier_rs/current_allreduce_slice", current),
                       ("hier_rs/staged_outer_first", outer_first),
                       ("hier_rs/staged_inner_first_permuted", inner_first)):
        _compile(name, results,
                 _shard_mapped(mesh, body, P(axes), P(axes)), _f32(n, L))


def check_sparse(results: dict, mesh: Mesh, n: int, cap: int = 1024):
    def body(i, v):
        return sparse_ops.sparse_allreduce(
            i[0], v[0], cap * n, Operators.SUM, AXIS)
    _compile("sparse/allreduce", results,
             _shard_mapped(mesh, body, (P(AXIS), P(AXIS)),
                           (P(None), P(None))),
             _i32(n, cap), _f32(n, cap))

    def body_rs(i, v):
        oi, ov = sparse_ops.sparse_reduce_scatter(
            i[0], v[0], cap * n, cap * n, Operators.SUM, AXIS)
        return oi[None], ov[None]
    _compile("sparse/reduce_scatter", results,
             _shard_mapped(mesh, body_rs, (P(AXIS), P(AXIS)),
                           (P(AXIS), P(AXIS))),
             _i32(n, cap), _f32(n, cap))

    def body_ag(i, v):
        return sparse_ops.sparse_allgather(i[0], v[0], AXIS)
    _compile("sparse/allgather", results,
             _shard_mapped(mesh, body_ag, (P(AXIS), P(AXIS)),
                           (P(None), P(None))),
             _i32(n, cap), _f32(n, cap))


def check_gbdt(results: dict, devices, n: int, per: int = 8192):
    """The flagship consumer's full train step (Pallas histogram kernel
    + psum allreduce + routing + leaf update) at the bench shape, on a
    flat mesh and on the hierarchical inter x intra mesh."""
    from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer

    kd = jax.eval_shape(lambda: jax.random.key_data(jax.random.key(0)))
    meshes = {"flat": Mesh(np.asarray(devices[:n]), (AXIS,))}
    if n % 2 == 0:
        meshes["hier"] = _hier_mesh(devices, n)
    cfgs = {
        "": GBDTConfig(n_features=28, n_bins=256, depth=6),
        # the data-handling graph: learned missing direction +
        # categorical equality splits
        "_missing_cat": GBDTConfig(n_features=28, n_bins=256, depth=6,
                                   missing_bin=True,
                                   categorical_features=(3, 17)),
        # the multiclass consumer: one tree per class per round
        "_softmax": GBDTConfig(n_features=28, n_bins=256, depth=6,
                               loss="softmax", n_classes=3),
    }
    for label, mesh in meshes.items():
        for suffix, cfg in cfgs.items():
            if suffix and label != "flat":
                continue            # one topology proof is enough
            tr = GBDTTrainer(cfg, mesh=mesh)
            if cfg.loss == "softmax":
                y_aval = _i32(n, per)                      # class ids
                preds_aval = _f32(n, per, cfg.n_classes)   # margins
            else:
                y_aval = _f32(n, per)
                preds_aval = _f32(n, per)
            _compile(f"gbdt/train_step_{label}{suffix}", results,
                     tr._build_step(),
                     _i32(n, per, cfg.n_features), y_aval,
                     preds_aval, _f32(n, per),
                     jax.ShapeDtypeStruct(kd.shape, kd.dtype))


def check_ffm(results: dict, devices, n: int, per: int = 1024):
    """The FFM sparse-gradient step (BASELINE.json configs[4] shape):
    score + grads + device-native sparse allreduce + update."""
    from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer

    cfg = FMConfig(model="ffm", n_features=100_000, n_fields=8, k=8,
                   max_nnz=8, learning_rate=0.05)
    mesh = Mesh(np.asarray(devices[:n]), (AXIS,))
    tr = FMTrainer(cfg, mesh=mesh, sparse_grads=True)
    batch_avals = (_i32(n, per, cfg.max_nnz), _i32(n, per, cfg.max_nnz),
                   _f32(n, per, cfg.max_nnz), _f32(n, per, cfg.max_nnz),
                   _f32(n, per), _f32(n, per))
    # the replicated sparse step takes (w0, T): the table by feature, in
    # blocks that hold the linear weights too
    _compile("ffm/sparse_train_step", results,
             tr._build_step(per * cfg.max_nnz),
             tr._state_avals(), *batch_avals)
    # the table sharded by feature over the mesh: the distinct features'
    # blocks fetched from and returned to their owners over all_to_all,
    # the owner gathering and scatter-adding what it owns and a chunk
    # touches; its state is a member's share of the blocks
    trs = FMTrainer(cfg, mesh=mesh, sparse_grads=True,
                    table_sharding="sharded")
    _compile("ffm/sparse_train_step_sharded", results,
             trs._build_step(per * cfg.max_nnz),
             trs._state_avals(), *batch_avals)
    # the sharded SERVE program reads the public params, a row a slot pair
    sharded_avals = (
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((cfg.n_features,), jnp.float32),
        _f32(trs.n_rows_padded, cfg.k))
    # round-5: fit_stream's double-buffered dispatch compiles THIS SAME
    # program (the stream stages chunks into identical padded shapes),
    # so the sharded+stream composition is covered by the row above;
    # the sharded SERVE program (owner-routed row fetch, no full-table
    # replica anywhere) is the remaining sharded surface
    _compile("ffm/sharded_serve", results, trs._build_sharded_predict(),
             sharded_avals, *batch_avals[:4])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--topology", default="v5e:2x4",
                    help="TPU topology name (PJRT C-API spelling)")
    ap.add_argument("--out", default=None, help="write JSON artifact here")
    args = ap.parse_args(argv)
    enable_compilation_cache()

    from jax.experimental import topologies
    topo = topologies.get_topology_desc(topology_name=args.topology,
                                        platform="tpu")
    devices = topo.devices
    n = len(devices)
    mesh = Mesh(np.asarray(devices), (AXIS,))
    print(f"topology {args.topology}: {n} x {devices[0].device_kind}")

    results: dict = {}
    check_collectives(results, mesh, n)
    check_rooted_lowerings(results, mesh, n)
    check_hier_reduce_scatter(results, devices, n)
    check_rings(results, mesh, n)
    check_sparse(results, mesh, n)
    check_gbdt(results, devices, n)
    check_ffm(results, devices, n)

    ok = all(r["ok"] for r in results.values())
    artifact = {
        "topology": args.topology,
        "n_devices": n,
        "device_kind": devices[0].device_kind,
        "programs": results,
        "ok": ok,
    }
    line = json.dumps(artifact)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
