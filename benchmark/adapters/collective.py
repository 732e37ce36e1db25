"""Drives ``ops.collectives.allreduce`` inside a jitted ``shard_map`` over
``parallel.mesh.make_mesh()``: the path PARITY.md names as the performance
path. Operands are resident on the devices; every timed program is closed
by ``jax.block_until_ready``.

Two phases, each half the window:

- *hist*: one program performs a tree's worth of histogram allreduces
  (``arith.hist_message_bytes``: the root, then the left children of every
  level), each waiting for the one before through an
  ``optimization_barrier`` as a tree's levels wait for each other, and
  repeats that ``hist_repeats`` times in a ``fori_loop``.
- *bulk*: one program chains ``bulk_repeats`` allreduces of one large
  message.

After every allreduce the sum is divided by the number of ranks, so values
stay finite however long the chain and XLA cannot fold two allreduces into
one. The operands are ``traffic.small_ints``: with a power-of-two number of
ranks every result is exact, and the outputs of the timed programs
themselves are checked against ``reference/collective.py``.
"""

from __future__ import annotations

import statistics
import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from benchmark import arith, traffic as traffic_gen
from benchmark.reference import collective as reference
from ytk_mp4j_tpu.operators import Operators
from ytk_mp4j_tpu.ops import collectives
from ytk_mp4j_tpu.parallel.mesh import make_mesh

BULK_SAMPLE = 65_536    # elements of the bulk result compared on the host


def hist_shapes(config) -> list[tuple]:
    """Per-rank shape of each level's message: (nodes, F, B, 2) f32."""
    return [(n, config["hist_features"], config["hist_bins"], 2)
            for n in arith.hist_level_nodes(config["hist_depth"])]


def build_programs(mesh, config, traffic):
    """(make_operands, hist_program, bulk_program), jitted for ``mesh``.
    Arrays carry a leading rank axis sharded over the mesh; each rank
    makes its own operands on its own device, from a seed that is an
    argument: every seed runs the same three programs."""
    axis = mesh.axis_names[0]
    inv_n = 1.0 / mesh.size
    shapes = hist_shapes(config)
    spec = P(axis)
    op = Operators.by_name(config["operator"])

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(),),
             out_specs=((spec,) * len(shapes), spec))
    def make_operands(seed):
        rank = lax.axis_index(axis)

        def one(shape, salt):
            idx = lax.iota(jnp.uint32, int(np.prod(shape)))
            return traffic_gen.small_ints(
                jnp, idx, rank, seed + salt).reshape((1,) + tuple(shape))
        return (tuple(one(s, i) for i, s in enumerate(shapes)),
                one((config["bulk_elements"],), len(shapes)))

    def reduce_mean(x):
        # psum's result is typed as equal on every rank; the loop carries
        # a per-rank operand, so cast it back
        return lax.pcast(collectives.allreduce(x, op, axis) * inv_n, axis,
                         to="varying")

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,), out_specs=spec)
    def hist_tree_allreduces(bufs):
        def tree(_, bufs):
            out, prev = [], None
            for b in bufs:
                if prev is not None:    # level d waits for level d - 1
                    b, prev = lax.optimization_barrier((b, prev))
                prev = reduce_mean(b)
                out.append(prev)
            return tuple(out)
        return lax.fori_loop(0, traffic["hist_repeats"], tree, bufs)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,), out_specs=spec)
    def bulk_allreduce(x):
        return lax.fori_loop(0, traffic["bulk_repeats"],
                             lambda _, v: reduce_mean(v), x)

    return (jax.jit(make_operands), jax.jit(hist_tree_allreduces),
            jax.jit(bulk_allreduce))


class Adapter:
    def __init__(self, config, traffic, seed, devices, spans):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.spans = devices, spans
        self.last = {}      # phase -> output of its last timed program

    def setup(self):
        self.mesh = make_mesh(len(self.devices), devices=self.devices)
        make, hist, bulk = build_programs(self.mesh, self.config,
                                          self.traffic)
        with self.spans.span("collective.make_operands"):
            hist_in, bulk_in = jax.block_until_ready(
                make(jnp.uint32(self.seed % 2 ** 32)))
        self.programs = {"hist": (hist, hist_in), "bulk": (bulk, bulk_in)}

    def _run(self, phase) -> float:
        fn, operand = self.programs[phase]
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(operand))
        dt = time.perf_counter() - t0
        self.last[phase] = out
        return dt

    def warmup(self):
        with self.spans.span("collective.warmup"):
            for phase in ("hist", "bulk"):
                self._run(phase)
                self._run(phase)

    def _phases(self, keep_going) -> dict:
        times = {"hist": [], "bulk": []}
        for phase in times:
            with self.spans.span(f"collective.{phase}"):
                t0 = time.perf_counter()
                while keep_going(len(times[phase]),
                                 time.perf_counter() - t0):
                    times[phase].append(self._run(phase))
        c, t = self.config, self.traffic
        n = self.mesh.size
        hist_s = statistics.median(times["hist"]) / t["hist_repeats"]
        bulk_s = statistics.median(times["bulk"]) / t["bulk_repeats"]
        busbw = arith.busbw_bytes_per_s(n, c["bulk_elements"] * 4, bulk_s)
        programs = len(times["hist"]) + len(times["bulk"])
        return {"attempted": programs, "failed": 0,
                "metrics": {"hist_allreduce_us": hist_s * 1e6,
                            "allreduce_busbw_gbps": busbw / 1e9},
                "counters": {"hist_programs": len(times["hist"]),
                             "bulk_programs": len(times["bulk"]),
                             "hist_trees": len(times["hist"]) * t["hist_repeats"],
                             "bulk_allreduces": len(times["bulk"]) * t["bulk_repeats"]},
                "log": {"hist_program_ms": _quartiles(times["hist"]),
                        "bulk_program_ms": _quartiles(times["bulk"])}}

    def window(self, seconds: float) -> dict:
        return self._phases(lambda done, elapsed: elapsed < seconds / 2)

    def slice(self) -> dict:
        limit = self.traffic["trace_programs"]
        return self._phases(lambda done, elapsed: done < limit)

    def check(self):
        """The outputs of the last timed programs against the numpy mean
        of the ranks' operands: every histogram message whole, the bulk
        message on an evenly spaced sample; exactly."""
        n = self.mesh.size
        shapes = hist_shapes(self.config)
        detail, ok = {}, True
        for i, (shape, got) in enumerate(zip(shapes, self.last["hist"])):
            want = reference.mean_of_ranks(
                np.arange(int(np.prod(shape))), n, self.seed + i)
            same = all(np.array_equal(np.asarray(got[r]).reshape(-1), want)
                       for r in range(n))
            detail[f"hist_level_{i}"] = bool(same)
            ok &= same
        bulk_len = self.config["bulk_elements"]
        idx = np.arange(0, bulk_len, max(1, bulk_len // BULK_SAMPLE))
        sample = np.asarray(jax.jit(lambda x: x[:, idx])(self.last["bulk"]))
        want = reference.mean_of_ranks(idx, n, self.seed + len(shapes))
        same = all(np.array_equal(sample[r], want) for r in range(n))
        detail["bulk_sampled"] = bool(same)
        detail["bulk_sample_size"] = int(idx.size)
        detail["nonzero"] = bool(want.any())
        detail["compared"] = {
            "hist_messages_off": [
                sum(not detail[f"hist_level_{i}"]
                    for i in range(len(shapes))), 0],
            "bulk_sample_same": [int(same), 1],
            "bulk_sample_nonzero": [int(detail["nonzero"]), 1]}
        return bool(ok and same and want.any()), detail


def _quartiles(secs) -> list:
    q = np.quantile(np.asarray(secs) * 1e3, [0.25, 0.5, 0.75])
    return [float(v) for v in q] + [len(secs)]
