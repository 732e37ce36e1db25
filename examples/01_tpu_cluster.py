"""Device-path collectives with TpuCommCluster.

Runs on whatever devices are available; to simulate an 8-chip pod on CPU:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python 01_tpu_cluster.py
"""
import numpy as np

from ytk_mp4j_tpu import trace, trace_collectives
from ytk_mp4j_tpu.comm.tpu_comm import TpuCommCluster
from ytk_mp4j_tpu.operands import Operands
from ytk_mp4j_tpu.operators import Operator, Operators

cluster = TpuCommCluster()  # all devices; TpuCommCluster(5) also works
n = cluster.slave_num
print(f"{n} rank(s)")

with trace_collectives():
    # dense allreduce, in place across per-rank buffers
    arrs = [np.full(1 << 16, float(r + 1), np.float32) for r in range(n)]
    cluster.allreduce_array(arrs, Operands.FLOAT, Operators.SUM)
    assert arrs[0][0] == sum(range(1, n + 1))

    # sub-range semantics (the reference's [from, to))
    arrs = [np.arange(10, dtype=np.float32) for _ in range(n)]
    cluster.allreduce_array(arrs, Operands.FLOAT, Operators.SUM,
                            from_=2, to=6)

    # reduce-scatter + allgather over per-rank segments
    arrs = [np.ones(13, np.float32) * (r + 1) for r in range(n)]
    cluster.reduce_scatter_array(arrs, Operands.FLOAT, Operators.SUM)
    cluster.allgather_array(arrs, Operands.FLOAT)

    # sparse Map<K, V> operands (keys on host, values on device)
    maps = [{f"w:{r % 3}": np.ones(4, np.float32) * r} for r in range(n)]
    cluster.allreduce_map(maps, Operands.FLOAT, Operators.SUM)

    # pipelined map allreduce: chain dispatches, resolve later — the
    # deferred handles overlap host encodes with device work, so k
    # chained calls pay ~one round trip instead of k
    step1 = [{r: 1.0} for r in range(n)]
    step2 = [{r + 1: 2.0} for r in range(n)]
    h1 = cluster.allreduce_map_async(step1, Operands.FLOAT,
                                     Operators.SUM)
    h2 = cluster.allreduce_map_async(step2, Operands.FLOAT,
                                     Operators.SUM)
    h1.result(), h2.result()                 # mutates in place, like
    assert len(step1[0]) == n                # the sync call

    # user-defined operator: on the DEVICE path the reduction runs
    # inside jit, so write it with jnp (jnp also works on host numpy
    # inputs; an np-only fn would fail to trace on multi-device meshes)
    import jax.numpy as jnp
    absmax = Operator.custom(
        "ABSMAX",
        lambda x, y: jnp.where(jnp.abs(x) >= jnp.abs(y), x, y), 0.0)
    # (64-bit operands need jax_enable_x64 on the device path)
    arrs = [np.full(8, float(r - 1), np.float32) for r in range(n)]
    cluster.allreduce_array(arrs, Operands.FLOAT, absmax)

print(trace.format_summary())
