"""The transport schedules, side by side.

The same allreduce runs as (1) one fused XLA collective, (2) a
hand-scheduled ppermute ring, (3) the Pallas RDMA ring kernel that
owns the transport itself (remote DMA + entry barrier + credit
backpressure; interpreted off-TPU), and (4) the bidirectional RDMA
variant that rings the buffer's halves in opposite directions so both
full-duplex ICI link directions carry payload — selectable per call on
the driver API and composable inside your own jitted shard_map code.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python 08_ring_transports.py
"""
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ytk_mp4j_tpu.comm.tpu_comm import TpuCommCluster
from ytk_mp4j_tpu.operands import Operands
from ytk_mp4j_tpu.operators import Operators
from ytk_mp4j_tpu.ops import collectives as coll
from ytk_mp4j_tpu.ops import ring, ring_kernel
from ytk_mp4j_tpu.parallel import make_mesh

cluster = TpuCommCluster()
n = cluster.slave_num
print(f"{n} rank(s)")

# -- driver API: same call, three schedules, identical results --------
# analytic ground truth, not a self-comparison: sum_r (r+1) * iota
want = np.arange(1000, dtype=np.float32) * (n * (n + 1) / 2)
for algo in ("xla", "ring", "rdma"):
    arrs = [np.arange(1000, dtype=np.float32) * (r + 1) for r in range(n)]
    cluster.allreduce_array(arrs, Operands.FLOAT, Operators.SUM, algo=algo)
    assert np.allclose(arrs[0], want, rtol=1e-5)
    print(f"algo={algo:4s}: ok (first elems {arrs[0][:3]})")

# -- functional layer: the same schedules inside YOUR jit -------------
mesh = make_mesh(n)
on_tpu = mesh.devices.flat[0].platform == "tpu"
print("rdma kernels:", "compiled (Mosaic)" if on_tpu
      else f"interpreted ({mesh.devices.flat[0].platform} mesh)")
data = np.tile(np.arange(n, dtype=np.float32)[:, None], (1, 16 * n))


@partial(jax.shard_map, mesh=mesh, in_specs=P("mp4j"),
         out_specs=(P("mp4j"),) * 4, check_vma=False)
def four_ways(x):
    v = x[0]
    a = coll.allreduce(v, Operators.SUM, "mp4j")
    b = ring.ring_allreduce(v, Operators.SUM, "mp4j")
    c = ring_kernel.ring_allreduce_kernel(v, Operators.SUM, "mp4j",
                                          interpret=not on_tpu)
    # both full-duplex ICI link directions busy at once
    d = ring_kernel.ring_allreduce_kernel(v, Operators.SUM, "mp4j",
                                          interpret=not on_tpu,
                                          bidirectional=True)
    return a[None], b[None], c[None], d[None]


a, b, c, d = jax.jit(four_ways)(data)
want = data.sum(0)
for name, out in (("psum", a), ("ppermute ring", b),
                  ("rdma kernel", c), ("rdma bidirectional", d)):
    assert np.allclose(np.asarray(out)[0], want, rtol=1e-5)
    print(f"in-jit {name}: ok")
print("all transports agree")
