"""Hierarchical (inter x intra) device collectives — the TPU analogue of
the reference's process x thread nesting (SURVEY.md section 3d)."""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ytk_mp4j_tpu.comm.tpu_comm import TpuCommCluster
from ytk_mp4j_tpu.operands import Operands
from ytk_mp4j_tpu.operators import Operator, Operators
from ytk_mp4j_tpu.ops import collectives as coll
from ytk_mp4j_tpu.parallel import make_hier_mesh

from helpers import expected_reduce, make_inputs


@pytest.fixture(scope="module")
def hier_cluster():
    return TpuCommCluster(mesh=make_hier_mesh(4, 2))


@pytest.mark.parametrize("op", ["SUM", "PROD", "MAX", "MIN"])
def test_hier_allreduce(hier_cluster, op, rng):
    n = hier_cluster.n
    assert n == 8
    arrs = make_inputs(n, 40, Operands.DOUBLE, rng)
    want = expected_reduce(arrs, op)
    hier_cluster.allreduce_array(arrs, Operands.DOUBLE,
                                 Operators.by_name(op))
    for a in arrs:
        np.testing.assert_allclose(a, want, rtol=1e-9)


@pytest.mark.parametrize("root", [0, 5])
def test_hier_broadcast(hier_cluster, root, rng):
    arrs = make_inputs(8, 17, Operands.FLOAT, rng)
    src = arrs[root].copy()
    hier_cluster.broadcast_array(arrs, Operands.FLOAT, root=root)
    for a in arrs:
        np.testing.assert_array_equal(a, src)


def test_hier_reduce_scatter(hier_cluster, rng):
    from ytk_mp4j_tpu import meta
    L = 27
    arrs = make_inputs(8, L, Operands.DOUBLE, rng)
    want = expected_reduce(arrs, "SUM")
    ranges = meta.partition_range(0, L, 8)
    hier_cluster.reduce_scatter_array(arrs, Operands.DOUBLE, Operators.SUM)
    for r, (s, e) in enumerate(ranges):
        np.testing.assert_allclose(arrs[r][s:e], want[s:e], rtol=1e-9)


def test_hier_allgather(hier_cluster, rng):
    from ytk_mp4j_tpu import meta
    L = 19
    ranges = meta.partition_range(0, L, 8)
    arrs = make_inputs(8, L, Operands.LONG, rng)
    want = np.zeros(L, dtype=np.int64)
    for r, (s, e) in enumerate(ranges):
        want[s:e] = arrs[r][s:e]
    hier_cluster.allgather_array(arrs, Operands.LONG)
    for a in arrs:
        np.testing.assert_array_equal(a, want)


def test_hier_maps(hier_cluster, rng):
    maps = [{f"k{r % 3}": float(r)} for r in range(8)]
    want = {}
    for m in maps:
        for k, v in m.items():
            want[k] = want.get(k, 0.0) + v
    hier_cluster.allreduce_map(maps, Operands.DOUBLE, Operators.SUM)
    for m in maps:
        assert set(m) == set(want)
        for k in want:
            np.testing.assert_allclose(m[k], want[k])


def test_functional_two_level_inside_jit(rng):
    """Per-level reductions composed in user jit: intra-mean then
    inter-max — the kind of staged hierarchy users write directly."""
    mesh = make_hier_mesh(2, 4)
    x = np.arange(8, dtype=np.float64).reshape(8, 1)

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=P(("inter", "intra")),
             out_specs=P(("inter", "intra")))
    def f(v):
        intra_sum = coll.allreduce(v, Operators.SUM, "intra")
        return coll.allreduce(intra_sum, Operators.MAX, "inter")

    out = np.asarray(f(x))
    # intra groups: [0..3] sum=6, [4..7] sum=22; inter max = 22
    np.testing.assert_allclose(out, np.full((8, 1), 22.0))


def test_flat_index_layout():
    """flat_index must match the blocked global-rank layout."""
    mesh = make_hier_mesh(4, 2)

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=P(("inter", "intra")),
             out_specs=P(("inter", "intra")))
    def f(v):
        return v + coll.flat_index(("inter", "intra"))[None, None]

    out = np.asarray(f(np.zeros((8, 1))))
    np.testing.assert_array_equal(out[:, 0], np.arange(8))
