"""The compare-count of quantile binning as an upper-bound search —
``bin(x) = #edges <= x`` for every cell of a float table, found in
``ceil(log2(E + 1))`` probes of the column's sorted edges where a chain
of compares pays E.

The search. A column's edges are sorted (NaN last: an edge of NaN is
below nothing, and it pads the list to ``2**steps - 1``) and laid out
in level order (``search_table``): node 1 the median, nodes 2 and 3 the
quartiles, node i's children 2i and 2i + 1. A cell starts at node 1 and
goes to ``2i + (x >= node i)``; after ``steps`` levels it stands at
``2**steps + count``. Every compare of a NaN cell is false, so NaN
counts 0. The same walk runs in plain ``jnp`` off the TPU
(``upper_bound`` with a probe that indexes the table), where a gather
costs what any other operation does.

The kernel (``pallas_bin_counts``, ``mp4j_bin``). XLA's gather goes to
the serial unit (5.6 ns a descriptor: 51 s for 1.146e9 cells x 8); a
Mosaic kernel has the other one: ``jnp.take_along_axis`` on two (8,
128) 32-bit operands lowers to ``tpu.dynamic_gather``, one ``vperm``
inside a vector register. With eight columns on a register's sublanes,
128 rows along its lanes and a column's nodes along the lanes of the
edges' register, a level is one such gather, a compare, a select and two
adds. Levels 0-6 (127 nodes) share register 0 of a column's table, a
level of 128 nodes or more takes ``2**(k - 7)`` registers, gathered
from one after another and selected between. The top ``_SELECT_LEVELS``
levels are not gathered: their few nodes are held along all lanes, a
block long, and a cell's node is selected by its compares so far (a
gather keeps a cross-lane unit 18 cycles, and the units bound the
kernel). On the chip (PR 48): 1,183,747 x 968 cells by 254 edges in 31.3
ms, 27 ps a cell, where XLA's chain of 254 compares took 659.8.

The table is taken as it rests (``hist_kernel._rests_tiled``): a [N, F]
f32 table whose width is a multiple of 8 rests as [F, N] in (8, 128)
tiles and is read in blocks of that (eight columns, ``_BLOCK_ROWS``
rows: 32 registers, ``bin_blocks``); another width rests
as F rows of N lanes ([F, 1, N], (1, 128) tiles), where 1,024
consecutive rows of one column fill a register, its (8, 128) view costs
nothing and the column's nodes are broadcast along the sublanes. Either
way the operand is a bitcast of the table and the bins are written in
the same layout, which is a binned table's own. Nothing is padded or
masked: a ragged last block computes on whatever rests past the end
(any float indexes inside its register) and Pallas writes back only what
is inside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ytk_mp4j_tpu.ops.hist_kernel import _rests_tiled

# Rows of a block of the kernel's grid, which is eight columns wide: 32
# registers of cells, searched one after another (statically unrolled,
# so that the scheduler has a block's independent walks to interleave).
# A piece of 32,768 x 968 at 254 edges, ms (my chip runs, PR 48; the top
# four levels by selects), columns x rows:
#            1,024   2,048   4,096   8,192
#        8   2.199   1.333   0.987   2.092
#       16   1.409   1.020   2.517   3.583
#       32   1.425   2.862   3.878   4.369
#       64   3.652   4.248   4.680
# 32 registers a block whatever its shape; more, and the unrolled walks
# outgrow what the core keeps of a program (the whole table, 1,183,747
# rows: 31.4 ms at 8 x 4,096 and 135.3 at 32 x 4,096).
_BLOCK_ROWS = 4096
# out[s, l] = register[s, lane[s, l]]: the form Mosaic lowers to
# ``tpu.dynamic_gather`` along the lanes
_ALONG_LANES = lax.GatherDimensionNumbers(
    offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
    operand_batching_dims=(0,), start_indices_batching_dims=(0,))
# Levels from the root whose node is built by selects between the
# level's nodes, each held along all lanes, and not gathered: a gather
# keeps one of the three cross-lane units for about 18 cycles, a select
# is a quarter of a bundle, and level k costs 2**k - 1 of them. The same
# piece at 8 x 4,096, 3 / 4 / 5 / 6 levels: 1.451 / 0.984 / 0.971 /
# 1.248 ms; the whole table 48.7 / 31.3 / 30.9 ms at 3 / 4 / 5, and 54.5
# with none (my chip runs, PR 48). Four hold fifteen registers of nodes
# a block, five would hold 31 of the core's 64 for 1%.
_SELECT_LEVELS = 4


def search_steps(n_edges: int) -> int:
    """Levels of the search over ``n_edges`` edges: the count has
    ``n_edges + 1`` values."""
    return max(1, int(n_edges).bit_length())


def search_table(edges):
    """[F, E] edges, in any order -> [F, T] f32, a column's sorted edges
    in level order: lane i holds node i (lane 0 nothing), T a whole
    number of 128-lane registers. Sorted here because the count is a
    property of the set (``binning._edges_of`` can put +inf before a
    finite edge); NaN sorts last and fills the nodes past E."""
    F, E = edges.shape
    steps = search_steps(E)
    ordered = jnp.sort(edges, axis=1, stable=False)     # no index rides
    ordered = jnp.pad(ordered, ((0, 0), (0, 2 ** steps - 1 - E)),
                      constant_values=jnp.nan)
    # level k: every 2**(steps - k)-th edge from the middle of the first
    # (lax.slice: the strided ``ordered[:, a::b]`` is a gather to jnp)
    levels = [lax.slice(ordered, (0, 2 ** (steps - k - 1) - 1),
                        ordered.shape, (1, 2 ** (steps - k)))
              for k in range(steps)]
    table = jnp.concatenate(
        [jnp.full((F, 1), jnp.nan, edges.dtype)] + levels, axis=1)
    return jnp.pad(table, ((0, 0), (0, max(0, 128 - 2 ** steps))),
                   constant_values=jnp.nan)


def upper_bound(x, probe, steps: int, shift: bool):
    """#edges <= x by ``steps`` levels of the walk; ``probe(k, i,
    went)`` gives node ``i`` (of level ``k``) of the cell's column,
    ``went`` the compares of the levels above (what ``i`` was made of).
    With ``shift`` NaN cells count 0 and the others one more."""
    i = jnp.ones(x.shape, jnp.int32)
    went = []
    for k in range(steps):
        went.append(x >= probe(k, i, went))
        i = i + i + jnp.where(went[-1], jnp.int32(1), jnp.int32(0))
    if shift:
        return jnp.where(x != x, jnp.int32(0), i - jnp.int32(2 ** steps - 1))
    return i - jnp.int32(2 ** steps)


def level_registers(k: int) -> int:
    """128-lane registers that hold level ``k``'s nodes."""
    return max(1, 2 ** k // 128)


def bin_blocks(n_edges: int) -> tuple[int, int]:
    """(columns, rows) of a block of the kernel's grid where the table
    rests in sublane tiles: eight columns, and ``_BLOCK_ROWS`` rows where
    a level is one gather (up to 255 edges); where the deep levels take
    a gather a register of nodes, as many fewer as keep a block's
    unrolled walks about as long. (Where it rests a column a row, a
    block is one column and eight times the rows: the same registers.)"""
    steps = search_steps(n_edges)
    work = 8 + sum(level_registers(k) - 1 for k in range(steps))
    rows = _BLOCK_ROWS * 8 // work
    return 8, max(1024, rows - rows % 1024)


def _bin_kernel(x_ref, e_ref, o_ref, *, steps, shift, tiled):
    def gather(register, lane):
        # ``jnp.take_along_axis(register, lane, axis=1)`` spelt out: jnp
        # makes the indices int64 under jax_enable_x64, which Mosaic
        # refuses, and wraps negative ones, which there are none of
        return lax.gather(
            register, lane[..., None], _ALONG_LANES, slice_sizes=(1, 1),
            mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)

    def top_nodes(nodes):
        # nodes 1 .. 2**levels - 1 of the columns, each along all lanes
        return [None] + [gather(nodes(0), jnp.full((8, 128), n, jnp.int32))
                         for n in range(1, 2 ** min(_SELECT_LEVELS, steps))]

    def counts(x, nodes, top):
        # x: (8, 128) cells; nodes(r): register r of their columns'
        # tables, a column (or the one column) on every sublane
        def probe(k, i, went):
            if k < _SELECT_LEVELS:      # 2**k - 1 selects by the compares
                level = top[2 ** k:2 ** (k + 1)]
                for ge in reversed(went):
                    level = [jnp.where(ge, right, left) for left, right
                             in zip(level[::2], level[1::2])]
                return level[0]
            first = 0 if k < 7 else 2 ** k // 128
            # node i is lane i of register 0, or level k starts a register
            lane = i if k < 7 else i & jnp.int32(127)
            v = gather(nodes(first), lane)
            for r in range(1, level_registers(k)):
                v = jnp.where((i >> 7) == jnp.int32(first + r),
                              gather(nodes(first + r), lane), v)
            return v

        return upper_bound(x, probe, steps, shift)

    if tiled:       # [8, rows]: eight columns a register, 128 rows each
        def nodes(r):
            return e_ref[:, r * 128:(r + 1) * 128]
        top = top_nodes(nodes)
        for c in range(0, x_ref.shape[1], 128):
            o_ref[:, c:c + 128] = counts(x_ref[:, c:c + 128], nodes, top)
    else:           # [1, 1, rows]: 1,024 rows of the column a register
        def nodes(r):
            return jnp.broadcast_to(e_ref[0, :, r * 128:(r + 1) * 128],
                                    (8, 128))
        top = top_nodes(nodes)
        for c in range(0, x_ref.shape[2], 1024):
            o_ref[0, :, c:c + 1024] = counts(
                x_ref[0, :, c:c + 1024].reshape(8, 128), nodes,
                top).reshape(1, 1024)


def pallas_bin_counts(X, edges, shift: bool, interpret: bool = False):
    """``#edges <= x`` of every cell of ``X`` [N, F] f32 against its
    column's ``edges`` [F, E] (any order; NaN counts for nothing), int32
    [N, F]; with ``shift`` NaN cells are 0 and the others one more."""
    N, F = X.shape
    if N == 0:
        return jnp.zeros((N, F), jnp.int32)
    steps = search_steps(edges.shape[1])
    table = search_table(edges)
    width = table.shape[1]
    tiled = _rests_tiled(F)
    columns, rows = bin_blocks(edges.shape[1])
    if not tiled:           # a register is 1,024 rows of one column
        columns, rows = 1, 8 * rows
    rows = min(rows, -(-N // 1024) * 1024)      # a table under a block
    # under shard_map with check_vma the out_shape carries what its
    # inputs vary over (``hist_kernel.pallas_histograms``)
    vma = frozenset().union(*(
        getattr(jax.typeof(a), "vma", None) or frozenset()
        for a in (X, edges)))
    typed = {"vma": vma} if vma else {}
    # the index maps say jnp.int32(0): Mosaic cannot legalize the i64 a
    # bare 0 becomes under jax_enable_x64
    if tiled:
        Xt, shape = X.T, (F, N)
        block = pl.BlockSpec((columns, rows), lambda j, i: (j, i))
        edge_block = pl.BlockSpec((columns, width),
                                  lambda j, i: (j, jnp.int32(0)))
    else:
        Xt, shape = jnp.transpose(X[:, None, :], (2, 1, 0)), (F, 1, N)
        table = table[:, None, :]
        block = pl.BlockSpec((columns, 1, rows),
                             lambda j, i: (j, jnp.int32(0), i))
        edge_block = pl.BlockSpec(
            (columns, 1, width), lambda j, i: (j, jnp.int32(0), jnp.int32(0)))
    out = pl.pallas_call(
        functools.partial(_bin_kernel, steps=steps, shift=shift,
                          tiled=tiled),
        grid=(pl.cdiv(F, columns), pl.cdiv(N, rows)),
        in_specs=[block, edge_block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(shape, jnp.int32, **typed),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="mp4j_bin",
    )(Xt, table)
    return out.T if tiled else jnp.transpose(out, (2, 1, 0))[:, 0, :]
