"""The GBDT step compiled for a described v5e (no chip, nothing runs):
the binned table must stay the way it rests on the chip.

A [N, 28] int32 table rests feature-major on the TPU (N on the lanes,
``{1,0,2:T(1,128)}``, unpadded). Until PR 25 the histogram kernel asked
for row-major [tile, F] blocks, so every tree paid a ``copy`` and a
``pad`` of the table at 28 of 128 lanes (two temporaries of 4.57x the
table each) and routing read the padded copy six times. These compiles
pin that this cannot come back silently. Since PR 26 the same holds of
a wide table: 1,183,747 x 968 rests as [F, N] in (8, 128) tiles
(``{1,2,0:T(8,128)}``), the kernel takes it in feature blocks as it
rests, and the step holds no copy of its 4.58 GB. Since PR 27 the FFM
cell's sparse step is pinned here too (one file, because one process may
load libtpu): the table by feature in rows of whole 128-lane words,
which rests row-major by XLA's own choice, donated, gathered and
scattered where it rests. All topology work happens inside fixtures.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ytk_mp4j_tpu.models.gbdt import (_SLAB_ROWS, GBDTConfig, GBDTTrainer,
                                      packed_shape)
from ytk_mp4j_tpu.ops.hist_kernel import (_acc_bytes_a_feature,
                                          _rests_tiled, feature_blocks,
                                          hist_radix, pallas_hist_supported,
                                          pallas_histograms)

ROWS, F, B, DEPTH = 1_000_000, 28, 256, 6
TABLE_BYTES = ROWS * F * 4


@pytest.fixture(scope="module")
def topo_devices():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _compile_step(devices, chips, depth=DEPTH, n_rows=ROWS, n_features=F,
                  **cfg):
    mesh = Mesh(np.asarray(devices[:chips]), ("mp4j",))
    trainer = GBDTTrainer(GBDTConfig(n_features=n_features, n_bins=B,
                                     depth=depth, loss="logistic", **cfg),
                          mesh=mesh)
    rows = NamedSharding(mesh, P("mp4j"))
    per, F = n_rows // chips, n_features
    kd = jax.eval_shape(lambda: jax.random.key_data(jax.random.key(0)))

    def aval(shape, dtype, sharding=rows):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    second = ()
    if cfg.get("grow_policy") == "loss":    # the table's second form
        second = (aval((chips * per, packed_shape(F, B)[2]), jnp.uint32),)
    return trainer._build_step().lower(
        aval((chips, per, F), jnp.int32), aval((chips, per), jnp.float32),
        aval((chips, per), jnp.float32), aval((chips, per), jnp.float32),
        aval(kd.shape, kd.dtype, NamedSharding(mesh, P())),
        *second).compile()


@pytest.fixture(scope="module")
def step_one_chip(topo_devices):
    return _compile_step(topo_devices, 1)


def _row_major_tables(text, min_rows, n_features=F):
    # an instruction whose result is an int32 array with the features as
    # its last dimension: group 1 the dimensions, group 2 the most-minor
    # dimension
    table = re.compile(r"= s32\[((?:\d+,)+%d)\]\{(\d+)" % n_features)
    found = []
    for line in text.splitlines():
        m = table.search(line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        if max(dims[:-1]) >= min_rows and int(m.group(2)) == len(dims) - 1:
            found.append(line.strip()[:200])
    return found


def test_row_major_table_detector():
    """The detector sees what PR 24's step held, and not the layouts
    the step holds now (lines from the compiled texts)."""
    old = """
  %copy.1 = s32[1,1000000,28]{2,1,0:T(8,128)} copy(%param_0.1)
  %pad.2 = s32[1000448,28]{1,0:T(8,128)} pad(%bitcast.3, %constant.4), padding=0_448x0_0
"""
    new = """
  %bins.1 = s32[1,1000000,28]{1,0,2:T(1,128)} parameter(0)
  %copy.612 = s32[1,1000000,28]{1,2,0:T(8,128)} copy(%param_0.45)
  %bitcast.133 = s32[1000000,28]{0,1:T(8,128)} bitcast(%copy.627)
  %copy_bitcast_fusion = s32[28,1000000]{1,0:T(8,128)} fusion(%bins.1)
"""
    assert len(_row_major_tables(old, ROWS)) == 2
    assert _row_major_tables(new, ROWS) == []


def test_step_holds_no_row_major_table(step_one_chip):
    text = step_one_chip.as_text()
    # the table comes in as it rests: N minor, no padding
    assert "s32[1,%d,%d]{1,0,2:T(1,128)} parameter(0)" % (ROWS, F) in text
    assert _row_major_tables(text, ROWS // 2) == []
    # the kernel's operand is a bitcast of that parameter, not a copy
    assert re.search(r"= s32\[%d,1,%d\]\{2,1,0:T\(1,128\)\} bitcast\(%%bins"
                     % (F, ROWS), text)
    # and nothing pads a table-sized array at all
    assert not re.search(r"= s32\[[\d,]*\d{6,}[\d,]*\]\S* pad\(", text)


def test_step_temporaries_under_twice_the_table(step_one_chip):
    """PR 24's step held 9.1x the table in temporaries (two lane-padded
    copies); one sublane-tiled copy (32/28 of the table) may remain."""
    temp = step_one_chip.memory_analysis().temp_size_in_bytes
    assert temp < 2 * TABLE_BYTES, temp / TABLE_BYTES


def test_step_runs_six_kernels(step_one_chip):
    assert step_one_chip.as_text().count("tpu_custom_call") == DEPTH


def test_four_chip_step_compiles_with_the_kernel(topo_devices):
    """Under shard_map the kernel's out_shape carries the shards' vma;
    each level's histograms are all-reduced."""
    step = _compile_step(topo_devices, 4, depth=3)
    text = step.as_text()
    assert text.count("tpu_custom_call") == 3
    assert " all-reduce(" in text or " all-reduce-start(" in text
    assert _row_major_tables(text, ROWS // 8) == []
    temp = step.memory_analysis().temp_size_in_bytes
    assert temp < 2 * TABLE_BYTES // 4, temp


WIDE_ROWS, WIDE_F = 1_183_747, 968      # benchmark/configs/gbdt-bosch-968
WIDE_TABLE_BYTES = WIDE_ROWS * WIDE_F * 4


@pytest.fixture(scope="module")
def wide_step(topo_devices):
    return _compile_step(topo_devices, 1, n_rows=WIDE_ROWS,
                         n_features=WIDE_F, missing_bin=True)


def test_wide_step_reads_the_table_as_it_rests(wide_step):
    text = wide_step.as_text()
    assert "s32[1,%d,%d]{1,2,0:T(8,128)} parameter(0)" % (
        WIDE_ROWS, WIDE_F) in text
    assert re.search(r"= s32\[%d,%d\]\{1,0:T\(8,128\)\} bitcast\(%%bins"
                     % (WIDE_F, WIDE_ROWS), text)
    assert _row_major_tables(text, WIDE_ROWS // 2, WIDE_F) == []
    # no copy, pad or transpose of anything with a table-sized dimension
    assert not re.search(
        r"= s32\[[\d,]*\d{6,},[\d,]*\d{3,}[\d,]*\]\S* (copy|pad|transpose)\(",
        text)
    assert text.count("tpu_custom_call") == DEPTH


def test_wide_step_temporaries_far_below_the_table(wide_step):
    """The six kernel outputs (up to 63 MB) and the split search's
    [32, 968, 256] arrays: 0.108 GB when this was written, of 4.58."""
    temp = wide_step.memory_analysis().temp_size_in_bytes
    assert temp < WIDE_TABLE_BYTES // 16, temp / WIDE_TABLE_BYTES


# ------------------------------------------------------------------------
# ISSUE 53: the step that grows its trees leaf by leaf, at the size of
# benchmark/configs/gbdt-bosch-968-leafwise: 70 leaves, depth at most 7.
# Since ISSUE 54 a split's pass reads its child's rows a slab at a time,
# gathered from the table's second form (a row 256 words, one descriptor),
# and the step takes that form beside the table and only reads it.
LEAVES, LEAF_DEPTH = 70, 7
OPEN_LEAVES_BYTES = LEAVES * WIDE_F * B * 2 * 4     # g and h, f32
CHIP_BYTES = 16 * 10 ** 9
WORDS = packed_shape(WIDE_F, B)[2]
SECOND_FORM_BYTES = WIDE_ROWS * WORDS * 4


@pytest.fixture(scope="module")
def leafwise_step(topo_devices):
    return _compile_step(topo_devices, 1, depth=LEAF_DEPTH, n_rows=WIDE_ROWS,
                         n_features=WIDE_F, missing_bin=True,
                         grow_policy="loss", max_leaves=LEAVES)


def test_a_slab_is_small_beside_the_table():
    assert (WORDS, SECOND_FORM_BYTES) == (256, 1_212_156_928)
    assert _SLAB_ROWS % 2048 == 0       # whole tiles of the kernel
    assert _SLAB_ROWS * WIDE_F * 4 < WIDE_TABLE_BYTES // 64


def test_leafwise_step_fits_the_chip_beside_its_table(leafwise_step):
    """Arguments (the table, its second form, labels, margins, weights)
    and temporaries (the open leaves' histograms, 139 MB; this tree's g
    and h side by side, 9.5 MB; a slab's rows, gathered and unpacked,
    0.02 GB) as XLA sizes them: under half the chip. The second form is
    an argument like the table: nothing is donated, and the step's
    results are the margins, the tree and the counts."""
    mem = leafwise_step.memory_analysis()
    held = WIDE_TABLE_BYTES + SECOND_FORM_BYTES
    assert held <= mem.argument_size_in_bytes < held + 4 * 4 * WIDE_ROWS
    assert mem.alias_size_in_bytes == 0
    assert mem.output_size_in_bytes < 2 * 4 * WIDE_ROWS
    assert mem.temp_size_in_bytes < 2 * OPEN_LEAVES_BYTES, mem
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < CHIP_BYTES // 2


def _moved_tables(text):
    """Every ``copy``, ``pad`` or ``transpose`` of the compiled text,
    inside a fusion or out, whose result has the table's rows (half of
    them or more) by a hundred columns or more: the table, its second
    form, or either transposed."""
    found = []
    for m in re.finditer(r"= \w+\[([\d,]+)\]\S* (copy|pad|transpose)\(",
                         text):
        dims = [int(d) for d in m.group(1).split(",")]
        if max(dims) >= WIDE_ROWS // 2 and sorted([1] + dims)[-2] >= 100:
            found.append(m.group(0))
    return found


def test_moved_tables_detector():
    text = """
  %copy.218 = u32[1,1183747,256]{2,1,0:T(8,128)} copy(%param_0.918)
  %copy = s32[1,1183747,968]{2,1,0:T(8,128)} copy(%bins.1), sharding={}
  %transpose.9 = u32[256,1183747]{1,0:T(8,128)} transpose(%p), dimensions={1,0}
  %copy.177 = u32[1183747,2]{1,0:T(8,128)} copy(%maximum_fusion)
  %pad.231 = bf16[1183747,4]{0,1:T(4,128)(2,1)} pad(%param_0.942, %c)
  %copy.256 = u32[131072,248]{0,1:T(8,128)} copy(%bitcast.430)
"""
    assert [m.split(" ")[1] for m in _moved_tables(text)] == [
        "u32[1,1183747,256]{2,1,0:T(8,128)}",
        "s32[1,1183747,968]{2,1,0:T(8,128)}",
        "u32[256,1183747]{1,0:T(8,128)}"]


def test_leafwise_step_loops_over_one_kernel_call_on_a_slab(leafwise_step):
    """The root's histogram from the table as it rests, and in the body
    of the loop over a split's slabs, inside the loop over the splits,
    one call on a slab's rows: two calls, not 70, and no ``switch``;
    the table is the root kernel's operand by a bitcast; nothing the
    size of the table or of its second form is copied, padded or
    transposed anywhere."""
    text = leafwise_step.as_text()
    assert text.count("tpu_custom_call") == 2
    assert len(re.findall(r" while\(", text)) >= 2
    assert not re.search(r" conditional\(", text)
    assert "s32[1,%d,%d]{1,2,0:T(8,128)} parameter(0)" % (
        WIDE_ROWS, WIDE_F) in text
    assert re.search(r"u32\[%d,%d\]\{1,0:T\(8,128\)\} parameter\(\d\)" % (
        WIDE_ROWS, WORDS), text)         # the second form, row-major
    assert len(re.findall(r"= s32\[%d,%d\]\{1,0:T\(8,128\)\} bitcast\("
                          % (WIDE_F, WIDE_ROWS), text)) == 1
    assert _row_major_tables(text, WIDE_ROWS // 2, WIDE_F) == []
    assert _moved_tables(text) == []
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert sorted(m for ln in calls for m in _op_names(ln)) == [
        "jit(step)/gbdt.hist/mp4j_hist/pallas_call",
        "jit(step)/while/body/closed_call/while/body/gbdt.hist/mp4j_hist/"
        "pallas_call"]
    # the loop's kernel reads a slab's rows: [F, slab] int32, unpacked
    operands = sorted(int(n) for ln in calls for n in re.findall(
        r"operand_layout_constraints=\{s32\[%d,(\d+)\]\{1,0\}" % WIDE_F, ln))
    assert operands == [_SLAB_ROWS, WIDE_ROWS]
    for part in ("gbdt.grow.pick", "gbdt.grow.book", "gbdt.route",
                 "gbdt.best_splits", "gbdt.grow.book/gbdt.grow.compact",
                 "while/body/gbdt.grow.book/gbdt.grow.compact"):
        assert _op_names(text, r"while/body/closed_call/" + re.escape(part))
    assert not _op_names(text, r"gbdt\.level\.")
    assert not _op_names(text, r"gbdt\.grow\..*gbdt\.hist")


def test_leafwise_step_gathers_a_row_a_descriptor(leafwise_step):
    """Every gather of the second form, and of the prefix counts that
    find a slab's rows, is XLA's native row gather: a slab's size of
    descriptors, each a whole row (256 words of the second form, 128
    counts). The one gather beside them brings the slab's g and h, a
    pair a descriptor, and no cell of either table is gathered alone."""
    text = leafwise_step.as_text()
    found = _table_gathers(text, r"gbdt\.grow\.compact")
    assert sorted(g[:2] for g in found) == sorted(
        [(_SLAB_ROWS, WORDS)] + [(_SLAB_ROWS, 128)] * 2
        + [(_SLAB_ROWS, 2)]), found
    # and nothing else gathers there: every gather under the scope is
    # the body of one of those fusions
    assert len([ln for ln in text.splitlines() if " gather(" in ln
                and "gbdt.grow.compact" in ln]) == len(found)


def test_leafwise_step_updates_the_open_leaves_where_they_rest(leafwise_step):
    """Two slots of 2 MB are written a split. Without the barrier round
    what is written XLA reads the parent's slot inside the fusions that
    write the table and copies both tables (139 MB) in and out of every
    step."""
    text = leafwise_step.as_text()
    table = r"f32\[%d,%d,%d\]" % (LEAVES, WIDE_F, B)
    assert re.search(table + r"\S* (fusion|dynamic-update-slice)\(", text)
    assert not re.findall(r"= " + table + r"\S* copy\(", text)


def test_four_chip_leafwise_step_reduces_a_node_a_split(topo_devices):
    """Rows sharded over four chips: the children's row counts are
    summed over the axis inside the loop over the splits, and the built
    child's histogram after the loop over its slabs, not inside it, so
    that every shard may take the slabs its own rows need."""
    step = _compile_step(topo_devices, 4, depth=LEAF_DEPTH,
                         n_rows=WIDE_ROWS + 1, n_features=WIDE_F,
                         missing_bin=True, grow_policy="loss",
                         max_leaves=LEAVES)
    text = step.as_text()
    assert text.count("tpu_custom_call") == 2
    reduced = _op_names(text, r"while/body/closed_call/.*psum")
    assert any("gbdt.grow.book" in m for m in reduced)      # the counts
    assert any("gbdt.grow.book" not in m for m in reduced)  # the histogram
    assert not [m for m in reduced if "closed_call/while/body" in m]
    reduces = [ln for ln in text.splitlines() if " all-reduce(" in ln
               or " all-reduce-start(" in ln]
    assert any(re.search(r"f32\[[\d,]*%d,%d\]" % (WIDE_F, B), ln)
               for ln in reduces)
    mem = step.memory_analysis()
    assert mem.argument_size_in_bytes < (
        WIDE_TABLE_BYTES + SECOND_FORM_BYTES) // 4 + 2 ** 24
    assert mem.temp_size_in_bytes < 3 * OPEN_LEAVES_BYTES


def _computations(text):
    """name -> lines of each computation of a compiled module's text."""
    found, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%(\S+) \(.*\) -> .* \{$", line)
        if m:
            name = "ENTRY" if line.startswith("ENTRY") else m.group(1)
            found[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            found[name].append(line)
    return found


def _route_table_readers(text, table):
    """What reads the table under ``gbdt.route``: (slices, whole,
    others). ``slices``: the ``dynamic-slice``s of the table inside the
    scope's fusions; ``whole``: the fusions of the scope that use the
    table in any other way (a level that selects among all F columns);
    ``others``: instructions of the scope that take the table and are
    no fusion. ``table``: the parameter's name in the entry
    computation; a copy of it that XLA prefetches into the fast memory
    (``copy-start`` / ``copy-done``: the 112 MB of 1M x 28) is the
    table too."""
    comps = _computations(text)
    tables = {table}
    slices, whole, others = 0, [], []
    for line in comps["ENTRY"]:
        head = line.split(", metadata=")[0]
        taken = [t for t in tables if re.search(re.escape(t) + r"[,)]", head)]
        if not taken:
            continue
        m = re.match(r"\s*(%\S+) = .* copy-(?:start|done)\(", head)
        if m:
            tables.add(m.group(1))
            continue
        m = re.search(r" fusion\((.*?)\), kind=\w+, calls=%([\w.-]+)", head)
        if m is None:
            if "gbdt.route" in line:
                others.append(line.strip()[:160])
            continue
        body = comps[m.group(2)]
        if "gbdt.route" not in line + "".join(body):
            continue
        operands = re.sub(r"/\*.*?\*/", "", m.group(1)).split(", ")
        for k in (k for k, o in enumerate(operands) if o in taken):
            param = next(re.match(r"\s*(%\S+) = ", b).group(1) for b in body
                         if re.search(r" parameter\(%d\)" % k, b))
            inside = [b for b in body
                      if re.search(re.escape(param) + r"[,)]", b)]
            sliced = [b for b in inside if re.search(
                r" dynamic-slice\(%s," % re.escape(param), b)]
            slices += len(sliced)
            if len(sliced) < len(inside):
                whole.append(line.strip()[:160])
    return slices, whole, others


def test_route_table_readers_detector():
    """Two sliced levels (one on a prefetched copy of the table), a
    whole-table level and a table-sized gather, as lines of compiled
    text."""
    text = """
%fused_computation.1 (param_0.1: s32[1,1000000,28], param_1.2: s32[], param_2.3: s32[1000000,1]) -> s32[1000000,1] {
  %param_0.1 = s32[1,1000000,28]{1,0,2:T(1,128)} parameter(0)
  %param_1.2 = s32[]{:T(128)S(6)} parameter(1)
  %constant.1 = s32[]{:T(128)} constant(0)
  %dynamic-slice.7 = s32[1,1000000,1]{1,0,2:T(1,128)} dynamic-slice(%param_0.1, %constant.1, %constant.1, %param_1.2), dynamic_slice_sizes={1,1000000,1}, metadata={op_name="jit(step)/gbdt.route/dynamic_slice"}
  %param_2.3 = s32[1000000,1]{0,1:T(1,128)} parameter(2)
  ROOT %select.1 = s32[1000000,1]{0,1:T(1,128)} select(%param_2.3, %dynamic-slice.7, %param_2.3)
}

%fused_computation.2 (param_0.4: s32[1000000], param_1.5: s32[1,1000000,28]) -> s32[1000000] {
  %param_1.5 = s32[1,1000000,28]{1,0,2:T(1,128)} parameter(1)
  %param_0.4 = s32[1000000]{0:T(1024)} parameter(0)
  ROOT %reduce_sum.1 = s32[1000000]{0:T(1024)} reduce(%param_1.5, %param_0.4), dimensions={1}, metadata={op_name="jit(step)/gbdt.route/reduce_sum"}
}

ENTRY %main.1 (bins.1: s32[1,1000000,28], ids.1: s32[1000000]) -> s32[1000000] {
  %bins.1 = s32[1,1000000,28]{1,0,2:T(1,128)} parameter(0)
  %ids.1 = s32[1000000]{0:T(1024)} parameter(1)
  %bitcast.1 = s32[28,1,1000000]{2,1,0:T(1,128)} bitcast(%bins.1), metadata={op_name="jit(step)/gbdt.hist/transpose"}
  %fusion.1 = s32[1000000,1]{0,1:T(1,128)} fusion(%bins.1, %c.1, /*index=2*/%col.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/gbdt.route/select_n"}
  %copy-start = (s32[1,1000000,28]{1,0,2:T(1,128)S(1)}, s32[1,1000000,28]{1,0,2:T(1,128)}, u32[]{:S(2)}) copy-start(%bins.1)
  %copy-done = s32[1,1000000,28]{1,0,2:T(1,128)S(1)} copy-done(%copy-start)
  %fusion.3 = s32[1000000,1]{0,1:T(1,128)} fusion(%copy-done, %c.2, %col.2), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = s32[1000000]{0:T(1024)} fusion(%ids.1, %copy-done), kind=kLoop, calls=%fused_computation.2
  %gather.1 = s32[1000000,4]{1,0} gather(%bins.1, %ids.1), metadata={op_name="jit(step)/gbdt.route/gather"}
}
"""
    slices, whole, others = _route_table_readers(text, "%bins.1")
    assert slices == 2
    assert len(whole) == 1 and whole[0].startswith("%fusion.2")
    assert len(others) == 1 and others[0].startswith("%gather.1")


@pytest.mark.parametrize("which,n_feat,sliced_levels", [
    ("step_one_chip", F, 5),        # F rows of N lanes: Higgs
    ("wide_step", WIDE_F, 6),       # (8, 128) tiles: Bosch
])
def test_routing_reads_its_levels_columns(request, which, n_feat,
                                          sliced_levels):
    """Under ``gbdt.route`` a sliced level (``route_sliced``: five of
    six at F = 28, all six at F = 968) takes the table through
    ``dynamic-slice``s of one column alone, one a node, inside the
    fusion that selects among them: no column is written out first, no
    gather is there, and only the levels the rule leaves whole read
    every column."""
    from ytk_mp4j_tpu.models.gbdt import route_sliced

    text = request.getfixturevalue(which).as_text()
    assert re.search(r"%%bins\.1 = s32\[1,\d+,%d\]\S+ parameter\(0\)" % n_feat,
                     text)
    levels = [route_sliced(2 ** d, n_feat) for d in range(DEPTH)]
    assert sum(levels) == sliced_levels
    slices, whole, others = _route_table_readers(text, "%bins.1")
    assert slices == sum(2 ** d for d, s in enumerate(levels) if s)
    assert len(whole) == DEPTH - sliced_levels, whole
    assert others == []
    route = [line for line in text.splitlines() if "gbdt.route" in line]
    assert route and not [line for line in route if " gather(" in line]
    # a slice of the table is one column of its rows, never more
    sizes = {m.group(1) for m in (
        re.search(r" dynamic-slice\(.*dynamic_slice_sizes=\{([\d,]+)\}", line)
        for line in route) if m and "," in m.group(1)}
    assert len(sizes) == 1 and sizes.pop().endswith(",1"), sizes


SCORE_ROWS, SCORE_TREES = 1_183_748, 500   # configs/gbdt-bosch-score-500
# rows of a piece of this table (_array_cuts: 128 MiB in whole rows of
# 128 lanes), which ``predict`` scores as it crossed; the last piece
# starts early and the rows it adds take a program of their own
SCORE_CHUNK_ROWS = (GBDTTrainer._EACH_CHUNK_BYTES // (WIDE_F * 4)
                    // 128 * 128)
SCORE_REST_ROWS = SCORE_ROWS % SCORE_CHUNK_ROWS
SCORE_WIRE = (1, SCORE_CHUNK_ROWS * WIDE_F // 128, 128)


@pytest.fixture(scope="module")
def score_program(topo_devices):
    """``GBDTTrainer.predict``'s two scoring programs at the Bosch
    scoring cell's size, each taking a piece as it crossed, and their
    build spans: ``{rows scored: (compiled, span)}``."""
    from ytk_mp4j_tpu.models.gbdt import score_group_size
    from ytk_mp4j_tpu.obs import spans

    mesh = Mesh(np.asarray(topo_devices[:1]), ("mp4j",))
    trainer = GBDTTrainer(GBDTConfig(n_features=WIDE_F, n_bins=B,
                                     depth=DEPTH, loss="logistic",
                                     missing_bin=True), mesh=mesh)
    rows, whole = NamedSharding(mesh, P("mp4j")), NamedSharding(mesh, P())
    group = score_group_size(SCORE_TREES)
    shape = (-(-SCORE_TREES // group), 2 ** DEPTH, group, 1)
    stacked = tuple(jax.ShapeDtypeStruct(shape, d, sharding=whole)
                    for d in (jnp.int32, jnp.int32, jnp.int32, jnp.float32))
    out = {}
    for scored in (SCORE_CHUNK_ROWS, SCORE_REST_ROWS):
        spans.clear()
        program = trainer._build_score(SCORE_WIRE, scored, SCORE_TREES)
        (built,) = [s[-1] for s in spans.snapshot()
                    if s[0] == "mp4j.step.build"]
        # 64-bit types off, as the cell and every user who has not asked
        # for them run it (``tests/conftest.py`` turns them on)
        with jax.enable_x64(False):
            out[scored] = program.lower(
                jax.ShapeDtypeStruct(SCORE_WIRE, jnp.int32, sharding=rows),
                stacked,
                jax.ShapeDtypeStruct((1, 1, SCORE_ROWS), jnp.float32,
                                     sharding=rows),
                jax.ShapeDtypeStruct((), jnp.int32,
                                     sharding=whole)).compile(), built
    return out


def _whole_lane_words(rows):
    """The rows a chunk of ``rows`` rows is scored as: filled up with
    empty rows to whole 128-lane words (``gbdt._SCORE_LANES``)."""
    from ytk_mp4j_tpu.models import gbdt

    return -(-rows // gbdt._SCORE_LANES) * gbdt._SCORE_LANES


def _dynamic_slices(text):
    """The sizes of what a compiled program slices at a computed place:
    in a scoring program a group of the ensemble, and no row of any
    table."""
    return set(re.findall(
        r" dynamic-slice\(.*?dynamic_slice_sizes=\{([\d,]+)\}", text))


@pytest.mark.parametrize("scored", [SCORE_CHUNK_ROWS, SCORE_REST_ROWS])
def test_scoring_program_holds_a_piece_and_no_table(score_program, scored):
    compiled, built = score_program[scored]
    m = compiled.memory_analysis()
    piece = SCORE_CHUNK_ROWS * WIDE_F * 4
    assert (SCORE_CHUNK_ROWS, SCORE_REST_ROWS) == (34_560, 8_708)
    # the piece, the margins, the ensemble: no table among the arguments
    assert piece <= m.argument_size_in_bytes < piece + 8e6
    assert m.argument_size_in_bytes < SCORE_ROWS * WIDE_F * 4 / 30
    # the temporaries are the piece's bf16 copies and a group's decisions
    assert m.temp_size_in_bytes < 0.5e9, m.temp_size_in_bytes
    assert built["key"] == "gbdt_score" and built["rows"] == scored
    assert built["row_chunks"] == 1
    # a piece's bf16 copy is read once a group of trees: at most 64 times
    assert -(-SCORE_TREES // built["group"]) <= 64


@pytest.mark.parametrize("scored", [SCORE_CHUNK_ROWS, SCORE_REST_ROWS])
def test_scoring_program_takes_the_piece_as_it_crossed(score_program,
                                                       scored):
    """The piece comes in as [M, 128] words, as the host held it; the
    program makes of its scored rows one [968, rows] bf16 array (rows on
    the lanes), selects by one bf16 matmul a group and decides in its
    output. Nothing of a table's size is sliced, updated or made: the
    only ``dynamic-update-slice`` writes the margins where they rest."""
    compiled, built = score_program[scored]
    text = compiled.as_text()
    assert "s32[%d,%d,%d]{2,1,0:T(8,128)} parameter(0)" % SCORE_WIRE in text
    assert not re.search(r"= \w+\[[\d,]*\d{7,}[\d,]*\]\S* "
                         r"(copy|pad|transpose|dynamic-slice)\(", text)
    updates = re.findall(r"= (\w+\[[\d,]+\])\S* dynamic-update-slice\(", text)
    assert updates == ["f32[1,1,%d]" % SCORE_ROWS], updates
    assert _dynamic_slices(text) <= {"1,%d,%d,1" % (2 ** DEPTH,
                                                    built["group"])}
    assert "stage.place" in text
    filled = _whole_lane_words(scored)      # 8,708 rows are scored as 8,832
    assert re.search(r"bf16\[%d,%d\]\{1,0:T\(8,128\)\(2,1\)" % (WIDE_F, filled),
                     text)
    assert re.search(r"= pred\[%d,%d\]\S* fusion\(.*kind=kOutput"
                     % (built["group"] * 2 ** DEPTH, filled), text)
    assert "gbdt.score.select/dot_general" in text
    assert "gbdt.score.walk" in text and "gbdt.route" not in text
    assert " gather(" not in text and "tpu_custom_call" not in text
    # the margins are updated where they rest
    assert "input_output_alias" in text


@pytest.mark.parametrize("n_feat,rests", [
    (28, "{1,0,2:T(1,128)}"),       # F rows of N lanes: Higgs
    (250, "{1,0,2:T(1,128)}"),
    (700, "{1,0,2:T(1,128)}"),
    (8, "{1,2,0:T(8,128)}"),        # [F, N] in (8, 128) tiles
    (136, "{1,2,0:T(8,128)}"),
    (968, "{1,2,0:T(8,128)}"),      # Bosch
    (2000, "{1,2,0:T(8,128)}"),
    (1024, "{2,"),                  # F minor, row-major: a transposing
                                    # copy a step in either operand form
])
def test_where_a_table_rests_is_what_the_kernel_assumes(topo_devices, n_feat,
                                                        rests):
    """``_rests_tiled`` writes the runtime's choice of a parameter's
    layout, made from the shape alone, into the kernel's choice of its
    operand. A runtime that chooses otherwise would bring a copy of the
    table back into every tree without any error: this lowering of the
    parameter alone would then fail first."""
    from jax.sharding import SingleDeviceSharding

    table = jax.ShapeDtypeStruct(
        (1, WIDE_ROWS, n_feat), jnp.int32,
        sharding=SingleDeviceSharding(topo_devices[0]))
    text = jax.jit(lambda b: (b[0] > 3).sum(1)).lower(table).compile() \
        .as_text()
    layout = re.search(r"entry_computation_layout=\{\(s32\[[\d,]+\](\S+?)\)->",
                       text).group(1)
    assert layout.startswith(rests), layout
    if n_feat % 128:
        assert _rests_tiled(n_feat) == rests.startswith("{1,2,0")


@pytest.mark.parametrize("B,n_feat,n_nodes,radix", [
    (256, 28, 64, 1),    # one block of the Higgs width near the limit
    (4096, 2, 32, 1),    # tallest one-hot: [B, tile]
    (256, 968, 1, 4),    # Bosch's root: 11 blocks of 88, (8, 128)-tiled
    (256, 968, 16, 1),   # ... and its deepest level
    (256, 1024, 16, 1),  # 8 blocks whose accumulators are 8 MiB each:
                         # the out block must be single-buffered to fit
    (256, 250, 16, 1),   # rows of (1, 128): two blocks of 125
    (256, 28, 128, 1),   # depth 8 at the Higgs width: two blocks of 14
    # the shallow levels of both cells, where a bin is split: every
    # (radix, operand form, out-block form) the two tables meet
    (256, 28, 1, 4),     # rows of (1, 128); 16 rows, a block [16, 64]
    (256, 28, 2, 4),     # a feature
    (256, 28, 4, 2),     # 32 and 64 rows against 128 low digits side
    (256, 28, 8, 2),     # by side
    (256, 968, 2, 4),    # the same in sublane tiles
    (256, 968, 4, 2),
    (256, 968, 8, 2),
    (256, 250, 1, 4),    # 125 rows of (1, 128), 64 low digits
    (256, 1024, 1, 4),   # 8 blocks of 128 in sublane tiles
    (128, 28, 1, 4),     # 32 low digits: a quarter of a lane word
    (4096, 2, 1, 16),    # 64 rows against 256 low digits
])
def test_kernel_compiles_where_the_gate_says_so(topo_devices, B, n_feat,
                                                n_nodes, radix):
    """What pallas_hist_supported admits, Mosaic compiles at the
    module's tile (VMEM is the limit that interpret mode cannot see),
    at the radix the level takes."""
    from jax.sharding import SingleDeviceSharding

    assert pallas_hist_supported(B, n_feat, n_nodes)
    assert hist_radix(n_nodes, B) == radix
    one_chip = SingleDeviceSharding(topo_devices[0])

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n = 100_000
    assert feature_blocks(n_feat, B, n_nodes)[0] \
        * _acc_bytes_a_feature(B, n_nodes) <= 8 * 2 ** 20
    jax.jit(lambda b, g, h, i: pallas_histograms(
        b, g, h, i, n_nodes, n_feat, B)).lower(
        aval((n, n_feat), jnp.int32), aval((n,), jnp.float32),
        aval((n,), jnp.float32), aval((n,), jnp.int32)).compile()


# ------------------------------------------------ the FFM cell's sparse step
def _ffm_cell(config="ffm-criteo"):
    import json
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent / "benchmark"
    return (json.loads((root / "configs" / f"{config}.json").read_text()),
            json.loads((root / "traffic" / "stream-zipf.json").read_text()))


def _ffm_batch(mesh, chips, c, t):
    """The cell's chunk as the step takes it, rows sharded."""
    rows = NamedSharding(mesh, P("mp4j"))
    slots = (chips, t["rows_per_chunk"], c["max_nnz"])
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=rows)
            for shape, dtype in (
                (slots, jnp.int32), (slots, jnp.int32),
                (slots, jnp.float32), (slots, jnp.float32),
                (slots[:2], jnp.float32), (slots[:2], jnp.float32))]


def _step_descriptors(c, t):
    """The slots a replicated step runs on: the cell's chunk is 2,048 x 39
    = 79,872, a whole number of 1,024s, so the step takes eight dead rows
    more (``fm._with_dead_rows``, PR 40): gather, select, pairs,
    backward and the merge's sort run on 80,184 slots; the segmented sum
    and the update keep the chunk's 79,872 (``fm._merge_slots``)."""
    from ytk_mp4j_tpu.models import fm

    rows, K = t["rows_per_chunk"], c["max_nnz"]
    assert (rows * K, fm._dead_rows(rows * K)) == (79872, 8)
    return (rows + fm._dead_rows(rows * K)) * K


@pytest.fixture(scope="module")
def ffm_programs(topo_devices):
    """The step and both conversions of ``ffm-criteo.stream-zipf`` at the
    cell's own size, compiled for one described chip; the step also for
    the four of the described host, each with the cell's chunk."""
    from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer

    c, t = _ffm_cell()
    descriptors = t["rows_per_chunk"] * c["max_nnz"]

    def trainer_and_step(chips):
        mesh = Mesh(np.asarray(topo_devices[:chips]), ("mp4j",))
        trainer = FMTrainer(FMConfig(
            model=c["model"], n_features=c["n_features"],
            n_fields=c["n_fields"], k=c["k"], max_nnz=c["max_nnz"],
            learning_rate=c["learning_rate"]),
            mesh=mesh, sparse_grads=c["sparse_grads"],
            table_sharding=c["table_sharding"])
        lowered = trainer._build_step(descriptors).lower(
            trainer._state_avals(), *_ffm_batch(mesh, chips, c, t))
        return trainer, lowered.compile(), lowered.as_text()

    trainer, step, lowered = trainer_and_step(1)
    _, step_on_four, lowered_on_four = trainer_and_step(4)
    rep = NamedSharding(trainer.mesh, P())
    public = tuple(
        jax.ShapeDtypeStruct(shape, jnp.float32, sharding=rep)
        for shape in ((), (c["n_features"],), (trainer.n_rows, c["k"])))
    widen, narrow = trainer._build_converters()
    return {
        "config": c,
        "descriptors": descriptors,
        "step_descriptors": _step_descriptors(c, t),
        "step": step,
        "step_on_four": step_on_four,
        "lowered": {"step": lowered, "step_on_four": lowered_on_four},
        "widen": widen.lower(public).compile(),
        "narrow": narrow.lower(trainer._state_avals()).compile(),
    }


def _table_sized(text, opcode, elements):
    """Instructions of ``opcode`` whose result holds ``elements`` values
    or more."""
    found = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* %s\(" % opcode, line)
        if m and np.prod([int(d) for d in m.group(1).split(",")],
                         dtype=np.int64) >= elements:
            found.append(line.strip()[:200])
    return found


def test_table_sized_detector():
    text = """
  %copy.1 = f32[163577856,4]{1,0:T(8,128)} copy(%params_2_.1)
  %copy.18 = f32[4194304]{0:T(1024)} copy(%copy-done)
  %while.2 = (s32[], f32[4194304,156]{1,0:T(8,128)}) while(%tuple.24)
"""
    assert len(_table_sized(text, "copy", 10 ** 8)) == 1
    assert _table_sized(text, "copy", 10 ** 9) == []


def test_ffm_step_scatters_into_the_table_where_it_rests(ffm_programs):
    from ytk_mp4j_tpu.models import fm

    c, step = ffm_programs["config"], ffm_programs["step"]
    text = step.as_text()
    # 39 fields x 4 floats and the linear weight in two 128-lane words
    F, width = c["n_features"], 256
    assert c["n_fields"] * c["k"] + 1 == 157
    table = r"f32\[%d,%d\]\{1,0:T\(8,128\)\}" % (F, width)
    # the table comes in row-major by feature, which no layout is pinned
    # for: a width that is not whole 128-lane words rests with the
    # features on the lanes and is copied whole twice a step
    assert re.search(table + r" parameter\(1\)", text)
    assert re.search(r"input_output_alias=\{.*\{1\}: \(1, \{\}, may-alias\)",
                     text)
    # one native gather of the step's N x K descriptors (the chunk's and
    # the dead rows'), on the parameter itself
    d = ffm_programs["descriptors"]
    assert re.search(
        r"= f32\[%d,%d\]\S* fusion\(%%params_1_\S*, [^)]*\), kind=kCustom"
        r".*ffm\.table_gather" % (ffm_programs["step_descriptors"], width),
        text)
    # the merged list's blocks are added a tile at a time through ONE loop
    # over its live prefix (PR 39), whose carry is the table itself: no
    # copy of 4.29 GB into or out of it
    loops = re.findall(r"^\s*%\S+ = \((.*)\) while\(", text, re.M)
    assert len(loops) == 1
    assert len(re.findall(table, loops[0])) == 1
    carried = set(re.findall(
        r"(%\S+) = " + table + r" get-tuple-element\(", text))
    tile = fm._update_tile(d)
    assert d % tile == 0 and tile < d // 8
    update = re.search(
        r"= " + table + r" fusion\((%[^,)]+), [^)]*\), kind=kCustom"
        r".*while/body/ffm\.table_update", text)
    assert update is not None and update.group(1) in carried
    # nothing of the old form: no scatter-add of all d slots into the table
    assert not re.search(r"fusion\(%params_1_.*ffm\.table_update", text)
    # the merge is on the step's path, the library's one sort in it, and
    # what it holds is the slots' [79,872, 256] blocks, 82 MB each
    assert re.search(r" sort\(.*ffm\.grad_merge/sparse\.sort_by_key", text)
    assert re.search(r"ffm\.grad_merge/sparse\.segment_reduce", text)
    assert len(re.findall(r" sort\(", text)) == 1
    assert _table_sized(text, "copy", F * width // 2) == []
    assert _table_sized(text, "transpose", F * width // 2) == []
    assert step.memory_analysis().temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("which", ["step", "step_on_four"])
def test_ffm_step_has_one_index_stream(ffm_programs, which):
    """The linear weights ride in the blocks: a step holds one gather and
    one scatter that take the table, and nothing at all of the shape
    [n_features] (before PR 31 a step gathered ``w[feats]``, scattered the
    weights' gradient into a dense zero vector, updated all of ``w`` and,
    on more than one chip, all-reduced that vector). Since PR 39 the
    scatter is the loop's, a tile of the merged list a trip, and is NOT
    told that a tile's ids ascend (told, XLA passes over the whole 4.29 GB
    operand a call: 14.3 ms on the chip, PERF.md section 6, PR 35); the
    merge's own segmented sum, whose operand is the list, is told."""
    c = ffm_programs["config"]
    text = ffm_programs[which].as_text()
    F, width = c["n_features"], 256
    chips = 4 if which == "step_on_four" else 1
    table = r"f32\[%d,%d\]" % (F, width)

    def instructions(opcode):
        return re.findall(r"^.* = (.+?) %s\(" % opcode, text, re.M)

    def under(opcode, scope):
        return [line for line in text.splitlines()
                if re.search(r" %s\(.*op_name=\"[^\"]*%s" % (opcode, scope),
                             line)]

    # of the table's shape: one scatter, the loop's, and the one gather's
    # operand; every other gather and scatter is the merge's, on the slots
    (update,) = [line for line in text.splitlines()
                 if re.search(r"= " + table + r"\S* scatter\(", line)]
    assert re.search(r"while/body/ffm\.table_update", update)
    assert "indices_are_sorted=true" not in update
    assert len(under("gather", r"ffm\.table_gather")) == 1
    others = [s for s in instructions("scatter") + instructions("gather")
              if not re.match(table, s)]
    # (the step's slots, 80,184 a member, or what the merge keeps of
    # them after its sort, 79,872)
    slots = chips * ffm_programs["step_descriptors"]
    merged = chips * ffm_programs["descriptors"]
    rows = ffm_programs["step_descriptors"] // c["max_nnz"]
    assert all(re.match(r"[fs]32\[(%d|%d|%d,%d)[,\]]"
                        % (slots, merged, rows, c["max_nnz"]), s)
               for s in others), others
    (added,) = [line for line in under(
        "scatter", r"ffm\.grad_merge/sparse\.segment_reduce")
        if "scatter-add" in line]
    assert "indices_are_sorted=true" in added
    # both as native fusions whose first operand is the table itself: the
    # parameter for the gather, the loop's carry for the scatter-add
    custom = [line for line in text.splitlines()
              if " fusion(" in line and "kind=kCustom" in line
              and re.search(r"ffm\.table_(gather|update)", line)]
    assert len(custom) == 2
    by_name = dict(re.findall(
        r"(%\S+) = (\S+) (?:parameter|get-tuple-element)\(", text))
    for line in custom:
        first = re.search(r" fusion\((%[^,)]+)", line).group(1)
        assert by_name[first].startswith("f32[%d,%d]" % (F, width)), line
    # no operand or result of the shape [n_features], of any type, in any
    # instruction: no gather, scatter, all-reduce or elementwise op on one
    assert re.search(r"\[%d\]" % F, text) is None
    # what crosses chips: the scalars (loss, weight sum, the bias's
    # gradient) all-reduced, the slots' indices and blocks all-gathered
    # (since PR 40 a member's index list is 80,184 long, not whole tiles
    # of 1,024, and XLA gathers such a list as an all-reduce of the
    # members' lists laid into zeros: 1.28 MB beside the blocks' 328 MB)
    reduced = [shapes for shapes in instructions("all-reduce")
               if not shapes.startswith("s32[%d]" % slots)]
    assert all(re.fullmatch(r"\(?(f32\[\]\S*,? ?)+\)?", shapes)
               for shapes in reduced), reduced
    assert bool(reduced) == (which == "step_on_four")
    assert bool(instructions("all-gather")) == (which == "step_on_four")
    assert _table_sized(text, "copy", F * width // 2) == []
    assert _table_sized(text, "transpose", F * width // 2) == []
    # the merge's operands: every member's slots' blocks, [chips x 79,872,
    # 256] f32, 82 MB a member each
    assert (ffm_programs[which].memory_analysis().temp_size_in_bytes
            < 0.25e9 * (1 + chips))


def test_ffm_step_holds_one_table_and_small_temporaries(ffm_programs):
    c = ffm_programs["config"]
    m = ffm_programs["step"].memory_analysis()
    # 157 floats in 256: 4.29 GB for 2.64 GB of values
    padded = c["n_features"] * 256 * 4
    assert padded <= m.alias_size_in_bytes < padded + 2 ** 25
    assert m.temp_size_in_bytes < 0.5e9, m.temp_size_in_bytes
    assert m.output_size_in_bytes - m.alias_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("which", ["widen", "narrow"])
def test_ffm_conversions_go_a_block_at_a_time(ffm_programs, which):
    """2.63 GB in (the table and the weights) and 4.29 GB out, or the
    reverse, with a third of a GB between them: a whole-table relayout
    would need 83.7 GB."""
    m = ffm_programs[which].memory_analysis()
    assert m.temp_size_in_bytes < 0.5e9, m.temp_size_in_bytes
    F = ffm_programs["config"]["n_features"]
    text = ffm_programs[which].as_text()
    for opcode in ("copy", "transpose", "pad", "concatenate"):
        assert _table_sized(text, opcode, F * 156 // 2) == [], opcode
    assert m.alias_size_in_bytes == 0       # the caller's table is kept
    sizes = sorted([m.argument_size_in_bytes, m.output_size_in_bytes])
    assert 2.6e9 < sizes[0] < 2.7e9 and 4.29e9 < sizes[1] < 4.35e9


# What ``FMTrainer._build_step`` lowers for ``ffm-criteo.stream-zipf``
# (jax 0.9.0 with x64 on as ``conftest.py`` sets it, for the described
# v5e): sha256 of ``lowered.as_text()``, first taken at the commit before
# ``FMConfig.optimizer`` existed (PR 31's tree) and again at PR 37, which
# changed the step on purpose (``_select_fields``' output columns run
# component by component, the weight inside the runs) and at PR 39, which
# did too (the slots' gradients merged, the merged list's live prefix
# scatter-added in tiles), and at PR 40 (the cell's chunk, 79,872 slots
# and so whole 1,024s, is stepped with eight dead rows after it: gather,
# select, pairs, backward and the merge's sort run on 80,184 slots, the
# segmented sum and the update on the chunk's 79,872).
# The AdaGrad step is another function; choosing it must leave SGD's program
# as it was, to the letter. A PR that changes the SGD step on purpose, or
# a new jax, changes these with it.
SGD_STEP_LOWERED_SHA256 = {
    "step": "fb3a7086048a0461e45dcbdf329b9458b2594d935de468220e5653d24177eb6c",
    "step_on_four":
        "0211adbc75b0747a494082d61444270950b51a8c225c6b8236ae30aec8f3c19b",
}


@pytest.mark.parametrize("which", ["step", "step_on_four"])
def test_sgd_step_lowers_to_the_program_it_was(ffm_programs, which):
    import hashlib

    text = ffm_programs["lowered"][which]
    assert "384" not in text                 # no AdaGrad block anywhere
    assert (hashlib.sha256(text.encode()).hexdigest()
            == SGD_STEP_LOWERED_SHA256[which])


# ------------------------------------- the AdaGrad cell's step (PR 32)
@pytest.fixture(scope="module")
def adagrad_programs(topo_devices):
    """The step and both conversions of ``ffm-criteo-adagrad.stream-zipf``
    at the cell's own size, compiled for one described chip."""
    from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer

    c, t = _ffm_cell("ffm-criteo-adagrad")
    mesh = Mesh(np.asarray(topo_devices[:1]), ("mp4j",))
    trainer = FMTrainer(FMConfig(
        model=c["model"], n_features=c["n_features"], n_fields=c["n_fields"],
        k=c["k"], max_nnz=c["max_nnz"], learning_rate=c["learning_rate"],
        l2=c["l2"], optimizer=c["optimizer"],
        adagrad_init=c["adagrad_init"]),
        mesh=mesh, sparse_grads=c["sparse_grads"],
        table_sharding=c["table_sharding"])
    descriptors = t["rows_per_chunk"] * c["max_nnz"]
    rep = NamedSharding(mesh, P())
    public = tuple(
        jax.ShapeDtypeStruct(shape, jnp.float32, sharding=rep)
        for shape in ((), (c["n_features"],), (trainer.n_rows, c["k"])))
    widen, narrow = trainer._build_converters()
    return {
        "config": c,
        "descriptors": descriptors,
        "step_descriptors": _step_descriptors(c, t),
        "step": trainer._build_step(descriptors).lower(
            trainer._state_avals(), *_ffm_batch(mesh, 1, c, t)).compile(),
        "widen": widen.lower(public, public).compile(),
        "narrow": narrow.lower(trainer._state_avals()).compile(),
    }


def test_adagrad_step_sets_the_blocks_into_the_table_where_it_rests(
        adagrad_programs):
    from ytk_mp4j_tpu.models import fm

    c, step = adagrad_programs["config"], adagrad_programs["step"]
    text, d = step.as_text(), adagrad_programs["descriptors"]
    # 157 parameters and 157 accumulators in three 128-lane words
    F, width = c["n_features"], 384
    table = r"f32\[%d,%d\]\{1,0:T\(8,128\)\}" % (F, width)
    assert re.search(table + r" parameter\(1\)", text)
    assert re.search(r"input_output_alias=\{.*\{1\}: \(1, \{\}, may-alias\)",
                     text)
    # the slots' blocks are gathered from the parameter itself, one
    # native fusion of the step's N x K descriptors (the dead rows' too)
    assert len(re.findall(
        r"= f32\[%d,%d\]\S* fusion\(%%params_1_\S*, [^)]*\), kind=kCustom"
        r".*ffm\.table_gather"
        % (adagrad_programs["step_descriptors"], width), text)) == 1
    # the distinct features' blocks go a tile at a time through ONE loop
    # over the merged list's live prefix, whose carry is the table
    # itself: no second loop, no copy of 6.44 GB into or out of it
    loops = re.findall(r"^\s*%\S+ = \((.*)\) while\(", text, re.M)
    assert len(loops) == 1
    assert len(re.findall(table, loops[0])) == 1
    carried = set(re.findall(
        r"(%\S+) = " + table + r" get-tuple-element\(", text))
    tile = fm._update_tile(d)
    assert d % tile == 0 and tile < d // 8

    def in_the_loop(shape, scope):
        """The native fusion under ``scope`` in the loop's body, if its
        first operand is the carried table."""
        m = re.search(
            r"= " + shape + r"\S* fusion\((%[^,)]+), [^)]*\), kind=kCustom"
            r".*while/body/" + scope, text)
        return m is not None and m.group(1) in carried

    # a tile's gather and a tile's set, native fusions on that carry
    assert in_the_loop(r"f32\[%d,%d\]" % (tile, width),
                       r"ffm\.table_gather")
    assert in_the_loop(table, r"ffm\.table_update")
    assert re.search(r"while/body/ffm\.adagrad_rule", text)
    # nothing of the old form: no gather, rule or set over all d slots
    assert not re.search(r"f32\[%d,%d\]\S* fusion\(.*ffm\.table_update"
                         % (d, width), text)
    assert _table_sized(text, "copy", F * width // 2) == []
    assert _table_sized(text, "transpose", F * width // 2) == []
    # the merge is on the step's path: a sort under ffm.grad_merge, and
    # nothing of the shape [n_features]
    assert re.search(r" sort\(.*ffm\.grad_merge/sparse\.sort_by_key", text)
    assert re.search(r"ffm\.grad_merge/sparse\.segment_reduce", text)
    assert re.search(r"ffm\.adagrad_rule", text)
    assert re.search(r"\[%d\]" % F, text) is None
    m = step.memory_analysis()
    # arguments 6.44 GB (the table; the chunk is 1.3 MB), the output is
    # the table itself, temporaries 0.335 GB (0.4575 with the rule on all
    # 79,872 slots): the slots' blocks and gradients and the merge's
    # buffers, [79,872, 192..384] f32 each; the loop's are a tile's
    assert F * width * 4 <= m.alias_size_in_bytes < F * width * 4 + 2 ** 25
    assert m.argument_size_in_bytes < F * width * 4 + 2 ** 25
    assert m.output_size_in_bytes - m.alias_size_in_bytes < 2 ** 20
    assert m.temp_size_in_bytes < 0.4e9, m.temp_size_in_bytes


def test_adagrad_scatters_are_told_what_pays_to_tell(adagrad_programs):
    """The segment ids are a cumulative sum, and the merge's scatter-add
    is told so (PR 35): XLA's own sort of the 79,872 ids and its gather
    of the ``[79,872, 232]`` rows by that order are not in the program,
    and the one ``sort`` left is the library's. The loop's scatter into
    the table is NOT told that a tile's ids ascend, though they do: told,
    XLA takes its large-buffer scatter, which passes over the whole
    6.44 GB operand a call (19.4 ms a tile of 768 on the chip against
    0.075; PERF.md section 6, PR 35). It stays the native fusion on the
    carried table, in place."""
    c, step = adagrad_programs["config"], adagrad_programs["step"]
    text = step.as_text()
    F, width = c["n_features"], 384
    table = r"f32\[%d,%d\]\{1,0:T\(8,128\)\}" % (F, width)

    def scatters(scope):
        return [line for line in text.splitlines()
                if re.search(r" scatter\(.*op_name=\"[^\"]*" + scope, line)]

    (update,) = scatters(r"while/body/ffm\.table_update")
    assert re.search(r"= " + table + r" scatter\(", update)
    assert "indices_are_sorted=true" not in update
    merge = scatters(r"ffm\.grad_merge/sparse\.segment_reduce")
    (added,) = [line for line in merge if "scatter-add" in line]
    assert "indices_are_sorted=true" in added
    sorts = [line for line in text.splitlines()
             if re.search(r" sort\(.*ffm\.grad_merge", line)]
    assert len(sorts) == 1 and "sparse.sort_by_key" in sorts[0]
    assert len(re.findall(r" sort\(", text)) == 1


@pytest.mark.parametrize("which", ["widen", "narrow"])
def test_adagrad_conversions_go_a_block_at_a_time(adagrad_programs, which):
    """5.27 GB in (parameters and accumulators, the shapes of (w0, w, V)
    twice) and 6.44 GB out, or the reverse, nothing between them."""
    m = adagrad_programs[which].memory_analysis()
    assert m.temp_size_in_bytes < 0.5e9, m.temp_size_in_bytes
    F = adagrad_programs["config"]["n_features"]
    text = adagrad_programs[which].as_text()
    for opcode in ("copy", "transpose", "pad", "concatenate"):
        assert _table_sized(text, opcode, F * 156 // 2) == [], opcode
    assert m.alias_size_in_bytes == 0       # the caller's arrays are kept
    sizes = sorted([m.argument_size_in_bytes, m.output_size_in_bytes])
    assert 5.26e9 < sizes[0] < 5.28e9 and 6.44e9 < sizes[1] < 6.45e9


# ------------------------------------- the FFM scoring cell (PR 36)
@pytest.fixture(scope="module")
def ffm_score_programs(topo_devices):
    """``FMTrainer.predict``'s scoring program at the size of
    ``ffm-criteo-score.file-zipf``, a piece's rows a call (ids, fields
    and values as each crossed, [M, 128] words a shard), on one
    described chip and on the four of the described host; the conversion
    that ``enter_model`` runs for a trainer whose step has another block
    (AdaGrad's), on one; and the scoring program's build span."""
    import json
    from pathlib import Path

    from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer
    from ytk_mp4j_tpu.obs import spans

    c = json.loads((Path(__file__).resolve().parent.parent / "benchmark"
                    / "configs" / "ffm-criteo-score.json").read_text())
    cfg = FMConfig(model=c["model"], n_features=c["n_features"],
                   n_fields=c["n_fields"], k=c["k"], max_nnz=c["max_nnz"],
                   loss=c["loss"], optimizer="adagrad")
    out = {"config": c}
    for chips in (1, 4):
        mesh = Mesh(np.asarray(topo_devices[:chips]), ("mp4j",))
        trainer = FMTrainer(cfg, mesh=mesh, sparse_grads=True)
        rows, rep = NamedSharding(mesh, P("mp4j")), NamedSharding(mesh, P())
        per = -(-c["rows"] // chips)
        chunk = (FMTrainer._EACH_CHUNK_BYTES // (3 * c["max_nnz"] * 4)
                 // 128 * 128)
        model = tuple(jax.ShapeDtypeStruct(shape, jnp.float32, sharding=rep)
                      for shape in ((), (c["n_features"], 256)))
        wire = (chips, chunk * c["max_nnz"] // 128, 128)
        spans.clear()
        program = trainer._build_score(wire, chunk)
        out[f"built_on_{chips}"] = [s[-1] for s in spans.snapshot()
                                    if s[0] == "mp4j.step.build"]
        out[f"score_on_{chips}"] = program.lower(
            *(jax.ShapeDtypeStruct(wire, d, sharding=rows)
              for d in (jnp.int32, jnp.int32, jnp.float32)), model,
            jax.ShapeDtypeStruct((chips, per), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()
        if chips == 1:
            public = tuple(
                jax.ShapeDtypeStruct(shape, jnp.float32, sharding=rep)
                for shape in ((), (c["n_features"],),
                              (trainer.n_rows, c["k"])))
            out["enter"] = trainer._build_converters(scoring=True)[0].lower(
                public).compile()
            out["chunk"], out["rows"] = chunk, per
    return out


def test_ffm_scoring_program_holds_a_tile_beside_its_arguments(
        ffm_score_programs):
    from ytk_mp4j_tpu.models import fm

    p = ffm_score_programs
    rows, chunk = p["rows"], p["chunk"]
    assert (rows, chunk) == (6_042_135, 286_720)
    built, = p["built_on_1"]
    assert built["key"] == "ffm_score" and built["rows"] == chunk
    assert built["tile"] == fm._SCORE_TILE
    assert built["tiles"] == -(-chunk // fm._SCORE_TILE)
    assert built["block_width"] == 256      # parameters only, AdaGrad or not
    assert built["select_columns"] == "component"
    for chips in (1, 4):
        m = p[f"score_on_{chips}"].memory_analysis()
        # the 4.29 GB table, a piece's three arrays, the probabilities:
        # nothing of the file's size
        piece = 3 * chunk * 39 * 4
        assert 2 ** 32 + piece <= m.argument_size_in_bytes \
            < 2 ** 32 + piece + rows // chips * 4 + 1e6
        # a piece's 286,720 rows gathered at once would be 11.5 GB
        assert m.temp_size_in_bytes < 1e9, m.temp_size_in_bytes
        assert m.argument_size_in_bytes + m.temp_size_in_bytes < 9e9
        # the probabilities where they rest, and the piece's first word
        assert 0 <= m.output_size_in_bytes - m.alias_size_in_bytes <= 4096
        assert m.alias_size_in_bytes >= rows // chips * 4


def test_ffm_scoring_program_reads_pieces_and_table_as_they_rest(
        ffm_score_programs):
    """The three arrays of a piece come in as [M, 128] words, as the host
    held them, and nothing of the file's size is there to copy; the
    table rests row-major and is gathered a block a (row, slot); it is
    not copied."""
    from ytk_mp4j_tpu.models import fm

    p = ffm_score_programs
    text = p["score_on_1"].as_text()
    F, tile = p["config"]["n_features"], fm._SCORE_TILE
    words = p["chunk"] * 39 // 128
    for k, dtype in enumerate(("s32", "s32", "f32")):
        assert "%s[1,%d,128]{2,1,0:T(8,128)} parameter(%d)" % (
            dtype, words, k) in text
    assert "f32[%d,256]{1,0:T(8,128)} parameter(4)" % F in text
    for opcode in ("copy", "transpose", "pad", "concatenate"):
        assert _table_sized(text, opcode, p["rows"] * 39) == [], opcode
    assert "stage.place" in text
    gathers = [ln for ln in text.splitlines() if " gather(" in ln]
    assert len(gathers) == 1 and "ffm.table_gather" in gathers[0]
    # 39 slots and an empty one: whole sublane tiles, so the gathered
    # blocks are the select's operand as they lie
    assert "f32[%d,40,256]" % tile in gathers[0]
    assert [ln for ln in text.splitlines()
            if " reshape(" in ln and "f32[%d,40,256]" % tile in ln] == []
    assert "slice_sizes={1,256}" in gathers[0]
    assert "ffm.score.select" in text and "ffm.score.pairs" in text
    assert "operand_precision={highest,highest}" in text
    assert "all-reduce" not in text and "all-gather" not in text
    assert "input_output_alias" in text
    four = p["score_on_4"].as_text()
    assert "all-reduce" not in four and "all-gather" not in four
    assert "all-to-all" not in four and "collective-permute" not in four


def _lanes(text, dtype="f32"):
    """(dimensions, size of the dimension on the lanes) of every array of
    ``dtype`` and rank two or more that the program's text names: the
    first index of a layout's minor-to-major list is the dimension that
    rests on the lanes."""
    found = set()
    for dims, layout in re.findall(
            r"\b%s\[((?:\d+,)+\d+)\]\{((?:\d+,)+\d+)" % dtype, text):
        dims = tuple(int(d) for d in dims.split(","))
        found.add((dims, dims[int(layout.split(",")[0])]))
    return found


@pytest.mark.parametrize("chips", [1, 4])
def test_ffm_scoring_program_never_rests_the_components_on_the_lanes(
        ffm_score_programs, chips, capsys):
    """The select hands the pairs a component's [K, K] matrix of a row
    as a run of slots (``E[n, a, j, b]``), so no array of the compiled
    program has the k = 4 components as the dimension that rests on the
    128 lanes (31 of 32 lanes would be padding). Its temporaries are
    reported: a tile's, and since ISSUE 52 the piece's three arrays put
    into rows of 39 slots (the piece itself crossed as whole words)."""
    p = ffm_score_programs
    k = p["config"]["k"]
    program = p[f"score_on_{chips}"]
    arrays = _lanes(program.as_text())
    tile = p["built_on_1"][0]["tile"]
    # the tile's own arrays are among them: the gathered blocks, and the
    # select's output cut into runs
    assert any(dims[0] == tile and dims[-1] == 256 for dims, _ in arrays)
    assert [dims for dims, lanes in arrays if lanes == k] == []
    # (XLA may still VIEW the rows-minor copy of the select's output as
    # [tile, K, K, k]: a bitcast, whose lanes hold the tile's rows)
    m = program.memory_analysis()
    with capsys.disabled():
        print(f"\nffm scoring program on {chips} chip(s), tile {tile}: "
              f"temporaries {m.temp_size_in_bytes / 1e6:.1f} MB, "
              f"arguments {m.argument_size_in_bytes / 1e9:.3f} GB")
    assert 0 < m.temp_size_in_bytes < 0.5e9, m.temp_size_in_bytes


def test_ffm_model_enters_a_block_at_a_time(ffm_score_programs):
    """2.63 GB in and 4.29 GB out whatever the trainer's rule: the block
    scoring reads has no accumulators."""
    m = ffm_score_programs["enter"].memory_analysis()
    assert m.temp_size_in_bytes < 0.5e9, m.temp_size_in_bytes
    assert m.alias_size_in_bytes == 0       # the caller's table is kept
    assert 2.6e9 < m.argument_size_in_bytes < 2.7e9
    assert 4.29e9 < m.output_size_in_bytes < 4.35e9


# ------------- the cell whose table no chip can hold (PR 38): four chips
@pytest.fixture(scope="module")
def sharded_programs(topo_devices):
    """The step and both conversions of
    ``ffm-criteo-sharded.stream-zipf-4chip`` at the cell's own size (2^25
    features, 8.59 GB of blocks a chip), compiled for the four described
    chips of the host, with the cell's chunk (2,048 rows a chip)."""
    import json
    from pathlib import Path

    from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer

    root = Path(__file__).resolve().parent.parent / "benchmark"
    c = json.loads((root / "configs/ffm-criteo-sharded.json").read_text())
    t = json.loads((root / "traffic/stream-zipf-4chip.json").read_text())
    chips = c["chips"]
    per = t["rows_per_chunk"] // chips
    mesh = Mesh(np.asarray(topo_devices[:chips]), ("mp4j",))
    trainer = FMTrainer(FMConfig(
        model=c["model"], n_features=c["n_features"],
        n_fields=c["n_fields"], k=c["k"], max_nnz=c["max_nnz"],
        learning_rate=c["learning_rate"]),
        mesh=mesh, sparse_grads=c["sparse_grads"],
        table_sharding=c["table_sharding"])
    lowered = trainer._build_step(per * c["max_nnz"]).lower(
        trainer._state_avals(),
        *_ffm_batch(mesh, chips, c, {"rows_per_chunk": per}))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("mp4j"))
    public = (jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
              jax.ShapeDtypeStruct((c["n_features"],), jnp.float32,
                                   sharding=rep),
              jax.ShapeDtypeStruct((trainer.n_rows_padded, c["k"]),
                                   jnp.float32, sharding=rows))
    widen, narrow = trainer._build_converters()
    return {
        "config": c, "chips": chips, "slots": per * c["max_nnz"],
        "lowered": lowered.as_text(),
        "step": lowered.compile(),
        "widen": widen.lower(public).compile(),
        "narrow": narrow.lower(trainer._state_avals()).compile(),
    }


def _collectives(text):
    """(result shape, opcode) of every collective of a compiled program."""
    return re.findall(r"= (\(?[^=]*?\)?) (all-to-all|all-gather|all-reduce|"
                      r"collective-permute|reduce-scatter)(?:-start)?\(",
                      text)


def test_sharded_step_updates_its_shard_where_it_rests(sharded_programs):
    p = sharded_programs
    c, text = p["config"], p["step"].as_text()
    mine = c["n_features"] // p["chips"]            # features a chip owns
    table = r"f32\[%d,256\]\{1,0:T\(8,128\)\}" % mine
    # a chip's share of the table by feature comes in row-major, donated,
    # and goes out in the same buffer: 8.59 GB, and nothing like it beside
    assert re.search(r"\(param\S*: f32\[\], param\S*: f32\[%d,256\]" % mine,
                     text)
    assert re.search(r"input_output_alias=\{.*\{1\}: \(1, \{\}, may-alias\)",
                     text)
    m = p["step"].memory_analysis()
    shard = mine * 256 * 4
    assert shard <= m.alias_size_in_bytes < shard + 2 ** 20
    assert m.temp_size_in_bytes < 1.0e9, m.temp_size_in_bytes
    for opcode in ("copy", "transpose", "pad", "concatenate"):
        assert _table_sized(text, opcode, mine * 256 // 2) == [], opcode
    assert re.search(r"= " + table + r" fusion\([^)]*\), kind=kCustom"
                     r".*ffm\.table_update", text)


def test_sharded_step_pays_for_what_a_chip_owns_and_touches(
        sharded_programs):
    """The owner's gather and scatter-add run a tile of 512 blocks at a
    time inside the walk over each member's list (a loop whose trips go
    with the list's live prefix): no gather or scatter of the table takes
    n x S slots (319,488 here), let alone n x N x K^2, and nothing of
    size [n_features] or [n_features / chips] is held."""
    p = sharded_programs
    text, S = p["step"].as_text(), p["slots"]
    custom = [ln for ln in text.splitlines()
              if " fusion(" in ln and "kind=kCustom" in ln]
    gathers = [ln for ln in custom if "ffm.table_gather" in ln]
    updates = [ln for ln in custom if "ffm.table_update" in ln]
    assert len(gathers) == len(updates) == p["chips"]   # one a member's list
    for ln in gathers:
        assert re.search(r"= f32\[512,256\]", ln), ln
        # the exchange's round loop, then the walk over a member's list
        # (since PR 50 under its own name)
        assert "while/body/sparse.fold_live_tiles/while/body" in ln, ln
    for ln in updates:
        assert "while/body/sparse.fold_live_tiles/while/body" in ln, ln
        assert "scatter-add" in ln, ln
    # the requester's own gathers and sums take its S slots, from lists
    # it holds itself, never from the table
    mine = p["config"]["n_features"] // p["chips"]
    shard = set(re.findall(r"(%%\S+) = f32\[%d,256\]" % mine, text))
    assert shard
    for ln in custom:
        if "ffm.table_" in ln:
            continue
        operands = re.search(r" fusion\(([^)]*)\)", ln).group(1).split(", ")
        assert not shard & set(operands), ln
    assert S == 79872
    V = p["config"]["n_features"]
    assert re.search(r"\[%d\]|\[%d\]" % (V, V // p["chips"]), text) is None


def test_sharded_step_exchanges_by_owner_and_reduces_scalars_only(
        sharded_programs):
    found = _collectives(sharded_programs["step"].as_text())
    exchanged = sorted(shape.split("{")[0] for shape, op in found
                       if op == "all-to-all")
    # ids out and blocks back, ids and gradients out: 16,384 a member a
    # round, 256 floats a block
    assert exchanged == ["f32[4,16384,256]", "f32[4,16384,256]",
                         "s32[4,1,16384]", "s32[4,1,16384]"]
    others = [(shape, op) for shape, op in found if op != "all-to-all"]
    assert {op for _, op in others} == {"all-reduce"}
    for shape, _ in others:     # the rounds' pmax; loss, weight sum, bias
        assert re.fullmatch(r"\(?((f32|s32)\[\]\S*,? ?)+\)?", shape), shape


@pytest.mark.parametrize("which", ["widen", "narrow"])
def test_sharded_conversions_stay_on_the_chip_that_owns_the_features(
        sharded_programs, which):
    """5.37 GB a chip in (its rows of the public table, and the weights)
    and 8.59 GB out, or the reverse, a block of features at a time; the
    table crosses no link, and the only collective is ``narrow``'s
    gathering of the linear weights into their replicated vector."""
    p = sharded_programs
    m = p[which].memory_analysis()
    assert m.temp_size_in_bytes < 0.5e9, m.temp_size_in_bytes
    assert m.alias_size_in_bytes == 0       # the caller's table is kept
    sizes = sorted([m.argument_size_in_bytes, m.output_size_in_bytes])
    assert 5.2e9 < sizes[0] < 5.4e9 and 8.58e9 < sizes[1] < 8.65e9
    text = p[which].as_text()
    mine = p["config"]["n_features"] // p["chips"]
    for opcode in ("copy", "transpose", "pad", "concatenate"):
        assert _table_sized(text, opcode, mine * 156 // 2) == [], opcode
    found = _collectives(text)
    if which == "widen":
        assert found == []
    else:
        assert [(shape.split("{")[0], op) for shape, op in found] == [
            ("f32[%d]" % p["config"]["n_features"], "all-gather")]


# What ``FMTrainer._build_step`` lowers for the sharded cell (jax 0.9.0
# with x64 on as ``conftest.py`` sets it, for the described v5e:2x2):
# sha256 of ``lowered.as_text()``, first taken at PR 38. A PR that changes
# the sharded step, ``ops/sparse``'s merge or ``ops/collectives.
# all_to_all`` on purpose, or a new jax, changes it with them.
SHARDED_STEP_LOWERED_SHA256 = (
    "b40337e7c767a6c6fd2caa2af88473392db837f1b3ea436297063564337d598a")


def test_sharded_step_lowers_to_the_program_it_was(sharded_programs):
    import hashlib

    text = sharded_programs["lowered"]
    assert "all_to_all" in text
    assert (hashlib.sha256(text.encode()).hexdigest()
            == SHARDED_STEP_LOWERED_SHA256)


# ------------------- which form of its gather XLA emits for the table (PR 40)
def _table_gathers(text, scope=r"ffm\.table_gather"):
    """``(rows, width, integer_config, scoped VMEM bytes, index operand)``
    of every native gather fusion under ``scope``: how many descriptors
    XLA issues at a time, what it holds of VMEM for them, and the fusion
    that makes its index list."""
    found = []
    for line in text.splitlines():
        if not (" fusion(" in line and "kind=kCustom" in line
                and re.search(scope + r"/gather", line)):
            continue
        rows, width = re.search(r"= \w+\[(\d+),(\d+)\]", line).groups()
        feeder = re.search(r" fusion\(%[^,)]+, %([^,)]+)\)", line).group(1)
        found.append((
            int(rows), int(width),
            int(re.search(r'"integer_config":\{"integer":"(\d+)"',
                          line).group(1)),
            int(re.search(r'"used_scoped_memory_configs":\[\{[^}]*'
                          r'"size":"(\d+)"', line).group(1)),
            re.sub(r"[.\d]+$", "", feeder)))
    return found


def test_table_gathers_detector():
    text = """
  %fusion = f32[80184,256]{1,0:T(8,128)S(1)} fusion(%params_1_.1, %pad_clamp_fusion.1), kind=kCustom, calls=%fused_computation, metadata={op_name="jit(step)/ffm.table_gather/gather" stack_frame_id=1}, backend_config={"flag_configs":[],"integer_config":{"integer":"256"},"scoped_memory_configs":[],"used_scoped_memory_configs":[{"memory_space":"1","offset":"0","size":"1048576"}],"retry_config":{"retry_count":"0"}}
  %fusion.1 = f32[79872,384]{1,0:T(8,128)} fusion(%params_1_.1, %broadcast_clamp_fusion), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(step)/ffm.table_gather/gather"}, backend_config={"integer_config":{"integer":"128"},"used_scoped_memory_configs":[{"memory_space":"1","offset":"0","size":"458752"}]}
  %fusion.17 = f32[79872,232]{1,0:T(8,128)} fusion(%param_0.71, %param_1.90), kind=kCustom, metadata={op_name="jit(step)/ffm.grad_merge/sparse.sort_by_key/gather"}
"""
    assert _table_gathers(text) == [
        (80184, 256, 256, 1048576, "pad_clamp_fusion"),
        (79872, 384, 128, 458752, "broadcast_clamp_fusion")]


# (fixture, program, the gathers' [rows, width] in the program's order)
_WIDE_GATHERS = {
    "sgd": ("ffm_programs", "step", [(80184, 256)]),
    "sgd_on_four": ("ffm_programs", "step_on_four", [(80184, 256)]),
    "adagrad": ("adagrad_programs", "step", [(768, 384), (80184, 384)]),
    "sharded": ("sharded_programs", "step", [(512, 256)] * 4),
    "score": ("ffm_score_programs", "score_on_1", [(1600 * 40, 256)]),
}


@pytest.mark.parametrize("which", list(_WIDE_GATHERS))
def test_every_table_gather_is_the_wide_form(request, which):
    """XLA emits the table gather 256 descriptors at a time, with 4 KB of
    scoped VMEM a descriptor's 1 KB block, where it pads the index list to
    the next 1,024 itself (``pad_clamp_fusion``), and 128 at a time where
    the list is whole 1,024s already (``broadcast_clamp_fusion``): 5.6 ns
    a block on the chip against 11.1 (PERF.md section 6, PR 40). Every
    program that gathers from the table holds the wide form only: the
    replicated steps because a chunk of 2,048 x 39 slots takes eight dead
    rows more (``fm._with_dead_rows``), the sharded step's tiles of 512
    and the scoring program's of 1,600 x 40 because they are off the
    1,024s as they are. And the steps cut nothing back in a pass of its
    own: no ``slice``, ``pad`` or ``copy`` of anything the size of the
    slots' blocks (the gathered blocks cut to the chunk's rows compile to
    one more pass over the 82 MB): the dead rows' slots leave with the
    tail of the merge's sorted order (``fm._merge_slots``)."""
    from ytk_mp4j_tpu.models import fm

    fixture, program, want = _WIDE_GATHERS[which]
    programs = request.getfixturevalue(fixture)
    text = programs[program].as_text()
    found = _table_gathers(text)
    assert sorted(g[:2] for g in found) == sorted(want), found
    for rows, width, at_a_time, vmem, feeder in found:
        assert at_a_time == 256, found
        assert vmem == 256 * width * 16, found
        assert feeder == "pad_clamp_fusion", found
    if "step_descriptors" in programs:
        cut = re.findall(
            r"= f32\[(\d+),%d\]\S* (?:slice|pad|copy|concatenate)\("
            % want[-1][1], text)
        # (the AdaGrad rule lays a tile's halves side by side: 768 rows)
        assert [n for n in cut if int(n) > fm._UPDATE_TILE] == [], cut
        # nor inside another instruction at a price: the gradient blocks
        # cut to the chunk's rows compile into the backward's last matmul,
        # which XLA then prices at 1e18 cycles and the chip runs in 536 ms
        # (PERF.md section 6, PR 40); the dearest here is under a million
        cycles = [int(n) for n in re.findall(r'"estimated_cycles":"(\d+)"',
                                             text)]
        assert cycles and max(cycles) < 10 ** 7, max(cycles)


# The raw front end's programs at the size of
# benchmark/configs/gbdt-bosch-968-raw (ISSUE 44): the staged floats rest
# as the bins do, [F, N] in (8, 128) tiles; a reader's chunk of 65,536
# rows crosses in two pieces of 32,768 and the placer puts a piece into
# the donated table; the sketch sorts eight columns at a time as they
# rest and holds little beside the table; the transform reads the floats
# and writes the bins in one pass, in the layout the step's parameter(0)
# has (``test_wide_step_reads_the_table_as_it_rests``).
RAW_CHUNK_ROWS = 65_536                 # the configuration's chunk_rows
RAW_PIECE_ROWS = RAW_CHUNK_ROWS // 2    # under _EACH_CHUNK_BYTES


@pytest.fixture(scope="module")
def raw_programs(topo_devices):
    from jax.sharding import SingleDeviceSharding

    from ytk_mp4j_tpu.models import binning

    mesh = Mesh(np.asarray(topo_devices[:1]), ("mp4j",))
    rows, whole = NamedSharding(mesh, P("mp4j")), NamedSharding(mesh, P())
    one = SingleDeviceSharding(topo_devices[0])
    table = jax.ShapeDtypeStruct((1, WIDE_ROWS, WIDE_F), jnp.float32,
                                 sharding=rows)
    trainer = GBDTTrainer(GBDTConfig(n_features=WIDE_F, n_bins=B,
                                     missing_bin=True), mesh=mesh)
    assert (-(-RAW_CHUNK_ROWS * WIDE_F * 4 // trainer._EACH_CHUNK_BYTES)
            == RAW_CHUNK_ROWS // RAW_PIECE_ROWS)
    wire = (RAW_PIECE_ROWS * WIDE_F // 128, 128)

    def transform(chips):
        members = Mesh(np.asarray(topo_devices[:chips]), ("mp4j",))
        staged = NamedSharding(members, P("mp4j"))
        return binning._transform_program(True, B - 2, staged).lower(
            jax.ShapeDtypeStruct((chips, -(-WIDE_ROWS // chips), WIDE_F),
                                 jnp.float32, sharding=staged),
            jax.ShapeDtypeStruct((WIDE_F, B - 2), jnp.float32,
                                 sharding=NamedSharding(members, P()))
        ).compile()

    with pytest.MonkeyPatch.context() as patch:
        # the transform as it is built on a TPU (here the backend is the
        # CPU and ``_count_edges`` would take its ``jnp`` form)
        patch.setattr(binning, "_kernel_compiles", lambda: True)
        transforms = {chips: transform(chips) for chips in (1, 4)}
        binning._transform_program.cache_clear()
    return {
        "placer": trainer._row_chunk_placer(
            WIDE_ROWS, WIDE_F, RAW_PIECE_ROWS, wire).lower(
                jax.ShapeDtypeStruct((1, WIDE_ROWS, WIDE_F), jnp.float32,
                                     sharding=one),
                jax.ShapeDtypeStruct(wire, jnp.float32, sharding=one),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile(),
        "transform": transforms[1], "transform_on_four": transforms[4],
        "sketch": binning._sketch_program(B - 1, rows).lower(
            table, jax.ShapeDtypeStruct((WIDE_ROWS,), jnp.bool_,
                                        sharding=whole)).compile()}


def test_raw_placer_puts_a_piece_into_the_table_it_was_given(raw_programs):
    compiled = raw_programs["placer"]
    mem = compiled.memory_analysis()
    # the table is donated and updated where it rests: never held twice
    assert mem.alias_size_in_bytes >= WIDE_TABLE_BYTES
    # beside it the piece, as it crossed and in the table's tiles
    assert mem.temp_size_in_bytes < 3 * RAW_PIECE_ROWS * WIDE_F * 4
    assert re.search(r"f32\[1,%d,%d\]\{1,2,0:T\(8,128\)\} parameter\(0\)"
                     % (WIDE_ROWS, WIDE_F), compiled.as_text())


def test_train_placer_puts_a_piece_into_the_table_it_was_given(topo_devices):
    """``_put_in_row_chunks``' placer at the Bosch cell's size, a 128 MiB
    piece as ``train()`` stages it (``predict`` scores the same piece and
    places none, ISSUE 52): the donated table
    is updated where it rests, and nothing else as large as it is made."""
    mesh = Mesh(np.asarray(topo_devices[:1]), ("mp4j",))
    rows, whole = NamedSharding(mesh, P("mp4j")), NamedSharding(mesh, P())
    trainer = GBDTTrainer(GBDTConfig(n_features=WIDE_F, n_bins=B,
                                     missing_bin=True), mesh=mesh)
    piece = SCORE_CHUNK_ROWS * WIDE_F * 4
    assert piece <= trainer._EACH_CHUNK_BYTES < piece + 128 * WIDE_F * 4
    compiled = trainer._build_row_placer(
        (1, SCORE_CHUNK_ROWS, WIDE_F)).lower(
            jax.ShapeDtypeStruct((1, WIDE_ROWS, WIDE_F), jnp.int32,
                                 sharding=rows),
            jax.ShapeDtypeStruct((1, piece // 512, 128), jnp.int32,
                                 sharding=rows),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=whole)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= WIDE_TABLE_BYTES
    # beside the table the piece, as it crossed and in the table's tiles
    assert mem.temp_size_in_bytes < 3 * piece
    text = compiled.as_text()
    table = r"s32\[1,%d,%d\]\{1,2,0:T\(8,128\)\}" % (WIDE_ROWS, WIDE_F)
    assert re.search(table + r" parameter\(0\)", text)
    assert "input_output_alias" in text
    made = re.findall(r"= %s (\S+?)\(" % table, text)
    assert made == ["parameter", "dynamic-update-slice"], made


def _kernel_call(text, name="mp4j_bin"):
    """(the ``name`` kernel's custom call, the instruction that makes its
    first operand), as the compiled text prints them."""
    (call,) = re.findall(
        r"^\s*(%%%s\S* = .*custom-call\(.*tpu_custom_call.*)$" % name, text,
        re.M)
    operand = re.search(r"custom-call\((%[\w.-]+)", call).group(1)
    (made,) = re.findall(r"^\s*(%s = .*)$" % re.escape(operand), text,
                         re.M)
    return call, made


@pytest.mark.parametrize("which,chips", [("transform", 1),
                                         ("transform_on_four", 4)])
def test_transform_writes_the_bins_where_the_step_reads_them(raw_programs,
                                                             which, chips):
    """The Mosaic compiler takes the search (a libtpu that refused the
    lane gather would fail the fixture), the float table reaches the
    kernel as it rests, by a bitcast, and the kernel's result is the
    binned table as the step reads it, by another: nothing of the
    table's size is copied, padded, transposed or fused, on one chip
    and on a member of four."""
    compiled = raw_programs[which]
    text = compiled.as_text()
    rows = -(-WIDE_ROWS // chips)
    resting = r"\[1,%d,%d\]\{1,2,0:T\(8,128\)\}" % (rows, WIDE_F)
    kernel = r"\[%d,%d\]\{1,0:T\(8,128\)\}" % (WIDE_F, rows)
    (floats,) = re.findall(r"(%%\S+) = f32%s parameter\(0\)" % resting, text)
    call, operand = _kernel_call(text)
    assert re.search(r"= s32%s custom-call\(" % kernel, call)
    assert "bin.transform/mp4j_bin" in call
    assert re.search(r"= f32%s bitcast\(%s\)" % (kernel, re.escape(floats)),
                     operand), operand
    assert re.search(r"ROOT \S+ = s32%s bitcast\(%%mp4j_bin" % resting, text)
    assert text.count("tpu_custom_call") == 1
    made = re.findall(
        r"= \w+\[[\d,]*\d{6,}[\d,]*\]\S* (\w[\w-]*)\(", text)
    assert sorted(made) == ["bitcast", "bitcast", "custom-call",
                            "parameter"], made
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < WIDE_TABLE_BYTES // chips // 1000
    assert mem.output_size_in_bytes < WIDE_TABLE_BYTES // chips * 1.001


def test_kernel_call_detector():
    """The detector reads the lines the compiled text holds, and a copy
    that fed the kernel would be the operand it reports."""
    text = """
  %bitcast.1 = f32[968,1183747]{1,0:T(8,128)} bitcast(%X.1), metadata={op_name="jit(program)/bin.transform/transpose"}
  %mp4j_bin.1 = s32[968,1183747]{1,0:T(8,128)} custom-call(%bitcast.1, %concatenate.1), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[968,1183747]{1,0}, f32[968,256]{1,0}}
"""
    call, operand = _kernel_call(text)
    assert call.startswith("%mp4j_bin.1 = s32[968,1183747]")
    assert operand.startswith("%bitcast.1 = f32[968,1183747]")
    relaid = text.replace("bitcast(%X.1)", "copy(%X.1)")
    assert " copy(" in _kernel_call(relaid)[1]


def test_sketch_holds_a_block_of_columns_beside_the_table(raw_programs):
    compiled = raw_programs["sketch"]
    text = compiled.as_text()
    # one sort, in the loop over column blocks, of every row of eight
    # columns, as the table rests: the rows along the lanes
    (sort,) = re.findall(r"= f32\[(\d+),(\d+)\]\{0,1:\S* sort\(", text)
    assert sort == (str(WIDE_ROWS), "8")
    for scope in ("bin.sketch.gather", "bin.sketch.sort", "bin.sketch.edges"):
        assert re.search(r"/while/body/(\w+/)?%s/" % re.escape(scope),
                         text), scope
    # the blocks in flight and the mask: 0.61 GB when this was written
    assert compiled.memory_analysis().temp_size_in_bytes \
        < WIDE_TABLE_BYTES // 4


# Scoring from floats (ISSUE 47, benchmark/configs/
# gbdt-bosch-score-raw-500.json): the reader's chunk of 65,536 rows
# crosses in two pieces of 32,768 and each piece is binned and scored by
# one call of the scoring program as it crossed (ISSUE 52: the program
# takes the piece, no table of floats rests anywhere); the last chunk's
# 4,100 rows take a program of their own. A piece's bins exist for the
# length of a call.
RAW_SCORE_LAST_ROWS = SCORE_ROWS - 18 * RAW_CHUNK_ROWS


def _float_wire(piece):
    """A piece of ``piece`` rows as ``_reader_cuts`` sends it."""
    return ((piece * WIDE_F // 128, 128) if piece * WIDE_F % 128 == 0
            else (piece, WIDE_F))


def _float_score(trainer, piece, stacked, one):
    """(the scoring program for a piece of ``piece`` rows of floats at
    the cell's size, compiled for one device; its build span's
    arguments)."""
    from ytk_mp4j_tpu.obs import spans

    wire = _float_wire(piece)
    spans.clear()
    program = trainer._build_score(wire, piece, SCORE_TREES, (B - 2, True))
    (built,) = [s[-1] for s in spans.snapshot()
                if s[0] == "mp4j.step.build"]
    return program.lower(
        jax.ShapeDtypeStruct(wire, jnp.float32, sharding=one), stacked,
        jax.ShapeDtypeStruct((1, 1, SCORE_ROWS), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((WIDE_F, B - 2), jnp.float32,
                             sharding=one)).compile(), built


def test_float_scoring_programs_bin_the_piece_they_are_handed(
        topo_devices):
    """Both programs of the cell compile for the chip, take a piece of
    floats as it crossed and nothing of the table's size, and make of
    its rows no more than their relaid copy, their counts and their bf16
    digits. Compiled with 64-bit types off, as the cell and every user
    who has not asked for them run it (``tests/conftest.py`` turns them
    on)."""
    from jax.sharding import SingleDeviceSharding

    from ytk_mp4j_tpu.models import binning
    from ytk_mp4j_tpu.models.gbdt import score_group_size

    mesh = Mesh(np.asarray(topo_devices[:1]), ("mp4j",))
    trainer = GBDTTrainer(GBDTConfig(n_features=WIDE_F, n_bins=B,
                                     depth=DEPTH, loss="logistic",
                                     missing_bin=True), mesh=mesh)
    assert RAW_SCORE_LAST_ROWS == 4_100
    assert (-(-RAW_CHUNK_ROWS * WIDE_F * 4 // trainer._EACH_CHUNK_BYTES)
            == RAW_CHUNK_ROWS // RAW_PIECE_ROWS)
    one = SingleDeviceSharding(topo_devices[0])
    group = score_group_size(SCORE_TREES)
    shape = (-(-SCORE_TREES // group), 2 ** DEPTH, group, 1)
    stacked = tuple(jax.ShapeDtypeStruct(shape, d, sharding=one)
                    for d in (jnp.int32, jnp.int32, jnp.int32, jnp.float32))
    for piece in (RAW_PIECE_ROWS, RAW_SCORE_LAST_ROWS):
        with jax.enable_x64(False), pytest.MonkeyPatch.context() as patch:
            # as on a TPU: the backend here is the CPU
            patch.setattr(binning, "_kernel_compiles", lambda: True)
            compiled, built = _float_score(trainer, piece, stacked, one)
        assert built == {"key": "gbdt_score_raw", "edges": B - 2,
                         "compares": 8, "bin_block_columns": 8,
                         "bin_block_rows": 4096,
                         "form": "bins", "group": group, "rows": piece,
                         "row_chunk": piece, "row_chunks": 1}
        mem = compiled.memory_analysis()
        # the piece, the margins, the ensemble and the edges: no table
        assert piece * WIDE_F * 4 <= mem.argument_size_in_bytes \
            < piece * WIDE_F * 4 + 8e6
        # the piece's floats as the kernel reads them, its counts (int32,
        # the kernel's) and its bf16 digits: 10 bytes a cell of the
        # piece, 0.32 GB at 32,768 rows; and the margins are updated
        # where they rest
        assert mem.temp_size_in_bytes < 3 * piece * WIDE_F * 4, piece
        text = compiled.as_text()
        assert "input_output_alias" in text
        wire = ",".join(str(d) for d in _float_wire(piece))
        assert re.search(r"f32\[%s\]\{\S+\} parameter\(0\)" % wire, text)
        # nothing of a table's size is there or made, and nothing is
        # sliced: the margins alone have the table's rows
        made = re.findall(
            r"= \w+\[[\d,]*\d{6,},[\d,]*\d{3,}[\d,]*\]\S* (\w[\w-]*)\(",
            text)
        assert made in ([], ["parameter"]), made     # the piece, as words
        assert _dynamic_slices(text) <= {"1,%d,%d,1" % (2 ** DEPTH, group)}
        updates = re.findall(r"= (\w+\[[\d,]+\])\S* dynamic-update-slice\(",
                             text)
        assert updates == ["f32[1,1,%d]" % SCORE_ROWS], updates
        # the piece is put to rest once, rows along the lanes, under the
        # placers' name, and that is what the kernel reads; its counts
        # are what the digits are made of: nothing is transposed after
        # the kernel
        call, operand = _kernel_call(text)
        resting = r"\[%d,%d\]\{1,0:T\(8,128\)" % (
            WIDE_F, _whole_lane_words(piece))
        assert re.search(r"= s32%s\S* custom-call\(" % resting, call)
        assert "bin.transform/mp4j_bin" in call
        assert "stage.place" in text
        assert re.search(
            r"= bf16%s\(2,1\)\S* (?:fusion|convert)\(%%mp4j_bin" % resting, text)
        assert not re.search(r"s32\[%d,%d\]\S* (?:copy|transpose)\("
                             % (piece, WIDE_F), text)
        assert not re.search(r"s32\[%d,%d\]\S* (?:copy|transpose)\("
                             % (WIDE_F, piece), text)
        assert "gbdt.score.select/dot_general" in text
        assert "gbdt.score.walk" in text
        assert " gather(" not in text
        assert text.count("tpu_custom_call") == 1


# ------------------------------------------------------------------------
# ISSUE 50: the scopes a device trace reads the steps by are in the
# programs compiled for the v5e at the cells' sizes, and they are metadata:
# the fixtures above are the texts, and nothing here compiles for minutes.
def _op_names(text, pattern=""):
    return [m for m in re.findall(r'op_name="([^"]*)"', text)
            if re.search(pattern, m)]


@pytest.mark.parametrize("which", ["step_one_chip", "wide_step"])
def test_every_kernel_call_says_its_level(request, which):
    """What ``gbdt_hist_level0_ms_per_tree`` / ``..level5..`` find: each
    of the six kernel calls under its own ``gbdt.level.<d>``, inside the
    ``gbdt.hist`` the accepted metrics search for."""
    text = request.getfixturevalue(which).as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert sorted(m for ln in calls for m in _op_names(ln)) == [
        f"jit(step)/gbdt.level.{d}/gbdt.hist/mp4j_hist/pallas_call"
        for d in range(DEPTH)]
    for part in ("gbdt.route", "gbdt.best_splits"):
        assert {m.split("/")[1] for m in _op_names(text, re.escape(part))} \
            == {f"gbdt.level.{d}" for d in range(DEPTH)}
    assert not _op_names(text, r"gbdt\.level\.\d/.*gbdt\.leaf")


@pytest.mark.parametrize("which,inside_the_loop", [
    ("ffm_programs", ["ffm.table_update"]),
    ("adagrad_programs", ["ffm.table_gather", "ffm.adagrad_rule",
                          "ffm.table_update"]),
    ("sharded_programs", ["ffm.table_gather", "ffm.table_update"]),
])
def test_ffm_steps_say_select_pairs_and_backward(request, which,
                                                 inside_the_loop):
    """What ``ffm_select_ms_per_chunk``, ``ffm_pairs_ms_per_chunk`` and
    ``ffm_backward_ms_per_chunk`` find, and the loop's own name round the
    scopes the accepted metrics read."""
    text = request.getfixturevalue(which)["step"].as_text()
    for stack in ("jvp(ffm.select)", "transpose(jvp(ffm.select))",
                  "jvp(ffm.pairs)", "transpose(jvp(ffm.pairs))"):
        assert _op_names(text, rf"/{re.escape(stack)}/"), stack
    for scope in inside_the_loop:
        assert _op_names(
            text, rf"sparse\.fold_live_tiles/while/body/{re.escape(scope)}")
    assert not _op_names(text, r"ffm\.score\.")


def test_scopes_change_nothing_of_the_step_compiled_for_the_chip(
        step_one_chip, topo_devices, monkeypatch):
    """``tests/test_trainer_scopes.py``'s comparison by the TPU's own
    compiler: the GBDT step at 1M x 28 and both placers at the Bosch
    cells' sizes, compiled once more with ``jax.named_scope`` a null
    context, are the programs the fixtures hold. (By hand, PR 50, the same
    held of the Bosch step and of the three FFM steps at their cells'
    sizes: 32 to 100 s a compile, so not here.)"""
    import contextlib

    from tests.helpers import program_without_provenance as program

    def placers():
        mesh = Mesh(np.asarray(topo_devices[:1]), ("mp4j",))
        rows, whole = NamedSharding(mesh, P("mp4j")), NamedSharding(mesh, P())
        trainer = GBDTTrainer(GBDTConfig(n_features=WIDE_F, n_bins=B,
                                         missing_bin=True), mesh=mesh)
        piece = SCORE_CHUNK_ROWS * WIDE_F
        wire = (RAW_PIECE_ROWS * WIDE_F // 128, 128)
        yield trainer._build_row_placer((1, SCORE_CHUNK_ROWS, WIDE_F)).lower(
            jax.ShapeDtypeStruct((1, WIDE_ROWS, WIDE_F), jnp.int32,
                                 sharding=rows),
            jax.ShapeDtypeStruct((1, piece // 128, 128), jnp.int32,
                                 sharding=rows),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=whole)).compile()
        yield trainer._row_chunk_placer(
            WIDE_ROWS, WIDE_F, RAW_PIECE_ROWS, wire).lower(
                jax.ShapeDtypeStruct((1, WIDE_ROWS, WIDE_F), jnp.float32,
                                     sharding=rows),
                jax.ShapeDtypeStruct(wire, jnp.float32, sharding=whole),
                jax.ShapeDtypeStruct((), jnp.int32,
                                     sharding=whole)).compile()

    as_written = [step_one_chip.as_text()] + [c.as_text() for c in placers()]
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = ([_compile_step(topo_devices, 1).as_text()]
               + [c.as_text() for c in placers()])
    for a, b, scope in zip(as_written, without,
                           ("gbdt.level.5", "stage.place", "stage.place")):
        assert scope in a and scope not in b
        assert program(a) == program(b), scope
