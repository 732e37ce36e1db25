"""The training steps' and the row placers' names for a device trace
(ISSUE 50): ``ffm.select`` / ``ffm.pairs`` in the three sparse FFM steps
(forward as ``jvp(...)``, backward as ``transpose(jvp(...))``),
``sparse.fold_live_tiles`` round their update loops, ``gbdt.level.<d>``
round every level of a tree, ``stage.place`` inside both placers and,
since ISSUE 52, round the relayout of the piece a scoring program takes.

(a) the lowered programs hold them; (b) they are metadata and nothing
else: compiled as written and with ``jax.named_scope`` a null context,
the optimised text is the same once ``metadata={...}`` is stripped; (c)
every scope the program enters in ``models/`` and ``ops/`` is one the
residual metric of the cells that run it lists (or a listed wrapper), so
that a later scope cannot fall silently into "unscoped"."""

import ast
import contextlib
import json
import os
import re
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer
from ytk_mp4j_tpu.parallel.mesh import make_mesh

from test_trainer_spans import (_lower_chunk_placer, _lower_ffm,
                                 _lower_placer)
from tests.helpers import program_without_provenance as _stripped

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEPTH = 6           # every GBDT configuration of the benchmark


def _lower_gbdt(rng, n_features, **cfg):
    """The GBDT step at the cells' depth and width, on a few rows."""
    n_rows, n_bins = 256, 256
    bins = rng.integers(0, n_bins, (n_rows, n_features)).astype(np.int32)
    tr = GBDTTrainer(GBDTConfig(n_features=n_features, n_bins=n_bins,
                                depth=DEPTH, loss="logistic", **cfg),
                     mesh=make_mesh(2))
    data = tr.shard_data(bins, (bins[:, 0] > 128).astype(np.float32))
    if tr.cfg.grow_policy == "loss":    # with the table's second form
        data += (tr._build_pack()(data[0]),)
    return tr._build_step().lower(
        *data[:4], jax.random.key_data(jax.random.key(0)), *data[4:])


def _stacked(shape, sharding=None):
    return tuple(jax.ShapeDtypeStruct(shape, d, sharding=sharding)
                 for d in (jnp.int32, jnp.int32, jnp.int32, jnp.float32))


def _lower_score_bins(rng, rows=128):
    """``GBDTTrainer.predict``'s program for a piece of a binned table as
    it crossed, a slice of every shard as [n, M, 128] words (``rows``
    under 128: a last piece that began early)."""
    F, held, per = 16, 128, 1000
    tr = GBDTTrainer(GBDTConfig(n_features=F, n_bins=256, depth=3,
                                loss="logistic", missing_bin=True),
                     mesh=make_mesh(2))
    sharding = tr._row_sharding()
    wire = (2, held * F // 128, 128)
    return tr._build_score(wire, rows, 5).lower(
        jax.ShapeDtypeStruct(wire, jnp.int32, sharding=sharding),
        _stacked((1, 8, 5, 1)),
        jax.ShapeDtypeStruct((2, 1, per), jnp.float32, sharding=sharding),
        np.int32(0))


def _lower_score_floats(rng):
    """``predict_raw_chunks``' program for a piece of floats that went
    to one device, [M, 128] words."""
    F, held, per = 16, 64, 1000
    tr = GBDTTrainer(GBDTConfig(n_features=F, n_bins=32, depth=3,
                                loss="logistic", missing_bin=True),
                     mesh=make_mesh(2))
    wire = (held * F // 128, 128)
    return tr._build_score(wire, held, 5, (30, True)).lower(
        jax.ShapeDtypeStruct(wire, jnp.float32), _stacked((1, 8, 5, 1)),
        jax.ShapeDtypeStruct((1, 1, per), jnp.float32), np.int32(0),
        jax.ShapeDtypeStruct((F, 30), jnp.float32))


def _lower_score_ffm(rng):
    """``FMTrainer.predict``'s program for a piece of instances: ids,
    fields and values as each crossed, three operands."""
    from ytk_mp4j_tpu.models import fm
    from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer

    K, held, per = 8, 64, 1000
    tr = FMTrainer(FMConfig(n_features=120, n_fields=5, k=4, max_nnz=K,
                            model="ffm"), mesh=make_mesh(2))
    sharding = tr._row_sharding()
    wire = (2, held * K // 128, 128)
    return tr._build_score(wire, held).lower(
        *(jax.ShapeDtypeStruct(wire, d, sharding=sharding)
          for d in (jnp.int32, jnp.int32, jnp.float32)),
        (jax.ShapeDtypeStruct((), jnp.float32),
         jax.ShapeDtypeStruct((120, fm._block_width(tr._score_cfg)),
                              jnp.float32)),
        jax.ShapeDtypeStruct((2, per), jnp.float32, sharding=sharding),
        np.int32(0))


def _lower_gbdt_leafwise(rng):
    """The step that grows its trees leaf by leaf (ISSUE 53), at the
    new cell's width and cap on depth, a small budget of leaves."""
    return _lower_gbdt(rng, 968, missing_bin=True, grow_policy="loss",
                       max_leaves=6)


def _lower_gbdt_pack(rng):
    """The program a leaf-wise job's staging runs once its table is
    placed (ISSUE 54): the table's second form, a row a descriptor."""
    tr = GBDTTrainer(GBDTConfig(n_features=968, n_bins=256, depth=DEPTH,
                                loss="logistic", missing_bin=True,
                                grow_policy="loss", max_leaves=6),
                     mesh=make_mesh(2))
    return tr._build_pack().lower(jax.ShapeDtypeStruct(
        (2, 128, 968), jnp.int32, sharding=tr._row_sharding()))


FFM_STEP = ["jvp(ffm.select)", "transpose(jvp(ffm.select))",
            "jvp(ffm.pairs)", "transpose(jvp(ffm.pairs))", "ffm.grad_merge"]
LOOP = "sparse.fold_live_tiles"
LEVELS = [f"gbdt.level.{d}/gbdt.{part}" for d in range(DEPTH)
          for part in ("hist", "best_splits", "route")]
# program: (its lowering, the name stacks it must hold, a new scope that
# the null context must take out of the compiled text)
PROGRAMS = {
    "ffm-sgd": (_lower_ffm,
                FFM_STEP + [f"{LOOP}/while/body/ffm.table_update"],
                "ffm.select"),
    "ffm-adagrad": (partial(_lower_ffm, optimizer="adagrad"),
                    FFM_STEP + [f"{LOOP}/while/body/ffm.{part}" for part in
                                ("table_gather", "adagrad_rule",
                                 "table_update")],
                    "ffm.pairs"),
    "ffm-sharded": (partial(_lower_ffm, table_sharding="sharded"),
                    FFM_STEP + [f"{LOOP}/while/body/ffm.table_gather",
                                f"{LOOP}/while/body/ffm.table_update"],
                    LOOP),
    "gbdt-higgs": (partial(_lower_gbdt, n_features=28), LEVELS,
                   "gbdt.level.5"),
    "gbdt-bosch": (partial(_lower_gbdt, n_features=968, missing_bin=True),
                   LEVELS, "gbdt.level.0"),
    # the splits are a loop of the program, under no level's name
    "gbdt-leafwise": (_lower_gbdt_leafwise,
                      ["gbdt.hist", "gbdt.best_splits", "gbdt.route",
                       "gbdt.grow.pick", "gbdt.grow.book", "gbdt.leaf",
                       "gbdt.grow.book/gbdt.grow.compact"],
                      "gbdt.grow.pick"),
    "gbdt-pack": (_lower_gbdt_pack, ["stage.place/gbdt.grow.pack"],
                  "gbdt.grow.pack"),
    "placer": (_lower_placer, ["stage.place"], "stage.place"),
    # the scoring programs take the piece that crossed (ISSUE 52): its
    # relayout is theirs, under the placers' name
    "score-bins": (_lower_score_bins,
                   ["stage.place", "gbdt.score.select", "gbdt.score.walk"],
                   "stage.place"),
    "score-bins-rest": (partial(_lower_score_bins, rows=40),
                        ["stage.place", "gbdt.score.select",
                         "gbdt.score.walk"], "gbdt.score.select"),
    "score-floats": (_lower_score_floats,
                     ["stage.place", "bin.transform", "gbdt.score.select",
                      "gbdt.score.walk"], "stage.place"),
    "score-ffm": (_lower_score_ffm,
                  ["stage.place", "ffm.table_gather", "ffm.score.select",
                   "ffm.score.pairs"], "stage.place"),
    "placer-chunks": (_lower_chunk_placer, ["stage.place"], "stage.place"),
}


def _lowered(program):
    return PROGRAMS[program][0](np.random.default_rng(0))


# ------------------------------------------------- (a) the names are there
@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_lowered_program_holds_the_new_scopes(program):
    text = _lowered(program).as_text(debug_info=True)
    for stack in PROGRAMS[program][1]:
        # a name stack, not a file's path: the scopes are whole components
        assert re.search(rf'loc\("(?:[^"]*/)?{re.escape(stack)}[/"]', text), \
            stack
    # the scoring programs' names stay the scoring programs'
    assert ("ffm.score." in text) == (program == "score-ffm")
    assert ("gbdt.score." in text) == (
        program.startswith("score-") and program != "score-ffm")


# what a scoring program may slice or update in place: a piece or the
# results, never an array of the input's size (there is none to take)
@pytest.mark.parametrize("program,largest", [
    ("score-bins", 2 * 1000), ("score-bins-rest", 2 * 128 * 16),
    ("score-floats", 1000), ("score-ffm", 2 * 1000)])
def test_a_scoring_program_slices_no_table(program, largest):
    """``dynamic_slice`` and ``dynamic_update_slice`` in the lowered
    text: of a piece's rows (a last piece's, or a tile's) and of the
    results, whose sizes are known here; nothing else is taken apart."""
    text = _lowered(program).as_text()
    ops = re.findall(r"stablehlo\.dynamic_(?:update_)?slice.*?: \(tensor<"
                     r"([\dx]+)x\w+>", text)
    assert ops, text[:400]
    for dims in ops:
        assert np.prod([int(d) for d in dims.split("x")]) <= largest, dims
    # the piece comes in as it crossed and is put into rows in here
    assert re.search(r"stablehlo\.reshape.*tensor<[\dx]*128x\w+>\) -> ", text)


def test_a_level_wraps_its_scopes_and_hides_none():
    """``gbdt.hist`` is still found unanchored (the accepted metrics'
    regexes), once a level, and every level holds its own."""
    text = _lowered("gbdt-higgs").as_text(debug_info=True)
    stacks = set(re.findall(r'loc\("([^"]*)"', text))
    for d in range(DEPTH):
        # (inside ``shard_map`` a stack starts at the level)
        inside = {s for s in stacks if f"/gbdt.level.{d}/" in f"/{s}"}
        for part in ("gbdt.hist", "gbdt.best_splits", "gbdt.route"):
            assert any(re.search(re.escape(part) + r"(/|$)", s)
                       for s in inside), (d, part)
    assert not any("gbdt.level.6" in s for s in stacks)
    # the leaves are the tree's, under no level
    assert any("gbdt.leaf" in s for s in stacks)
    assert not any("gbdt.level" in s and "gbdt.leaf" in s for s in stacks)


# ------------------------------------------ (b) and they are nothing else
@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_scopes_change_nothing_of_the_compiled_program(program, monkeypatch):
    """Off is the only state a scope has: the optimised text as written
    and with ``jax.named_scope`` a null context, ``metadata={...}``
    stripped from both, is one text (the CPU's compiler here;
    ``tests/test_gbdt_aot.py`` does the same for a described v5e)."""
    scope = PROGRAMS[program][2]
    as_written = _lowered(program).compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _lowered(program).compile().as_text()
    assert scope in as_written and "metadata={" in as_written
    assert scope not in without
    assert "metadata={" not in _stripped(as_written)
    assert "StackFrames" not in _stripped(as_written)
    assert _stripped(as_written) == _stripped(without)
    assert len(as_written.splitlines()) > 10


# ----------------------------- (c) no scope falls silently into "unscoped"
WRAPPERS = {"gbdt.level.<d>"}       # name what they wrap: never listed
# Entered only inside a listed scope, which names them with all else it
# holds: the scope each must stand under and a program that enters it
# (ISSUE 54: the residual's file is the benchmark's, and a later
# ``benchmark`` PR can give an inner scope an entry of its own)
INNER = {"gbdt.grow.compact": ("gbdt.grow.book", "gbdt-leafwise"),
         "gbdt.grow.pack": ("stage.place", "gbdt-pack")}


def _entered_scopes():
    """Every string a ``jax.named_scope(...)`` of ``models/`` and ``ops/``
    can be handed: literals, both arms of a conditional, and an f-string
    with ``<d>`` where it formats a value; with the file it is in."""
    found = []
    for sub in ("models", "ops"):
        folder = os.path.join(ROOT, "ytk_mp4j_tpu", sub)
        for name in sorted(os.listdir(folder)):
            if not name.endswith(".py"):
                continue
            with open(os.path.join(folder, name), encoding="utf-8") as fh:
                tree = ast.parse(fh.read())
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "named_scope"):
                    continue
                arg, = node.args
                if isinstance(arg, ast.JoinedStr):
                    found.append((f"{sub}/{name}", "".join(
                        part.value if isinstance(part, ast.Constant)
                        else "<d>" for part in arg.values)))
                    continue
                texts = [n.value for n in ast.walk(arg)
                         if isinstance(n, ast.Constant)
                         and isinstance(n.value, str)]
                assert texts, f"{sub}/{name}:{node.lineno}: a scope this " \
                              f"test cannot read"
                found.extend((f"{sub}/{name}", t) for t in texts)
    return sorted(set(found))


RESIDUALS = ("ffm_unscoped_ms_per_chunk", "gbdt_unscoped_ms_per_tree",
             "score_unscoped_ms_per_job", "gbdt_grow_unscoped_ms_per_tree")


def _residuals_that_run(scope: str) -> list[str]:
    """The residual metrics whose cells run the program a scope is in.
    The leaf-wise training cell (ISSUE 53) has a residual of its own,
    which lists what the level-wise cells' lists and the grower's two."""
    ffm, gbdt, score, grow = RESIDUALS
    if scope.startswith(("gbdt.score.", "ffm.score.")):
        return [score]
    if scope.startswith("stage.") or scope == "bin.transform":
        return [gbdt, score, grow]  # training and scoring stage and bin
    if scope == "ffm.table_gather":
        return [ffm, score]         # the scoring program gathers blocks too
    if scope.startswith(("ffm.", "sparse.", "mp4j.")):
        return [ffm]
    if scope.startswith("gbdt.grow."):
        return [grow]
    if scope.startswith(("gbdt.", "bin.")):
        return [gbdt, grow]
    raise AssertionError(
        f"{scope}: a new family of scopes; say here which cells run it and "
        f"list it in their residual's file under benchmark/layer_metrics/")


def _listed(metric: str) -> re.Pattern:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           f"{metric}.json"), encoding="utf-8") as fh:
        spec = json.load(fh)
    assert spec["reader"] == "trace_unscoped_time"
    return re.compile(spec["scope"])


def test_the_scan_finds_the_scopes():
    scopes = {s for _, s in _entered_scopes()}
    assert {"ffm.select", "ffm.pairs", "sparse.fold_live_tiles",
            "stage.place", "gbdt.level.<d>", "gbdt.hist",
            "gbdt.score.select", "bin.transform", "mp4j.all_to_all",
            "ffm.score.pairs", "gbdt.grow.pick", "gbdt.grow.book",
            "gbdt.grow.compact", "gbdt.grow.pack"} <= scopes
    assert len(scopes) >= 33


@pytest.mark.parametrize("where,scope", _entered_scopes(),
                         ids=[s for _, s in _entered_scopes()])
def test_every_scope_is_listed_where_it_runs(where, scope):
    if scope in WRAPPERS:
        # and no residual lists it: a wrapper names all it wraps
        for metric in RESIDUALS:
            assert not _listed(metric).search(scope.replace("<d>", "3"))
        return
    if scope in INNER:
        # what lists it is the scope round it: the stack a trace shows
        scope = f"{INNER[scope][0]}/{scope}"
    for metric in _residuals_that_run(scope):
        m = _listed(metric).search(f"jit(step)/{scope}/add:")
        assert m, f"{where}: {scope} is in no alternative of {metric}"
        # the whole component, not a prefix of it
        assert f"/{m.group(0)}/" in f"/{scope}/" or scope.endswith(
            m.group(0)), (scope, m.group(0))


@pytest.mark.parametrize("scope", sorted(INNER))
def test_an_inner_scope_is_entered_only_inside_its_listed_one(scope):
    """Every name stack of the lowered program that holds an inner scope
    holds the listed scope above it, so that the residual's file, which
    does not know the inner name, still lists all that runs under it;
    and the kernel's call stands outside the grower's rounds, where
    ``gbdt_hist_ms_per_tree`` alone reads it."""
    above, program = INNER[scope]
    text = _lowered(program).as_text(debug_info=True)
    stacks = [t for t in set(re.findall(r'loc\("([^"]*)"', text))
              if re.search(rf"(^|/){re.escape(scope)}(/|$)", t)]
    assert stacks
    for stack in stacks:
        assert re.search(rf"(^|/){re.escape(above)}/(.*/)?{re.escape(scope)}"
                         r"(/|$)", stack), stack
    assert not [t for t in re.findall(r'loc\("([^"]*)"', text)
                if "gbdt.grow." in t and "gbdt.hist" in t]
