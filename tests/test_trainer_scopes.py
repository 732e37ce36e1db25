"""The training steps' and the row placers' names for a device trace
(ISSUE 50): ``ffm.select`` / ``ffm.pairs`` in the three sparse FFM steps
(forward as ``jvp(...)``, backward as ``transpose(jvp(...))``),
``sparse.fold_live_tiles`` round their update loops, ``gbdt.level.<d>``
round every level of a tree, ``stage.place`` inside both placers.

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

from ytk_mp4j_tpu.models._base import DataParallelTrainer
from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer
from ytk_mp4j_tpu.parallel.mesh import make_mesh

from test_trainer_spans import _lower_ffm, _lower_placer
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
    return tr._build_step().lower(
        *data, jax.random.key_data(jax.random.key(0)))


def _lower_packed_placer(rng):
    """``_put_in_row_chunks``' program for a tuple of arrays (ids, fields
    and values a row, side by side: ``FMTrainer.predict``'s staging)."""
    t = DataParallelTrainer(n_devices=2)
    n, rows, cols, pad = 2, 128, 5, 1
    place = t._build_row_placer((n, rows, cols), pad)
    sharding = t._row_sharding()
    table = jax.ShapeDtypeStruct((n, 512, 3 * cols + pad), jnp.int32,
                                 sharding=sharding)
    chunk = tuple(jax.ShapeDtypeStruct((n, rows * cols // 128, 128), dtype,
                                       sharding=sharding)
                  for dtype in (jnp.int32, jnp.int32, jnp.float32))
    return place.lower(table, chunk, np.int32(0))


def _lower_chunk_placer(rng):
    """``_put_row_chunks``' program: a piece of floats into one shard."""
    t = DataParallelTrainer(n_devices=1)
    per, width, rows = 512, 16, 64
    wire = (rows * width // 128, 128)
    return t._row_chunk_placer(per, width, rows, wire).lower(
        jax.ShapeDtypeStruct((1, per, width), jnp.float32),
        jax.ShapeDtypeStruct(wire, jnp.float32), np.int32(0))


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
    "placer": (_lower_placer, ["stage.place"], "stage.place"),
    "placer-packed": (_lower_packed_placer, ["stage.place"], "stage.place"),
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
    # the scoring program's names stay the scoring program's
    assert "ffm.score." not in text


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


def _residuals_that_run(scope: str) -> list[str]:
    """The residual metrics whose cells run the program a scope is in."""
    ffm, gbdt, score = ("ffm_unscoped_ms_per_chunk",
                        "gbdt_unscoped_ms_per_tree",
                        "score_unscoped_ms_per_job")
    if scope.startswith(("gbdt.score.", "ffm.score.")):
        return [score]
    if scope.startswith("stage.") or scope == "bin.transform":
        return [gbdt, score]        # training and scoring stage and bin
    if scope == "ffm.table_gather":
        return [ffm, score]         # the scoring program gathers blocks too
    if scope.startswith(("ffm.", "sparse.", "mp4j.")):
        return [ffm]
    if scope.startswith(("gbdt.", "bin.")):
        return [gbdt]
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
            "ffm.score.pairs"} <= scopes
    assert len(scopes) >= 29


@pytest.mark.parametrize("where,scope", _entered_scopes(),
                         ids=[s for _, s in _entered_scopes()])
def test_every_scope_is_listed_where_it_runs(where, scope):
    if scope in WRAPPERS:
        # and no residual lists it: a wrapper names all it wraps
        for metric in ("ffm_unscoped_ms_per_chunk",
                       "gbdt_unscoped_ms_per_tree",
                       "score_unscoped_ms_per_job"):
            assert not _listed(metric).search(scope.replace("<d>", "3"))
        return
    for metric in _residuals_that_run(scope):
        m = _listed(metric).search(f"jit(step)/{scope}/add:")
        assert m, f"{where}: {scope} is in no alternative of {metric}"
        # the whole component, not a prefix of it
        assert f"/{m.group(0)}/" in f"/{scope}/" or scope.endswith(
            m.group(0)), (scope, m.group(0))
