#!/usr/bin/env python3
"""Compile each cell's programs at their real sizes for a described TPU
topology, without a chip, and print compile seconds and XLA's memory
analysis, so that chip time is not spent finding that a size does not fit.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_aot.py [--topology v5e:2x2] [--cell NAME]

A compile that passes is a compile proof, never a run: nothing executes,
the analysis counts one program at a time and not what else the process
keeps on the device. The trainers' step programs are reached through
``_build_step()`` because ``train()`` / ``fit_stream()`` place data on
``jax.devices()``, which a described topology has none of; the adapters
themselves never do this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from benchmark import cells  # noqa: E402

GB = 1e9


def _report(name: str, jitted, *avals) -> None:
    t0 = time.perf_counter()
    compiled = jitted.lower(*avals).compile()
    secs = time.perf_counter() - t0
    m = compiled.memory_analysis()
    text = compiled.as_text()
    out = {"program": name, "compile_s": round(secs, 1),
           "arguments_gb": m.argument_size_in_bytes / GB,
           "outputs_gb": m.output_size_in_bytes / GB,
           "temp_gb": m.temp_size_in_bytes / GB,
           "aliased_gb": m.alias_size_in_bytes / GB,
           "mosaic_calls": text.count("tpu_custom_call"),
           "all_reduces": text.count(" all-reduce(")
           + text.count(" all-reduce-start(")}
    print(json.dumps(out), flush=True)


def _aval(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def rehearse_gbdt(cell, devices):
    from ytk_mp4j_tpu.models.gbdt import GBDTConfig, GBDTTrainer

    c = cell.config
    mesh = Mesh(np.asarray(devices[: cell.chips]), ("mp4j",))
    trainer = GBDTTrainer(GBDTConfig(
        n_features=c["n_features"], n_bins=c["n_bins"], depth=c["depth"],
        loss=c["loss"]), mesh=mesh)
    rows = NamedSharding(mesh, P("mp4j"))
    per = -(-c["rows"] // cell.chips)
    kd = jax.eval_shape(lambda: jax.random.key_data(jax.random.key(0)))
    _report("gbdt step", trainer._build_step(),
            _aval((cell.chips, per, c["n_features"]), jnp.int32, rows),
            _aval((cell.chips, per), jnp.float32, rows),
            _aval((cell.chips, per), jnp.float32, rows),
            _aval((cell.chips, per), jnp.float32, rows),
            _aval(kd.shape, kd.dtype, NamedSharding(mesh, P())))


def rehearse_ffm(cell, devices):
    from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer

    c, t = cell.config, cell.traffic
    mesh = Mesh(np.asarray(devices[: cell.chips]), ("mp4j",))
    trainer = FMTrainer(FMConfig(
        model=c["model"], n_features=c["n_features"], n_fields=c["n_fields"],
        k=c["k"], max_nnz=c["max_nnz"], learning_rate=c["learning_rate"]),
        mesh=mesh, sparse_grads=c["sparse_grads"],
        table_sharding=c["table_sharding"])
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("mp4j"))
    n_rows = c["n_features"] * c["n_fields"]
    per = t["rows_per_chunk"] // cell.chips
    params = (_aval((), jnp.float32, rep),
              _aval((c["n_features"],), jnp.float32, rep),
              _aval((n_rows, c["k"]), jnp.float32, rep))
    slots = (cell.chips, per, c["max_nnz"])
    batch = (_aval(slots, jnp.int32, rows), _aval(slots, jnp.int32, rows),
             _aval(slots, jnp.float32, rows), _aval(slots, jnp.float32, rows),
             _aval(slots[:2], jnp.float32, rows),
             _aval(slots[:2], jnp.float32, rows))
    _report("ffm sparse step", trainer._build_step(per * c["max_nnz"]),
            params, *batch)
    adapter = cells.load_module(cell.root, "adapters", "ffm")
    key = jax.eval_shape(lambda: jax.random.key(0))
    _report("ffm parameters from the seed", adapter.params_maker(c, rep),
            jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep))


def rehearse_collective(cell, devices):
    adapter = cells.load_module(cell.root, "adapters", "collective")
    mesh = Mesh(np.asarray(devices[: cell.chips]), ("mp4j",))
    make, hist, bulk = adapter.build_programs(mesh, cell.config,
                                              cell.traffic)
    sharded = NamedSharding(mesh, P("mp4j"))
    _report("collective operands", make,
            _aval((), jnp.uint32, NamedSharding(mesh, P())))
    _report("collective hist program", hist, tuple(
        _aval((cell.chips,) + tuple(s), jnp.float32, sharded)
        for s in adapter.hist_shapes(cell.config)))
    _report("collective bulk program", bulk, _aval(
        (cell.chips, cell.config["bulk_elements"]), jnp.float32, sharded))


REHEARSALS = {"gbdt": rehearse_gbdt, "ffm": rehearse_ffm,
              "collective": rehearse_collective}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--cell", action="append",
                    help="a workload of BENCHMARK.json (default: all)")
    args = ap.parse_args(argv)
    from jax.experimental import topologies

    devices = topologies.get_topology_desc(
        platform="tpu", topology_name=args.topology).devices
    print(f"topology {args.topology}: {len(devices)} x "
          f"{devices[0].device_kind} (compile proofs, not runs)")
    with open(os.path.join(ROOT, cells.BENCHMARK_FILE)) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    for name in args.cell or names:
        cell = cells.load_cell(ROOT, name)
        print(f"cell {name}:")
        REHEARSALS[cell.adapter_name](cell, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
