"""Rehearsal tests of the benchmark: run by hand, on the CPU, not part of
tier-1 (``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``)."""

import json
import os
import shutil
import sys

# before jax is imported: four virtual CPU devices for the collective cell
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# every size of the real files that the CPU cannot hold, cut to a toy
TINY = {
    "configs/gbdt-higgs-11m.json": {"rows": 4096, "n_trees": 2},
    "configs/ffm-criteo.json": {"n_features": 39 * 64},
    "configs/allreduce-4rank.json": {"bulk_elements": 4096, "hist_bins": 16},
    "traffic/stream-zipf.json": {"rows_per_chunk": 16, "pool_chunks": 4,
                                 "trace_chunks": 3},
    "traffic/hist-and-bulk.json": {"hist_repeats": 2, "trace_programs": 2},
}


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A checkout in a temporary directory: the real ``BENCHMARK.json``,
    adapters, readers and per-layer metrics, and the real configuration
    and traffic files with their sizes cut to a toy. The platform check
    is stubbed: the rehearsal runs on the CPU."""
    from benchmark import arith, machine

    monkeypatch.setattr(machine, "REQUIRED_PLATFORM", "cpu")
    # the CPU is in no table of peaks: the rehearsal lends it the v5e's
    with open(arith._PEAKS_FILE) as f:
        peaks = json.load(f)
    peaks["cpu"] = peaks["TPU v5 lite"]
    (tmp_path / "peaks.json").write_text(json.dumps(peaks))
    monkeypatch.setattr(arith, "_PEAKS_FILE", str(tmp_path / "peaks.json"))
    # a compile for the CPU must not land in the checkout's cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    bench = tmp_path / "benchmark"
    bench.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for sub in ("adapters", "readers"):
        os.symlink(os.path.join(ROOT, "benchmark", sub), bench / sub)
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub), bench / sub)
    for rel, cut in TINY.items():
        with open(bench / rel) as f:
            doc = json.load(f)
        doc.update(cut)
        with open(bench / rel, "w") as f:
            json.dump(doc, f)
    return str(tmp_path)
