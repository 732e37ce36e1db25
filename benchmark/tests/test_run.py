"""Every adapter end to end at a toy size through ``run.main`` itself, on
the CPU with the platform check stubbed; the last line is the contract's
object; files dropped in are found by name; what must be refused is."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import cells, run

from conftest import ROOT

CELLS = ["gbdt-higgs-11m.train", "ffm-criteo.stream-zipf",
         "allreduce-4rank.hist-and-bulk"]
# ``compared``: each number the check compared beside its limit, last
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _run(capsys, root, workload, trace, seed=5, seconds=0.5):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  root=root)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, lines


@pytest.mark.parametrize("workload", CELLS)
def test_untraced_run_reports_the_cells_end_to_end_metrics(
        capsys, tiny_root, workload):
    rc, lines = _run(capsys, tiny_root, workload, trace=0)
    assert rc == 0
    line = json.loads(lines[-1])
    assert set(line) == LINE_KEYS
    # every adapter names what its check compared: each number beside
    # its limit, last in the line
    assert list(line)[-1] == "compared" and line["compared"]
    assert all(len(pair) == 2 for pair in line["compared"].values())
    if workload.startswith("gbdt-"):
        assert line["compared"]["margin_max_abs_err"][1] == 1e-5
    assert set(line["device"]) == DEVICE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    cell = cells.load_cell(tiny_root, workload)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    for m in cell.end_to_end:
        got = line["metrics"][m["name"]]
        assert set(got) == {"value", "unit"} and got["unit"] == m["unit"]
        assert got["value"] > 0
    # the earlier lines carry the machine facts and the window's detail
    facts = json.loads(next(ln for ln in lines if ln.startswith("facts: "))
                       [len("facts: "):])
    assert {"versions", "scalar_round_trip_secs", "block_until_ready",
            "compile_cache_dir", "setup_cache_hits", "runtime_start_s",
            "process_to_window_s"} <= set(facts)
    assert facts["setup_s"] == pytest.approx(
        facts["process_to_window_s"] - facts["runtime_start_s"])
    assert line["metrics"]["setup_s"]["value"] == facts["setup_s"]
    window = json.loads(next(ln for ln in lines if ln.startswith("window: "))
                        [len("window: "):])
    assert window["compiles_in_window"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_per_layer_metrics_and_a_breakdown(
        capsys, tiny_root, workload):
    rc, lines = _run(capsys, tiny_root, workload, trace=1)
    assert rc == 0
    line = json.loads(lines[-1])
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert set(line["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert line["correct"] is True
    cell = cells.load_cell(tiny_root, workload)
    declared = {m["name"]: m for m in cell.per_layer}
    # the CPU's trace has no device plane: trace readers find nothing and
    # their metrics are left out; counters and host spans are there
    assert set(line["metrics"]) <= set(declared)
    assert {"compile_s", "compiles_in_window", "peak_hbm_gb"} \
        <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"]
    assert os.path.isdir(os.path.join(tiny_root, "benchmark", "out", "trace",
                                      workload))


def test_same_seed_same_inputs_other_seed_other_inputs(capsys, tiny_root):
    def check_detail(seed):
        _, lines = _run(capsys, tiny_root, CELLS[0], trace=0, seed=seed)
        window = next(ln for ln in lines if ln.startswith("window: "))
        return json.loads(window[len("window: "):])["check"]
    a, b, c = check_detail(1), check_detail(1), check_detail(2)
    assert a == b and a != c


def _digest(root):
    out = {}
    for base, _, files in os.walk(root):
        if os.sep + "out" in base or os.sep + "cache" in base:
            continue
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_config_mix_metric_reader_and_adapter_are_only_new_files(
        capsys, tiny_root):
    """A later PR's cell: a configuration, a traffic mix, a per-layer
    metric, its reader and an adapter dropped in as new files, with new
    entries in BENCHMARK.json. Nothing that was there changes."""
    bench = os.path.join(tiny_root, "benchmark")
    # adapters/ and readers/ are links to the repo's in the fixture: a new
    # file must not land there, so make them real directories first
    for sub in ("adapters", "readers"):
        target = os.path.realpath(os.path.join(bench, sub))
        os.unlink(os.path.join(bench, sub))
        shutil.copytree(target, os.path.join(bench, sub))
    before = _digest(tiny_root)

    with open(os.path.join(bench, "configs", "echo-tiny.json"), "w") as f:
        json.dump({"adapter": "echo", "chips": 1, "size": 7}, f)
    with open(os.path.join(bench, "traffic", "pings.json"), "w") as f:
        json.dump({"pings": 3}, f)
    with open(os.path.join(bench, "layer_metrics", "echo_pings.json"),
              "w") as f:
        json.dump({"name": "echo_pings", "adapters": ["echo"],
                   "reader": "double_counter", "counter": "pings"}, f)
    with open(os.path.join(bench, "readers", "double_counter.py"), "w") as f:
        f.write("def read(spec, run):\n"
                "    return 2 * run['counters'][spec['counter']]\n")
    with open(os.path.join(bench, "adapters", "echo.py"), "w") as f:
        f.write(
            "import jax.numpy as jnp\n"
            "class Adapter:\n"
            "    def __init__(self, config, traffic, seed, devices, spans):\n"
            "        self.n, self.size = traffic['pings'], config['size']\n"
            "    def setup(self): pass\n"
            "    def warmup(self): self._ping()\n"
            "    def _ping(self): return float(jnp.arange(self.size).sum())\n"
            "    def _go(self):\n"
            "        total = sum(self._ping() for _ in range(self.n))\n"
            "        return {'attempted': self.n, 'failed': 0,\n"
            "                'metrics': {'echo_sum': total},\n"
            "                'counters': {'pings': self.n}}\n"
            "    def window(self, seconds): return self._go()\n"
            "    def slice(self): return self._go()\n"
            "    def check(self): return True, {}\n")
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "echo-tiny", "source": "test",
                           "file": "benchmark/configs/echo-tiny.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "echo-tiny.pings", "config": "echo-tiny",
                             "traffic": "pings", "chips": 1, "why": "test"})
    doc["end_to_end"].append({"name": "echo_sum", "unit": "1",
                              "better": "higher", "bound": 0.01,
                              "source": "host_clock",
                              "workloads": ["echo-tiny.pings"]})
    doc["per_layer"].append({"name": "echo_pings", "unit": "pings",
                             "better": "higher", "source": "program_counter",
                             "layer": "Echo", "moves": "echo_sum",
                             "workloads": ["echo-tiny.pings"]})
    with open(path, "w") as f:
        json.dump(doc, f)

    rc, lines = _run(capsys, tiny_root, "echo-tiny.pings", trace=0)
    assert rc == 0
    line = json.loads(lines[-1])
    assert line["metrics"]["echo_sum"]["value"] == 3 * 21
    rc, lines = _run(capsys, tiny_root, "echo-tiny.pings", trace=1)
    assert rc == 0
    line = json.loads(lines[-1])
    assert line["metrics"]["echo_pings"] == {"value": 6.0, "unit": "pings"}
    # the old cells neither see the new metric nor changed
    assert "echo_pings" not in {m["name"] for m in cells.load_cell(
        tiny_root, CELLS[0]).per_layer}
    after = _digest(tiny_root)
    after.pop("BENCHMARK.json"), before.pop("BENCHMARK.json")
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "benchmark/configs/echo-tiny.json", "benchmark/traffic/pings.json",
        "benchmark/layer_metrics/echo_pings.json",
        "benchmark/readers/double_counter.py", "benchmark/adapters/echo.py"}


def test_refuses_another_platform_and_prints_no_result(capsys, tiny_root,
                                                       monkeypatch):
    from benchmark import machine
    monkeypatch.setattr(machine, "REQUIRED_PLATFORM", "tpu")
    rc, lines = _run(capsys, tiny_root, CELLS[0], trace=0)
    assert rc != 0 and lines == []


def test_refuses_fewer_devices_than_the_cell_names(capsys, tiny_root,
                                                   monkeypatch):
    import jax
    two = jax.devices()[:2]
    monkeypatch.setattr(jax, "devices", lambda *a: two)
    rc, lines = _run(capsys, tiny_root, CELLS[2], trace=0)
    assert rc != 0 and lines == []


def test_refuses_an_unknown_workload(capsys, tiny_root):
    rc, lines = _run(capsys, tiny_root, "no-such.cell", trace=0)
    assert rc != 0 and lines == []


def test_exits_nonzero_where_only_the_benchmark_is(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths``: the system under test is not there."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "system under test" in proc.stderr
