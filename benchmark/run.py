#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix, adapter, per-layer metrics and their readers
are files found by name (``cells.py``). The run refuses any platform but
a TPU and any machine with fewer chips than the cell names, sets the
system up and warms every shape, then:

- ``--trace 0``: measures the window for ``--seconds`` with no profiler
  and reports the cell's end-to-end metrics;
- ``--trace 1``: profiles a short slice of the same traffic and reports
  the cell's per-layer metrics, ``device.busy_s`` / ``window_s`` and a
  ``breakdown`` from the device trace.

``setup_s`` is the time from process start to the first instant of the
window or slice, less the start of the installation's runtime (``import
jax`` and the first ``jax.devices()``): that part took 9 to 14 s run by run
on the machine the benchmark was defined on, more than everything else in
set-up together, and nothing in this repository can change it. Both are on
the ``facts:`` line.

Outputs are checked against the plain references outside the window. The
last line of stdout is one JSON object with the keys ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` in a
traced run) and ``compared`` last: each number the adapter's check
compared as ``name: [number, limit]``, which are also the last lines of
standard error; everything else goes on earlier lines or under
``benchmark/out/``.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()       # set-up is counted from process start

import argparse                 # noqa: E402
import collections              # noqa: E402
import contextlib               # noqa: E402
import glob                     # noqa: E402
import json                     # noqa: E402
import math                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells     # noqa: E402

SLICE_SPAN = "bench.slice"      # the traced slice, on the profiler's clock
EXIT_REFUSED = 2


class Spans:
    """Host-clock spans recorded from the benchmark's own files, around
    the calls into each layer. Each is also a ``TraceAnnotation``, so a
    traced run shows it on the profiler's clock beside the device."""

    def __init__(self):
        self.seconds: dict[str, list[float]] = collections.defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.seconds[name].append(time.perf_counter() - t0)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return ap.parse_args(argv)


def _profile_slice(adapter, spans: Spans, trace_dir: str):
    """Run the adapter's slice under the profiler; returns (slice result,
    path of the .xplane.pb)."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # host TraceMe events only: the
    options.host_tracer_level = 2       # python tracer slows the host
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with spans.span(SLICE_SPAN):
            result = adapter.slice()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                           f"{trace_dir}")
    return result, found[0]


def main(argv=None, root: str = ROOT) -> int:
    args = _parse(argv)
    try:
        cell = cells.load_cell(root, args.workload)
    except cells.CellError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_REFUSED
    # ``import jax`` and the first ``jax.devices()`` are the start of the
    # installation's runtime, which no file of this repository can move;
    # they are timed apart and left out of ``setup_s``
    t = time.perf_counter()
    import jax
    runtime_start_s = time.perf_counter() - t
    try:
        import ytk_mp4j_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"benchmark: the system under test is not in this checkout "
              f"({e})", file=sys.stderr)
        return EXIT_REFUSED

    from benchmark import arith, machine, xplane
    from ytk_mp4j_tpu.utils.compile_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    # every program of a cell, however small, is read from the cache by
    # the cell's next run (jax's default keeps those under 1 s out)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    t = time.perf_counter()
    try:
        devices = machine.require_devices(cell.chips)
    except machine.DeviceError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_REFUSED
    runtime_start_s += time.perf_counter() - t
    dev = devices[0]
    peaks = arith.peaks_for(dev.device_kind)

    spans = Spans()
    adapter_module = cells.load_module(root, "adapters", cell.adapter_name)
    with machine.CompileClock() as clock:
        adapter = adapter_module.Adapter(cell.config, cell.traffic,
                                         args.seed, devices, spans)
        adapter.setup()
        adapter.warmup()
        facts = {
            "cell": cell.name, "seed": args.seed, "trace": args.trace,
            "versions": machine.versions(),
            "device": f"{dev.platform} {dev.device_kind} x{len(devices)} "
                      f"of {jax.device_count()}",
            "scalar_round_trip_secs": machine.scalar_round_trip(dev),
            "block_until_ready": machine.block_until_ready_blocks(dev),
            "compile_cache_dir": cache_dir,
            "setup_compile_secs": clock.secs,
            "setup_programs": clock.programs,
            "setup_cache_hits": clock.cache_hits,
            "setup_parts_secs": {k: sum(v) for k, v in spans.seconds.items()},
            "memory_after_setup": machine.memory_stats(dev),
        }
        process_to_window_s = time.perf_counter() - _T0
        setup_s = process_to_window_s - runtime_start_s
        facts.update(setup_s=setup_s, runtime_start_s=runtime_start_s,
                     process_to_window_s=process_to_window_s)
        print("facts: " + json.dumps(facts), flush=True)

        programs_before = clock.programs
        trace_path = None
        if args.trace:
            result, trace_path = _profile_slice(
                adapter, spans,
                os.path.join(root, cells.BENCH_DIR, "out", "trace", cell.name))
        else:
            result = adapter.window(args.seconds)
        compiles_in_window = clock.programs - programs_before
        compile_s = clock.secs
        memory_after_window = machine.memory_stats(dev)
    check_ok, check_detail = adapter.check()
    # what a check counts for the readers stands beside the window's own
    # counters and may not take the name of one
    taken = set(check_detail.get("counters", {})) & set(result["counters"])
    if taken:
        raise ValueError(f"the check's counters {sorted(taken)} are "
                         f"already the window's")
    print("window: " + json.dumps(
        {"counters": result["counters"], "log": result.get("log"),
         "compiles_in_window": compiles_in_window,
         "memory_after_window": memory_after_window,
         "check": check_detail, "trace_file": trace_path}), flush=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": machine.memory_peak_bytes(devices)}
    line = {
        # a window in which jax built a program measured set-up, not the
        # steady state: its numbers do not stand
        "correct": bool(check_ok) and compiles_in_window == 0,
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
    }
    metrics = {}
    if args.trace:
        trace = xplane.load(trace_path)
        t0, t1 = xplane.window_of(trace, SLICE_SPAN)
        busy = xplane.mean_busy_ns(trace, t0, t1)
        device["busy_s"] = busy / 1e9
        device["window_s"] = (t1 - t0) / 1e9
        run = {"trace": trace, "window_ns": (t0, t1), "spans": spans.seconds,
               # what the check counted for the readers (the rows a
               # job's trees needed) stands beside the window's counts
               "counters": {**result["counters"],
                            **check_detail.get("counters", {}),
                            "compile_s": compile_s,
                            "compiles_in_window": compiles_in_window,
                            "memory_peak_bytes": device["memory_peak_bytes"]},
               "config": cell.config, "traffic": cell.traffic,
               "peaks": peaks, "chips": cell.chips}
        for m in cell.per_layer:
            reader = cells.load_module(root, "readers", m["spec"]["reader"])
            value = reader.read(m["spec"], run)
            if value is not None:       # nothing to read: left out
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        line["breakdown"] = xplane.breakdown(trace, t0, t1)
    else:
        measured = {**result["metrics"], "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(measured[m["name"]]),
                                  "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    # each number the check compared beside its limit, as the adapter
    # names them (a check that could compare nothing says why under
    # ``error``): last in the line and the last lines on standard error,
    # which is what is kept of a run that is not correct
    compared = check_detail.get(
        "compared", {"error": [check_detail.get("error"), None]})
    # strict JSON has no word for an infinite error: say it in letters
    line["compared"] = compared = {
        name: [v if not isinstance(v, float) or math.isfinite(v) else str(v)
               for v in pair] for name, pair in compared.items()}
    print(json.dumps(line), flush=True)
    for name, (number, limit) in compared.items():
        print(f"compared: {name} {number} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
