"""What a run records about the machine and the installation it ran on.

``CompileClock``, ``scalar_round_trip`` and the versions are copies of
``chip_smoke.py``'s (PERF.md, Open questions). They are facts printed on
an earlier line of every run, so that a change of installation shows in
the logs; none is a metric.
"""

from __future__ import annotations

import importlib.metadata
import statistics
import time

import numpy as np

import jax
import jax.numpy as jnp

REQUIRED_PLATFORM = "tpu"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class DeviceError(RuntimeError):
    """The machine does not hold what the cell asks for."""


def require_devices(chips: int) -> list:
    """The first ``chips`` devices, or an error: a measurement path that
    finds no chip, or fewer than the cell names, fails; it never falls
    back to the CPU."""
    platform = REQUIRED_PLATFORM        # read here: tests stub it
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != platform:
        raise DeviceError(
            f"needs a {platform} device, jax found platform "
            f"{dev.platform!r} ({dev.device_kind}, {len(devices)} device(s))")
    if len(devices) < chips:
        raise DeviceError(
            f"the cell needs {chips} chip(s), jax found {len(devices)} "
            f"({dev.device_kind})")
    return devices[:chips]


def versions() -> dict:
    out = {}
    for dist in ("jax", "jaxlib", "libtpu"):
        try:
            out[dist] = importlib.metadata.version(dist)
        except importlib.metadata.PackageNotFoundError:
            out[dist] = "not installed"
    return out


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling (or reading the
    persistent cache instead), the number of programs it built or read,
    and the cache hits among them, from ``jax.monitoring``'s own events."""

    def __init__(self):
        self.secs = 0.0
        self.programs = 0
        self.cache_hits = 0

    def on_duration(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.secs += secs
        if event == BACKEND_COMPILE_EVENT:
            self.programs += 1

    def on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self.on_duration)
        jax.monitoring.unregister_event_listener(self.on_event)


def scalar_round_trip(device, reps: int = 50) -> float:
    """Median seconds to dispatch a scalar program and fetch its result."""
    bump = jax.jit(lambda v: v + 1)
    x = jax.device_put(np.float32(0), device)
    float(bump(x))
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(bump(x))
        samples.append(time.perf_counter() - t0)
    return statistics.median(samples)


def block_until_ready_blocks(device) -> dict:
    """Dispatch a program of some tens of milliseconds, then time
    ``jax.block_until_ready`` against the ``np.asarray`` that follows.
    Where the first really waits for the device, the second finds the
    value ready and is the shorter of the two."""
    @jax.jit
    def spin(x):
        x = jax.lax.fori_loop(0, 256, lambda _, a: jnp.tanh(a @ a) * 0.01, x)
        return x[:8, :8]

    x = jax.device_put(np.full((2048, 2048), 0.01, np.float32), device)
    np.asarray(spin(x))
    out = spin(x)
    t0 = time.perf_counter()
    jax.block_until_ready(out)
    t1 = time.perf_counter()
    np.asarray(out)
    t2 = time.perf_counter()
    return {"block_secs": t1 - t0, "fetch_secs": t2 - t1,
            "blocks": (t1 - t0) > (t2 - t1)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes held on the fullest of ``devices`` (0 where the backend
    reports none, as the CPU does). On this runtime ``peak_bytes_in_use``
    counts arrays and program outputs but not the temporaries of a running
    program, which are reserved apart (``peak_bytes_reserved``: a probe
    with a known temporary showed it there and nowhere else; PERF.md,
    Findings, PR 22). A step's temporaries are live while its arguments
    are, so the peak is the sum."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peak = (int(stats.get("peak_bytes_in_use", 0))
                + int(stats.get("peak_bytes_reserved", 0)))
        # the two peaks need not fall together; the chip holds no more
        # than its limit
        peaks.append(min(peak, int(stats.get("bytes_limit", peak))))
    return max(peaks)


def memory_stats(device) -> dict:
    """The allocator's counters as the backend gives them, for the log."""
    stats = device.memory_stats() or {}
    return {k: stats[k] for k in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
        "peak_bytes_reserved", "bytes_limit") if k in stats}
