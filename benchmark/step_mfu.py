"""What the ``*_step_mfu`` readers share: a slice's flops as a share of
the chip's bf16 peak over the slice's wall time, in percent. The flops
are each reader's own arithmetic; the time is the traced slice
(``run["window_ns"]``, ``run.py``'s ``bench.slice`` span), so everything
the slice does counts as time, whatever ran on the device."""

from __future__ import annotations


def percent_of_peak(flops: float, run: dict) -> float | None:
    """None where there is no traced slice to divide by."""
    t0, t1 = run.get("window_ns") or (0, 0)
    if run.get("trace") is None or t1 <= t0:
        return None
    return 100.0 * flops / run["peaks"]["bf16_flops"] / ((t1 - t0) / 1e9)
