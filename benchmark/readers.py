"""The per-layer metrics' readers. Each takes the traced run's context
and returns its number, or None where it finds nothing to read (the
harness then leaves the metric out of the line; a share of a peak is
never reported as 0 for want of a reading).

Context keys: cfg, traffic, window (cell.Program.window's result),
trace (reduce_trace.reduce's result), peaks (the device's row of
peaks.json).
"""

from __future__ import annotations

from .cell import resolve


def _histogram_delta(ctx, name: str):
    """(sum, count) the named histogram gained over the window, over all
    of its label children."""
    def total(snap):
        children = snap.get(name, {}).get("values", {}).values()
        return (sum(float(c["sum"]) for c in children),
                sum(float(c["count"]) for c in children))

    (s0, c0), (s1, c1) = total(ctx["window"]["obs_before"]), \
        total(ctx["window"]["obs_after"])
    return s1 - s0, c1 - c0


def input_wait_ms(ctx):
    """Mean wait of the train loop for its next batches, per dispatch
    (the program observes one wait for each scanned window's 32)."""
    s, c = _histogram_delta(ctx, "estimator_input_wait_ms")
    return s / c if c else None


def dispatch_ms_max(ctx):
    secs = ctx["window"]["dispatch_secs"]
    return 1e3 * max(secs) / ctx["window"]["spl"] if secs else None


def compiles_in_window(ctx):
    return float(ctx["window"]["compiles"])


def _work(ctx):
    weighted = ctx["traffic"]["edge_weights"]["kind"] != "unit"
    return resolve(ctx["cfg"]["work"])(
        ctx["cfg"], int(ctx["traffic"]["root_batch"]), weighted)


def _busy_steps_per_s(ctx):
    """The traced dispatches' steps over the time in which the device
    ran an operation: the device's own rate, which neither the feeder's
    gaps nor the profiler's start and stop on the host can move."""
    t = ctx["trace"]
    if not t["busy_s"] or not t["dispatches"]:
        return None
    return t["dispatches"] * ctx["window"]["spl"] / t["busy_s"]


def step_mfu_pct(ctx):
    rate = _busy_steps_per_s(ctx)
    if not rate or not ctx["peaks"]:
        return None
    return 100.0 * _work(ctx)["flops"] * rate \
        / ctx["peaks"]["bf16_flops_per_s"]


def step_hbm_pct(ctx):
    rate = _busy_steps_per_s(ctx)
    if not rate or not ctx["peaks"]:
        return None
    return 100.0 * _work(ctx)["bytes"] * rate \
        / ctx["peaks"]["hbm_bytes_per_s"]


def device_idle_pct(ctx):
    t = ctx["trace"]
    if not t or not t["window_s"] or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
