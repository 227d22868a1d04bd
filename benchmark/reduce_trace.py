"""From a profiler trace (.xplane.pb) to the device's busy and idle time.

`read_planes` turns the file into plain lists with nothing but jax
(jax.profiler.ProfileData); `reduce` works on those lists, so a test can
hand it planes it made up or a cut of a recorded one.

Busy is the union of the intervals in which an operation ran on a device
plane's "XLA Ops" line. The traced window runs from the start of the
runner's first traced dispatch span (host plane) to the end of its last,
so a device left idle at either end of a dispatch counts as idle; both
planes are on one clock. An operation that encloses others (the scan's
while loop) is charged only the time its children leave.
"""

from __future__ import annotations

import glob
import os

OP_LINES = ("XLA Ops",)          # per-operation events on a device plane
DISPATCH_SPAN = "bench.dispatch"  # the runner's span around one dispatch


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_planes(path: str) -> dict:
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}}."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((ev.name, float(ev.start_ns),
                               float(ev.duration_ns)))
    return planes


def union_seconds(intervals) -> float:
    """Total length of the union of (start_ns, end_ns) intervals, in s."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps(intervals, lo: float, hi: float):
    """The idle stretches (start_ns, end_ns) of [lo, hi] that the union
    of `intervals` leaves open, longest first."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if e <= lo or s >= hi:
            continue
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def self_times(ops) -> dict:
    """{name: seconds}, each operation charged its duration less that of
    the operations nested directly inside it."""
    out, stack = {}, []   # stack of [name, end, duration, children]

    def close(top):
        out[top[0]] = out.get(top[0], 0.0) + (top[2] - top[3]) / 1e9

    for name, s, d in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += d
        stack.append([name, s + d, d, 0.0])
    while stack:
        close(stack.pop())
    return out


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper()


def _label(gap, spans) -> str:
    """What the runner's host was doing in a gap: inside a dispatch span
    (the estimator gathering 32 batches, stacking, enqueueing) or
    between two of them (the runner's own loop)."""
    s, e = gap
    mid = 0.5 * (s + e)
    for i, (a, b) in enumerate(spans):
        if a <= mid <= b:
            head = (s - a) < 0.5 * (b - a)
            return (f"dispatch {i}: before its device work (feeder wait, "
                    "stack, enqueue)") if head else \
                f"dispatch {i}: after its device work (loss fetch)"
    return "between dispatches (runner loop)"


def reduce(planes: dict, top: int = 10, top_gaps: int = 5) -> dict:
    """busy_s and window_s averaged over the device planes that ran
    operations, the runner's dispatch spans the trace holds, the
    operations that took most time, the longest idle gaps with what the
    host was doing."""
    spans = sorted(
        (s, s + d) for lines in planes.values()
        for events in lines.values()
        for name, s, d in events if name == DISPATCH_SPAN)
    per_device, op_time, all_gaps = [], {}, []
    for pname, lines in sorted(planes.items()):
        if not _is_device(pname):
            continue
        ops = [ev for lname in OP_LINES for ev in lines.get(lname, [])]
        if not ops:
            continue
        iv = [(s, s + d) for _, s, d in ops]
        lo = spans[0][0] if spans else min(s for s, _ in iv)
        hi = spans[-1][1] if spans else max(e for _, e in iv)
        per_device.append((union_seconds(iv), (hi - lo) / 1e9))
        for name, secs in self_times(ops).items():
            op_time[name] = op_time.get(name, 0.0) + secs
        for g in gaps(iv, lo, hi)[:top_gaps]:
            all_gaps.append((_label(g, spans), (g[1] - g[0]) / 1e9))
    if not per_device:
        return {"busy_s": None, "window_s": None, "devices": 0,
                "dispatches": len(spans), "device_ops": [],
                "idle_gaps": []}
    n = len(per_device)
    return {
        "busy_s": sum(b for b, _ in per_device) / n,
        "window_s": sum(w for _, w in per_device) / n,
        "devices": n,
        "dispatches": len(spans),
        "device_ops": [[k, v / n] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(
            all_gaps, key=lambda kv: -kv[1])[:top_gaps]],
    }


class Tracer:
    """Starts and stops jax's profiler on a fixed directory inside the
    checkout; stop() returns that directory."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir

    def start(self):
        import jax

        # the python tracer would add a host event for every call made
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> str:
        import jax

        jax.profiler.stop_trace()
        return self.dir
