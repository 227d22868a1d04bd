"""What a jitted function's first call costs, booked to the function.

jax records, through jax.monitoring, how long each stage of the compile
path took: the Python trace to a jaxpr, its lowering to MLIR, the backend
compile, the fetch from the persistent cache. The events carry the name
jax gave the function, not who in this program called it; the caller
says so with `calling("train_step")` around the call (one thread-local
assignment), and the two listeners below book each event to it:

  estimator_compile_ms{fn,stage}     stage: trace | lower | compile |
                                     cache_fetch; SELF time, so the four
                                     add up to what the calls paid
  estimator_compiles_total{fn,cache} executables: `hit` came out of the
                                     persistent cache, `miss` was compiled
                                     (cache cold, off, or entry refused)
  span `first_call` (fn, stage, self_ms) on the ring, a child of the span
                                     open on the thread that paid; only
                                     for an interval of a millisecond or
                                     more (a trace fires hundreds of
                                     events for inner functions it has
                                     traced before, microseconds each)

Self time: stages nest (a jitted function traced inside another's trace;
an eager operation compiled while a trace runs; the cache fetch inside
the interval jax calls backend_compile, which is compile-OR-fetch), and
jax reports each interval whole. An event that ends now and lasted d
began at now - d; earlier events of this thread that began after that
lie inside it and are taken off its time.

This module imports no jax: estimator/base_estimator.py hands
`on_duration` and `on_event` to jax.monitoring when it is imported.
Once every shape is warm jax records nothing and the listeners are never
called. Anything compiled outside a `calling` block is fn="other".
"""

from __future__ import annotations

import threading
import time

__all__ = ["calling", "on_duration", "on_event", "STAGES", "OTHER"]

STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_fetch",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
OTHER = "other"
# a listener runs a few microseconds after its interval ended, so a
# child's reckoned start can fall that much before its parent's
_SLACK_S = 1e-4
# finished intervals kept a thread (only those no later event can hold
# any more are dropped: a trace holds a few hundred inner ones at most)
_KEEP = 4096
# shorter intervals are booked to the histogram and leave no span
_SPAN_MIN_S = 1e-3

_tls = threading.local()


class calling:
    """`with calling("train_step"):` around a call of a jitted function:
    compile-path events of this thread are booked to that name inside."""

    __slots__ = ("fn", "_outer")

    def __init__(self, fn: str):
        self.fn = fn

    def __enter__(self):
        self._outer = getattr(_tls, "fn", OTHER)
        _tls.fn = self.fn
        return self

    def __exit__(self, *exc):
        _tls.fn = self._outer
        return False


def on_event(event: str, **kw) -> None:
    """jax.monitoring.register_event_listener: a persistent-cache hit
    is remembered for the backend_compile event that closes around it."""
    if event == _CACHE_HIT:
        _tls.hit = True


def on_duration(event: str, secs: float, **kw) -> None:
    """jax.monitoring.register_event_duration_secs_listener."""
    stage = STAGES.get(event)
    if stage is None:
        return
    from euler_tpu import obs

    fn = getattr(_tls, "fn", OTHER)
    begun = time.monotonic() - secs
    done = _tls.__dict__.setdefault("done", [])
    inside = 0.0
    while done and done[-1][0] >= begun - _SLACK_S:
        inside += done.pop()[1]
    done.append((begun, secs))
    if len(done) > _KEEP:
        del done[:_KEEP // 2]
    own = max(secs - inside, 0.0)
    obs.histogram(
        "estimator_compile_ms",
        "self time of the compile path's stages (trace, lower, compile, "
        "cache_fetch), by the jitted function whose call paid them",
        ("fn", "stage"), buckets=obs.SETUP_MS_BUCKETS
    ).labels(fn=fn, stage=stage).observe(own * 1e3)
    if stage == "compile":
        hit, _tls.hit = getattr(_tls, "hit", False), False
        obs.counter(
            "estimator_compiles_total",
            "executables built (miss) or fetched from the persistent "
            "compile cache (hit), by the jitted function that needed them",
            ("fn", "cache")
        ).labels(fn=fn, cache="hit" if hit else "miss").inc()
    if secs >= _SPAN_MIN_S:
        obs.record_span("first_call", secs, fn=fn, stage=stage,
                        self_ms=round(own * 1e3, 3))
