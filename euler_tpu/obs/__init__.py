"""euler_tpu.obs: unified metrics + tracing for every layer.

One dependency-free (stdlib-only) subsystem answering "where did this
step's milliseconds go?" across the whole stack — host sampling, RPC
wait, retry sleep, device dispatch — instead of the per-layer ad-hoc
surfaces (`RemoteGraphEngine.health()`, `BaseEstimator.health()`,
`Query.stats()`, hand-rolled time deltas) it unifies:

  metrics.py   Counter / Gauge / Histogram on a thread-safe Registry;
               labeled children, plain-dict snapshot(), Prometheus text
  trace.py     span("name", **attrs) context managers with per-thread
               parenting, a bounded ring of finished spans, and a
               chrome://tracing JSON exporter
  server.py    obs.serve(port): /metrics + /healthz on a stdlib
               http.server daemon thread
  first_calls.py  jax.monitoring's compile-path events booked to the
               jitted function whose call paid them

Module-level convenience API (the process-global default registry and
tracer — what the wired layers use)::

    from euler_tpu import obs

    obs.counter("my_events_total").inc()
    with obs.span("load", shard=3):
        ...
    obs.dump_trace("run.json")        # → chrome://tracing / Perfetto
    srv = obs.serve(port=9464)        # scrape http://127.0.0.1:9464/metrics

Wired out of the box: `graph/remote.py` (per-call spans; retry /
failover / degrade counters — `health()` is a view over these),
`estimator/base_estimator.py` (one parent `train` a call of est.train,
estimator_train_call_ms, → the first batch's `input_wait` / `init_state`
(→ `model_init` / `create_state` / `commit_state`) /
`restore_checkpoint` / `build_fn` / the steps / `train_finish`;
single-step path: `train_step` → `device_step` / `hook` / `input_wait`;
scanned path, one parent `train_dispatch` a window of steps_per_loop →
`input_wait` / `build_fn` / `stack` / `device_step` (the enqueue) /
`read_ahead` (→ the NEXT window's `input_wait` and `stack`, while the
chip runs this one) / `result_wait` (the host waiting for the chip) /
`hook`, back to back; histograms estimator_input_wait_ms,
_device_step_ms, _result_wait_ms, _hook_ms; counter
estimator_window_batches_total{how=ahead|waited}; under whichever of them is open when a jitted function is
called the first time, `first_call` spans with estimator_compile_ms
{fn,stage} and estimator_compiles_total{fn,cache}: first_calls.py),
`parallel/feature_store.py` and `parallel/device_sampler.py` (set-up's
placement: parents `place_features` / `place_neighbors` → `pad` / `cast`
/ `quantize` / `detect_uniform_rows` / `store_rows` / `transfer`, each
an observation in placement_ms{table,stage}; PERF.md section 3 has the
tree), the models' trace-time branches (`traced_path`:
traced_paths_total{path,detail}), `estimator/prefetch.py`
(both feeders, on the producing thread: `feeder_produce` →
`feeder_transform`; feeder_produce_ms, feeder_queue_depth,
feeder_batches_total), `parallel/train.py`, `gql.py` (engine-side
Query.stats() + UDF-cache gauges via collectors), `graph/chaos.py`
(`chaos_injected_total{kind=...}`), and `bench.py` (`detail.obs`
snapshot on every artifact; `--trace out.json`).

Two sinks, one API. Every span lands in the ring (`dump_trace`). Once
the jax side has called `install_profiler_annotation` (importing
euler_tpu.estimator does), a span opened while a jax.profiler session
runs is also the event `euler.<name>` on that session's host plane, on
its own thread and on the device plane's clock, so a device-idle gap
can be put down to the phase the host was in (benchmark/scope_readers.py
reads them). This package imports no jax.

`obs.disable()` turns the span path into a shared no-op; counters stay
live — they are the health() bookkeeping. Cost of one span on this
sandbox's CPU (PR 24): 0.26 µs disabled, 2.4 µs ring only, 3.5 µs with
the profiler sink installed and no session, 4.7 µs inside a session.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from euler_tpu.obs.metrics import (  # noqa: F401
    DEFAULT_MS_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    Registry,
    log2_buckets,
    snapshot_delta,
)
from euler_tpu.obs.server import (  # noqa: F401
    ObsServer,
    health_snapshot,
    register_health,
    unregister_health,
)
from euler_tpu.obs.trace import NULL_SPAN, Span, Tracer  # noqa: F401

# bounds for set-up's phases, which last seconds where a step's phases
# last milliseconds: 1 ms .. 131 s
SETUP_MS_BUCKETS = log2_buckets(1.0, 18)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "Tracer", "Span",
    "ObsServer", "default_registry", "default_tracer", "counter", "gauge",
    "histogram", "span", "timed_span", "record_span", "traced_path",
    "SETUP_MS_BUCKETS", "serve", "snapshot",
    "snapshot_delta", "render_prometheus", "dump_trace", "clear_trace",
    "enable", "disable", "enabled", "install_profiler_annotation",
    "register_health",
    "unregister_health", "health_snapshot", "log2_buckets",
    "DEFAULT_MS_BUCKETS", "reset_for_tests",
]

_mu = threading.Lock()
_registry: Optional[Registry] = None
_tracer: Optional[Tracer] = None
_enabled = True


def default_registry() -> Registry:
    """The process-global registry every wired layer reports into."""
    global _registry
    with _mu:
        if _registry is None:
            _registry = Registry()
        return _registry


def default_tracer() -> Tracer:
    """The process-global tracer behind obs.span()."""
    global _tracer
    with _mu:
        if _tracer is None:
            _tracer = Tracer()
        return _tracer


# -- metrics shorthands (default registry) --------------------------------
def counter(name: str, help: str = "", labelnames=()) -> Counter:
    return default_registry().counter(name, help, labelnames)


def gauge(name: str, help: str = "", labelnames=()) -> Gauge:
    return default_registry().gauge(name, help, labelnames)


def histogram(name: str, help: str = "", labelnames=(),
              buckets=None) -> Histogram:
    return default_registry().histogram(name, help, labelnames, buckets)


def snapshot() -> dict:
    """Plain-dict (JSON-safe) view of the default registry."""
    return default_registry().snapshot()


def render_prometheus() -> str:
    return default_registry().render_prometheus()


# -- tracing shorthands (default tracer) ----------------------------------
def span(name: str, **attrs):
    """Context manager timing a named interval on the default tracer.
    A shared no-op when tracing is disabled (obs.disable())."""
    if not _enabled:
        return NULL_SPAN
    return default_tracer().span(name, **attrs)


class _TimedSpan:
    """Span + millisecond-histogram observation in one context manager
    (the wired layers' shared timing idiom: estimator phases, graph rpc
    calls). Class-based — never a @contextmanager — so exceptions,
    including StopIteration, propagate untouched; the histogram is
    observed on BOTH the success and the raise path. __enter__ returns
    the span so callers can sp.set(...) attributes mid-flight."""

    __slots__ = ("_sp", "_hist", "_t0")

    def __init__(self, sp, hist):
        self._sp = sp
        self._hist = hist

    def __enter__(self):
        self._t0 = time.monotonic()
        self._sp.__enter__()
        return self._sp

    def __exit__(self, *exc):
        self._sp.__exit__(*exc)
        self._hist.observe((time.monotonic() - self._t0) * 1000.0)
        return False


def timed_span(name: str, hist, **attrs) -> _TimedSpan:
    """`with obs.timed_span("phase", hist_ms, **attrs) as sp:` — a span
    on the default tracer whose wall time also lands in `hist` (in ms),
    success or raise."""
    return _TimedSpan(span(name, **attrs), hist)


def record_span(name: str, dur_s: float, **attrs) -> None:
    """A finished span that ended now and lasted `dur_s`, timed by
    somebody else (first_calls.py: jax.monitoring's durations); nothing
    when tracing is disabled."""
    if _enabled:
        default_tracer().record(name, dur_s, **attrs)


def traced_path(path: str, detail="", n: int = 1) -> None:
    """Count a branch taken while a program is TRACED (or run eagerly):
    traced_paths_total{path,detail} says which form of a layer went into
    the compiled step without fetching anything from the device. Tests
    read it; `path` names the branch, `detail` the layer, hop, table or
    collection it was taken for."""
    counter(
        "traced_paths_total",
        "branches of the models, the draw and the guard taken while a "
        "program was traced (or run eagerly), by branch and by the layer, "
        "hop, table, encoder or collection it was taken for",
        ("path", "detail")).labels(path=path, detail=str(detail)).inc(n)


def dump_trace(path: str) -> str:
    """Export the default tracer's span ring as chrome://tracing JSON."""
    return default_tracer().export(path)


def clear_trace() -> None:
    """Drop all finished spans (start of a measured region)."""
    default_tracer().clear()


# -- the profiler's clock --------------------------------------------------
def install_profiler_annotation(factory) -> None:
    """Give every span of the default tracer a second sink:
    `factory("euler.<name>", **attrs)` must return a context manager,
    entered and left with the span on the span's thread. The jax-side
    code (estimator/base_estimator.py) hands jax.profiler.TraceAnnotation
    in, so a span opened during a jax.profiler session is an event of
    that session's host plane; obs itself never imports jax. None takes
    the sink away (as does reset_for_tests(): a fresh tracer has none)."""
    default_tracer().annotate = factory


# -- global switch ---------------------------------------------------------
def enable() -> None:
    """(Re-)enable span recording (the default)."""
    global _enabled
    _enabled = True
    default_tracer().enabled = True


def disable() -> None:
    """Disable span recording: obs.span() returns a shared no-op (~0.1µs
    per call). Counters/gauges stay live — health() compat views and
    /metrics depend on them, and a bump is already ≲1µs."""
    global _enabled
    _enabled = False
    default_tracer().enabled = False


def enabled() -> bool:
    return _enabled


# -- serving ---------------------------------------------------------------
def serve(port: int = 0, registry: Optional[Registry] = None,
          addr: str = "127.0.0.1") -> ObsServer:
    """Start the /metrics + /healthz endpoint (daemon thread). port=0
    picks an ephemeral port — read srv.port; srv.close() shuts down
    cleanly (no leaked thread, port freed)."""
    return ObsServer(port=port, registry=registry, addr=addr)


def reset_for_tests() -> None:
    """Fresh default registry + tracer (hermetic tests only — production
    code must never drop live counters out from under health() views)."""
    global _registry, _tracer, _enabled
    with _mu:
        _registry = Registry()
        _tracer = Tracer()
        _enabled = True
