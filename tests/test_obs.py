"""euler_tpu.obs coverage (ISSUE 3): registry concurrency, histogram
bucket edges, span nesting/parenting, Prometheus exposition golden
text, chrome-trace JSON shape, the /metrics http endpoint lifecycle,
trace_dump --self-test, and the wired-layer acceptance scenarios
(estimator phase split; health() as an exact registry view; chaos
faults visible as metrics)."""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from euler_tpu import obs

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_concurrency_exact_total():
    """N threads bumping ONE counter child must lose no increments."""
    r = obs.Registry()
    c = r.counter("hits_total")
    n_threads, per = 8, 5000

    def worker():
        for _ in range(per):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert int(c.value) == n_threads * per


def test_counter_rejects_negative_and_gauge_moves():
    r = obs.Registry()
    with pytest.raises(ValueError):
        r.counter("c_total").inc(-1)
    g = r.gauge("g")
    g.set(5)
    g.inc(2)
    g.dec(3)
    assert g.value == 4


def test_histogram_bucket_edges_le_inclusive():
    """Prometheus `le` semantics: a value exactly ON a bound lands in
    that bucket; above the last bound lands in +Inf."""
    r = obs.Registry()
    h = r.histogram("lat_ms", buckets=[1.0, 2.0, 4.0])
    for v in (0.5, 1.0, 1.0001, 2.0, 4.0, 4.0001):
        h.observe(v)
    snap = h.value
    # cumulative per bound: le=1 → {0.5, 1.0}; le=2 adds {1.0001, 2.0};
    # le=4 adds {4.0}; +Inf adds {4.0001}
    assert snap["buckets"] == [[1.0, 2], [2.0, 4], [4.0, 5], ["+Inf", 6]]
    assert snap["count"] == 6
    assert abs(snap["sum"] - 12.5002) < 1e-9


def test_histogram_default_buckets_are_log_scale():
    b = obs.DEFAULT_MS_BUCKETS
    assert len(b) == 24 and b[0] == 0.001
    ratios = {round(b[i + 1] / b[i], 9) for i in range(len(b) - 1)}
    assert ratios == {2.0}  # fixed log-scale (powers of two)


def test_registry_get_or_create_and_conflicts():
    r = obs.Registry()
    a = r.counter("x_total", "help", ("k",))
    assert r.counter("x_total", labelnames=("k",)) is a
    a.labels(k="1").inc()
    assert a.labels(k="1").value == 1
    with pytest.raises(ValueError):
        r.gauge("x_total")  # kind conflict
    with pytest.raises(ValueError):
        r.counter("x_total", labelnames=("other",))  # label conflict
    with pytest.raises(ValueError):
        a.inc()  # labeled metric used without labels
    with pytest.raises(ValueError):
        a.labels(wrong="1")


def test_prometheus_exposition_golden():
    r = obs.Registry()
    c = r.counter("rpc_total", "rpc calls", ("engine",))
    c.labels(engine="r0").inc(3)
    r.gauge("temp", "a gauge").set(1.5)
    h = r.histogram("ms", "latency", buckets=[1, 2])
    h.observe(0.5)
    h.observe(3.0)
    assert r.render_prometheus() == (
        "# HELP ms latency\n"
        "# TYPE ms histogram\n"
        'ms_bucket{le="1"} 1\n'
        'ms_bucket{le="2"} 1\n'
        'ms_bucket{le="+Inf"} 2\n'
        "ms_sum 3.5\n"
        "ms_count 2\n"
        "# HELP rpc_total rpc calls\n"
        "# TYPE rpc_total counter\n"
        'rpc_total{engine="r0"} 3\n'
        "# HELP temp a gauge\n"
        "# TYPE temp gauge\n"
        "temp 1.5\n")


def test_histogram_bucket_conflict_raises():
    """A silently-dropped bucket spec would park every observe in the
    wrong bounds — re-registration with different bounds must raise."""
    r = obs.Registry()
    h = r.histogram("lat", buckets=[1, 10, 100])
    assert r.histogram("lat", buckets=[100, 10, 1]) is h  # order-free
    assert r.histogram("lat") is h                        # default = keep
    with pytest.raises(ValueError, match="buckets"):
        r.histogram("lat", buckets=[1000, 10000])


def test_metric_remove_and_registry_prune():
    r = obs.Registry()
    c = r.counter("jobs_total", "", ("est",))
    h = r.histogram("jobs_ms", "", ("est",), buckets=[1])
    for e in ("a", "b"):
        c.labels(est=e).inc()
        h.labels(est=e).observe(0.5)
    c.remove(est="a")
    assert set(c._snapshot_values()) == {"est=b"}
    r.prune("est", "b")  # retires est=b across ALL metrics
    assert c._snapshot_values() == {}
    assert set(h._snapshot_values()) == {"est=a"}
    r.prune("est", "a")
    assert h._snapshot_values() == {}
    # pruned children stay usable for holders; registry just forgot them
    text = r.render_prometheus()
    assert "est=" not in text


def test_snapshot_delta_measured_region():
    r = obs.Registry()
    c = r.counter("n_total")
    g = r.gauge("level")
    h = r.histogram("ms", buckets=[1.0, 4.0])
    c.inc(5)
    g.set(10)
    h.observe(0.5)
    before = r.snapshot()
    c.inc(2)
    g.set(3)
    h.observe(2.0)
    delta = obs.snapshot_delta(before, r.snapshot())
    assert delta["n_total"]["values"][""] == 2          # counter: diff
    assert delta["level"]["values"][""] == 3            # gauge: level
    hd = delta["ms"]["values"][""]
    assert hd["count"] == 1 and abs(hd["sum"] - 2.0) < 1e-9
    assert hd["buckets"] == [[1.0, 0], [4.0, 1], ["+Inf", 1]]
    json.dumps(delta)


def test_timed_span_observes_on_raise():
    r = obs.Registry()
    h = r.histogram("op_ms", buckets=[1e9])
    with pytest.raises(RuntimeError):
        with obs.timed_span("op", h):
            raise RuntimeError("boom")
    assert h.value["count"] == 1  # latency recorded on the raise path


def test_snapshot_is_json_safe_and_collectors_run():
    r = obs.Registry()
    g = r.gauge("bridged")
    calls = []
    r.add_collector(lambda: (calls.append(1), g.set(len(calls)))[0])
    snap = r.snapshot()
    json.dumps(snap)  # must serialize as-is (bench embeds it)
    assert snap["bridged"]["values"][""] == 1.0
    r.snapshot()
    assert g.value == 2.0


def test_collector_removal_on_false_and_raise():
    r = obs.Registry()
    r.add_collector(lambda: False)          # source gone → dropped
    boom = {"n": 0}

    def bad():
        boom["n"] += 1
        raise RuntimeError("scrape-time failure")

    r.add_collector(bad)
    r.snapshot()
    r.snapshot()
    assert boom["n"] == 1  # raised once, then dropped
    assert int(r.counter("obs_collector_errors_total").value) == 1


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_span_nesting_and_parenting():
    tr = obs.Tracer()
    with tr.span("outer") as outer:
        assert tr.current_span() is outer
        with tr.span("mid") as mid:
            with tr.span("leaf"):
                pass
        assert mid.parent_id == outer.span_id
    by_name = {s.name: s for s in tr.spans()}
    assert set(by_name) == {"outer", "mid", "leaf"}
    assert by_name["outer"].parent_id == 0
    assert by_name["mid"].parent_id == by_name["outer"].span_id
    assert by_name["leaf"].parent_id == by_name["mid"].span_id


def test_span_threads_do_not_inherit_parents():
    tr = obs.Tracer()
    got = {}

    def worker():
        with tr.span("in_thread") as s:
            got["parent"] = s.parent_id

    with tr.span("main_span"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert got["parent"] == 0  # parenting is thread-local


def test_trace_ring_is_bounded():
    tr = obs.Tracer(capacity=8)
    for i in range(50):
        with tr.span(f"s{i}"):
            pass
    spans = tr.spans()
    assert len(spans) == 8
    assert spans[0].name == "s42"  # oldest fell off


def test_chrome_trace_json_fields(tmp_path):
    tr = obs.Tracer()
    with tr.span("parent", shard=3):
        with tr.span("child"):
            time.sleep(0.001)
    path = str(tmp_path / "trace.json")
    tr.export(path)
    with open(path) as f:
        trace = json.load(f)
    ev = trace["traceEvents"]
    assert len(ev) == 2 and trace["displayTimeUnit"] == "ms"
    for e in ev:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert e["pid"] == os.getpid() and e["tid"]
    parent = next(e for e in ev if e["name"] == "parent")
    child = next(e for e in ev if e["name"] == "child")
    assert parent["args"]["shard"] == 3
    assert child["args"]["parent_id"] == parent["args"]["span_id"]
    # containment: the child interval sits inside the parent's
    assert child["ts"] >= parent["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-3
    assert child["dur"] >= 1000  # the 1ms sleep, in µs


def test_trace_ids_roots_fresh_children_inherit():
    """Cross-process correlation ids (ISSUE 14): a ROOT span draws a
    fresh nonzero trace id, children inherit it, the next root gets a
    different one, and chrome args carry it on every event."""
    tr = obs.Tracer()
    with tr.span("root1") as a:
        assert a.trace_id != 0
        with tr.span("child") as b:
            assert b.trace_id == a.trace_id
    with tr.span("root2") as c:
        assert c.trace_id not in (0, a.trace_id)
    ev = tr.chrome_trace()["traceEvents"]
    assert all("trace_id" in e["args"] for e in ev)
    ids = {e["args"]["trace_id"] for e in ev}
    assert len(ids) == 2  # two traces, child shares root1's
    # two tracers (≈ two processes) never collide in a merge
    other = obs.Tracer()
    with other.span("elsewhere") as d:
        pass
    assert d.trace_id not in ids


def test_tracer_export_under_concurrent_recording(tmp_path):
    """ISSUE 14 satellite pin: chrome_trace()/export() while recording
    threads are still appending (and mutating span attrs via set()) —
    the harness dumps traces while load is draining. Every export must
    succeed and leave parseable JSON; concurrent exports to the SAME
    path must never corrupt each other (the shared-.tmp race)."""
    tr = obs.Tracer(capacity=4096)
    stop = threading.Event()
    errs = []

    def recorder(widx):
        i = 0
        try:
            while not stop.is_set():
                with tr.span("work", w=widx) as sp:
                    sp.set(i=i, extra=f"e{i}")
                i += 1
        except BaseException as e:  # pragma: no cover - diagnostics
            errs.append(e)

    path = str(tmp_path / "live.json")

    def exporter():
        try:
            for _ in range(15):
                tr.export(path)
        except BaseException as e:  # pragma: no cover - diagnostics
            errs.append(e)

    recs = [threading.Thread(target=recorder, args=(w,))
            for w in range(3)]
    exps = [threading.Thread(target=exporter) for _ in range(2)]
    for t in recs + exps:
        t.start()
    try:
        for _ in range(15):
            p = tr.export(path)
            with open(p) as f:
                trace = json.load(f)  # parseable EVERY time
            assert "traceEvents" in trace
    finally:
        stop.set()
        for t in recs + exps:
            t.join(timeout=10)
    assert not errs, errs
    assert not any(t.is_alive() for t in recs + exps)
    # final export is complete and well-formed
    final = json.load(open(tr.export(path)))
    assert all(e["ph"] == "X" for e in final["traceEvents"])
    # no .tmp litter from the concurrent exports
    leftovers = [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    assert leftovers == []


def test_disabled_span_is_shared_noop():
    tr = obs.Tracer()
    tr.enabled = False
    s1, s2 = tr.span("a"), tr.span("b")
    assert s1 is s2 is obs.NULL_SPAN
    with s1:
        pass
    assert len(tr.spans()) == 0
    tr.enabled = True
    with tr.span("real"):
        pass
    assert len(tr.spans()) == 1


def test_trace_dump_self_test_cli():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_dump.py"),
         "--self-test"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "self-test OK" in out.stdout


# ---------------------------------------------------------------------------
# exposition endpoint
# ---------------------------------------------------------------------------

def test_serve_scrape_and_clean_shutdown():
    """obs.serve(port=0) must serve /metrics + /healthz and shut down
    without leaking its thread or the port."""
    r = obs.Registry()
    r.counter("smoke_total", "endpoint smoke").inc(7)
    obs.register_health("smoke_probe", lambda: {"ok": 1})
    try:
        srv = obs.serve(port=0, registry=r)
        body = urllib.request.urlopen(
            f"{srv.url}/metrics", timeout=5).read().decode()
        assert "# TYPE smoke_total counter" in body
        assert "smoke_total 7" in body
        hz = json.loads(urllib.request.urlopen(
            f"{srv.url}/healthz", timeout=5).read())
        assert hz["status"] == "ok"
        assert hz["providers"]["smoke_probe"] == {"ok": 1}
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{srv.url}/nope", timeout=5)
        port = srv.port
        srv.close()
        assert not srv._thread.is_alive()  # no leaked serve thread
        with pytest.raises(OSError):       # port actually released
            socket.create_connection(("127.0.0.1", port), timeout=0.5)
    finally:
        obs.unregister_health("smoke_probe")


def test_health_provider_weakref_drops_dead_object():
    class Probe:
        def health(self):
            return {"alive": True}

    p = Probe()
    obs.register_health("weak_probe", p.health)
    assert obs.health_snapshot()["weak_probe"] == {"alive": True}
    del p
    import gc

    gc.collect()
    assert "weak_probe" not in obs.health_snapshot()


# ---------------------------------------------------------------------------
# wired layers
# ---------------------------------------------------------------------------

def test_chaos_faults_land_on_registry():
    """Fault injection and observability must agree on counts:
    chaos_injected_total{engine,kind} == ChaosGraphEngine.stats()."""
    from euler_tpu.graph.chaos import ChaosGraphEngine, ChaosPlan

    class Stub:
        def sample_node(self, count, node_type=-1):
            return np.zeros(count, np.uint64)

    chaos = ChaosGraphEngine(Stub(), ChaosPlan(
        fail_calls=(1, 3), latency_ms=1.0, truncate_rate=0.0))
    for _ in range(5):
        try:
            chaos.sample_node(4)
        except Exception:
            pass
    st = chaos.stats()
    assert st["errors"] == 2 and st["delayed"] == 5
    snap = obs.snapshot()["chaos_injected_total"]["values"]
    name = chaos._obs_name
    assert snap[f"engine={name},kind=error"] == st["errors"]
    assert snap[f"engine={name},kind=delay"] == st["delayed"]
    assert snap.get(f"engine={name},kind=truncate", 0) == 0


def _tiny_citation():
    from euler_tpu.dataset.base_dataset import synthetic_citation

    return synthetic_citation("obs_tiny", n=60, d=8, num_classes=3,
                              train_per_class=8, val=10, test=10, seed=4)


def _tiny_estimator(graph, sleep_s=0.0, **extra):
    from euler_tpu.dataflow import FullBatchDataFlow
    from euler_tpu.estimator import NodeEstimator
    from euler_tpu.mp_utils import BaseGNNNet, SuperviseModel

    class TinyGCN(SuperviseModel):
        def embed(self, batch):
            return BaseGNNNet("gcn", 8, 2, name="gnn")(batch)

    flow = FullBatchDataFlow(graph, feature_ids=["feature"])
    params = {"batch_size": 8, "learning_rate": 0.05,
              "log_steps": 1 << 30, "checkpoint_steps": 0,
              "label_dim": 3, **extra}
    est = NodeEstimator(TinyGCN(num_classes=3, multilabel=False),
                        params, graph, flow, label_fid="label",
                        label_dim=3)
    if sleep_s:
        base_fn = est.train_input_fn

        def slowed():
            it = base_fn()
            for b in it:
                time.sleep(sleep_s)
                yield b

        return est, slowed
    return est, est.train_input_fn


def test_estimator_phase_split_accounts_for_wall_time():
    """input_wait + device_step must approximately account for train()
    wall time (the 'where did the milliseconds go' acceptance check) —
    here the input path is made deliberately slow so the split is
    dominated by a known quantity."""
    est, input_fn = _tiny_estimator(_tiny_citation().engine, sleep_s=0.02)
    # step 1 separately: model.init + jit compile happen OUTSIDE the
    # phase spans and would dominate the wall clock of a cold call
    est.train(input_fn, max_steps=1)
    iw0 = est._hist_input_wait.value
    ds0 = est._hist_device_step.value
    t0 = time.monotonic()
    res = est.train(input_fn, max_steps=9)
    wall_ms = (time.monotonic() - t0) * 1000.0
    assert res["global_step"] == 9
    iw = est._hist_input_wait.value
    ds = est._hist_device_step.value
    assert iw["count"] - iw0["count"] == 8  # first fetch + 7 tail fetches
    assert ds["count"] - ds0["count"] == 8
    covered = (iw["sum"] - iw0["sum"]) + (ds["sum"] - ds0["sum"])
    # async dispatch and the end-of-run summary stacking leave a little
    # wall time outside the two phases, hence "approximately"
    assert covered <= wall_ms * 1.05
    assert covered >= wall_ms * 0.6, (covered, wall_ms)
    assert iw["sum"] - iw0["sum"] >= 8 * 20 * 0.8  # 20ms sleeps are seen

    # per-step spans carry the same split into the chrome trace
    names = [s.name for s in obs.default_tracer().spans()]
    assert "input_wait" in names and "device_step" in names \
        and "train_step" in names


def test_estimator_health_is_exact_registry_view():
    """estimator.health() must EQUAL the registry children — one
    bookkeeping, two surfaces."""
    est, input_fn = _tiny_estimator(_tiny_citation().engine)
    est.train(input_fn, max_steps=3)
    h = est.health()
    snap = obs.snapshot()
    lbl = f"estimator={est._obs_name}"
    assert h["input_failures"] == snap[
        "estimator_input_failures_total"]["values"][lbl]
    assert h["input_retries"] == snap[
        "estimator_input_retries_total"]["values"][lbl]
    assert h["skipped_batches"] == snap[
        "estimator_skipped_batches_total"]["values"][lbl]
    assert snap["estimator_global_step"]["values"][lbl] == 3.0
    assert snap["estimator_steps_per_sec"]["values"][lbl] > 0
    # and the same numbers serve over HTTP
    srv = obs.serve(port=0)
    try:
        body = urllib.request.urlopen(
            f"{srv.url}/metrics", timeout=5).read().decode()
        assert (f'estimator_device_step_ms_count'
                f'{{estimator="{est._obs_name}"}} 3') in body
        hz = json.loads(urllib.request.urlopen(
            f"{srv.url}/healthz", timeout=5).read())
        assert hz["providers"][est._obs_name]["input_failures"] == \
            h["input_failures"]
    finally:
        srv.close()


@pytest.mark.chaos
def test_remote_engine_obs_acceptance(tmp_path):
    """The ISSUE 3 acceptance scenario: one estimator train() against a
    live shard yields (a) a Prometheus scrape containing RPC,
    input-pipeline, and step metrics; (b) a chrome trace whose spans
    show the per-step input_wait/device_step split with graph_rpc spans
    nested under input_wait; (c) remote.health() == the registry's
    counters (compat view, not parallel bookkeeping)."""
    from test_chaos import _featured_graph

    from euler_tpu.dataflow import FanoutDataFlow
    from euler_tpu.estimator import NodeEstimator
    from euler_tpu.gql import start_service
    from euler_tpu.graph.remote import RemoteGraphEngine
    from euler_tpu.models import SupervisedGraphSage

    data_dir = _featured_graph(tmp_path)
    server = start_service(data_dir, shard_idx=0, shard_num=1, port=0)
    remote = RemoteGraphEngine(f"hosts:127.0.0.1:{server.port}", seed=3)
    tracer = obs.default_tracer()
    tracer.clear()
    try:
        flow = FanoutDataFlow(remote, [3, 2], feature_ids=["feature"])
        est = NodeEstimator(
            SupervisedGraphSage(num_classes=4, multilabel=False, dim=8,
                                fanouts=(3, 2)),
            dict(batch_size=8, learning_rate=0.05, log_steps=1 << 30,
                 checkpoint_steps=0, label_dim=4),
            remote, flow, label_fid="label", label_dim=4)
        res = est.train(est.train_input_fn, max_steps=4)
        assert res["global_step"] == 4

        # (a) one scrape carries all three layers
        text = obs.render_prometheus()
        lbl = f'engine="{remote._obs_name}"'
        assert f"graph_rpc_calls_total{{{lbl}}}" in text
        assert f"graph_rpc_ms_count{{{lbl}}}" in text
        assert "estimator_input_wait_ms_bucket" in text
        assert "estimator_device_step_ms_bucket" in text
        assert "gql_proxy_queries" in text  # engine-side stats bridged

        # (b) rpc spans parent under the input_wait phase spans
        spans = {s.span_id: s for s in tracer.spans()}
        rpc = [s for s in spans.values() if s.name == "graph_rpc"]
        assert rpc, "no graph_rpc spans recorded"
        parent_names = {spans[s.parent_id].name for s in rpc
                        if s.parent_id in spans}
        assert "input_wait" in parent_names, parent_names

        # (c) health() is a view over the SAME counters
        h = remote.health()
        snap = obs.snapshot()
        elbl = f"engine={remote._obs_name}"
        for k in ("calls", "retries", "failovers", "degraded",
                  "deadline_exhausted"):
            assert h[k] == snap[f"graph_rpc_{k}_total"]["values"][elbl], k
        assert h["calls"] == h["proxy_queries"]  # every call hit the wire
        assert snap["gql_proxy_queries"]["values"][
            f"proxy={remote._obs_name}"] == h["proxy_queries"]
    finally:
        remote.close()
        server.stop()


def test_remote_health_merge_failure_is_counted(tmp_path):
    """After close() the proxy stats merge fails: pre-obs that was an
    `except Exception: pass`; now it must be narrow and COUNTED."""
    from test_chaos import _featured_graph

    from euler_tpu.gql import start_service
    from euler_tpu.graph.remote import RemoteGraphEngine

    data_dir = _featured_graph(tmp_path, n=20)
    server = start_service(data_dir, shard_idx=0, shard_num=1, port=0)
    remote = RemoteGraphEngine(f"hosts:127.0.0.1:{server.port}", seed=1)
    try:
        remote.sample_node(4, -1)
        h = remote.health()
        assert h["health_merge_errors"] == 0
        assert h["proxy_queries"] >= 1
    finally:
        remote.close()
        server.stop()
    h = remote.health()  # merge now fails: counted, not swallowed
    assert h["health_merge_errors"] == 1
    assert "proxy_queries" not in h
    assert h["calls"] >= 1  # local counters still serve


def test_disabled_path_cost_is_tiny():
    """obs.disable(): a span() call must be a no-op singleton — bound
    the per-call cost loosely (≤5µs even on a loaded CI box; measured
    ~0.1-0.6µs, PERF.md)."""
    obs.disable()
    try:
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("x"):
                pass
        per_call_us = (time.perf_counter() - t0) / n * 1e6
        assert per_call_us < 5.0, per_call_us
    finally:
        obs.enable()


# ---------------------------------------------------------------------------
# the second sink: spans on the profiler's clock (ISSUE 24)
# ---------------------------------------------------------------------------

def _host_events(trace_dir):
    """[(line index, name, start_ns, end_ns, stats)] of the host planes of
    the one .xplane.pb under trace_dir."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                out.append((i, ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def test_span_in_a_profiler_session_is_an_event_of_its_host_plane(
        tmp_path):
    import jax

    import euler_tpu.estimator  # noqa: F401 - installs the bridge

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    obs.clear_trace()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("caller"):
            with obs.span("probe", shard=3):
                time.sleep(0.002)
            obs.disable()
            try:
                with obs.span("hidden"):
                    time.sleep(0.001)
            finally:
                obs.enable()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    (caller,) = [e for e in events if e[1] == "caller"]
    (probe,) = [e for e in events if e[1] == "euler.probe"]
    # same thread, nested in time inside the caller's own annotation
    assert probe[0] == caller[0]
    assert caller[2] <= probe[2] and probe[3] <= caller[3]
    assert probe[3] - probe[2] >= 2e6
    assert probe[4]["shard"] == 3
    assert not [e for e in events if e[1] == "euler.hidden"]
    # the ring keeps what it kept before
    assert [s.name for s in obs.default_tracer().spans()] == ["probe"]
    # outside a session the sink is the profiler's own no-op
    with obs.span("after"):
        pass
    assert obs.default_tracer().spans()[-1].name == "after"


def test_obs_imports_and_records_without_jax_or_the_bridge():
    code = (
        "import sys\n"
        "from euler_tpu import obs\n"
        "assert obs.default_tracer().annotate is None\n"
        "with obs.span('outer', k=1):\n"
        "    with obs.span('inner'):\n"
        "        pass\n"
        "inner, outer = obs.default_tracer().spans()\n"
        "assert inner.parent_id == outer.span_id\n"
        "assert 'jax' not in sys.modules, 'obs pulled jax in'\n")
    res = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr


def test_the_sink_is_entered_and_left_with_each_span():
    seen = []

    class Sink:
        def __init__(self, name, **attrs):
            self.what = (name, attrs)

        def __enter__(self):
            seen.append(("in",) + self.what)

        def __exit__(self, *exc):
            seen.append(("out",) + self.what)

    import jax

    try:
        obs.install_profiler_annotation(Sink)
        with obs.span("x", a=1):
            pass
        with obs.span("y"):
            pass
    finally:
        obs.install_profiler_annotation(jax.profiler.TraceAnnotation)
    assert seen == [("in", "euler.x", {"a": 1}), ("out", "euler.x", {"a": 1}),
                    ("in", "euler.y", {}), ("out", "euler.y", {})]


def test_scanned_dispatch_is_one_parent_fully_covered():
    """One scanned est.train = exactly one train_dispatch; input_wait,
    stack, device_step and result_wait are its children and leave under
    5 % of it open (the input is slowed so the span's own cost is small
    against it)."""
    est, input_fn = _tiny_estimator(_tiny_citation().engine, sleep_s=0.01,
                                    steps_per_loop=4)
    est.train(input_fn, max_steps=4)        # init + compile, not timed
    rw0 = est._hist_result_wait.value["count"]
    obs.clear_trace()
    res = est.train(input_fn, max_steps=8)
    assert res["global_step"] == 8
    spans = obs.default_tracer().spans()
    (parent,) = [s for s in spans if s.name == "train_dispatch"]
    assert parent.attrs["K"] == 4 and parent.attrs["step"] == 4
    kids = [s for s in spans if s.parent_id == parent.span_id]
    assert [s.name for s in sorted(kids, key=lambda s: s.ts_us)] == [
        "input_wait", "stack", "device_step", "result_wait"]
    covered = sum(s.dur_us for s in kids)
    assert covered >= 0.95 * parent.dur_us, (covered, parent.dur_us)
    assert covered <= parent.dur_us
    # the first batch's wait is a span of the same thread, before it;
    # both are children of the call's one `train` span (PR 34)
    (call,) = [s for s in spans if s.name == "train"]
    assert parent.parent_id == call.span_id and call.parent_id == 0
    assert call.attrs["max_steps"] == 8
    first = [s for s in spans if s.name == "input_wait"
             and s.parent_id == call.span_id]
    assert len(first) == 1 and first[0].tid == parent.tid
    assert first[0].ts_us + first[0].dur_us <= parent.ts_us
    assert est._hist_result_wait.value["count"] - rw0 == 1
    assert "ENQUEUE" in obs.snapshot()["estimator_device_step_ms"]["help"]


@pytest.mark.parametrize("carried", [False, True])
def test_read_ahead_is_a_child_of_the_window_it_runs_under(
        slow_step_estimator, numbered_source, carried):
    """A two-window call, and a call that finds its window carried: the
    children of train_dispatch are [input_wait, stack,] device_step,
    read_ahead, result_wait in time order; read_ahead holds the next
    window's input_wait and one stack; all on the train thread, the
    parent covered."""
    K = 4
    est = slow_step_estimator(150, steps_per_loop=K)
    if carried:
        it = numbered_source()()
        est.train(it, max_steps=K)
        obs.clear_trace()
        assert est.train(it, max_steps=2 * K)["global_step"] == 2 * K
    else:
        est.train(numbered_source(), max_steps=K)    # compiles
        obs.clear_trace()
        res = est.train(numbered_source(), max_steps=3 * K)
        assert res["global_step"] == 3 * K
    spans = obs.default_tracer().spans()
    windows = sorted((s for s in spans if s.name == "train_dispatch"),
                     key=lambda s: s.ts_us)
    assert len(windows) == (1 if carried else 2)
    if not carried:
        # a callable's last window reads nothing ahead: as it was
        last = windows.pop()
        assert [s.name for s in spans if s.parent_id == last.span_id] == [
            "device_step", "result_wait"]

    def kids(parent):
        return sorted((s for s in spans if s.parent_id == parent.span_id),
                      key=lambda s: s.ts_us)

    for window in windows:
        names = [s.name for s in kids(window)]
        # the first window of a call on a fresh iterator waits and stacks
        own = [] if carried else ["input_wait", "stack"]
        assert names == own + ["device_step", "read_ahead", "result_wait"]
        (ahead,) = [s for s in kids(window) if s.name == "read_ahead"]
        assert ahead.attrs["step"] == window.attrs["step"] + K
        assert ahead.attrs["K"] == ahead.attrs["got"] == K
        inner = kids(ahead)
        assert [s.name for s in inner] == ["input_wait", "stack"]
        assert {s.tid for s in kids(window) + inner} == {window.tid}
        covered = sum(s.dur_us for s in kids(window))
        assert 0.95 * window.dur_us <= covered <= window.dur_us


@pytest.mark.parametrize("workers", [0, 2])
def test_feeders_report_batches_and_produce_time(workers):
    from euler_tpu.estimator.prefetch import make_feeder

    def slow_double(x):
        time.sleep(0.002)
        return 2 * x

    obs.clear_trace()
    name = None
    with make_feeder(iter(range(6)), workers=workers, depth=2,
                     transform=slow_double) as feed:
        name = feed._obs.name
        assert list(feed) == [0, 2, 4, 6, 8, 10]
    snap = obs.snapshot()
    lbl = f"feeder={name}"
    assert snap["feeder_batches_total"]["values"][lbl] == 6
    assert snap["feeder_queue_depth"]["values"][lbl] == 0
    hist = snap["feeder_produce_ms"]["values"][lbl]
    assert hist["count"] >= 6 and hist["sum"] >= 6 * 2.0
    spans = obs.default_tracer().spans()
    produce = {s.span_id: s for s in spans if s.name == "feeder_produce"}
    transform = [s for s in spans if s.name == "feeder_transform"]
    assert len(transform) == 6
    for s in transform:
        assert s.parent_id in produce
        assert s.tid == produce[s.parent_id].tid != threading.get_ident()
        assert s.attrs["feeder"] == name
