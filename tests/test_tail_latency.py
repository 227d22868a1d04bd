"""Tail-latency machinery on the read path (ISSUE 12 tentpole).

Covers the four pillars end to end against REAL servers:

  * obs Histogram.quantile — bucket-interpolated estimates against
    known distributions (the signal the adaptive hedge delay and p2c
    read);
  * serving-client adaptive hedging — a straggling replica's sub-call
    fires a second leg at another replica; first reply wins,
    hedge_fired/won/wasted counted, the loser's reply discarded
    without ever reaching a decoder, results byte-identical;
  * mux-transport hedging (C++): through a chaos-proxy JITTER link
    (per-connection seeded latency) with 2 mux connections — the
    losing leg is cancelled by request_id at the demux reader, counted
    hedge_wasted exactly once per abandoned leg, and a
    CachedGraphEngine on top stays byte-coherent (a discarded reply
    can never mutate caches);
  * deadline propagation — v2 request frames carry the remaining
    budget; a shard sheds queued work whose budget expired (counted
    deadline_shed, explicit status, never a silent partial); v1
    interop is byte-unchanged (no deadline feature → no stamp);
  * chaos drill (slow): one replica with 50ms injected jitter —
    hedging recovers >= 2x on counted p999.

The transport config is process-global — the autouse fixture restores
defaults so no other test file runs on leaked hedge/p2c/mux knobs.
"""

import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from euler_tpu.graph import (
    CachedGraphEngine,
    GraphBuilder,
    RemoteGraphEngine,
    RetryPolicy,
    configure_rpc,
    rpc_transport_stats,
    seed,
)
from euler_tpu.graph.remote import RetryDeadlineExceeded
from euler_tpu.obs.metrics import Registry

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
from chaos_proxy import ChaosProxy, per_conn_jitter_ms  # noqa: E402

pytestmark = pytest.mark.tail_latency


@pytest.fixture(autouse=True)
def _restore_rpc_config():
    yield
    configure_rpc(mux=False, connections=1, compress_threshold=0,
                  max_inflight=256, hedge_delay_ms=0, p2c=False)


# ---------------------------------------------------------------------------
# Histogram.quantile
# ---------------------------------------------------------------------------

def test_quantile_known_distributions():
    reg = Registry()
    h = reg.histogram("q_ms", buckets=[1, 2, 4, 8, 16, 32])
    # bimodal: 80 obs in (2,4], 10 below 1, 10 in (16,32]
    for v in [0.5] * 10 + [3.0] * 80 + [20.0] * 10:
        h.observe(v)
    assert 2.0 <= h.quantile(0.5) <= 4.0
    assert 16.0 <= h.quantile(0.95) <= 32.0
    # q inside the first bucket interpolates down from its edge
    assert 0.0 <= h.quantile(0.05) <= 1.0
    # q=1 lands in the last occupied bucket
    assert h.quantile(1.0) <= 32.0


def test_quantile_uniform_interpolation_is_exact_on_edges():
    reg = Registry()
    h = reg.histogram("u_ms", buckets=[10, 20, 30, 40])
    # exactly uniform over 4 buckets -> quantiles land on bucket edges
    for v in (5, 15, 25, 35):
        h.observe(v)
    assert h.quantile(0.25) == pytest.approx(10.0)
    assert h.quantile(0.5) == pytest.approx(20.0)
    assert h.quantile(0.75) == pytest.approx(30.0)


def test_quantile_overflow_clamps_to_last_finite_bound():
    reg = Registry()
    h = reg.histogram("o_ms", buckets=[1, 2])
    for _ in range(10):
        h.observe(100.0)  # all in +Inf bucket
    assert h.quantile(0.99) == 2.0


def test_quantile_empty_and_invalid():
    reg = Registry()
    h = reg.histogram("e_ms", buckets=[1, 2])
    assert h.quantile(0.9) is None
    with pytest.raises(ValueError):
        h.quantile(1.5)
    lab = reg.histogram("l_ms", labelnames=("k",), buckets=[1, 2])
    lab.labels(k="a").observe(1.5)
    assert 1.0 <= lab.labels(k="a").quantile(0.5) <= 2.0


# ---------------------------------------------------------------------------
# serving-client hedging / p2c
# ---------------------------------------------------------------------------

def _bundle(tmp_path, nodes=400, dim=16):
    from euler_tpu.serving import ModelBundle

    rng = np.random.default_rng(3)
    emb = rng.normal(size=(nodes, dim)).astype(np.float32)
    b = ModelBundle({}, emb, np.arange(nodes, dtype=np.uint64),
                    meta={"bundle_version": "v1"})
    return b.save(str(tmp_path / "bundle"))


def _two_replica_fleet(tmp_path, stall_ms, stall_p=1.0):
    from euler_tpu.serving import InferenceServer

    bd = _bundle(tmp_path)
    reg = str(tmp_path / "reg")
    fast = InferenceServer(bd, registry=reg, service="tl", shard=0,
                           replica=0, flush_ms=0.5)
    slow = InferenceServer(bd, registry=reg, service="tl", shard=0,
                           replica=1, flush_ms=0.5,
                           inject_stall_ms=stall_ms,
                           inject_stall_p=stall_p, inject_seed=1)
    return reg, fast, slow


def test_serving_hedge_fires_wins_and_counts(tmp_path):
    """Against an always-stalling replica, rotated primaries hedge to
    the fast replica, the hedge wins, results stay byte-identical, and
    hedge_wasted counts exactly the abandoned legs."""
    from euler_tpu.serving import ServingClient

    reg, fast, slow = _two_replica_fleet(tmp_path, stall_ms=120.0)
    try:
        plain = ServingClient(registry=reg, service="tl")
        hedged = ServingClient(registry=reg, service="tl", hedge=True,
                               hedge_max_ms=20.0)
        ids = np.arange(12, dtype=np.uint64)
        ref = plain.embed(ids)
        for _ in range(20):
            assert np.array_equal(hedged.embed(ids), ref)
        h = hedged.health()
        # ~half the rotated primaries hit the stalled replica and hedge
        assert 0 < h["hedge_fired"] < 20
        assert h["hedge_won"] > 0
        # every fired hedge ends with exactly one abandoned leg
        assert h["hedge_wasted"] == h["hedge_fired"]
        plain.close()
        hedged.close()
    finally:
        fast.stop()
        slow.stop()


def test_serving_hedge_single_replica_degenerates_cleanly(tmp_path):
    """hedge=True against a 1-replica shard: nothing to hedge to —
    calls succeed unhedged, no counters move."""
    from euler_tpu.serving import InferenceServer, ServingClient

    bd = _bundle(tmp_path)
    reg = str(tmp_path / "reg")
    only = InferenceServer(bd, registry=reg, service="tl1", shard=0,
                           replica=0, flush_ms=0.5,
                           inject_stall_ms=30.0, inject_stall_p=1.0)
    try:
        cli = ServingClient(registry=reg, service="tl1", hedge=True,
                            hedge_max_ms=5.0)
        out = cli.embed(np.arange(4, dtype=np.uint64))
        assert out.shape == (4, 16)
        h = cli.health()
        assert h["hedge_fired"] == 0
        assert h["hedge_wasted"] == 0
        cli.close()
    finally:
        only.stop()


def test_serving_p2c_steers_away_from_straggler(tmp_path):
    """p2c replica selection: after warmup the EWMA ranks the stalled
    replica slower and picks stop landing on it (counted picks; the
    fast replica serves the steady state)."""
    from euler_tpu.serving import ServingClient

    reg, fast, slow = _two_replica_fleet(tmp_path, stall_ms=80.0)
    try:
        cli = ServingClient(registry=reg, service="tl", p2c=True, seed=5)
        ids = np.arange(8, dtype=np.uint64)
        for _ in range(10):
            cli.embed(ids)
        # steady state: the last calls should all be fast (the EWMA
        # table has both replicas by now)
        t0 = time.monotonic()
        for _ in range(5):
            cli.embed(ids)
        steady_ms = (time.monotonic() - t0) * 1000 / 5
        h = cli.health()
        assert h["p2c_picks"] > 0
        assert steady_ms < 40.0, f"p2c failed to steer ({steady_ms}ms)"
        cli.close()
    finally:
        fast.stop()
        slow.stop()


# ---------------------------------------------------------------------------
# graph/mux path: jitter proxy + request_id cancellation + caches
# ---------------------------------------------------------------------------

def _shard_graph(tmp_path, n=64, dim=16):
    seed(7)
    rng = np.random.default_rng(5)
    b = GraphBuilder()
    b.set_num_types(2, 1)
    b.set_feature(0, 0, dim, "feature")
    ids = np.arange(1, n + 1, dtype=np.uint64)
    b.add_nodes(ids, types=(ids % 2).astype(np.int32),
                weights=np.ones(n, np.float32))
    b.add_edges(ids, np.roll(ids, -1), types=np.zeros(n, np.int32),
                weights=np.ones(n, np.float32))
    b.set_node_dense(ids, 0, rng.normal(size=(n, dim)).astype(np.float32))
    d = str(tmp_path / "g")
    b.finalize().dump(d, num_partitions=1)
    return d, ids


def _jitter_seed(jitter_ms, fast_frac=0.1, slow_frac=0.6):
    """A seed whose first two per-connection draws are (fast, slow) —
    the straggler-link SETUP the drills need (mirrors the proxy's rng,
    see per_conn_jitter_ms)."""
    return next(
        s for s in range(1000)
        if per_conn_jitter_ms(jitter_ms, s, 2)[0] < fast_frac * jitter_ms
        and per_conn_jitter_ms(jitter_ms, s, 2)[1] > slow_frac * jitter_ms)


def test_mux_hedge_cancels_loser_by_request_id(tmp_path):
    """The pinned hedge-cancellation semantics: with one jittered mux
    connection, hedged deterministic reads return byte-identical
    results, hedge_wasted counts EXACTLY the abandoned legs (one per
    fired hedge — no leg failed here), and the loser's late reply is
    discarded by request_id without mutating a CachedGraphEngine on
    top (cached bytes == live bytes afterwards, no spurious entries)."""
    from euler_tpu.gql import start_service

    d, ids = _shard_graph(tmp_path)
    srv = start_service(d, shard_idx=0, shard_num=1, port=0)
    js = _jitter_seed(40.0)
    proxy = ChaosProxy("127.0.0.1", srv.port, mode="jitter",
                       jitter_ms=40.0, seed=js).start()
    try:
        configure_rpc(mux=True, connections=2)
        eng = RemoteGraphEngine(f"hosts:127.0.0.1:{proxy.port}", seed=11,
                                hedge=True, hedge_min_ms=2.0,
                                hedge_max_ms=8.0)
        cached = CachedGraphEngine(eng, budget_bytes=8 << 20)
        # reference from a clean, unhedged engine straight at the shard
        configure_rpc(hedge_delay_ms=0)
        ref_eng = RemoteGraphEngine(f"hosts:127.0.0.1:{srv.port}",
                                    seed=11)
        ref = ref_eng.get_dense_feature(ids, [0], [16])[0]
        configure_rpc(hedge_delay_ms=8.0)
        s0 = rpc_transport_stats()
        for _ in range(12):
            out = cached.get_dense_feature(ids, [0], [16])[0]
            assert np.array_equal(out, ref)
        s1 = rpc_transport_stats()
        fired = s1["hedge_fired"] - s0["hedge_fired"]
        wasted = s1["hedge_wasted"] - s0["hedge_wasted"]
        assert fired > 0, "no hedges fired through the jittered conn"
        # exactly one abandoned (request_id-cancelled) leg per fired
        # hedge: no leg failed in this drill
        assert wasted == fired
        # the discarded replies never reached the cache: a fully-warm
        # cache serves the same bytes with zero new wire calls
        stats0 = cached.cache_stats()
        warm = cached.get_dense_feature(ids, [0], [16])[0]
        stats1 = cached.cache_stats()
        assert np.array_equal(warm, ref)
        assert stats1["hits"] > stats0["hits"]
        assert stats1["misses"] == stats0["misses"]
        assert stats1["poison_skips"] == 0
        ref_eng.close()
        eng.close()
    finally:
        proxy.stop()
        srv.stop()


def test_mux_hedging_off_is_wire_identical(tmp_path):
    """Hedging/p2c/deadline all OFF: the transport must not stamp any
    deadline prefix or fire any hedge — the pre-ISSUE-12 wire, byte
    for byte (counted: zero deltas on every new counter)."""
    from euler_tpu.gql import start_service

    d, ids = _shard_graph(tmp_path)
    srv = start_service(d, shard_idx=0, shard_num=1, port=0)
    try:
        configure_rpc(mux=True, connections=2)
        eng = RemoteGraphEngine(f"hosts:127.0.0.1:{srv.port}", seed=11)
        s0 = rpc_transport_stats()
        eng.get_dense_feature(ids, [0], [16])
        s1 = rpc_transport_stats()
        for k in ("hedge_fired", "hedge_won", "hedge_wasted",
                  "deadline_propagated", "deadline_shed"):
            assert s1[k] == s0[k], f"{k} moved with the knobs off"
        eng.close()
    finally:
        srv.stop()


def test_deadline_propagation_sheds_queued_work(tmp_path):
    """Deadline propagation end to end: while every dispatch worker is
    pinned by O(graph) delta applies (the LOW lane), a read with a
    tiny propagated budget must be SHED by the server — counted
    deadline_shed, surfaced as an explicit retry-exhausted status,
    never a silent partial or a hang."""
    from euler_tpu.gql import start_service

    d, ids = _shard_graph(tmp_path, n=20_000)
    srv = start_service(d, shard_idx=0, shard_num=1, port=0)
    try:
        configure_rpc(mux=True, connections=1)
        # the warm-up read goes out under the engine's default, generous
        # policy (on a busy box a 5 ms budget sheds it before the part
        # under test starts); only the reads issued while the pool is
        # pinned carry the 5 ms budget
        eng = RemoteGraphEngine(
            f"hosts:127.0.0.1:{srv.port}", seed=11,
            deadline_propagation=True)
        warm = eng.get_dense_feature(ids[:8], [0], [16])
        assert warm[0].shape == (8, 16)
        eng.retry = RetryPolicy(deadline_s=0.005, max_attempts=1)
        # pin every pool worker: concurrent delta applies serialize on
        # the apply mutex INSIDE their pool tasks, each an O(graph)
        # rebuild of the 20k-node snapshot — far longer than the 5ms
        # read budget, for many rebuilds in a row
        appliers = []
        for i in range(16):
            t = threading.Thread(
                target=lambda i=i: eng.apply_delta(
                    node_ids=[100000 + i], node_types=[0],
                    node_weights=[1.0]))
            t.start()
            appliers.append(t)
        time.sleep(0.02)  # let the applies occupy the dispatch pool
        s0 = rpc_transport_stats()
        shed = 0
        # read while the pool is pinned (until the appliers drain)
        while any(t.is_alive() for t in appliers):
            try:
                eng.get_dense_feature(ids[:64], [0], [16])
            except RetryDeadlineExceeded as e:
                assert "deadline" in str(e).lower()
                shed += 1
        s1 = rpc_transport_stats()
        for t in appliers:
            t.join()
        assert s1["deadline_propagated"] > s0["deadline_propagated"]
        assert s1["deadline_shed"] > s0["deadline_shed"], \
            "server never shed a dead read while its pool was pinned"
        assert shed > 0
        # the shard is healthy afterwards: the same read succeeds
        eng2 = RemoteGraphEngine(f"hosts:127.0.0.1:{srv.port}", seed=11)
        ok = eng2.get_dense_feature(ids[:8], [0], [16])
        assert np.array_equal(ok[0], warm[0])
        eng2.close()
        eng.close()
    finally:
        srv.stop()


def test_v1_interop_unchanged_with_knobs_on(tmp_path):
    """A v1-only server (pre-v2 binary emulation) with every tail knob
    ON: the hello is refused, the channel falls back to v1, nothing is
    stamped or hedged — results byte-identical to a plain v1 client."""
    import os

    from euler_tpu.gql import start_service

    d, ids = _shard_graph(tmp_path)
    os.environ["EULER_TPU_RPC_SERVER_V1"] = "1"
    try:
        srv = start_service(d, shard_idx=0, shard_num=1, port=0)
    finally:
        del os.environ["EULER_TPU_RPC_SERVER_V1"]
    try:
        plain = RemoteGraphEngine(f"hosts:127.0.0.1:{srv.port}", seed=11)
        ref = plain.get_dense_feature(ids, [0], [16])[0]
        configure_rpc(mux=True, connections=2, p2c=True)
        # the refused hello (→ v1 fallback) fires during engine Init
        s0 = rpc_transport_stats()
        eng = RemoteGraphEngine(f"hosts:127.0.0.1:{srv.port}", seed=11,
                                hedge=True, hedge_max_ms=5.0,
                                deadline_propagation=True)
        out = eng.get_dense_feature(ids, [0], [16])[0]
        s1 = rpc_transport_stats()
        assert np.array_equal(out, ref)
        assert s1["hello_fallbacks"] > s0["hello_fallbacks"]
        for k in ("hedge_fired", "deadline_propagated", "deadline_shed",
                  "trace_propagated"):
            assert s1[k] == s0[k], f"{k} moved against a v1 server"
        eng.close()
        plain.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# jitter proxy
# ---------------------------------------------------------------------------

def test_jitter_proxy_per_connection_latency(tmp_path):
    """The jitter mode assigns one seeded draw per connection (accept
    order, mirrored by per_conn_jitter_ms) and counts every injected
    delay."""
    import socket as socketmod

    # target: a trivial echo server
    lst = socketmod.socket()
    lst.bind(("127.0.0.1", 0))
    lst.listen(4)
    stop = threading.Event()

    def echo():
        while not stop.is_set():
            try:
                c, _ = lst.accept()
            except OSError:
                return
            def pump(c=c):
                try:
                    while True:
                        b = c.recv(4096)
                        if not b:
                            return
                        c.sendall(b)
                except OSError:
                    pass
            threading.Thread(target=pump, daemon=True).start()

    threading.Thread(target=echo, daemon=True).start()
    js = _jitter_seed(60.0)
    draws = per_conn_jitter_ms(60.0, js, 2)
    proxy = ChaosProxy("127.0.0.1", lst.getsockname()[1], mode="jitter",
                       jitter_ms=60.0, seed=js).start()
    try:
        rtts = []
        for _ in range(2):
            s = socketmod.create_connection(("127.0.0.1", proxy.port))
            s.setsockopt(socketmod.IPPROTO_TCP,
                         socketmod.TCP_NODELAY, 1)
            s.sendall(b"ping")  # warm the pipes (conn setup excluded)
            s.recv(16)
            t0 = time.monotonic()
            s.sendall(b"ping")
            s.recv(16)
            rtts.append((time.monotonic() - t0) * 1000)
            s.close()
        # conn 1 carries draw[0] (fast), conn 2 draw[1] (slow): the
        # measured split must match the mirrored schedule
        assert rtts[0] < draws[0] + 25.0
        assert rtts[1] > draws[1] * 0.8
        assert proxy.counters["jitter"] == 2
        assert proxy.counters["jitter_injected"] >= 2
    finally:
        proxy.stop()
        stop.set()
        lst.close()


# ---------------------------------------------------------------------------
# chaos drill (slow): hedging recovers >= 2x on counted p999
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_hedging_recovers_p999_under_replica_jitter(tmp_path):
    """One replica with 50ms injected jitter (20% of flushes stall):
    counted p999 with hedging on recovers >= 2x vs off. The injected
    stall dominates every overhead on this container, so the ratio is
    robust even at 2 CPUs."""
    from euler_tpu.serving import ServingClient

    reg, fast, slow = _two_replica_fleet(tmp_path, stall_ms=50.0,
                                         stall_p=0.2)
    try:
        ids = np.arange(8, dtype=np.uint64)

        def leg(**kw):
            cli = ServingClient(registry=reg, service="tl", seed=3, **kw)
            for _ in range(8):
                cli.embed(ids)  # warm conns + the hedge-delay histogram
            lats = []
            for _ in range(200):
                t0 = time.monotonic()
                cli.embed(ids)
                lats.append((time.monotonic() - t0) * 1000)
            h = cli.health()
            cli.close()
            lats.sort()
            return lats[min(int(len(lats) * 0.999), len(lats) - 1)], h

        p999_off, _ = leg()
        p999_on, h = leg(hedge=True, hedge_max_ms=12.0)
        assert h["hedge_fired"] > 0
        assert h["hedge_wasted"] == h["hedge_fired"]
        assert p999_off >= 45.0, \
            f"straggler never showed in the tail (p999 {p999_off}ms)"
        assert p999_off / max(p999_on, 1e-9) >= 2.0, \
            f"hedging recovered only {p999_off / p999_on:.2f}x " \
            f"({p999_off:.1f} -> {p999_on:.1f}ms)"
    finally:
        fast.stop()
        slow.stop()
