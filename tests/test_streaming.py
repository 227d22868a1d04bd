"""Streaming graph deltas (ISSUE 9 tentpole): epoch-stamped ApplyDelta,
surgical cache invalidation, incremental alias patching, and the
continuous-learning loop.

The invariants pinned here are the ones the tentpole turns from
assumptions into checked contracts:

  * delta-applied graph == from-scratch build on the final edge set
    (adjacency, in-adjacency, features, weight sums, samplers' inputs);
  * engine rows are APPEND-ONLY across deltas (derived row-indexed
    state stays valid for untouched rows);
  * epoch cache coherence: after a bump is observed, no read returns
    pre-delta data — and untouched warm entries are RETAINED (counted);
  * DeviceNeighborTable.patch_rows rebuilds O(dirty) rows and the
    patched table is byte-identical to a scratch build;
  * wrappers (chaos / cache) never hide an engine method;
  * remote: a broadcast delta lands each row on its hash-owner shard,
    epochs propagate, and the fleet serves post-delta answers;
  * the StreamingDriver round makes served kNN reflect a node that did
    not exist at train start (slow).
"""

import os
import time

import numpy as np
import pytest

from euler_tpu.core.lib import EngineError
from euler_tpu.graph import GraphBuilder, GraphEngine
from euler_tpu.graph.api import delta_dirty_ids

pytestmark = pytest.mark.mutation


def _base_builder(n=40, weighted=True):
    """Small 2-type graph with dense + sparse features and some
    duplicate edges (exercises last-wins dedup through the delta path)."""
    rng = np.random.default_rng(5)
    b = GraphBuilder()
    b.set_num_types(2, 2)
    b.set_feature(0, 0, 3, "feat")
    b.set_feature(1, 1, 0, "tags")
    ids = np.arange(1, n + 1, dtype=np.uint64)
    b.add_nodes(ids, types=(ids % 2).astype(np.int32),
                weights=np.linspace(1, 2, n).astype(np.float32))
    m = n * 4
    src = rng.integers(1, n + 1, m).astype(np.uint64)
    dst = rng.integers(1, n + 1, m).astype(np.uint64)
    et = rng.integers(0, 2, m).astype(np.int32)
    w = (rng.random(m) + 0.1).astype(np.float32) if weighted \
        else np.ones(m, np.float32)
    b.add_edges(src, dst, types=et, weights=w)
    b.set_node_dense(ids, 0, rng.random((n, 3), dtype=np.float32))
    b.set_node_sparse(ids, 1, np.arange(n + 1, dtype=np.uint64) * 2,
                      np.arange(2 * n, dtype=np.uint64))
    return b, (src, dst, et, w), ids


_DELTA = {
    "node_ids": np.array([101, 102, 7], np.uint64),      # adds + update
    "node_types": np.array([0, 1, 1], np.int32),
    "node_weights": np.array([1.5, 2.5, 9.0], np.float32),
    "edge_src": np.array([101, 102, 3, 3], np.uint64),   # adds + update
    "edge_dst": np.array([1, 101, 4, 102], np.uint64),
    "edge_types": np.array([0, 1, 0, 0], np.int32),
    "edge_weights": np.array([0.5, 0.6, 7.0, 0.8], np.float32),
}


def _scratch_final(n=40, weighted=True):
    """From-scratch build on the final (base + delta) row set."""
    b, _, _ = _base_builder(n, weighted)
    b.add_nodes(_DELTA["node_ids"], types=_DELTA["node_types"],
                weights=_DELTA["node_weights"])
    b.add_edges(_DELTA["edge_src"], _DELTA["edge_dst"],
                types=_DELTA["edge_types"], weights=_DELTA["edge_weights"])
    return b.finalize()


def _assert_graph_parity(g, g2):
    assert g.node_count == g2.node_count
    assert g.edge_count == g2.edge_count
    ids = g.all_node_ids()
    assert np.array_equal(ids, g2.all_node_ids())  # row identity
    np.testing.assert_allclose(g.node_weight_sums(), g2.node_weight_sums(),
                               rtol=1e-6)
    np.testing.assert_allclose(g.edge_weight_sums(), g2.edge_weight_sums(),
                               rtol=1e-6)
    assert np.array_equal(g.all_node_weights(), g2.all_node_weights())
    assert np.array_equal(g.get_node_type(ids), g2.get_node_type(ids))
    for in_edges in (False, True):
        a = g.get_full_neighbor(ids, sorted_by_id=not in_edges,
                                in_edges=in_edges)
        b_ = g2.get_full_neighbor(ids, sorted_by_id=not in_edges,
                                  in_edges=in_edges)
        for x, y in zip(a, b_):
            assert np.array_equal(x, y)
    assert np.array_equal(g.get_dense_feature(ids, "feat"),
                          g2.get_dense_feature(ids, "feat"))
    so, sv = g.get_sparse_feature(ids, "tags")
    so2, sv2 = g2.get_sparse_feature(ids, "tags")
    assert np.array_equal(so, so2) and np.array_equal(sv, sv2)


def test_delta_parity_vs_scratch():
    """apply_delta == rebuilding from zero on the final edge set: node
    type/weight updates land, duplicate (src,dst,type) edges update the
    weight in place, new rows append, features carry over — and the
    whole derived surface (adjacency both directions, features, weight
    sums) is byte-identical."""
    b, _, _ = _base_builder()
    g = b.finalize()
    e0 = g.graph_epoch()
    epoch = g.apply_delta(**_DELTA)
    assert (e0, epoch) == (0, 1)
    _assert_graph_parity(g, _scratch_final())
    # the updated edge's weight really moved (3 -(t0)-> 4 is now 7.0)
    off, nbr, w, t = g.get_full_neighbor([3], edge_types=[0],
                                         sorted_by_id=True)
    sel = (nbr == 4)
    assert sel.any() and np.all(w[sel] == 7.0)


def test_row_identity_append_only():
    b, _, ids0 = _base_builder()
    g = b.finalize()
    rows_before = g.node_rows(ids0)
    g.apply_delta(**_DELTA)
    assert np.array_equal(g.node_rows(ids0), rows_before)
    assert np.array_equal(g.all_node_ids()[:len(ids0)], ids0)
    # new nodes appended past the old rows
    assert set(g.all_node_ids()[len(ids0):]) == {101, 102}


def test_epoch_dirty_history_and_overflow():
    b, _, _ = _base_builder()
    g = b.finalize()
    g.apply_delta(node_ids=[201], edge_src=[201], edge_dst=[1])
    g.apply_delta(edge_src=[2], edge_dst=[201])
    epoch, covered, dirty = g.delta_since(0)
    assert (epoch, covered) == (2, True)
    assert set(dirty) == {1, 2, 201}
    epoch, covered, dirty = g.delta_since(1)
    assert covered and set(dirty) == {2, 201}
    epoch, covered, dirty = g.delta_since(2)
    assert covered and dirty.size == 0
    # bounded history: push past the 64-epoch window → uncovered from 0
    for i in range(70):
        g.apply_delta(edge_src=[3], edge_dst=[4], edge_weights=[1.0 + i])
    epoch, covered, dirty = g.delta_since(0)
    assert epoch == 72 and not covered and dirty.size == 0
    # recent window still covered
    epoch, covered, dirty = g.delta_since(epoch - 5)
    assert covered and set(dirty) == {3, 4}


def test_delta_since_epoch_regression_uncovered():
    """Asking for deltas past the graph's CURRENT epoch means the
    caller observed an epoch this graph never reached — a restarted
    shard that reloaded pre-delta data. That must report uncovered
    (flush), never 'covered, nothing dirty' (review finding: silent
    permanent staleness)."""
    b, _, _ = _base_builder()
    g = b.finalize()
    g.apply_delta(edge_src=[1], edge_dst=[2])
    epoch, covered, dirty = g.delta_since(5)   # from > cur
    assert epoch == 1 and not covered and dirty.size == 0
    epoch, covered, dirty = g.delta_since(1)   # from == cur stays clean
    assert covered and dirty.size == 0


def test_cached_engine_flushes_on_epoch_regression():
    """An engine whose epoch goes BACKWARD (shard restart lost deltas)
    forces a counted full flush and re-anchors the observed epoch —
    warm rows from the lost future must not survive."""
    from euler_tpu.graph.pipeline import CachedGraphEngine

    class RewindableEngine:
        def __init__(self):
            self.epoch = 3
            self.serve = np.float32(1.0)

        def graph_epoch(self):
            return self.epoch

        def delta_since(self, from_epoch):
            return self.epoch, from_epoch <= self.epoch, \
                np.zeros(0, np.uint64)

        def get_dense_feature(self, ids, fids, dims=None):
            ids = np.asarray(ids)
            return np.full((ids.size, 2), self.serve, np.float32)

    eng = RewindableEngine()
    cache = CachedGraphEngine(eng)
    ids = np.arange(1, 5, dtype=np.uint64)
    assert cache.get_dense_feature(ids, "feat")[0, 0] == 1.0
    eng.epoch = 0                  # restart: pre-delta graph, epoch 0
    eng.serve = np.float32(9.0)    # and different data
    out = cache.get_dense_feature(ids, "feat")
    assert out[0, 0] == 9.0        # flushed, refetched — not stale 1.0
    st = cache.cache_stats()
    assert st["graph_epoch"] == 0 and st["epoch_flushes"] == 1


def test_empty_delta_rejected():
    b, _, _ = _base_builder()
    g = b.finalize()
    with pytest.raises(ValueError, match="empty delta"):
        g.apply_delta()
    with pytest.raises(ValueError, match="disagree"):
        g.apply_delta(node_ids=[1, 2], node_types=[0])


def test_local_query_proxy_sees_swap():
    """A Query bound to the handle BEFORE the delta serves post-delta
    answers after it (the GraphRef swap, not a rebuilt proxy)."""
    from euler_tpu.gql import Query

    b, _, _ = _base_builder()
    g = b.finalize()
    q = Query.local(g)
    try:
        g.apply_delta(node_ids=[301], edge_src=[301, 1],
                      edge_dst=[1, 301], edge_weights=[1.0, 2.0])
        out = q.run("v(r).getNB(*).as(nb)",
                    {"r": np.array([301], np.uint64)})
        assert 1 in out["nb:1"].astype(np.uint64)
        assert q.epoch() == 1
    finally:
        q.close()


def test_udf_cache_epoch_eviction():
    """The UDF result cache is a second results cache: entries for the
    swapped-out snapshot are dropped at the bump (counted), and the
    post-delta answer reflects the new graph."""
    from euler_tpu.gql import Query, udf_cache_clear, udf_cache_stats

    b, _, _ = _base_builder()
    g = b.finalize()
    udf_cache_clear()
    q = Query.local(g)
    try:
        ids = np.arange(1, 11, dtype=np.uint64)
        out1 = q.run("v(r).udf(mean, feat).as(m)", {"r": ids})
        q.run("v(r).udf(mean, feat).as(m)", {"r": ids})  # warm hit
        s0 = udf_cache_stats()
        assert s0["entries"] >= 1 and s0["hits"] >= 1
        g.apply_delta(node_ids=[7], node_types=[1], node_weights=[9.0])
        s1 = udf_cache_stats()
        assert s1["epoch_evictions"] > s0["epoch_evictions"]
        # recompute on the new snapshot still answers (and re-caches)
        out2 = q.run("v(r).udf(mean, feat).as(m)", {"r": ids})
        assert np.array_equal(out1["m:1"], out2["m:1"])  # features same
    finally:
        q.close()


# ---------------------------------------------------------------------------
# CachedGraphEngine epoch coherence
# ---------------------------------------------------------------------------

def _warm_cache(cache, ids):
    cache.get_dense_feature(ids, "feat")
    cache.get_full_neighbor(ids, sorted_by_id=True)


def test_cached_engine_surgical_invalidation():
    from euler_tpu.graph.pipeline import CachedGraphEngine

    b, _, ids0 = _base_builder()
    g = b.finalize()
    cache = CachedGraphEngine(g)
    _warm_cache(cache, ids0)
    warm = cache.cache_stats()["entries"]
    assert warm == 2 * len(ids0)
    epoch = cache.apply_delta(**_DELTA)
    st = cache.cache_stats()
    assert st["graph_epoch"] == epoch == 1
    dirty = delta_dirty_ids(**_DELTA)
    in_cache = np.intersect1d(dirty, ids0).size
    assert st["epoch_evicted"] == 2 * in_cache      # both stores
    assert st["epoch_retained"] == warm - 2 * in_cache
    assert st["epoch_flushes"] == 0
    # ZERO STALE: every cached answer equals the engine's direct answer
    ids_all = g.all_node_ids()
    got = cache.get_full_neighbor(ids_all, sorted_by_id=True)
    want = g.get_full_neighbor(ids_all, sorted_by_id=True)
    for x, y in zip(got, want):
        assert np.array_equal(x, y)
    assert np.array_equal(cache.get_dense_feature(ids_all, "feat"),
                          g.get_dense_feature(ids_all, "feat"))


def test_cached_engine_out_of_band_bump():
    """A delta applied directly on the engine (another client) is
    reconciled at the next cached read via the epoch poll + dirty
    history — no stale read after the bump is observed."""
    from euler_tpu.graph.pipeline import CachedGraphEngine

    b, _, ids0 = _base_builder()
    g = b.finalize()
    cache = CachedGraphEngine(g)
    _warm_cache(cache, ids0)
    g.apply_delta(edge_src=[3], edge_dst=[9], edge_types=[0],
                  edge_weights=[42.0])          # NOT through the wrapper
    off, nbr, w, t = cache.get_full_neighbor([3], edge_types=[0],
                                             sorted_by_id=True)
    assert 42.0 in w
    st = cache.cache_stats()
    assert st["graph_epoch"] == 1 and st["epoch_evicted"] >= 1
    assert st["epoch_retained"] > 0


def test_cached_engine_apply_gap_reconciles():
    """The wrapper's apply_delta fast path (invalidate from the LOCAL
    dirty set) is only sound when its delta is the very next epoch; if
    another client applied in between, the gap's dirty ids must be
    reconciled too — review finding pinned here."""
    from euler_tpu.graph.pipeline import CachedGraphEngine

    b, _, ids0 = _base_builder()
    g = b.finalize()
    cache = CachedGraphEngine(g)
    _warm_cache(cache, ids0)
    # out-of-band delta touches node 11 (epoch 1, unobserved)
    g.apply_delta(edge_src=[11], edge_dst=[12], edge_types=[0],
                  edge_weights=[33.0])
    # the wrapper's own delta touches DIFFERENT nodes (epoch 2)
    cache.apply_delta(edge_src=[20], edge_dst=[21], edge_types=[0],
                      edge_weights=[34.0])
    assert cache.cache_stats()["graph_epoch"] == 2
    # node 11's warm entry must NOT serve pre-epoch-1 data
    off, nbr, w, t = cache.get_full_neighbor([11], edge_types=[0],
                                             sorted_by_id=True)
    assert 33.0 in w


def test_cached_engine_wraps_epochless_chaos_engine():
    """A delegating wrapper (ChaosGraphEngine) always EXPOSES the epoch
    verbs but raises AttributeError when its inner engine lacks them —
    CachedGraphEngine over that composition must construct and serve
    (epoch tracking simply disabled), not crash."""
    from euler_tpu.graph.chaos import ChaosGraphEngine, ChaosPlan
    from euler_tpu.graph.pipeline import CachedGraphEngine

    class Epochless:
        def get_dense_feature(self, ids, fids, dims=None):
            ids = np.asarray(ids)
            return np.ones((ids.size, 2), np.float32)

    cache = CachedGraphEngine(ChaosGraphEngine(Epochless(), ChaosPlan()))
    out = cache.get_dense_feature(np.array([1, 2], np.uint64), "feat")
    assert out.shape == (2, 2)
    assert cache.cache_stats()["graph_epoch"] is None


def test_streaming_driver_fine_tune_advances_steps():
    """fine_tune(steps=k) trains k MORE steps even after prior training
    (BaseEstimator.train's max_steps is absolute — review finding)."""
    import flax.linen as nn
    import jax.numpy as jnp

    from euler_tpu.estimator import BaseEstimator, StreamingDriver
    from euler_tpu.mp_utils.base import ModelOutput

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, batch):
            v = nn.Dense(2)(batch["x"])
            loss = jnp.mean(v ** 2)
            return ModelOutput(v, loss, "l", loss)

    def fn():
        while True:
            yield {"x": np.ones((4, 3), np.float32)}

    est = BaseEstimator(Tiny(), {"log_steps": 1000,
                                 "checkpoint_steps": 0})
    est.train(fn(), max_steps=3)
    assert int(est.state.step) == 3
    b, _, _ = _base_builder()
    driver = StreamingDriver(est, b.finalize())
    driver.fine_tune(2, input_fn=fn())
    assert int(est.state.step) == 5


def test_server_rejects_oversized_delta_counts(tmp_path):
    """A malformed kApplyDelta body declaring huge row counts fails
    with a status instead of allocating from the wire-supplied counts
    (review finding: bad_alloc would kill the shard)."""
    import socket
    import struct

    g, _, servers, eps = _two_shard_cluster(tmp_path)
    try:
        host, port = eps.split(",")[0].rsplit(":", 1)
        body = struct.pack("<Q", 1 << 62)  # n_nodes = 2^62, no payload
        frame = struct.pack("<II", 0x52465445, 7)  # 'ETFR', kApplyDelta
        frame += struct.pack("<Q", len(body)) + body
        with socket.create_connection((host, int(port)), timeout=10) as s:
            s.sendall(frame)
            s.settimeout(10)
            hdr = s.recv(16)
        assert len(hdr) == 16  # server answered; it did not die
        # and the shard still serves real traffic afterwards
        from euler_tpu.graph import RemoteGraphEngine

        remote = RemoteGraphEngine(f"hosts:{eps}", seed=1)
        try:
            assert remote.sample_node(4, -1).size == 4
        finally:
            remote.close()
    finally:
        for s in servers:
            s.stop()


def test_cached_engine_flush_fallback():
    """Dirty sets past epoch_dirty_bound (or a history gap) fall back
    to the documented full flush — counted, never silent."""
    from euler_tpu.graph.pipeline import CachedGraphEngine

    b, _, ids0 = _base_builder()
    g = b.finalize()
    cache = CachedGraphEngine(g, epoch_dirty_bound=2)
    _warm_cache(cache, ids0)
    warm = cache.cache_stats()["entries"]
    cache.apply_delta(**_DELTA)                  # dirty set > bound
    st = cache.cache_stats()
    assert st["epoch_flushes"] == 1
    assert st["epoch_evicted"] == warm and st["epoch_retained"] == 0
    # correctness unaffected
    assert np.array_equal(
        cache.get_dense_feature(ids0, "feat"),
        g.get_dense_feature(ids0, "feat"))


def test_wrappers_never_hide_engine_methods():
    """Wrapper-drift guard: every public callable of the wrapped engine
    is reachable through ChaosGraphEngine and CachedGraphEngine (the
    new epoch/delta verbs included), and a genuinely missing attribute
    raises AttributeError naming it."""
    from euler_tpu.graph.chaos import ChaosGraphEngine, ChaosPlan
    from euler_tpu.graph.pipeline import CachedGraphEngine

    b, _, _ = _base_builder()
    g = b.finalize()
    for wrapper in (ChaosGraphEngine(g, ChaosPlan()),
                    CachedGraphEngine(g)):
        for name in dir(g):
            if name.startswith("_"):
                continue
            if callable(getattr(g, name)):
                assert callable(getattr(wrapper, name)), \
                    f"{type(wrapper).__name__} hides {name}"
        for verb in ("apply_delta", "graph_epoch", "delta_since"):
            assert callable(getattr(wrapper, verb))
        with pytest.raises(AttributeError):
            getattr(wrapper, "definitely_not_a_method")


def test_chaos_wrapper_delta_roundtrip():
    """The chaos wrapper delegates the delta verbs un-intercepted: an
    error-injecting plan must never fault an apply_delta (epoch
    bookkeeping would diverge from the engine's)."""
    from euler_tpu.graph.chaos import ChaosGraphEngine, ChaosPlan

    b, _, _ = _base_builder()
    g = b.finalize()
    chaos = ChaosGraphEngine(g, ChaosPlan(fail_from=0))  # every call fails
    epoch = chaos.apply_delta(node_ids=[400])
    assert epoch == 1 and chaos.graph_epoch() == 1
    _, covered, dirty = chaos.delta_since(0)
    assert covered and 400 in dirty


# ---------------------------------------------------------------------------
# DeviceNeighborTable incremental patching
# ---------------------------------------------------------------------------

def test_patch_rows_byte_parity_with_hubs():
    """Patched table == scratch-built table on the final edge set,
    byte-for-byte across nbr/cum/alias arrays — including hub rows
    (degree > cap), whose weighted subset draw is keyed statelessly per
    (seed, row, edge position)."""
    from euler_tpu.parallel.device_sampler import DeviceNeighborTable

    b, _, _ = _base_builder(weighted=True)
    g = b.finalize()
    # cap below the max degree so hub subsetting is exercised
    t = DeviceNeighborTable(g, cap=4, seed=7, keep_host=True, alias=True)
    g.apply_delta(**_DELTA)
    stats = t.patch_rows(g, delta_dirty_ids(**_DELTA))
    assert 0 < stats["rows_patched"] <= delta_dirty_ids(**_DELTA).size
    assert stats["grown_rows"] == 2
    assert stats["rebuild_frac"] < 0.5
    assert stats["upload"] == "replace"  # growth changes table shapes
    t2 = DeviceNeighborTable(_scratch_final(), cap=4, seed=7,
                             keep_host=True, alias=True)
    assert np.array_equal(t.host_tables[0], t2.host_tables[0])
    assert np.array_equal(t.host_tables[1], t2.host_tables[1])
    # the re-placed STORED tables are the scratch build's byte for byte
    _assert_stored_equal(t, t2)
    assert t.pad_row == t2.pad_row
    assert t.uniform_rows == t2.uniform_rows


def test_patch_rows_no_growth_edge_only():
    """An edge-only delta (no new nodes) patches in place: no growth,
    no pad remap, only the dirty rows re-derived."""
    from euler_tpu.parallel.device_sampler import DeviceNeighborTable

    b, _, _ = _base_builder()
    g = b.finalize()
    t = DeviceNeighborTable(g, cap=4, seed=7, keep_host=True, alias=True)
    before = t.host_tables[0].copy()
    delta = {"edge_src": np.array([3], np.uint64),
             "edge_dst": np.array([5], np.uint64),
             "edge_weights": np.array([4.0], np.float32)}
    g.apply_delta(**delta)
    stats = t.patch_rows(g, delta_dirty_ids(**delta))
    assert stats["grown_rows"] == 0
    # no growth → the DEVICE arrays take an O(dirty) .at[rows].set row
    # scatter, no O(N) host pull / re-upload
    assert stats["upload"] == "row_scatter"
    # untouched rows bit-copied
    row3 = int(g.node_rows(np.array([3], np.uint64))[0])
    row5 = int(g.node_rows(np.array([5], np.uint64))[0])
    untouched = np.ones(before.shape[0], bool)
    untouched[[row3, row5]] = False
    assert np.array_equal(t.host_tables[0][untouched], before[untouched])
    t2 = DeviceNeighborTable(g, cap=4, seed=7, keep_host=True, alias=True)
    assert np.array_equal(t.host_tables[0], t2.host_tables[0])
    assert np.array_equal(t.host_tables[1], t2.host_tables[1])
    # device copies match the scratch build byte-for-byte too — the
    # scattered rows really landed on device, not just in host_tables
    _assert_stored_equal(t, t2)


def _assert_stored_equal(t, t2):
    """The stored (byte-plane) device tables of `t` are `t2`'s byte for
    byte, and read back as t2's logical host tables."""
    from euler_tpu.parallel.device_sampler import logical_rows

    for name in ("neighbors", "cum_weights", "alias_table"):
        got, want = np.asarray(getattr(t, name)), np.asarray(
            getattr(t2, name))
        assert got.dtype == np.int8 and got.shape == want.shape
        assert np.array_equal(got, want), name
    assert np.array_equal(logical_rows(t.neighbors, "nbr"),
                          t2.host_tables[0])
    assert np.array_equal(
        logical_rows(t.cum_weights, "cum").view(np.int32),
        t2.host_tables[1].view(np.int32))


def test_patch_rows_refuses_unsupported_layouts():
    from euler_tpu.parallel.device_sampler import DeviceNeighborTable

    b, _, _ = _base_builder()
    g = b.finalize()
    t = DeviceNeighborTable(g, cap=4, fused=True)
    with pytest.raises(ValueError, match="replicated split"):
        t.patch_rows(g, np.array([1], np.uint64))


# ---------------------------------------------------------------------------
# Remote: broadcast deltas over the shard cluster
# ---------------------------------------------------------------------------

def _two_shard_cluster(tmp_path, n=40):
    from euler_tpu.gql import start_service

    b, _, _ = _base_builder(n)
    g = b.finalize()
    data_dir = str(tmp_path / "g")
    g.dump(data_dir, num_partitions=2)
    servers = [start_service(data_dir, shard_idx=i, shard_num=2, port=0)
               for i in range(2)]
    eps = ",".join(f"127.0.0.1:{s.port}" for s in servers)
    return g, data_dir, servers, eps


def test_remote_apply_delta_two_shards(tmp_path):
    """Broadcast delta over a 2-shard cluster: every shard bumps to the
    same epoch, each row lands on its hash-owner only (global node
    sampling stays single-counted), and post-delta reads through the
    cluster match the embedded delta-applied graph."""
    from euler_tpu.graph import RemoteGraphEngine

    g, _, servers, eps = _two_shard_cluster(tmp_path)
    remote = RemoteGraphEngine(f"hosts:{eps}", seed=1)
    try:
        assert remote.graph_epoch() == 0
        epoch = remote.apply_delta(**_DELTA)
        assert epoch == 1
        assert remote.graph_epoch() >= 1  # observed via the apply
        # dirty union over shards
        e2, covered, dirty = remote.delta_since(0)
        assert e2 == 1 and covered
        assert set(dirty) == set(delta_dirty_ids(**_DELTA))
        # reads match the embedded engine after the same delta
        g.apply_delta(**_DELTA)
        ids = g.all_node_ids()
        off_r, nbr_r, w_r, t_r = remote.get_full_neighbor(
            ids, sorted_by_id=True)
        off_l, nbr_l, w_l, t_l = g.get_full_neighbor(ids, sorted_by_id=True)
        assert np.array_equal(off_r, off_l)
        assert np.array_equal(nbr_r, nbr_l)
        assert np.array_equal(w_r, w_l)
        # a new node is sampleable from exactly one shard: drawing many
        # global samples never double-weights it (weight 1.5 of ~70)
        draws = remote.sample_node(2000, -1)
        frac = (draws == 101).mean()
        assert frac < 0.15  # double-placement would show ~2x weight
    finally:
        remote.close()
        for s in servers:
            s.stop()


def test_remote_epoch_piggyback_mux(tmp_path):
    """With the mux transport on, the epoch rides every v2 reply frame:
    a client that merely QUERIES observes another client's delta
    passively (no delta verbs issued)."""
    from euler_tpu.graph import RemoteGraphEngine
    from euler_tpu.graph.remote import configure_rpc

    g, _, servers, eps = _two_shard_cluster(tmp_path)
    configure_rpc(mux=True)
    try:
        observer = RemoteGraphEngine(f"hosts:{eps}", seed=1)
        writer = RemoteGraphEngine(f"hosts:{eps}", seed=2)
        try:
            observer.get_dense_feature(np.array([1], np.uint64), "feat")
            assert observer.graph_epoch() == 0
            writer.apply_delta(edge_src=[1], edge_dst=[2],
                               edge_weights=[3.0])
            # a plain read carries the bumped epoch back
            observer.get_dense_feature(np.array([1], np.uint64), "feat")
            assert observer.graph_epoch() == 1
        finally:
            observer.close()
            writer.close()
    finally:
        configure_rpc(mux=False)
        for s in servers:
            s.stop()


def test_remote_cached_engine_coherence(tmp_path):
    """CachedGraphEngine over a remote engine: an out-of-band delta by
    another client is reconciled via graph_epoch(refresh)/delta_since —
    post-delta reads through the cache match the cluster."""
    from euler_tpu.graph import RemoteGraphEngine
    from euler_tpu.graph.pipeline import CachedGraphEngine

    g, _, servers, eps = _two_shard_cluster(tmp_path)
    reader = RemoteGraphEngine(f"hosts:{eps}", seed=1)
    writer = RemoteGraphEngine(f"hosts:{eps}", seed=2)
    cache = CachedGraphEngine(reader)
    try:
        ids = np.arange(1, 41, dtype=np.uint64)
        cache.get_full_neighbor(ids, sorted_by_id=True)
        writer.apply_delta(edge_src=[3], edge_dst=[9], edge_types=[0],
                           edge_weights=[42.0])
        # v1 transport: the passive epoch doesn't move on its own —
        # maybe_invalidate picks the bump up once the epoch is observed
        assert reader.graph_epoch(refresh=True) == 1
        cache.maybe_invalidate()
        off, nbr, w, t = cache.get_full_neighbor(
            np.array([3], np.uint64), edge_types=[0], sorted_by_id=True)
        assert 42.0 in w
        st = cache.cache_stats()
        assert st["graph_epoch"] == 1 and st["epoch_retained"] > 0
    finally:
        cache.close()  # closes reader
        writer.close()
        for s in servers:
            s.stop()


# ---------------------------------------------------------------------------
# Drills (slow): mutation mid-train under chaos; the full streaming loop
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.chaos
def test_mutation_mid_train_chaos_drill(tmp_path):
    """Shard killed around ApplyDelta: the apply surfaces a status (no
    hang), the restarted shard re-joins from disk at epoch 0, re-issuing
    the delta converges the fleet (idempotent last-write-wins rows),
    training keeps making steps through the resilient input path, and
    at the end there are ZERO stale reads through the client cache."""
    from euler_tpu.gql import start_service
    from euler_tpu.graph import RemoteGraphEngine
    from euler_tpu.graph.pipeline import CachedGraphEngine
    from euler_tpu.graph.remote import RetryPolicy

    g, data_dir, servers, eps = _two_shard_cluster(tmp_path)
    # registry-dir discovery so the killed shard's replacement endpoint
    # re-resolves (the failover machinery under the delta verbs)
    reg_dir = str(tmp_path / "reg")
    os.makedirs(reg_dir, exist_ok=True)
    for s in servers:
        s.stop()
    servers = [start_service(data_dir, shard_idx=i, shard_num=2, port=0,
                             registry_dir=reg_dir) for i in range(2)]
    remote = RemoteGraphEngine(
        f"dir:{reg_dir}", seed=1,
        retry_policy=RetryPolicy(deadline_s=20.0, call_timeout_s=5.0))
    cache = CachedGraphEngine(remote)
    delta = {"node_ids": np.array([501], np.uint64),
             "edge_src": np.array([501, 2], np.uint64),
             "edge_dst": np.array([2, 501], np.uint64),
             "edge_weights": np.array([1.0, 2.0], np.float32)}
    try:
        ids0 = np.arange(1, 41, dtype=np.uint64)
        _ = cache.get_full_neighbor(ids0, sorted_by_id=True)
        servers[1].stop()                      # kill a shard mid-loop
        try:
            cache.apply_delta(**delta)
            applied_during_kill = True
        except EngineError:
            applied_during_kill = False        # surfaced, not hung
        # shard restarts FROM DISK (pre-delta, epoch 0) and re-registers
        servers[1] = start_service(data_dir, shard_idx=1, shard_num=2,
                                   port=0, registry_dir=reg_dir)
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            try:
                cache.apply_delta(**delta)     # idempotent re-issue
                break
            except EngineError:
                time.sleep(0.5)
        else:
            raise AssertionError("delta never converged after restart")
        # training-shaped load keeps flowing (sampling + features)
        steps = 0
        for _ in range(10):
            roots = remote.sample_node(32, -1)
            cache.get_dense_feature(roots, "feat")
            steps += 1
        assert steps == 10
        # zero stale reads: cache answers == live cluster answers on
        # every node incl. the delta's
        probe = np.concatenate([ids0, np.array([501], np.uint64)])
        got = cache.get_full_neighbor(probe, sorted_by_id=True)
        want = remote.get_full_neighbor(probe, sorted_by_id=True)
        for x, y in zip(got, want):
            assert np.array_equal(x, y)
        off, nbr, w, t = cache.get_full_neighbor(
            np.array([501], np.uint64), sorted_by_id=True)
        assert 2 in nbr.astype(np.uint64)      # the delta is serving
        assert applied_during_kill in (True, False)  # both paths legal
    finally:
        cache.close()
        for s in servers:
            s.stop()


@pytest.mark.slow
def test_streaming_driver_end_to_end(tmp_path):
    """ROADMAP item 3 acceptance: the graph grows mid-train via
    apply_delta, the driver fine-tunes, exports a fresh bundle, and
    hot-swaps it into the serving fleet — a kNN query then returns a
    node that did not exist at train start, within one export period."""
    import flax.linen as nn
    import jax.numpy as jnp

    from euler_tpu.estimator import BaseEstimator, StreamingDriver
    from euler_tpu.mp_utils.base import ModelOutput
    from euler_tpu.serving import InferenceServer, ServingClient

    b, _, ids0 = _base_builder(n=32)
    g = b.finalize()
    dim, B = 4, 8

    class FeatEmb(nn.Module):
        @nn.compact
        def __call__(self, batch):
            v = nn.Dense(dim, name="proj")(batch["feat"])
            loss = jnp.mean((v - batch["feat"][:, :dim - 1].sum(
                -1, keepdims=True)) ** 2)
            return ModelOutput(v, loss, "mse", loss)

    rng = np.random.default_rng(3)

    def train_fn():
        while True:
            ids = g.sample_node(B, -1)
            yield {"feat": g.get_dense_feature(ids, "feat"),
                   "infer_ids": ids}

    def sweep_fn():
        ids = g.all_node_ids()          # read at call time: post-delta
        for i in range(0, len(ids), B):
            part = ids[i:i + B]
            if len(part) < B:
                part = np.concatenate(
                    [part, np.full(B - len(part), part[-1], np.uint64)])
            yield {"feat": g.get_dense_feature(part, "feat"),
                   "infer_ids": part}

    est = BaseEstimator(FeatEmb(), {"log_steps": 1000,
                                    "checkpoint_steps": 0})
    est.train(train_fn(), max_steps=3)
    export_root = str(tmp_path / "bundles")
    v1_dir = os.path.join(export_root, "v1")
    bundle1 = est.export_bundle(v1_dir, input_fn=sweep_fn, nlist=2,
                                nprobe=2, version="v1")
    new_id = np.uint64(901)
    assert bundle1.ids.max() < new_id  # not in the fleet at train start
    with InferenceServer(v1_dir, service="stream", replica=0,
                         max_batch=8) as srv, \
            ServingClient(endpoints=f"hosts:127.0.0.1:{srv.port}",
                          service="stream") as cli:
        driver = StreamingDriver(est, g, serving_client=cli,
                                 export_dir=export_root)
        out = driver.round(
            {"node_ids": np.array([new_id], np.uint64),
             "edge_src": np.array([new_id], np.uint64),
             "edge_dst": np.array([1], np.uint64)},
            steps=3, train_input_fn=train_fn(), version="v2",
            input_fn=sweep_fn, nlist=2, nprobe=2)
        assert out["delta"]["epoch"] == 1
        assert out["swap"] is not None
        info = cli.info()
        assert info["bundle_version"] == "v2"
        assert info["count"] == len(bundle1.ids) + 1  # the new node serves
        # served kNN now RETURNS the node that did not exist at train
        # start (kNN ranks by inner product, so assert retrievability —
        # membership in the ranked id set — not self-top-1)
        nbr_ids, _ = cli.knn(np.array([new_id], np.uint64),
                             k=int(info["count"]))
        assert new_id in nbr_ids[0]
        # and the v1 fleet could not have: it did not hold the id at all
        assert new_id not in bundle1.ids
