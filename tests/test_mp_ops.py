"""mp_ops unit tests (parity: reference mp_ops_test.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from euler_tpu.ops import mp_ops as mp


def test_gather():
    p = jnp.arange(12.0).reshape(4, 3)
    out = mp.gather(p, jnp.array([2, 0]))
    np.testing.assert_allclose(out, [[6, 7, 8], [0, 1, 2]])


def test_scatter_add():
    src = jnp.ones((4, 2))
    idx = jnp.array([0, 1, 1, 2])
    out = mp.scatter_add(src, idx, 3)
    np.testing.assert_allclose(out[:, 0], [1, 2, 1])


def test_scatter_mean_empty_segment():
    src = jnp.array([[2.0], [4.0]])
    idx = jnp.array([0, 0])
    out = mp.scatter_mean(src, idx, 3)
    np.testing.assert_allclose(out.ravel(), [3.0, 0.0, 0.0])


def test_scatter_max():
    src = jnp.array([[1.0], [5.0], [-2.0]])
    idx = jnp.array([0, 0, 2])
    out = mp.scatter_max(src, idx, 3)
    assert out[0, 0] == 5.0
    assert out[1, 0] == 0.0  # empty segment clamps to 0
    assert out[2, 0] == -2.0


def test_scatter_softmax_sums_to_one():
    logits = jnp.array([1.0, 2.0, 3.0, -1.0])
    idx = jnp.array([0, 0, 1, 1])
    att = mp.scatter_softmax(logits, idx, 2)
    assert att[0] + att[1] == pytest.approx(1.0, abs=1e-5)
    assert att[2] + att[3] == pytest.approx(1.0, abs=1e-5)


def test_scatter_softmax_2d():
    logits = jnp.ones((4, 3))
    idx = jnp.array([0, 0, 1, 1])
    att = mp.scatter_softmax(logits, idx, 2)
    np.testing.assert_allclose(att, 0.5 * np.ones((4, 3)), atol=1e-5)


def test_degree_norm():
    ei = jnp.array([[0, 1, 2], [1, 1, 0]])
    norm = mp.degree_norm(ei, 3)
    assert norm.shape == (3,)
    assert jnp.all(norm > 0)


# ---------------------------------------------------------------------------
# utils: to_dense, spmm, barriers
# ---------------------------------------------------------------------------
def test_to_dense_batch_and_adj():
    import jax.numpy as jnp

    from euler_tpu.utils.to_dense import to_dense_adj, to_dense_batch

    # 2 graphs: nodes 0,1,2 in g0; 3,4 in g1
    x = jnp.arange(10, dtype=jnp.float32).reshape(5, 2)
    gi = jnp.array([0, 0, 0, 1, 1])
    dense, mask = to_dense_batch(x, gi, num_graphs=2, max_nodes=3)
    assert dense.shape == (2, 3, 2)
    np.testing.assert_allclose(dense[0], x[:3])
    np.testing.assert_allclose(dense[1, :2], x[3:])
    np.testing.assert_array_equal(mask, [[1, 1, 1], [1, 1, 0]])

    # edges 0→1, 1→2 in g0; 3→4 in g1
    ei = jnp.array([[0, 1, 3], [1, 2, 4]])
    adj = to_dense_adj(ei, gi, num_graphs=2, max_nodes=3)
    assert adj[0, 0, 1] == 1 and adj[0, 1, 2] == 1
    assert adj[1, 0, 1] == 1
    assert adj.sum() == 3


def test_spmm_matches_dense():
    import jax.numpy as jnp

    from euler_tpu.contrib import spmm

    rng = np.random.default_rng(0)
    n, e, d = 8, 30, 4
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    w = rng.random(e).astype(np.float32)
    x = rng.random((n, d)).astype(np.float32)
    A = np.zeros((n, n), np.float32)
    for s, t, ww in zip(src, dst, w):
        A[t, s] += ww
    expect = A @ x
    got = spmm(jnp.array([src, dst]), jnp.array(x), n,
               edge_weight=jnp.array(w))
    np.testing.assert_allclose(np.asarray(got), expect, rtol=1e-5)


def test_file_barrier(tmp_path):
    import threading

    from euler_tpu.utils.hooks import FileBarrier

    b = [FileBarrier(str(tmp_path), 3) for _ in range(3)]
    done = []

    def worker(i):
        b[i].wait(i)
        done.append(i)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10)
    assert sorted(done) == [0, 1, 2]


def test_sync_exit_single_host():
    from euler_tpu.utils.hooks import sync_exit

    sync_exit("test")  # no-op without jax.distributed


def test_pallas_gather_mean_interpret():
    """Fused gather+mean kernel numerics vs the XLA path (interpret mode
    runs the actual kernel body on CPU)."""
    import jax.numpy as jnp

    from euler_tpu.ops.pallas_ops import (
        _pallas_gather_mean, _xla_gather_mean, gather_mean,
    )

    rng = np.random.default_rng(0)
    table = jnp.array(rng.random((64, 128), np.float32))
    rows = jnp.array(rng.integers(0, 64, (16, 5)).astype(np.int32))
    ref = _xla_gather_mean(table, rows)
    got = _pallas_gather_mean(table, rows, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)
    # tile_n sweeps the DMA-batch size; numerics must be invariant
    got16 = _pallas_gather_mean(table, rows, tile_n=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got16), np.asarray(ref),
                               atol=1e-6)
    # public entry defaults to the XLA formulation
    np.testing.assert_allclose(np.asarray(gather_mean(table, rows)),
                               np.asarray(ref), atol=1e-6)
    # single-semaphore layout: identical numerics by construction
    got1s = _pallas_gather_mean(table, rows, interpret=True, one_sem=True)
    np.testing.assert_allclose(np.asarray(got1s), np.asarray(ref),
                               atol=1e-6)


def test_type_names_and_type_ops(ring_graph):
    """Named types end-to-end (reference type_ops): builder
    set_type_name → dump/load → engine type_id/type_name → ops facade
    get_node_type_id/get_edge_type_id."""
    import tempfile

    from euler_tpu.graph import GraphBuilder, GraphEngine
    from euler_tpu.ops import (
        get_edge_type_id, get_node_type_id, initialize_shared_graph,
    )

    b = GraphBuilder()
    b.set_num_types(2, 2)
    b.set_type_name(0, "user")
    b.set_type_name(1, "item")
    b.set_type_name(0, "click", edge=True)
    b.set_type_name(1, "buy", edge=True)
    ids = np.arange(1, 7, dtype=np.uint64)
    b.add_nodes(ids, types=(ids % 2).astype(np.int32))
    b.add_edges(ids[:-1], ids[1:],
                types=(ids[:-1] % 2).astype(np.int32))
    g = b.finalize()
    assert g.type_id("user") == 0 and g.type_id("item") == 1
    assert g.type_id("buy", edge=True) == 1
    assert g.type_id(1) == 1 and g.type_id("7") == 7  # passthroughs
    assert g.type_name(0) == "user" and g.type_name(1, edge=True) == "buy"
    with pytest.raises(KeyError):
        g.type_id("nosuch")
    # names survive dump/load (meta serde)
    with tempfile.TemporaryDirectory() as d:
        g.dump(d)
        g2 = GraphEngine.load(d)
        assert g2.type_id("item") == 1
        assert g2.type_name(0, edge=True) == "click"
    # facade (reference get_node_type_id / get_edge_type_id)
    initialize_shared_graph(g)
    assert get_node_type_id("item") == 1
    np.testing.assert_array_equal(get_edge_type_id(["click", "buy", 0]),
                                  [0, 1, 0])


def test_composite_sampling_facades(ring_graph):
    """The reference's composite euler_ops: sample_node_with_src,
    get_multi_hop_neighbor, sample_fanout_layerwise(_each_node),
    sample_fanout_with_feature."""
    from euler_tpu.ops import (
        get_multi_hop_neighbor, initialize_shared_graph,
        sample_fanout_layerwise, sample_fanout_layerwise_each_node,
        sample_fanout_with_feature, sample_node_with_src,
    )

    initialize_shared_graph(ring_graph)
    src = np.array([1, 2, 3, 4], dtype=np.uint64)

    # type-matched negatives: every sample shares its src row's type
    negs = sample_node_with_src(src, 6)
    assert negs.shape == (4, 6)
    src_t = ring_graph.get_node_type(src)
    for i in range(4):
        got_t = ring_graph.get_node_type(negs[i])
        assert set(got_t.tolist()) == {int(src_t[i])}

    # multi-hop with inter-hop adjacency
    nodes_list, adj_list = get_multi_hop_neighbor(src, [None, None])
    assert len(nodes_list) == 3 and len(adj_list) == 2
    for h, (ei, w) in enumerate(adj_list):
        assert ei.shape[0] == 2 and ei.shape[1] == w.shape[0]
        # every edge endpoint indexes into its hop's node list
        assert ei[0].max(initial=0) < len(nodes_list[h])
        assert ei[1].max(initial=0) < len(nodes_list[h + 1])
        # adjacency rows are real edges
        for s_row, d_row in zip(ei[0][:8], ei[1][:8]):
            u = nodes_list[h][s_row]
            v = nodes_list[h + 1][d_row]
            off, nb, _, _ = ring_graph.get_full_neighbor([u])
            assert v in set(nb.tolist())

    # layerwise fanout variants: shape contract [roots, m1, m2]
    out = sample_fanout_layerwise(src, [5, 7])
    assert [len(x) for x in out] == [4, 5, 7]
    out = sample_fanout_layerwise(src, [5, 7], weight_func="sqrt")
    assert [len(x) for x in out] == [4, 5, 7]
    out = sample_fanout_layerwise_each_node(src, [3, 7])
    assert [len(x) for x in out] == [4, 12, 7]

    # fanout + features in one call
    nb, w, t, dense, sparse = sample_fanout_with_feature(
        src, [3, 2], dense_feature_names=["f_dense"],
        sparse_feature_names=["f_sparse"])
    assert [len(x) for x in nb] == [4, 12, 24]
    assert len(dense) == 3 and dense[0][0].shape == (4, 4)
    assert len(sparse) == 3
    offs, vals = sparse[1][0]
    assert offs.shape == (13,)


def test_ops_condition_parameters():
    """The reference kernels' `condition` attr (index-DNF filters
    appended as `.has(...)` to the gremlin — sample_node_op.cc:61,
    sample_neighbor_op.cc:40, get_top_k_neighbor_op.cc:34) on the ops
    facade."""
    from euler_tpu.graph import GraphBuilder, seed as gseed
    from euler_tpu.ops import (
        get_full_neighbor, get_top_k_neighbor, initialize_shared_graph,
        sample_neighbor, sample_node,
    )

    gseed(17)
    b = GraphBuilder()
    b.set_num_types(1, 1)
    b.set_feature(0, 0, 1, "price")
    ids = np.arange(1, 21, dtype=np.uint64)
    b.add_nodes(ids)
    src = np.repeat(ids[:4], 5)
    dst = np.tile(ids[4:9], 4)
    b.add_edges(src, dst, weights=np.tile(
        np.arange(1, 6, dtype=np.float32), 4))
    b.set_node_dense(ids, 0, ids.astype(np.float32).reshape(20, 1))
    g = b.finalize()
    initialize_shared_graph(g)
    from euler_tpu.ops.base import set_index_spec

    set_index_spec("price:range_index")

    # sample_node: every draw satisfies the condition
    got = sample_node(64, -1, condition="price gt 15")
    assert got.shape == (64,)
    assert set(got.tolist()) <= set(range(16, 21))

    # sample_neighbor: only price>6 neighbors survive (7, 8 of 5..9)
    roots = ids[:2]
    nb, w, t = sample_neighbor(roots, 4, condition="price gt 6")
    assert nb.shape == (2, 4)
    real = nb[nb != 0]
    assert set(real.tolist()) <= {7, 8, 9}

    # get_full_neighbor: filtered CSR
    off, nbr, w, t = get_full_neighbor(roots, condition="price le 5")
    assert set(nbr.tolist()) <= {4, 5}
    assert off[-1] == nbr.size

    # top-k with condition: highest-weight surviving edges first
    ids_k, w_k, t_k = get_top_k_neighbor(roots, 2, condition="price le 8")
    assert ids_k.shape == (2, 2)
    # weight = dst-4 by construction; best allowed dst is 8 (w=5)... the
    # per-row top weights must be non-increasing and all dsts <= 8
    real = ids_k[ids_k != 0]
    assert set(real.tolist()) <= {4, 5, 6, 7, 8}
    assert (w_k[:, 0] >= w_k[:, 1]).all()


def test_sparse_get_adj(ring_graph):
    from euler_tpu.ops import initialize_shared_graph, sparse_get_adj

    initialize_shared_graph(ring_graph)
    roots = np.array([1, 2], dtype=np.uint64)
    pool = np.array([3, 4, 99], dtype=np.uint64)
    # ring: 1→{2(t0),3(t1)}, 2→{3(t0),4(t1)}; only pool members survive
    ei, w = sparse_get_adj(roots, pool)
    pairs = set(zip(ei[0].tolist(), ei[1].tolist()))
    assert pairs == {(0, 0), (1, 0), (1, 1)}  # 1→3, 2→3, 2→4
    assert w.shape == (3,)
