"""Dataflow + estimator end-to-end on tiny graphs (mirrors the
reference's Python op tests against an embedded graph, SURVEY.md §4)."""

import numpy as np
import pytest

from euler_tpu.dataflow import (
    FanoutDataFlow,
    FullBatchDataFlow,
    LayerwiseDataFlow,
    RelationDataFlow,
    WholeDataFlow,
)
from euler_tpu.dataset.base_dataset import synthetic_citation


@pytest.fixture(scope="module")
def tiny_data():
    return synthetic_citation("tiny", n=120, d=8, num_classes=3,
                              train_per_class=10, val=20, test=30, seed=1)


def test_fanout_dataflow(tiny_data):
    g = tiny_data.engine
    flow = FanoutDataFlow(g, [3, 2], feature_ids=["feature"])
    roots = g.sample_node(4, 0)
    batch = flow(roots)
    assert [a.shape[0] for a in batch["ids"]] == [4, 12, 24]
    assert batch["layers"][0].shape == (4, 8)
    assert batch["layers"][2].shape == (24, 8)


def test_whole_dataflow(tiny_data):
    g = tiny_data.engine
    flow = WholeDataFlow(g, hops=1, pad_to_multiple=16,
                         feature_ids=["feature"])
    batch = flow(g.sample_node(4, 0))
    assert batch["edge_index"].shape[0] == 2
    assert batch["nodes"].shape[0] % 16 == 0
    assert batch["x"].shape[0] == batch["nodes"].shape[0]
    assert batch["root_index"].shape == (4,)


def test_fullbatch_dataflow(tiny_data):
    g = tiny_data.engine
    flow = FullBatchDataFlow(g, feature_ids=["feature"])
    b1 = flow(g.sample_node(4, 0))
    b2 = flow(g.sample_node(4, 0))
    assert b1["nodes"] is b2["nodes"]  # static parts cached
    assert b1["edge_index"].shape[1] == g.edge_count


def test_layerwise_dataflow(tiny_data):
    g = tiny_data.engine
    flow = LayerwiseDataFlow(g, [6, 8], feature_ids=["feature"])
    batch = flow(g.sample_node(4, 0))
    # LADIES-style pools: each level unions the previous level's nodes
    # (connectivity guarantee) → level sizes 4, 4+6, 4+6+8
    assert batch["adjs"][0].shape == (4, 10)
    assert batch["adjs"][1].shape == (10, 18)
    # rows are normalized; with self-loops every row sums to 1
    sums = batch["adjs"][0].sum(axis=1)
    np.testing.assert_allclose(sums, 1.0, rtol=1e-4)
    # full (eval) mode: exact 1-hop closures instead of sampled pools
    full = LayerwiseDataFlow(g, [6, 8], sample=False,
                             feature_ids=["feature"])
    fb = full(g.sample_node(4, 0))
    assert fb["adjs"][0].shape[0] == 4
    np.testing.assert_allclose(fb["adjs"][0].sum(axis=1), 1.0, rtol=1e-4)


def test_layerwise_exact_closure_is_padded_to_few_shapes(tiny_data):
    """sample=False pads each closure with an id no node has: the real
    part is the exact closure and its propagation matrix, pad columns
    carry no weight and pad rows no features, and batches of many
    closure sizes come in a few shapes (each one a compile of the eval
    step)."""
    from euler_tpu.dataflow.base_dataflow import _NO_NODE

    g = tiny_data.engine
    full = LayerwiseDataFlow(g, [6, 8], sample=False,
                             feature_ids=["feature"])
    exact_sizes, shapes = set(), set()
    for _ in range(12):
        roots = np.unique(g.sample_node(8, -1))[:4]
        fb = full(roots)
        level = roots
        for l in range(2):
            _, nbr, _, _ = g.get_full_neighbor(level)
            closure = np.unique(np.concatenate([level, nbr]))
            got = fb["ids"][l + 1]
            n = len(closure)
            np.testing.assert_array_equal(got[:n], closure)
            assert (got[n:] == _NO_NODE).all()
            assert n <= len(got) <= 3 * (n + 1) // 2
            adj = fb["adjs"][l]
            rows = len(level)
            np.testing.assert_array_equal(
                adj[:rows, :n], full._dense_adj(level, closure))
            assert not adj[:rows, n:].any()
            assert not fb["layers"][l + 1][n:].any()
            level = closure
        exact_sizes.add(tuple(len(np.unique(i[i != _NO_NODE]))
                              for i in fb["ids"]))
        shapes.add(tuple(len(i) for i in fb["ids"]))
    assert len(shapes) < len(exact_sizes)


def test_relation_dataflow(tiny_data):
    g = tiny_data.engine
    flow = RelationDataFlow(g, fanout=3, num_relations=1,
                            feature_ids=["feature"])
    batch = flow(g.sample_node(4, 0))
    assert batch["nbr_ids"].shape == (1, 4, 3)
    assert batch["nbr_x"].shape == (1, 4, 3, 8)


def test_node_estimator_trains(tiny_data):
    """Loss decreases and checkpoint round-trips."""
    import tempfile

    from euler_tpu.estimator import NodeEstimator
    from euler_tpu.mp_utils import BaseGNNNet, SuperviseModel

    class TinyGCN(SuperviseModel):
        def embed(self, batch):
            return BaseGNNNet("gcn", 8, 2, name="gnn")(batch)

    g = tiny_data.engine
    flow = FullBatchDataFlow(g, feature_ids=["feature"])
    with tempfile.TemporaryDirectory() as d:
        est = NodeEstimator(
            TinyGCN(num_classes=3, multilabel=False),
            dict(batch_size=16, learning_rate=0.05, log_steps=1000,
                 checkpoint_steps=10, label_dim=3),
            g, flow, label_fid="label", label_dim=3, model_dir=d)
        res = est.train(est.train_input_fn, max_steps=12)
        assert res["global_step"] == 12
        ev = est.evaluate(est.eval_input_fn, steps=3)
        assert np.isfinite(ev["loss"])
        # fresh estimator restores from checkpoint
        est2 = NodeEstimator(
            TinyGCN(num_classes=3, multilabel=False),
            dict(batch_size=16, learning_rate=0.05, label_dim=3),
            g, flow, label_fid="label", label_dim=3, model_dir=d)
        ev2 = est2.evaluate(est2.eval_input_fn, steps=3)
        assert np.isfinite(ev2["loss"])
        # infer writes artifacts
        paths = est.infer(est.infer_input_fn, steps=3)
        emb = np.load(paths["embedding"])
        assert emb.shape[0] > 0


def test_steps_per_loop_matches_single_step(tiny_data):
    """steps_per_loop > 1 (lax.scan over K stacked batches per dispatch)
    must do the same optimization as K single dispatches: same step
    count, and bitwise-identical params given the same batch stream."""
    import jax
    from euler_tpu.dataflow import FullBatchDataFlow
    from euler_tpu.estimator import NodeEstimator
    from euler_tpu.mp_utils import BaseGNNNet, SuperviseModel

    g = tiny_data.engine

    class M(SuperviseModel):
        def embed(self, batch):
            return BaseGNNNet("gcn", 8, 2, name="gnn")(batch)

    def fit(spl, batches):
        flow = FullBatchDataFlow(g, feature_ids=["feature"])
        est = NodeEstimator(
            M(num_classes=tiny_data.num_classes, multilabel=False),
            dict(batch_size=8, learning_rate=0.05, seed=3,
                 label_dim=tiny_data.num_classes, steps_per_loop=spl,
                 checkpoint_steps=0, log_steps=1000),
            g, flow, label_fid="label", label_dim=tiny_data.num_classes)
        res = est.train(iter(batches), max_steps=10)
        return res, est.state.params

    def batches():
        flow2 = FullBatchDataFlow(g, feature_ids=["feature"])
        est = NodeEstimator(
            M(num_classes=tiny_data.num_classes, multilabel=False),
            dict(batch_size=8, label_dim=tiny_data.num_classes),
            g, flow2, label_fid="label", label_dim=tiny_data.num_classes)
        it = est.train_input_fn()
        return [next(it) for _ in range(10)]

    from euler_tpu.graph import seed as gseed

    gseed(7)
    stream = batches()
    res1, p1 = fit(1, stream)
    res4, p4 = fit(4, stream)
    assert res1["global_step"] == res4["global_step"] == 10
    flat1 = jax.tree_util.tree_leaves(p1)
    flat4 = jax.tree_util.tree_leaves(p4)
    for a, b in zip(flat1, flat4):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_walk_ops(tiny_data):
    from euler_tpu.ops import walk_ops

    g = tiny_data.engine
    walks = g.random_walk(g.sample_node(3, -1), 4)
    pairs = walk_ops.gen_pair(walks, 1, 1)
    assert pairs.shape[0] == 3 and pairs.shape[2] == 2


def test_prefetcher():
    from euler_tpu.estimator.prefetch import Prefetcher

    it = Prefetcher(iter(range(5)), depth=2)
    assert list(it) == [0, 1, 2, 3, 4]

    def boom():
        yield 1
        raise RuntimeError("x")

    it2 = Prefetcher(boom())
    assert next(it2) == 1
    with pytest.raises(RuntimeError):
        next(it2)


def test_eval_sweep_exact_and_masked(tiny_data):
    """eval_sweep_input_fn: every split node exactly once; the padded
    tail is masked out of the metric, so the sweep metric equals a
    hand-computed full-split micro-F1."""
    import jax

    from euler_tpu.estimator import NodeEstimator
    from euler_tpu.mp_utils import BaseGNNNet, SuperviseModel
    from euler_tpu.utils import metrics as M

    g = tiny_data.engine

    class ConvModel(SuperviseModel):
        dim: int = 8

        def embed(self, batch):
            return BaseGNNNet("gcn", self.dim, 2, name="gnn")(batch)

    model = ConvModel(num_classes=3, multilabel=False)
    flow = FullBatchDataFlow(g, feature_ids=["feature"])
    # batch 16 does NOT divide the 20-node val split → forces a padded
    # final chunk (the advisor-r2 double-count scenario)
    est = NodeEstimator(
        model, dict(batch_size=16, learning_rate=0.05, label_dim=3,
                    log_steps=1 << 30, checkpoint_steps=0),
        g, flow, label_fid="label", label_dim=3)
    est.train(est.train_input_fn(), max_steps=3)

    val_ids = est.split_ids(1)
    assert len(val_ids) == 20
    assert est.eval_sweep_steps() == 2  # ceil(20 / 16)
    # batches carry each id exactly once (pads excluded by the mask)
    seen = []
    masks = []
    for b in est.eval_sweep_input_fn():
        seen.append(np.asarray(b["infer_ids"])[b["metric_mask"] > 0])
        masks.append(b["metric_mask"].sum())
    assert masks == [16.0, 4.0]
    np.testing.assert_array_equal(np.sort(np.concatenate(seen)),
                                  np.sort(val_ids))

    res = est.evaluate(est.eval_sweep_input_fn, est.eval_sweep_steps())
    # hand-computed exact F1 over the val split at the same params
    batch = flow(val_ids)
    batch["labels"] = g.get_dense_feature(val_ids, "label", 3)
    variables = {"params": est.state.params, **(est.state.extra_vars or {})}
    out = est.model.apply(variables, {
        k: v for k, v in batch.items()})
    # recompute logits directly: embed + out layer is inside the model,
    # so compare via a full-split single batch with no padding instead
    np.testing.assert_allclose(res["metric"], float(out.metric), atol=1e-5)


def test_sample_estimator_trains_from_file(tiny_data, tmp_path):
    """SampleEstimator (reference sample_estimator.py): line-oriented
    'label,node_id' records drive supervised training; labels come from
    the FILE, not the graph store."""
    from euler_tpu.estimator import SampleEstimator
    from euler_tpu.models import SupervisedGraphSage

    g = tiny_data.engine
    ids = g.all_node_ids()
    train_ids = ids[g.get_node_type(ids) == 0]
    labels = g.get_dense_feature(train_ids, "label").argmax(-1)
    path = tmp_path / "sample.txt"
    path.write_text("".join(f"{int(l)},{int(i)}\n"
                            for l, i in zip(labels, train_ids)))

    flow = FanoutDataFlow(g, [3, 2], feature_ids=["feature"])

    def parse_fn(lines):
        labs, nodes = zip(*(ln.split(",") for ln in lines))
        roots = np.asarray([int(x) for x in nodes], np.uint64)
        batch = flow(roots)
        batch["labels"] = np.eye(3, dtype=np.float32)[
            [int(x) for x in labs]]
        batch["infer_ids"] = roots
        return batch

    model = SupervisedGraphSage(num_classes=3, multilabel=False, dim=8,
                                fanouts=(3, 2))
    est = SampleEstimator(
        model, dict(batch_size=8, learning_rate=0.05, log_steps=1 << 30,
                    checkpoint_steps=0),
        str(path), parse_fn)
    res = est.train(est.train_input_fn, max_steps=12)
    assert res["global_step"] == 12
    assert np.isfinite(res["loss"])
    ev = est.evaluate(est.eval_input_fn, 3)
    assert np.isfinite(ev["metric"])


def test_dense_adj_vectorized_matches_naive():
    """The vectorized _dense_adj must reproduce the per-edge loop
    exactly: duplicate pool columns, parallel-edge overwrite order,
    self-loop accumulation, row normalization."""
    import numpy as np

    from euler_tpu.dataflow import LayerwiseDataFlow
    from euler_tpu.graph import GraphBuilder

    rng = np.random.default_rng(2)
    n = 30
    b = GraphBuilder()
    ids = np.arange(1, n + 1, dtype=np.uint64)
    b.add_nodes(ids)
    src = rng.integers(1, n + 1, 120).astype(np.uint64)
    dst = rng.integers(1, n + 1, 120).astype(np.uint64)
    b.add_edges(src, dst, weights=rng.uniform(0.1, 2, 120).astype(np.float32))
    g = b.finalize()
    flow = LayerwiseDataFlow(g, [8, 8])

    def naive(rows, cols):
        col_pos = {}
        for j, c in enumerate(cols):
            col_pos.setdefault(int(c), []).append(j)
        adj = np.zeros((len(rows), len(cols)), np.float32)
        off, nbr, w, _ = g.get_full_neighbor(rows)
        for i in range(len(rows)):
            for e in range(int(off[i]), int(off[i + 1])):
                for j in col_pos.get(int(nbr[e]), ()):
                    adj[i, j] = w[e]
            for j in col_pos.get(int(rows[i]), ()):
                adj[i, j] += 1.0
        norm = adj.sum(axis=1, keepdims=True)
        return adj / np.maximum(norm, 1e-12)

    for trial in range(5):
        r = rng.integers(1, n + 1, 10).astype(np.uint64)
        # duplicate columns on purpose (sampled pools repeat nodes)
        c = rng.integers(1, n + 1, 24).astype(np.uint64)
        c[3] = c[7] = c[11]
        np.testing.assert_allclose(flow._dense_adj(r, c), naive(r, c),
                                   atol=1e-6)
