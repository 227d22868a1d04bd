"""Mini-batch subgraph builders ("dataflows").

Parity: tf_euler/python/dataflow/ (DataFlow/Block base_dataflow.py:22-37,
SageDataFlow, GCNDataFlow, FastGCNDataFlow, LayerwiseDataFlow,
WholeDataFlow, RelationDataFlow, NeighborDataFlow/UniqueDataFlow).

TPU-first redesign: a dataflow is a host-side callable
roots → batch dict of fixed-shape numpy arrays (the same roots count →
the same shapes every step, so the jitted train step never recompiles).
Two batch geometries are produced:

  * fanout batches — per-hop node ids + features; hop h has exactly
    n_roots·Πk_{≤h} rows (sampling pads with default_id). Feeds the dense
    encoders (euler_tpu.utils.encoders) — no scatter on device.
  * edge_index batches — a node table + [2, E] edge list for the conv zoo
    (whole-graph or k-hop closure training, Cora-scale).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from euler_tpu.graph import GraphEngine


@dataclass
class Block:
    """One hop of a sampled subgraph (parity: reference Block
    base_dataflow.py:22 — n_id, res_n_id, edge_index, size)."""

    n_id: np.ndarray          # [n_src] source node ids (uint64)
    res_n_id: np.ndarray      # [n_tgt] target node ids
    edge_index: np.ndarray    # [2, E] int32 (src_row, tgt_row)
    size: tuple               # (n_src, n_tgt)


class DataFlow:
    """Base: fetches features for id tensors; subclasses build topology."""

    def __init__(self, graph: GraphEngine, feature_ids: Sequence = (),
                 feature_dims: Optional[Sequence[int]] = None,
                 default_id: int = 0):
        self.graph = graph
        self.feature_ids = list(feature_ids)
        self.feature_dims = list(feature_dims) if feature_dims else None
        self.default_id = default_id

    def features(self, ids: np.ndarray) -> np.ndarray:
        """Concatenated dense features [n, sum(dims)] for ids."""
        if not self.feature_ids:
            raise ValueError("dataflow has no feature_ids configured")
        feats = self.graph.get_dense_feature(ids, self.feature_ids,
                                             self.feature_dims)
        if isinstance(feats, list):
            return np.concatenate(feats, axis=1)
        return feats

    def __call__(self, roots: np.ndarray) -> Dict:
        raise NotImplementedError


class FanoutDataFlow(DataFlow):
    """Multi-hop fanout batches (≈ reference SageDataFlow/NeighborDataFlow).

    Batch dict:
      ids:    list of L+1 uint64 arrays, ids[0] = roots
      layers: list of L+1 float32 feature arrays (if feature_ids set)
      weights/types: per-hop sample metadata (optional use)
    """

    def __init__(self, graph, fanouts: Sequence[int], edge_types=None,
                 with_features: bool = True, **kw):
        super().__init__(graph, **kw)
        self.fanouts = list(fanouts)
        self.edge_types = edge_types
        self.with_features = with_features

    def __call__(self, roots: np.ndarray) -> Dict:
        roots = np.ascontiguousarray(roots, dtype=np.uint64).ravel()
        ids, w, t = self.graph.sample_fanout(
            roots, self.fanouts, edge_types=self.edge_types,
            default_id=self.default_id)
        all_ids = [roots] + ids
        batch = {"ids": all_ids, "weights": w, "types": t}
        if self.with_features and self.feature_ids:
            batch["layers"] = [self.features(i) for i in all_ids]
        return batch


class WholeDataFlow(DataFlow):
    """Full 1-hop closure as an edge_index batch (reference WholeDataFlow
    whole_dataflow.py:26; also serves GCNDataFlow's full-neighbor mode).

    Returns the batch nodes plus ALL their neighbors, deduplicated, with a
    local edge_index. Shapes vary with the closure size — pad_to_multiple
    rounds table/edge sizes up so jit recompiles are bounded (bucketing).
    """

    def __init__(self, graph, edge_types=None, hops: int = 1,
                 pad_to_multiple: int = 256, **kw):
        super().__init__(graph, **kw)
        self.edge_types = edge_types
        self.hops = hops
        self.pad = pad_to_multiple

    def __call__(self, roots: np.ndarray) -> Dict:
        roots = np.ascontiguousarray(roots, dtype=np.uint64).ravel()
        frontier = roots
        nodes = [roots]
        src_rows: List[np.ndarray] = []
        dst_rows: List[np.ndarray] = []
        edges_src: List[np.ndarray] = []
        edges_dst: List[np.ndarray] = []
        for _ in range(self.hops):
            off, nbr, w, t = self.graph.get_full_neighbor(
                frontier, edge_types=self.edge_types)
            counts = np.diff(off).astype(np.int64)
            e_dst = np.repeat(frontier, counts)
            edges_src.append(nbr)
            edges_dst.append(e_dst)
            frontier = np.unique(nbr)
            nodes.append(frontier)
        node_table = np.unique(np.concatenate(nodes))
        # np.unique returns sorted ids → local rows via binary search
        src = np.concatenate(edges_src) if edges_src else np.zeros(0, np.uint64)
        dst = np.concatenate(edges_dst) if edges_dst else np.zeros(0, np.uint64)
        src_idx = np.searchsorted(node_table, src).astype(np.int32)
        dst_idx = np.searchsorted(node_table, dst).astype(np.int32)
        root_idx = np.searchsorted(node_table, roots).astype(np.int32)
        n_real = len(node_table)
        # pad table and edges to bucket boundaries for bounded recompiles
        n_pad = -len(node_table) % self.pad
        e_pad = -len(src_idx) % self.pad
        node_table = np.concatenate(
            [node_table, np.full(n_pad, self.default_id, np.uint64)])
        pad_row = len(node_table) - 1 if n_pad else 0
        src_idx = np.concatenate([src_idx, np.full(e_pad, pad_row, np.int32)])
        dst_idx = np.concatenate([dst_idx, np.full(e_pad, pad_row, np.int32)])
        batch = {
            "nodes": node_table,
            "edge_index": np.stack([src_idx, dst_idx]).astype(np.int32),
            "root_index": root_idx,
            "n_real_nodes": n_real,
            "n_real_edges": len(src),
        }
        if self.feature_ids:
            batch["x"] = self.features(node_table)
        return batch


class FullBatchDataFlow(DataFlow):
    """Whole-graph batches (Cora-scale transductive training): the node
    table and edge_index are the entire graph, built once and cached;
    per-step only root_index varies. The reference's GCN examples train
    this way through GCNDataFlow's full-neighbor mode."""

    def __init__(self, graph, edge_types=None, **kw):
        super().__init__(graph, **kw)
        self.edge_types = edge_types
        self._static: Optional[Dict] = None

    def _build_static(self) -> Dict:
        nodes = np.sort(self.graph.all_node_ids())
        off, nbr, w, t = self.graph.get_full_neighbor(
            nodes, edge_types=self.edge_types)
        counts = np.diff(off).astype(np.int64)
        src_ids = nbr
        dst_ids = np.repeat(nodes, counts)
        src_idx = np.searchsorted(nodes, src_ids).astype(np.int32)
        dst_idx = np.searchsorted(nodes, dst_ids).astype(np.int32)
        static = {
            "nodes": nodes,
            "edge_index": np.stack([src_idx, dst_idx]),
            "edge_weight": w.astype(np.float32),
            "edge_type": t.astype(np.int32),
        }
        if self.feature_ids:
            static["x"] = self.features(nodes)
        return static

    def __call__(self, roots: np.ndarray) -> Dict:
        if self._static is None:
            self._static = self._build_static()
        roots = np.ascontiguousarray(roots, dtype=np.uint64).ravel()
        batch = dict(self._static)
        batch["root_index"] = np.searchsorted(
            self._static["nodes"], roots).astype(np.int32)
        return batch


_NO_NODE = np.iinfo(np.uint64).max


def _bucket(n: int) -> int:
    """n rounded up to the next 2^k or 1.5 * 2^k."""
    p = 1 << max(n - 1, 1).bit_length()
    return p * 3 // 4 if n <= p * 3 // 4 else p


class LayerwiseDataFlow(DataFlow):
    """LADIES-style layerwise batches (reference layerwise_dataflow.py:26):
    per-layer importance-sampled pools + dense inter-pool adjacency."""

    def __init__(self, graph, layer_sizes: Sequence[int], edge_types=None,
                 sample: bool = True, **kw):
        """sample=False expands exact 1-hop closures instead of sampled
        pools — FastGCN's standard eval mode (train with importance
        sampling, evaluate with the full propagation matrix)."""
        super().__init__(graph, **kw)
        self.layer_sizes = list(layer_sizes)
        self.edge_types = edge_types
        self.sample = sample

    def _dense_adj(self, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
        """Row-normalized dense adjacency [len(rows), len(cols)] of
        Â = A + I restricted to the sampled pool (FastGCN/LADIES use the
        self-loop-augmented GCN propagation matrix — without the diagonal
        a root whose neighbors missed the pool gets a zero embedding).

        Vectorized: each (edge, matching-col) pair is expanded via
        searchsorted ranges over the sorted col array — duplicate pool
        columns each receive the edge weight, and edge writes land in
        edge order (later parallel edges overwrite earlier, matching
        the original per-edge loop)."""
        rows = np.asarray(rows, np.uint64)
        cols_arr = np.asarray(cols, np.uint64)
        order = np.argsort(cols_arr, kind="stable")
        sc = cols_arr[order]
        adj = np.zeros((len(rows), len(cols_arr)), dtype=np.float32)
        off, nbr, w, _ = self.graph.get_full_neighbor(
            rows, edge_types=self.edge_types)

        def expand(ids, per_id_row):
            """(row, col, run-length) triples for every position of
            each id in the sorted col array."""
            lo = np.searchsorted(sc, ids)
            hi = np.searchsorted(sc, ids, side="right")
            cnt = (hi - lo).astype(np.int64)
            total = int(cnt.sum())
            if total == 0:
                return (np.empty(0, np.int64), np.empty(0, np.int64),
                        np.empty(0, np.int64))
            rep = np.repeat(np.arange(len(ids)), cnt)
            pos_in_run = np.arange(total) - np.repeat(
                np.cumsum(cnt) - cnt, cnt)
            cpos = order[np.repeat(lo, cnt) + pos_in_run]
            return per_id_row[rep], cpos, rep

        edge_row = np.repeat(np.arange(len(rows)),
                             np.diff(off).astype(np.int64))
        er, ec, eidx = expand(nbr, edge_row)
        adj[er, ec] = w[eidx]
        sr, scol, _ = expand(rows, np.arange(len(rows)))
        # (row, col) pairs cannot repeat (distinct positions per sorted
        # run, one run per row), so plain fancy += is exact — and faster
        # than an unbuffered np.add.at scatter
        adj[sr, scol] += 1.0
        norm = adj.sum(axis=1, keepdims=True)
        return adj / np.maximum(norm, 1e-12)

    def __call__(self, roots: np.ndarray) -> Dict:
        roots = np.ascontiguousarray(roots, dtype=np.uint64).ravel()
        levels = [roots]
        if self.sample:
            pools = self.graph.sample_layerwise(
                roots, self.layer_sizes, edge_types=self.edge_types,
                default_id=self.default_id)
            # LADIES-style connectivity guarantee: each level's pool also
            # contains the previous level's nodes, so self-loops always
            # have a column to land on (reference layerwise_dataflow.py
            # unions the batch into the sampled layer).
            for p in pools:
                levels.append(np.concatenate([levels[-1], p]))
        else:
            for _ in self.layer_sizes:
                _, nbr, _, _ = self.graph.get_full_neighbor(
                    levels[-1], edge_types=self.edge_types)
                closure = np.unique(np.concatenate([levels[-1], nbr]))
                # a closure's size differs from batch to batch, and every
                # new shape is a compile of the eval step: pad it up to
                # the next 2^k or 1.5 * 2^k with an id no node has (no
                # neighbours, zero features, so its adjacency columns
                # are zeros and its rows feed nothing real)
                levels.append(np.concatenate([closure, np.full(
                    _bucket(len(closure)) - len(closure), _NO_NODE,
                    np.uint64)]))
        adjs = [self._dense_adj(levels[i], levels[i + 1])
                for i in range(len(levels) - 1)]
        batch = {"ids": levels, "adjs": adjs}
        if self.feature_ids:
            batch["layers"] = [self.features(i) for i in levels]
        return batch


class FastGCNDataFlow(LayerwiseDataFlow):
    """FastGCN = layerwise sampling with per-layer independent pools
    (reference fastgcn via LayerwiseEachDataFlow); the engine's layerwise
    sampler already importance-samples per layer, so this shares the
    implementation with distinct default layer sizes."""


class RelationDataFlow(DataFlow):
    """Per-edge-type fanout batches for relational models (reference
    relation_dataflow.py:25): one fanout per relation, stacked."""

    def __init__(self, graph, fanout: int, num_relations: int, **kw):
        super().__init__(graph, **kw)
        self.fanout = fanout
        self.num_relations = num_relations

    def __call__(self, roots: np.ndarray) -> Dict:
        roots = np.ascontiguousarray(roots, dtype=np.uint64).ravel()
        per_rel_ids = []
        per_rel_w = []
        for r in range(self.num_relations):
            nb, w, _ = self.graph.sample_neighbor(
                roots, self.fanout, edge_types=[r], default_id=self.default_id)
            per_rel_ids.append(nb)
            per_rel_w.append(w)
        batch = {
            "ids": roots,
            "nbr_ids": np.stack(per_rel_ids),   # [R, B, K]
            "nbr_weights": np.stack(per_rel_w),
        }
        if self.feature_ids:
            batch["x"] = self.features(roots)
            batch["nbr_x"] = np.stack(
                [self.features(i.ravel()).reshape(len(roots), self.fanout, -1)
                 for i in per_rel_ids])
        return batch
