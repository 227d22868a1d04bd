"""Device-resident feature store: the TPU-first answer to per-step
feature shipping.

The reference streams features from the graph engine to the trainer on
every batch (GetDenseFeature over gRPC, tf_euler/kernels/
get_dense_feature_op.cc). On TPU the host↔device link (PCIe)
is the bottleneck: a 15×10 fanout batch of 100-dim float features is
~66MB/step, while the same batch as int32 row ids is ~0.7MB. When the
node feature matrix fits in HBM (ogbn-products at 100-dim f32 is ~1GB),
the right design is: upload the table ONCE, ship only rows, gather on
device (one MXU-adjacent take() — sub-ms).

For multi-chip, pass a mesh: the table is replicated by default (row
sharding composes with ShardedEmbedding when the table itself is
trainable — here it's frozen input data, and replication keeps the
gather local, no collective per step).

Tier selection: when the table no longer fits one chip's HBM
replicated, use euler_tpu.parallel.partitioned_store
.PartitionedFeatureStore instead — contiguous 1/K row shards over the
'model' axis, a replicated hub cache (top hub_cache_frac
highest-degree rows, gathers routed cache-first), and host-RAM
overflow behind CachedGraphEngine. Its int8 path reuses quantize_int8
/ dequantize_rows below, with ONE scale computed over the full table
so partitioned and replicated lookups stay byte-identical;
memory_plan.plan_partitioned_table emits the per-chip fit verdict.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from euler_tpu.parallel.placement import (
    placement_stage as _stage, put_replicated, put_row_sharded,
)


# float32 elements a quantisation chunk holds (16 MB: it stays in cache
# between its passes) and the threads that work through the chunks: the
# transients are a few chunks a thread, whatever the table's size
_QUANT_CHUNK_ELEMS = 1 << 22
_QUANT_THREADS = 8


def quantize_int8(feats: np.ndarray):
    """Per-column symmetric int8 quantization: q = round(x/scale),
    scale = colmax|x|/127. Returns (q int8, scale f32[D]). Halves the
    bytes every feature-row gather moves out of HBM vs bf16 (the hop-2
    gather dominates step HBM traffic at products scale) and halves the
    table's HBM footprint; dequant (q·scale) runs after the gather,
    fused into the consumer by XLA. All-zero columns get scale 1.

    Works in ROW CHUNKS on a thread pool, straight from the dtype it is
    handed (each chunk cast to float32 on its thread): the column maxima
    first, then the rounded quotient chunk by chunk into the int8 table.
    Nothing table-sized is made but the result, at any row width; the
    bytes are those of the one-pass arithmetic. Span `quantize` (a stage
    of the feature table's placement: placement_ms{features,quantize}),
    counter `quantize_chunks_total` (chunks a pass, both passes)."""
    from euler_tpu import obs

    rows, dim = feats.shape
    step = max(1, _QUANT_CHUNK_ELEMS // max(dim, 1))
    starts = range(0, rows, step)
    chunks = obs.counter(
        "quantize_chunks_total",
        "row chunks the feature store's int8 quantisation worked through "
        "(the column maxima and the rounded quotient are a pass each)")

    def chunk(lo):
        chunks.inc()
        return feats[lo:lo + step].astype(np.float32, copy=False)

    def fill(lo):
        q[lo:lo + step] = np.clip(np.rint(chunk(lo) / scale), -127, 127)

    with _stage("quantize", "features", rows=rows, dim=dim), \
            ThreadPoolExecutor(_QUANT_THREADS) as pool:
        tops = list(pool.map(lambda lo: np.abs(chunk(lo)).max(axis=0),
                             starts))
        scale = np.max(tops, axis=0).astype(np.float32) / 127.0
        scale[scale == 0] = 1.0
        q = np.empty(feats.shape, np.int8)
        list(pool.map(fill, starts))
    return q, scale


def dequantize_rows(x, scale):
    """Inverse of quantize_int8 for gathered rows; output dtype follows
    scale (store the scale in the dtype you want features to train in)."""
    return x.astype(scale.dtype) * scale


class DeviceFeatureStore:
    """Uploads dense node features (and optionally labels) to device HBM
    once; translates u64 node ids → int32 table rows on the host.

    Usage:
        store = DeviceFeatureStore(graph, ["feature"], label_fid="label",
                                   label_dim=C)
        rows = store.lookup(ids_u64)        # host, ~µs/kid
        feats = store.features[rows_dev]    # device gather, in-jit
    """

    def __init__(self, graph, feature_ids: Sequence, label_fid=None,
                 label_dim: Optional[int] = None,
                 dtype=jnp.float32,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 keep_host: bool = False, shard_rows: bool = False,
                 quantize: Optional[str] = None):
        """quantize='int8' stores the feature table int8 with a
        per-column scale (quantize_int8, in row chunks: no table-sized
        float32 copy is made); the store exposes feature_scale and
        models dequantize after the gather. Rows keep the width they
        come with, whatever it is: a 768-wide table is gathered as
        768-byte int8 rows (six lane tiles), nothing is cut or split."""
        self.shard_rows = bool(shard_rows)
        self.mesh = mesh
        # table rows follow ENGINE row order so lookup() is the engine's
        # O(1) hash translation (etg_node_rows), not a binary search
        ids = graph.all_node_ids()
        self.ids = ids
        self._graph = graph
        # row N is a dedicated all-zero pad row: unknown ids and sampling
        # pads gather zeros, matching GetDenseFeature's unknown-id
        # behavior on the host path
        self.pad_row = len(ids)
        with _stage("place_features", "features", rows=len(ids) + 1,
                    shard_rows=self.shard_rows) as parent:
            with _stage("read_graph", "features"):
                feats = graph.get_dense_feature(ids, list(feature_ids))
                if isinstance(feats, list):
                    feats = np.concatenate(feats, axis=1)
                feats = np.concatenate(
                    [feats, np.zeros((1, feats.shape[1]), feats.dtype)])
            with _stage("cast", "features"):
                feats = feats.astype(np.dtype(dtype), copy=False)
            self._place_features(parent, feats, quantize, dtype, wait=False)
            labels = None
            if label_fid is not None:
                with _stage("read_graph", "labels"):
                    labels = graph.get_dense_feature(ids, label_fid,
                                                     label_dim)
                    labels = np.concatenate(
                        [labels,
                         np.zeros((1, labels.shape[1]), labels.dtype)])
            labels = self._place_labels(parent, labels)
        # host copies are opt-in (cache writers like bench): pinning them
        # by default would double host RAM for every training caller
        self.host_arrays = (feats, labels) if keep_host else None

    def _put(self, x):
        put = put_row_sharded if self.shard_rows else put_replicated
        return put(x, self.mesh)

    def _place_features(self, parent, features, quantize, scale_dtype,
                        wait: bool) -> None:
        """The feature table's way to the device, for both constructors,
        each stage under its span (placement.placement_stage): `quantize`
        or `cast`, `transfer` of the table and of its scale. `parent`, the
        constructor's own span, is told the row bytes as stored. A
        transfer is left in flight (its span holds the enqueue; the first
        step that reads the table waits for it) unless `wait`."""
        self.feature_scale = None
        if quantize == "int8":
            features, scale = quantize_int8(features)
        elif quantize is not None:
            raise ValueError(f"unknown quantize mode {quantize!r}")
        else:
            with _stage("cast", "features"):
                features = np.ascontiguousarray(features)
        with _stage("transfer", "features"):
            self.features = self._put(features)
            if wait:
                jax.block_until_ready(self.features)
        parent.set(row_bytes=features.shape[1] * features.dtype.itemsize)
        if quantize == "int8":
            with _stage("transfer", "scale"):
                self.feature_scale = put_replicated(
                    scale.astype(np.dtype(scale_dtype), copy=False),
                    self.mesh)

    def _place_labels(self, parent, labels):
        """The label table, float32, left in flight; returns the host
        array that was sent (None without labels)."""
        self.labels = None
        if labels is None:
            return None
        with _stage("cast", "labels"):
            labels = np.ascontiguousarray(
                labels.astype(np.float32, copy=False))
        with _stage("transfer", "labels"):
            self.labels = self._put(labels)
        parent.set(label_row_bytes=labels.shape[1] * 4)
        return labels

    @classmethod
    def from_arrays(cls, features: np.ndarray,
                    labels: Optional[np.ndarray] = None,
                    ids: Optional[np.ndarray] = None,
                    mesh: Optional[jax.sharding.Mesh] = None,
                    shard_rows: bool = False,
                    pad_dim_to: Optional[int] = None,
                    quantize: Optional[str] = None,
                    scale_dtype=jnp.float32):
        """Rehydrate from prebuilt arrays (a cache) without a graph
        engine. `features`/`labels` must already carry the trailing pad
        row; `ids` (sorted u64, len N) backs lookup() via searchsorted —
        when omitted, node ids are taken to BE table rows (dense-id
        graphs, e.g. the bench cache). pad_dim_to zero-pads the feature
        dim up to a lane multiple (e.g. 128) so each gathered row is an
        aligned tile — a throughput knob; downstream Dense layers see
        the wider (zero-extended) features. Rows WIDER than a tile (768
        features: six tiles) are placed and gathered as they are.
        quantize='int8' quantises in row chunks on threads from the
        dtype `features` comes in (pass bfloat16 or float16 as stored:
        no float32 copy of the table is made, here or inside)."""
        self = cls.__new__(cls)
        self._graph = None
        self.host_arrays = None
        self.pad_row = int(features.shape[0]) - 1
        self.ids = ids if ids is not None else np.arange(
            self.pad_row, dtype=np.uint64)
        self._sorted_ids = ids is not None
        self.shard_rows = bool(shard_rows)
        self.mesh = mesh
        with _stage("place_features", "features", rows=features.shape[0],
                    shard_rows=self.shard_rows) as parent:
            if pad_dim_to is not None and features.shape[1] < pad_dim_to:
                with _stage("pad", "features"):
                    features = np.concatenate(
                        [features,
                         np.zeros((features.shape[0],
                                   pad_dim_to - features.shape[1]),
                                  features.dtype)], axis=1)
            # the host's int8 table and its transfer buffer go before the
            # labels' come: left to overlap, a 768-wide share's placement
            # held both tables twice beside the caller's own copies and
            # met a 40 GiB host's limit (PERF.md, PR 32)
            self._place_features(parent, features, quantize, scale_dtype,
                                 wait=quantize == "int8")
            self._place_labels(parent, labels)
        return self

    @property
    def dim(self) -> int:
        return int(self.features.shape[-1])

    def lookup(self, ids) -> np.ndarray:
        """u64 node ids → int32 rows into the device tables. Unknown ids
        (including default_id=0 sampling pads) map to the zero pad row."""
        if self._graph is not None:
            return self._graph.node_rows(ids, missing=self.pad_row)
        ids = np.asarray(ids, np.uint64).ravel()
        if not self._sorted_ids:
            rows = ids.astype(np.int64)
            return np.where(rows < self.pad_row, rows,
                            self.pad_row).astype(np.int32)
        pos = np.searchsorted(self.ids, ids)
        pos = np.minimum(pos, len(self.ids) - 1)
        hit = self.ids[pos] == ids
        return np.where(hit, pos, self.pad_row).astype(np.int32)
