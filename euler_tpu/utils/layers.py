"""Basic NN layers: Dense, Embedding, SparseEmbedding, AttLayer, LSTMLayer.

Parity: tf_euler/python/utils/layers.py:35-245 (a mini-Keras). Here the
layer system is flax.linen; this module provides the pieces the reference
defines that flax lacks — id-keyed embeddings (uint64 node ids → bucketed
rows), sparse-id embedding with mean/sum combiner, and the small attention
/ LSTM wrappers the encoders use.

The PS-sharded embedding of the reference (layers.py:119-171,
embedding.py) has its TPU-native counterpart in
euler_tpu.parallel.sharded_embedding (HBM-sharded table + ICI all-gather).
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

Array = jax.Array

__all__ = ["Dense", "Embedding", "SparseEmbedding", "AttLayer", "LSTMLayer",
           "bucketize_ids", "undo_collection"]

Dense = nn.Dense  # re-export: flax Dense is the reference's Dense


def bucketize_ids(ids: Array, num_buckets: int) -> Array:
    """node ids → int32 table rows, wrapping by modulo (preserves
    contiguous datasets' 1:1 mapping, matching reference behavior where
    ids index directly). Host feeders pre-convert uint64 ids to int32
    (estimator._to_device_tree) since device x64 is disabled; this handles
    any integer dtype that reaches the device."""
    ids = jnp.asarray(ids)
    if ids.dtype != jnp.int32:
        ids = ids.astype(jnp.int32)
    return ids % jnp.int32(num_buckets)


def undo_collection(collection: str) -> str:
    """Where a module that overwrites ROWS of a variable in the mutable
    `collection` may record what it overwrote: sow (rows, the old rows
    in the stored dtype) under the variable's own name into this
    collection, before the write. The record is an output of that apply
    alone, never state (not sown while initializing). The estimator's
    non-finite guard reads it: a collection whose every variable has a
    record is taken as written and a skipped step puts the old rows
    back, so the table never passes through the guard's lax.cond
    (base_estimator._make_one_step)."""
    return f"{collection}_undo"


class Embedding(nn.Module):
    """Node-id embedding table: [max_id+1, dim], uint64-id-keyed."""

    num_embeddings: int
    dim: int
    init_scale: float = 0.05

    @nn.compact
    def __call__(self, ids: Array) -> Array:
        table = self.param(
            "table",
            nn.initializers.uniform(scale=self.init_scale),
            (self.num_embeddings, self.dim),
        )
        rows = bucketize_ids(ids, self.num_embeddings)
        return jnp.take(table, rows, axis=0)


class SparseEmbedding(nn.Module):
    """Embedding over variable-length sparse-id features, combined.

    Input is the padded dense form [B, L] with `pad_id` marking empties
    (the feeder pads CSR sparse features to a static L — see
    euler_tpu.dataflow.padding). combiner ∈ {mean, sum, max}.
    """

    num_embeddings: int
    dim: int
    combiner: str = "mean"
    pad_id: int = 0
    init_scale: float = 0.05

    @nn.compact
    def __call__(self, ids: Array) -> Array:
        table = self.param(
            "table",
            nn.initializers.uniform(scale=self.init_scale),
            (self.num_embeddings, self.dim),
        )
        rows = bucketize_ids(ids, self.num_embeddings)
        emb = jnp.take(table, rows, axis=0)            # [B, L, D]
        mask = (jnp.asarray(ids).astype(jnp.int32)
                != jnp.int32(self.pad_id)).astype(emb.dtype)[..., None]
        emb = emb * mask
        if self.combiner == "sum":
            return emb.sum(axis=1)
        if self.combiner == "max":
            return emb.max(axis=1)
        return emb.sum(axis=1) / jnp.maximum(mask.sum(axis=1), 1.0)


class AttLayer(nn.Module):
    """Single-query soft attention pooling over a set [B, L, D] → [B, D];
    `axis` names the set's axis (0: a set-major [L, B, D]).
    Parity: reference AttLayer (layers.py:~200)."""

    dim: int

    @nn.compact
    def __call__(self, x: Array, axis: int = 1) -> Array:
        q = self.param("query", nn.initializers.normal(stddev=0.1),
                       (self.dim,))
        keys = nn.Dense(self.dim, name="key")(x)            # x's, D → dim
        att = nn.softmax(jnp.tanh(keys) @ q, axis=axis)
        return (att[..., None] * x).sum(axis=axis)


class LSTMLayer(nn.Module):
    """Runs an LSTM over [B, L, D], returns the full sequence of hiddens.
    Parity: reference LSTMLayer (layers.py:~230, used by SageEncoder's lstm
    aggregation and GeniePath)."""

    dim: int

    @nn.compact
    def __call__(self, x: Array) -> Array:
        return nn.RNN(nn.OptimizedLSTMCell(features=self.dim))(x)
