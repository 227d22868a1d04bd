"""Node encoders over sampled fanouts — the scalable training path.

Parity: tf_euler/python/utils/encoders.py:32-872 (ShallowEncoder,
GCNEncoder, ScalableGCNEncoder, SageEncoder, ScalableSageEncoder,
LayerEncoder, SparseSageEncoder, GenieEncoder, LGCEncoder).

TPU-first redesign: the reference's encoders issue graph queries from
inside the TF graph; here sampling happens host-side (dataflow builds a
`FanoutBatch` of per-hop feature tensors with static shapes) and encoders
are pure flax modules: hop h's neighbors reshape to [n_h, k, D] and
aggregate densely — no scatter, all MXU-friendly reductions. The
"scalable" encoders keep per-node activation caches as a mutable flax
variable collection ("cache") updated functionally each step, replacing
the reference's TF variable assign machinery (encoders.py:294,629).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from euler_tpu.utils.aggregators import get_aggregator
from euler_tpu.utils.layers import AttLayer, Embedding, LSTMLayer, SparseEmbedding, bucketize_ids

Array = jax.Array


class ShallowEncoder(nn.Module):
    """Id-embedding and/or dense-feature encoder (reference encoders.py:32).

    combiner: 'concat' or 'add' of [id embedding, W·dense_feature].
    """

    dim: int
    max_id: int = 0              # >0 enables the id embedding
    use_feature: bool = True
    combiner: str = "concat"

    @nn.compact
    def __call__(self, ids: Array, feats: Optional[Array] = None) -> Array:
        parts = []
        if self.max_id > 0:
            parts.append(Embedding(self.max_id + 1, self.dim, name="id_emb")(ids))
        if self.use_feature and feats is not None:
            parts.append(nn.Dense(self.dim, name="feat")(feats))
        if not parts:
            raise ValueError("ShallowEncoder has neither id embedding nor features")
        if len(parts) == 1:
            return parts[0]
        if self.combiner == "add":
            return sum(parts)
        return jnp.concatenate(parts, axis=-1)


def _hop_neighbors(child: Array, parent: Array) -> Array:
    """Reshape hop h+1's flat layer to [n_h, k, D], deriving k from the
    (jit-static) shapes. Shared by all fanout encoders so the divisibility
    invariant lives in one place."""
    n = parent.shape[0]
    assert child.shape[0] % n == 0, (
        f"layer of {child.shape[0]} rows is not a whole fanout of the "
        f"{n}-row parent layer")
    return child.reshape(n, child.shape[0] // n, -1)


class SageEncoder(nn.Module):
    """GraphSAGE encoder over a sampled fanout (reference encoders.py SageEncoder).

    layers[h]: feature tensor of hop h, shape [B·Πk_{<h}, D]. Aggregates
    deepest-first with fresh aggregator params per hop. Per-hop widths k
    are derived from the layer shapes (static under jit), so parameters
    are fanout-independent — evaluation may use wider fanouts than
    training (pass a bigger-fanout eval_dataflow to NodeEstimator);
    `fanouts` only fixes the hop count.
    """

    dim: int
    fanouts: Sequence[int]
    aggregator: str = "mean"
    concat: bool = True

    @nn.compact
    def __call__(self, layers: Sequence[Array]) -> Array:
        n_hops = len(self.fanouts)
        assert len(layers) == n_hops + 1, (
            f"need {n_hops + 1} feature layers for {n_hops} fanouts"
        )
        agg_cls = get_aggregator(self.aggregator)
        hidden = list(layers)
        for depth in range(n_hops):
            agg = agg_cls(dim=self.dim, concat=self.concat,
                          name=f"agg_{depth}")
            next_hidden = []
            for hop in range(n_hops - depth):
                x = hidden[hop]
                nbr = _hop_neighbors(hidden[hop + 1], x)
                next_hidden.append(agg(x, nbr))
            hidden = next_hidden
        return hidden[0]


class GCNEncoder(nn.Module):
    """GCN-style encoder over a fanout (reference GCNEncoder): shared
    transform of self+neighbors, mean-combined, final layer linear."""

    dim: int
    fanouts: Sequence[int]

    @nn.compact
    def __call__(self, layers: Sequence[Array]) -> Array:
        n_hops = len(self.fanouts)
        assert len(layers) == n_hops + 1, (
            f"need {n_hops + 1} feature layers for {n_hops} fanouts")
        hidden = list(layers)
        for depth in range(n_hops):
            w = nn.Dense(self.dim, use_bias=False, name=f"w_{depth}")
            last = depth == n_hops - 1
            next_hidden = []
            for hop in range(n_hops - depth):
                x = hidden[hop]
                nbr = _hop_neighbors(hidden[hop + 1], x)
                both = jnp.concatenate([x[:, None, :], nbr], axis=1)
                h = w(both.mean(axis=1))
                next_hidden.append(h if last else nn.relu(h))
            hidden = next_hidden
        return hidden[0]


def _ema_update(old: Array, fresh: Array, decay: float) -> Array:
    """Bias-corrected cache write: rows never written before (all-zero —
    the init value) take the fresh activation at FULL scale; visited
    rows blend decay·old + (1-decay)·fresh. Without this, a node's
    first write lands at (1-decay)·h ≈ 0.1·h and rarely-visited nodes'
    cached activations stay massively under-scaled — the zero-init bias
    of a plain EMA. (A live activation that is exactly all-zero would be
    re-written at full scale too, which is the same value — harmless.)"""
    seen = jnp.any(old != 0, axis=-1, keepdims=True)
    return jnp.where(seen, decay * old + (1 - decay) * fresh, fresh)


class _ScalableCache(nn.Module):
    """Per-node activation cache: [max_id+1, dim] rows in the 'cache'
    collection, read for neighbor ids, written for the batch's own ids.

    dtype picks the stored row precision: bfloat16 halves the HBM
    footprint AND the per-step read bytes at products scale (the whole
    point of the cache is replacing a bigger gather); reads are upcast
    to float32 before use."""

    max_id: int
    dim: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, read_ids: Array, write_ids: Optional[Array] = None,
                 write_vals: Optional[Array] = None) -> Array:
        cache = self.variable(
            "cache", "h",
            lambda: jnp.zeros((self.max_id + 1, self.dim), self.dtype))
        out = jnp.take(cache.value, bucketize_ids(read_ids, self.max_id + 1),
                       axis=0).astype(jnp.float32)
        if (write_ids is not None and write_vals is not None
                and self.is_mutable_collection("cache")):
            # eval/infer apply the module with the cache frozen; historical
            # activations are read-only there (reference ScalableGCNEncoder
            # only updates stores inside the training op).
            rows = bucketize_ids(write_ids, self.max_id + 1)
            cache.value = cache.value.at[rows].set(
                write_vals.astype(self.dtype))
        return out


class ScalableGCNEncoder(nn.Module):
    """Scalable GCN (reference encoders.py:294): depth-L GCN but only 1-hop
    sampling — deeper-hop activations come from the historical cache, and
    this batch's fresh layer-l activations are written back.

    Inputs: ids [B], x [B, D] features, nbr_ids [B, K], nbr_x [B, K, D].
    Run with mutable=['cache'] during training.
    """

    dim: int
    num_layers: int
    max_id: int
    store_decay: float = 0.9
    cache_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, ids: Array, x: Array, nbr_ids: Array,
                 nbr_x: Array) -> Array:
        b, k = nbr_ids.shape
        # one cache module per non-input layer, created once
        caches = {layer: _ScalableCache(self.max_id, self.dim,
                                        dtype=self.cache_dtype,
                                        name=f"cache_{layer}")
                  for layer in range(1, self.num_layers)}
        h_self = x
        for layer in range(self.num_layers):
            w = nn.Dense(self.dim, use_bias=False, name=f"w_{layer}")
            if layer == 0:
                nbr_h = nbr_x
            else:
                nbr_h = caches[layer](nbr_ids.ravel()).reshape(b, k, self.dim)
            both = jnp.concatenate([h_self[:, None, :], nbr_h], axis=1)
            h_self = w(both.mean(axis=1))
            if layer < self.num_layers - 1:
                h_self = nn.relu(h_self)
                # store this batch's layer-(l+1) input activations
                store = caches[layer + 1]
                with jax.named_scope("cache"):
                    old = store(ids)
                    new = _ema_update(old, h_self, self.store_decay)
                    store(ids, write_ids=ids, write_vals=new)
        return h_self


class ScalableSageEncoder(nn.Module):
    """Scalable GraphSAGE (reference encoders.py:629): same cache trick,
    SAGE concat aggregation."""

    dim: int
    num_layers: int
    max_id: int
    store_decay: float = 0.9
    cache_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, ids: Array, x: Array, nbr_ids: Array,
                 nbr_x: Array) -> Array:
        b, k = nbr_ids.shape
        caches = {layer: _ScalableCache(self.max_id, self.dim,
                                        dtype=self.cache_dtype,
                                        name=f"cache_{layer}")
                  for layer in range(1, self.num_layers)}
        h_self = x
        for layer in range(self.num_layers):
            if layer == 0:
                nbr_h = nbr_x
            else:
                nbr_h = caches[layer](nbr_ids.ravel()).reshape(b, k, self.dim)
            h_cat = jnp.concatenate([h_self, nbr_h.mean(axis=1)], axis=-1)
            h_new = nn.Dense(self.dim, name=f"w_{layer}")(h_cat)
            if layer < self.num_layers - 1:
                h_new = nn.relu(h_new)
                store = caches[layer + 1]
                with jax.named_scope("cache"):
                    old = store(ids)
                    upd = _ema_update(old, h_new, self.store_decay)
                    store(ids, write_ids=ids, write_vals=upd)
            h_self = h_new
        return h_self


class LayerEncoder(nn.Module):
    """Layerwise (FastGCN/LADIES) encoder (reference LayerEncoder):
    h_{l+1} = act(Â_l h_l W_l) over importance-sampled layer pools.

    adjs[l]: dense [m_l, m_{l+1}] normalized adjacency between pools
    (built host-side by LayerwiseDataFlow); layers[l]: [m_l, D] features,
    layers[-1] is the deepest pool, layers[0] the batch nodes.
    """

    dim: int
    dropout: float = 0.0  # input dropout per layer (standard FastGCN setup)

    @nn.compact
    def __call__(self, layers: Sequence[Array], adjs: Sequence[Array]) -> Array:
        h = layers[-1]
        n_layers = len(adjs)
        for i in range(n_layers - 1, -1, -1):
            if self.dropout > 0.0:
                h = nn.Dropout(self.dropout)(
                    h, deterministic=not self.has_rng("dropout"))
            w = nn.Dense(self.dim, use_bias=False, name=f"w_{i}")
            h = adjs[i] @ w(h)
            if i > 0:
                h = nn.relu(h)
        return h


class SparseSageEncoder(nn.Module):
    """SAGE over sparse-id features (reference SparseSageEncoder): per-hop
    sparse embeddings + SageEncoder aggregation.

    sparse_layers[h]: padded sparse-id tensor [n_h, L]."""

    dim: int
    fanouts: Sequence[int]
    num_embeddings: int
    aggregator: str = "mean"
    concat: bool = True

    @nn.compact
    def __call__(self, sparse_layers: Sequence[Array]) -> Array:
        emb = SparseEmbedding(self.num_embeddings, self.dim, name="sp_emb")
        dense_layers = [emb(s) for s in sparse_layers]
        return SageEncoder(self.dim, self.fanouts, self.aggregator,
                           self.concat, name="sage")(dense_layers)


class GenieEncoder(nn.Module):
    """GeniePath (reference GenieEncoder): adaptive breadth (attention) +
    depth (LSTM gating) over a fanout."""

    dim: int
    fanouts: Sequence[int]

    @nn.compact
    def __call__(self, layers: Sequence[Array]) -> Array:
        n_hops = len(self.fanouts)
        assert len(layers) == n_hops + 1, (
            f"need {n_hops + 1} feature layers for {n_hops} fanouts")
        # project all layers to dim
        proj = nn.Dense(self.dim, name="proj")
        hidden = [proj(h) for h in layers]
        # adaptive depth: collect the root representation after every
        # breadth layer (reference encoders.py:265-277 depth_fc per layer)
        h_t = [nn.Dense(self.dim, name="depth_fc_0")(hidden[0])]
        # breadth: attention-pool each hop's neighborhood into the target
        for depth in range(n_hops):
            att = AttLayer(self.dim, name=f"att_{depth}")
            next_hidden = []
            for hop in range(n_hops - depth):
                x = hidden[hop]
                nbr = _hop_neighbors(hidden[hop + 1], x)
                pooled = att(jnp.concatenate([x[:, None, :], nbr], axis=1))
                next_hidden.append(nn.tanh(
                    nn.Dense(self.dim, name=f"w_{depth}_{hop}")(pooled)))
            hidden = next_hidden
            h_t.append(
                nn.Dense(self.dim, name=f"depth_fc_{depth + 1}")(hidden[0]))
        # depth gating: LSTM over the depth sequence [B, L+1, dim]. The
        # paper reads the final state; the reference's code reads
        # timestep 0 (encoders.py:287), which discards the gating — we
        # follow the paper.
        seq = jnp.stack(h_t, axis=1)
        out = LSTMLayer(self.dim, name="depth_lstm")(seq)
        return out[:, -1, :]


class LGCEncoder(nn.Module):
    """LGCN encoder (reference LGCEncoder): per-feature top-k ordering of
    neighbor values then 1-D conv over the ordered sequence."""

    dim: int
    k: int = 4

    @nn.compact
    def __call__(self, x: Array, nbr: Array) -> Array:
        # nbr: [B, K, D] with K >= k. top-k per feature channel
        b, K, d = nbr.shape
        topk = jax.lax.top_k(jnp.swapaxes(nbr, 1, 2), self.k)[0]  # [B, D, k]
        seq = jnp.concatenate([x[:, :, None], topk], axis=-1)     # [B, D, k+1]
        seq = jnp.swapaxes(seq, 1, 2)                             # [B, k+1, D]
        h = nn.Conv(features=self.dim, kernel_size=(self.k + 1,),
                    padding="VALID", name="conv")(seq)            # [B, 1, dim]
        return h[:, 0, :]
