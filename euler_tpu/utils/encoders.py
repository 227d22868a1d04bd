"""Node encoders over sampled fanouts — the scalable training path.

Parity: tf_euler/python/utils/encoders.py:32-872 (ShallowEncoder,
GCNEncoder, ScalableGCNEncoder, SageEncoder, ScalableSageEncoder,
LayerEncoder, SparseSageEncoder, GenieEncoder, LGCEncoder).

TPU-first redesign: the reference's encoders issue graph queries from
inside the TF graph; here sampling happens host-side (dataflow builds a
`FanoutBatch` of per-hop feature tensors with static shapes) and encoders
are pure flax modules: hop h's neighbors reshape to [n_h, k, D] (or
[k, n_h, D] where the caller drew them neighbour-major) and aggregate
densely — no scatter, all MXU-friendly reductions. The
"scalable" encoders keep per-node activation caches as a mutable flax
variable collection ("cache") updated functionally each step, replacing
the reference's TF variable assign machinery (encoders.py:294,629).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.custom_derivatives import SymbolicZero

from euler_tpu.utils.aggregators import (
    Parts,
    get_aggregator,
    mean_with_self,
)
from euler_tpu.utils.layers import (
    AttLayer,
    Embedding,
    LSTMLayer,
    SparseEmbedding,
    bucketize_ids,
    undo_collection,
)

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


def _hop_neighbors(child: Parts, parent: Array,
                   neighbor_major: bool = False) -> Tuple[Parts, int]:
    """Hop h+1's flat layer viewed by its parents' slots, and the axis
    the k slots lie on: ([n_h, k, D], 1) for the host's target-major
    order, ([k, n_h, D], 0) for `neighbor_major_rows`' order. k comes
    from the (jit-static) shapes. Shared by all fanout encoders so the
    divisibility invariant lives in one place.

    `child` may be a tuple of lane parts (`utils/aggregators`: the
    [n_{h+1}, D_part] arrays whose concat along the last axis the layer
    is; only `SageEncoder` makes one, of the deepest hop a depth wrote).
    Each part is viewed the same way and the tuple handed on, for the
    aggregator to reduce part by part.

    The order cannot be read off a feature array: `neighbor_major` is a
    fact the caller states, the one that made the order. On the chip the
    two minor axes are tiled (8, 128), so the target-major view of a
    gathered [n_h*k, D] array is a relayout whenever k is no multiple of
    8, forward and backward; the neighbour-major one is a bitcast."""
    n = parent.shape[0]
    rows = jax.tree_util.tree_leaves(child)[0].shape[0]
    assert rows % n == 0, (
        f"layer of {rows} rows is not a whole fanout of the "
        f"{n}-row parent layer")
    k = rows // n
    shape, axis = ((k, n, -1), 0) if neighbor_major else ((n, k, -1), 1)
    return jax.tree_util.tree_map(lambda p: p.reshape(shape), child), axis


class SageEncoder(nn.Module):
    """GraphSAGE encoder over a sampled fanout (reference encoders.py SageEncoder).

    layers[h]: feature tensor of hop h, shape [B·Πk_{<h}, D]: row m's k
    children at m*k .. m*k+k-1 (the host's dataflow), or, where
    `neighbor_major`, in `neighbor_major_rows`' order. Aggregates
    deepest-first with fresh aggregator params per hop. Per-hop widths k
    are derived from the layer shapes (static under jit), so parameters
    are fanout-independent — evaluation may use wider fanouts than
    training (pass a bigger-fanout eval_dataflow to NodeEstimator);
    `fanouts` only fixes the hop count.

    `neighbor_major` is no option to tune: it is set by the model class
    that re-ordered the sampled ids (models/graphsage, the device-sampled
    models), never from a kwarg or a configuration. Same parameters,
    same function of the same (node, slot) pairs either way.

    The deepest hop a depth writes (every depth but the last has one) is
    never an `x` of the next depth, only its `nbr`: it is asked of the
    aggregator in its lane parts (`apart=True`, `utils/aggregators`) and
    never concatenated at its k-times-the-parents row count. What the
    next depth's aggregator does with a tuple is its own: `mean` reduces
    it part by part (PERF.md, PR 33: layer 0's `f32[614400,512]` and its
    cotangent's passes, gone from the sage3 cells' step), the pooling
    ones concatenate it, `lstm` takes it apart in its input product.
    Nothing selects this but the hop's position.

    `linear_last` leaves the last depth without its activation (the
    authors' supervised GraphSAGE: its last aggregator is linear).
    `masks[h]` ([n_h] bool, False at a pad row), where given, reach the
    aggregator as `mask`, viewed by the parents' slots as hop h's
    features are: only `lstm` takes them (a pad slot is no step of its
    recurrence; the mean reads the pad row's zeros).
    """

    dim: int
    fanouts: Sequence[int]
    aggregator: str = "mean"
    concat: bool = True
    neighbor_major: bool = False
    linear_last: bool = False

    @nn.compact
    def __call__(self, layers: Sequence[Array],
                 masks: Optional[Sequence[Array]] = None) -> Array:
        n_hops = len(self.fanouts)
        assert len(layers) == n_hops + 1, (
            f"need {n_hops + 1} feature layers for {n_hops} fanouts"
        )
        agg_cls = get_aggregator(self.aggregator)
        hidden = list(layers)
        for depth in range(n_hops):
            linear = self.linear_last and depth == n_hops - 1
            agg = agg_cls(dim=self.dim, concat=self.concat,
                          activation=None if linear else "relu",
                          name=f"agg_{depth}")
            deepest = n_hops - depth - 1
            next_hidden = []
            for hop in range(deepest + 1):
                x = hidden[hop]
                nbr, axis = _hop_neighbors(hidden[hop + 1], x,
                                           self.neighbor_major)
                kw = {}
                if masks is not None:
                    kw["mask"] = _hop_neighbors(masks[hop + 1][:, None], x,
                                                self.neighbor_major)[0]
                # the next depth reads its deepest hop only as `nbr`
                next_hidden.append(agg(
                    x, nbr, axis, apart=deepest > 0 and hop == deepest,
                    **kw))
            hidden = next_hidden
        return hidden[0]


class GCNEncoder(nn.Module):
    """GCN-style encoder over a fanout (reference GCNEncoder): shared
    transform of self+neighbors, mean-combined, final layer linear.
    `neighbor_major` as SageEncoder's."""

    dim: int
    fanouts: Sequence[int]
    neighbor_major: bool = False

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
                nbr, axis = _hop_neighbors(hidden[hop + 1], x,
                                           self.neighbor_major)
                h = w(mean_with_self(x, nbr, axis))
                next_hidden.append(h if last else nn.relu(h))
            hidden = next_hidden
        return hidden[0]


def neighbor_major_rows(rows: Sequence[Array],
                        fanouts: Sequence[int]) -> list:
    """The fanout draw's per-hop rows (hop h+1 holds the k children of
    hop h's row m at m*k .. m*k+k-1) re-ordered NEIGHBOUR-MAJOR, every
    hop consistently: hop h+1's row j*n_h + m is slot j of hop h's row
    m. Its features then reshape to [k, n_h, D] for free and a sum over
    the slots adds k slabs, where the target-major [n_h, k, D] view
    costs the chip a relayout whenever k is no multiple of 8 (PERF.md,
    PR 31: 19 ms of the mean model's 94.9 ms step until it took this
    order too). Every encoder DeviceSampledGraphSage dispatches reads it
    (`_hop_neighbors(..., neighbor_major=True)`, the attention layers'
    own [k, M, D] views). Hop 0 keeps its order; int32 ids only are
    moved."""
    b = rows[0].shape[0]
    out = [rows[0]]
    for hop in range(1, len(rows)):
        shape = (b, *[int(k) for k in fanouts[:hop]])
        out.append(rows[hop].reshape(shape).transpose(
            tuple(reversed(range(hop + 1)))).reshape(-1))
    return out


class _HeadVectors(nn.Module):
    """One attention vector a head, [width, heads], as a leaf named
    `kernel` whose first axis is the head's width: initialisers that go
    by leaf name and fan-in (flax's own; the benchmark's seeded weights)
    then treat it as the weight it is, and not as a bias."""

    width: int
    heads: int

    @nn.compact
    def __call__(self) -> Array:
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (self.width, self.heads))


def _block_diagonal(vec: Array) -> Array:
    """[C, H] head vectors -> [H*C, H]: column h holds head h's vector in
    rows h*C .. h*C+C-1, so `z @ that` is every head's dot product with
    its own slice of z in one matrix product."""
    c, h = vec.shape
    return (vec.T[:, :, None] * jnp.eye(h, dtype=vec.dtype)[:, None, :]
            ).reshape(h * c, h)


# the own logit of an attention without an own term: finite, under every
# logit a slot can have
_NO_SELF = float(jnp.finfo(jnp.float32).min)


def _slot_softmax(e_nbr: Array, mask: Optional[Array], e_self: Array):
    """The masked softmax of every target over its k sampled slots and
    its own term: e_nbr [H, k, M] and e_self [H, M] logits (M minor:
    dense on the chip's lanes), mask bool [k, M], False where the slot is
    a pad -> (a_self [H, M], a_nbr [H, k, M]). Pad slots take no weight;
    a target whose slots are all pads gives its own term weight 1. An
    attention with no own term gives `_NO_SELF` logits: that term then
    weighs 0 beside any slot, and takes the weight of a target that has
    none, which so aggregates zero with nothing NaN in either pass."""
    if mask is not None:
        e_nbr = jnp.where(mask[None], e_nbr, -jnp.inf)
    top = jnp.maximum(e_self, e_nbr.max(axis=1))        # finite: e_self is
    p_self = jnp.exp(e_self - top)
    p_nbr = jnp.exp(e_nbr - top[:, None])
    total = p_self + p_nbr.sum(axis=1)
    return p_self / total, p_nbr / total[:, None]


def _attend(z_t: Array, z_s: Array, e_self: Array, e_nbr: Array,
            mask: Optional[Array], concat: bool) -> Array:
    """Softmax attention of every target over its k sampled slots and
    itself (`_slot_softmax`). z_t [M, H*C], z_s [k, M, H*C] projected
    rows; e_self [H, M], e_nbr [H, k, M] the logits; mask bool [k, M].
    -> [M, H*C] (heads concatenated) or [M, C] (their mean)."""
    a_self, a_nbr = _slot_softmax(e_nbr, mask, e_self)
    return _weighted_sum(z_t, z_s, a_self, a_nbr, concat)


def _weighted_sum(z_t: Array, z_s: Array, a_self: Array, a_nbr: Array,
                  concat: bool) -> Array:
    """`_attend`'s sum of the rows under the weights a_self [H, M] and
    a_nbr [H, k, M], a head at a time."""
    heads = a_self.shape[0]
    c = z_t.shape[-1] // heads
    outs = []
    for h in range(heads):
        lanes = slice(h * c, (h + 1) * c)
        outs.append(a_self[h][:, None] * z_t[:, lanes]
                    + (a_nbr[h][:, :, None] * z_s[:, :, lanes]).sum(axis=0))
    if concat:
        return jnp.concatenate(outs, axis=-1)
    return sum(outs) / heads


class HopBatchNorm(nn.Module):
    """Batch normalisation (Ioffe & Szegedy 2015; PyTorch's BatchNorm1d)
    of hop-structured activations: a layer applied pair by pair with
    shared weights writes one array a hop, and the statistics are taken
    per channel over EVERY row of EVERY array handed in, the arrays'
    sums combined before anything is divided, as if they were one array
    (the one PyG's model normalises). Rows whose mask is False (pad
    slots' rows) are left out of the statistics; a row drawn twice is
    two rows.

        y = (1 + g) (x - mu) / sqrt(var + eps) + beta

    with the batch's mu and biased var where the `batch_stats` collection
    is mutable (a train step), which then also moves the running ones:
    mean <- (1 - m) mean + m mu, var <- (1 - m) var + m var n / (n - 1);
    the running ones where it is not (`evaluate`, `infer`, a served
    bundle). The gain is stored as its OFFSET g from one, as
    `OffsetLayerNorm`'s. Counted at trace time, one a norm whatever
    the hops it spans:
    `traced_paths_total{path="batch_norm",detail=<layer>}`."""

    MOMENTUM = 0.1      # PyTorch's defaults, the published model's
    EPSILON = 1e-5

    @nn.compact
    def __call__(self, xs: Sequence[Array],
                 masks: Sequence[Optional[Array]]) -> list:
        from euler_tpu import obs

        d = xs[0].shape[-1]
        gain = 1.0 + self.param("gain_offset", nn.initializers.zeros, (d,))
        bias = self.param("bias", nn.initializers.zeros, (d,))
        mean = self.variable("batch_stats", "mean",
                             lambda: jnp.zeros((d,), jnp.float32))
        var = self.variable("batch_stats", "var",
                            lambda: jnp.ones((d,), jnp.float32))
        obs.traced_path("batch_norm", "/".join(self.path[-2:-1]))
        if self.is_mutable_collection("batch_stats"):
            weights = [None if m is None else m.astype(x.dtype)[:, None]
                       for x, m in zip(xs, masks)]

            def total(parts):
                return sum((p if w is None else p * w).sum(axis=0)
                           for p, w in zip(parts, weights))

            n = sum(jnp.float32(x.shape[0]) if m is None
                    else m.sum().astype(jnp.float32)
                    for x, m in zip(xs, masks))
            mu = total(xs) / n
            sigma2 = total([jnp.square(x - mu) for x in xs]) / n
            if not self.is_initializing():
                m = self.MOMENTUM
                mean.value = (1.0 - m) * mean.value + m * mu
                var.value = (1.0 - m) * var.value \
                    + m * sigma2 * n / jnp.maximum(n - 1.0, 1.0)
        else:
            mu, sigma2 = mean.value, var.value
        scale = gain * jax.lax.rsqrt(sigma2 + self.EPSILON)
        return [(x - mu) * scale + bias for x in xs]


class GATLayer(nn.Module):
    """One graph-attention layer (Velickovic et al. 2018; PyG's GATConv
    with a linear skip, as examples/ogbn_products_gat.py stacks them)
    applied with shared weights to every (hop h, hop h+1) pair of a
    NEIGHBOUR-MAJOR fanout (`neighbor_major_rows`):

        z = x W;  e_ij = LeakyReLU_0.2(a_src . z_j + a_dst . z_i) a head,
        j over i's k sampled slots and i itself; alpha = softmax_j e;
        y_i = concat_h or mean_h (sum_j alpha_ij z_j) + b;
        x_i' = y_i + x_i S + s, through ELU where heads are concatenated.

    A slot drawn twice counts twice; pad slots (masks False) take no
    weight. Every hop is projected once a layer. With `batch_norm`, where
    heads are concatenated, the sum y_i + x_i S + s passes a
    `HopBatchNorm` before its ELU, ONE for the layer: its statistics span
    every hop the layer writes (OGB-LSC's MAG240M baseline). Scopes `proj`, `attn`, `skip` (and `norm`) under
    the layer's name; counted at trace time, one a layer whatever its
    hops: `traced_paths_total{path="gat_attention",detail=<layer>}`.

    On the TPU, where a head's width C is a multiple of 128 and a block of
    targets fits VMEM (`ops/gat_pair.row_block`), a pair's attention is
    `ops/gat_pair.attend`: the same forward, its backward one Pallas
    kernel that reads the source rows once and writes their cotangent
    once; counted at trace time, one a pair:
    `traced_paths_total{path="gat_attention_kernel",detail=<M>x<k>x<H*C>}`."""

    width: int          # C, one head's
    heads: int
    concat: bool        # False: heads averaged, no ELU (the last layer)
    batch_norm: bool = False

    @nn.compact
    def __call__(self, hidden: Sequence[Array],
                 masks: Sequence[Optional[Array]]) -> list:
        from euler_tpu import obs
        from euler_tpu.ops import gat_pair
        from euler_tpu.utils import aggregators

        h, c = self.heads, self.width
        out_dim = h * c if self.concat else c
        # the layer that averages its heads onto the classes has no norm
        normed = self.batch_norm and self.concat
        proj = nn.Dense(h * c, use_bias=False, name="proj")
        zs = [proj(x) for x in hidden]
        att_src, att_dst = (_HeadVectors(c, h, name=name)()
                            for name in ("att_src", "att_dst"))
        att = jnp.concatenate([_block_diagonal(att_src),
                               _block_diagonal(att_dst)], axis=1)  # [H*C, 2H]
        bias = self.param("bias", nn.initializers.zeros, (out_dim,))
        skip = nn.Dense(out_dim, name="skip")
        obs.traced_path("gat_attention", self.name or "")
        with jax.named_scope("attn"):
            # [2H, n] a hop, n minor: a_src . z then a_dst . z, every head
            scores = [jnp.einsum("fg,nf->gn", att, z) for z in zs]
        out = []
        for hop in range(len(hidden) - 1):
            x, z_t, z_s = hidden[hop], zs[hop], zs[hop + 1]
            m = x.shape[0]
            assert z_s.shape[0] % m == 0, (
                f"layer of {z_s.shape[0]} rows is not a whole fanout of "
                f"the {m}-row parent layer")
            k = z_s.shape[0] // m
            with jax.named_scope("attn"):
                src, dst = scores[hop][:h], scores[hop][h:]
                mask = masks[hop + 1]
                if aggregators._on_tpu() and gat_pair.row_block(
                        k, m, h * c, h, out_dim):
                    obs.traced_path("gat_attention_kernel",
                                    f"{m}x{k}x{h * c}")
                    y = gat_pair.attend(
                        z_t, z_s.reshape(k, m, h * c), src, dst, att_src,
                        None if mask is None else mask.reshape(k, m),
                        self.concat)
                else:
                    e_self = nn.leaky_relu(src + dst, 0.2)
                    e_nbr = nn.leaky_relu(
                        scores[hop + 1][:h].reshape(h, k, m) + dst[:, None],
                        0.2)
                    y = _attend(z_t, z_s.reshape(k, m, h * c), e_self, e_nbr,
                                None if mask is None else mask.reshape(k, m),
                                self.concat)
                y = y + bias
            with jax.named_scope("skip"):
                y = y + skip(x)
                out.append(nn.elu(y) if self.concat and not normed else y)
        if normed:
            out = HopBatchNorm(name="norm")(out, masks[:len(out)])
            with jax.named_scope("norm"):
                out = [nn.elu(y) for y in out]
        return out


class _MLPHead(nn.Module):
    """Dense(width) - HopBatchNorm - ReLU - Dense(out_dim) on the roots'
    hidden rows: the classifier OGB-LSC's MAG240M baselines put behind
    their last layer. Scope `head` (the module's name), its norm under
    `head/norm`."""

    width: int
    out_dim: int
    batch_norm: bool

    @nn.compact
    def __call__(self, x: Array, mask: Optional[Array]) -> Array:
        h = nn.Dense(self.width, name="fc")(x)
        if self.batch_norm:
            h, = HopBatchNorm(name="norm")([h], [mask])
        return nn.Dense(self.out_dim, name="out")(nn.relu(h))


class _AttentionEncoder(nn.Module):
    """len(fanouts) attention layers (`layer_cls(width, heads, concat,
    name)`, the subclass's) over a sampled fanout, deepest pairs first as
    SageEncoder applies its aggregators: hidden layers of `heads` heads
    of width `dim` concatenated, the last of width `out_dim` averaged.
    With `head_dim` the last layer is a hidden one too and an `_MLPHead`
    of that width maps the roots to `out_dim`; `batch_norm` (GATLayer
    only) puts a `HopBatchNorm` into every hidden layer and the head.

    layers[h]: hop h's features NEIGHBOUR-MAJOR (`neighbor_major_rows`);
    masks[h]: bool per row of hop h, False for a pad slot (None: no pads).
    """

    dim: int
    fanouts: Sequence[int]
    heads: int
    out_dim: int
    batch_norm: bool = False
    head_dim: int = 0

    layer_cls = None

    @nn.compact
    def __call__(self, layers: Sequence[Array],
                 masks: Optional[Sequence[Optional[Array]]] = None) -> Array:
        n_hops = len(self.fanouts)
        assert len(layers) == n_hops + 1, (
            f"need {n_hops + 1} feature layers for {n_hops} fanouts")
        hidden = list(layers)
        masks = list(masks) if masks is not None else [None] * len(hidden)
        norm = {"batch_norm": True} if self.batch_norm else {}
        for depth in range(n_hops):
            last = depth == n_hops - 1 and not self.head_dim
            layer = self.layer_cls(self.out_dim if last else self.dim,
                                   self.heads, concat=not last,
                                   name=f"layer{depth}", **norm)
            hidden = layer(hidden, masks)
        if not self.head_dim:
            return hidden[0]
        return _MLPHead(self.head_dim, self.out_dim, self.batch_norm,
                        name="head")(hidden[0], masks[0])


class GATEncoder(_AttentionEncoder):
    """Multi-head attention encoder over a sampled fanout: GATLayers;
    hidden layers pass ELU, the last emits the class logits where the
    model has no output layer of its own."""

    layer_cls = GATLayer


class OffsetLayerNorm(nn.Module):
    """LayerNorm over the last axis whose gain is stored as its OFFSET
    from one (`gain_offset`, zeros): the same function of x, the same
    gradient and the same Adam step as `nn.LayerNorm`'s `scale` (ones),
    but an initialiser that zeroes whatever is no `kernel` (the
    benchmark's seeded weights) leaves the norm a norm, not a zero."""

    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x: Array) -> Array:
        d = x.shape[-1]
        gain = 1.0 + self.param("gain_offset", nn.initializers.zeros, (d,))
        bias = self.param("bias", nn.initializers.zeros, (d,))
        mean = x.mean(axis=-1, keepdims=True)
        var = jnp.square(x - mean).mean(axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + self.epsilon) * gain + bias


class TransformerConvLayer(nn.Module):
    """One graph-transformer layer (Shi et al. 2020, UniMP; PyG's
    TransformerConv(beta=True)) applied with shared weights to every
    (hop h, hop h+1) pair of a NEIGHBOUR-MAJOR fanout:

        q_i = W_q h_i + b_q;  k_j = W_k h_j + b_k;  v_j = W_v h_j + b_v
        alpha_ij = softmax over i's k sampled slots j of q_i . k_j / sqrt(C)
                   a head (no term of i's own: `_NO_SELF`)
        m_i = concat_h or mean_h (sum_j alpha_ij v_j);  r_i = W_r h_i + b_r
        beta_i = sigmoid(w_b . [r_i ; m_i ; r_i - m_i])
        o_i = beta_i r_i + (1 - beta_i) m_i, through LayerNorm
        (`OffsetLayerNorm`) and ReLU where heads are concatenated.

    Keys and values are apart from each other and from the queries; a
    slot drawn twice counts twice; pad slots (masks False) take no
    weight, and a target whose slots are all pads aggregates zero. The
    per-head dot product and the spreading of a head's weight over its
    C lanes are matrix products with the heads' 0/1 indicator, so the
    softmax runs with M on the lanes whatever C is. Scopes `qkv` (the
    four projections), `attn`, `gate` (gate, norm, ReLU) under the
    layer's name; counted at trace time, one a layer whatever its hops:
    `traced_paths_total{path="unimp_attention",detail=<layer>}`.

    On the TPU, where H*C is a multiple of 128 and a block of targets
    fits VMEM (`ops/dot_pair.row_block`), a pair's attention is
    `ops/dot_pair.attend`: the same function at the same rounding, each
    pass one Pallas kernel that reads the keys and values once and
    writes their cotangents once; counted at trace time, one a pair:
    `traced_paths_total{path="unimp_attention_kernel",detail=<M>x<k>x<H*C>}`."""

    width: int          # C, one head's
    heads: int
    concat: bool        # False: heads averaged, no norm (the last layer)

    @nn.compact
    def __call__(self, hidden: Sequence[Array],
                 masks: Sequence[Optional[Array]]) -> list:
        from euler_tpu import obs
        from euler_tpu.ops import dot_pair
        from euler_tpu.utils import aggregators

        h, c = self.heads, self.width
        out_dim = h * c if self.concat else c
        query, key, value = (nn.Dense(h * c, name=name)
                             for name in ("query", "key", "value"))
        skip = nn.Dense(out_dim, name="skip")
        beta = nn.Dense(1, use_bias=False, name="beta")
        norm = OffsetLayerNorm(name="norm") if self.concat else None
        obs.traced_path("unimp_attention", self.name or "")
        with jax.named_scope("qkv"):
            targets = [(query(x), skip(x)) for x in hidden[:-1]]
            sources = [(key(x), value(x)) for x in hidden[1:]]
        head_of = _block_diagonal(jnp.ones((c, h), jnp.float32))  # [H*C, H]
        out = []
        for hop, ((q, r), (k_s, v_s)) in enumerate(zip(targets, sources)):
            m = q.shape[0]
            assert k_s.shape[0] % m == 0, (
                f"layer of {k_s.shape[0]} rows is not a whole fanout of "
                f"the {m}-row parent layer")
            k = k_s.shape[0] // m
            with jax.named_scope("attn"):
                k_s, v_s = k_s.reshape(k, m, h * c), v_s.reshape(k, m, h * c)
                mask = masks[hop + 1]
                if aggregators._on_tpu() and dot_pair.row_block(
                        k, m, h * c, h):
                    obs.traced_path("unimp_attention_kernel",
                                    f"{m}x{k}x{h * c}")
                    msg = dot_pair.attend(
                        q, k_s, v_s,
                        None if mask is None else mask.reshape(k, m), h)
                else:
                    msg = _dot_attend(q, k_s, v_s, mask, head_of)
                if not self.concat:
                    msg = msg.reshape(m, h, c).mean(axis=1)
            with jax.named_scope("gate"):
                b = nn.sigmoid(beta(
                    jnp.concatenate([r, msg, r - msg], axis=-1)))
                o = b * r + (1.0 - b) * msg
                out.append(nn.relu(norm(o)) if self.concat else o)
        return out


def _dot_attend(q: Array, k_s: Array, v_s: Array, mask: Optional[Array],
                head_of: Array) -> Array:
    """`TransformerConvLayer`'s attention of one pair: q [M, H*C] the
    targets' queries, k_s and v_s [k, M, H*C] their slots' keys and
    values, mask bool [k, M] (or its k * M rows flat) or None, head_of
    [H*C, H] the heads' 0/1 indicator (`_block_diagonal`) -> [M, H*C],
    every head's softmax over the slots of q . k / sqrt(C) weighing its
    lanes of the values. The per-head sum and the weights' spread over a
    head's lanes are products with the indicator."""
    k, m, lanes = k_s.shape
    heads = head_of.shape[1]
    # [H, k, M], M minor: every head's q . k of every slot
    e = jnp.einsum("fg,kmf->gkm", head_of, q[None] * k_s) \
        / math.sqrt(lanes // heads)
    _, alpha = _slot_softmax(
        e, None if mask is None else mask.reshape(k, m),
        jnp.full((heads, m), _NO_SELF, e.dtype))
    # a head's weight on each of its C lanes, then the sum
    return (jnp.einsum("gkm,fg->kmf", alpha, head_of) * v_s).sum(axis=0)


class UniMPEncoder(_AttentionEncoder):
    """Graph-transformer encoder over a sampled fanout (UniMP's model
    body as PyG's examples/unimp_arxiv.py stacks it):
    TransformerConvLayers; hidden layers pass LayerNorm and ReLU, the
    last emits the class logits. The labels enter before it, in the rows
    it is given (`models/graphsage._GatherEncode`)."""

    layer_cls = TransformerConvLayer


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


def _write_read(cache: Array, rows: Array, vals: Array, read_rows: Array):
    """new = cache with vals at rows; out = new[read_rows] (read_rows of
    any shape); pos = the int32[N] table of which write owns each row
    (len(rows) where none lands). rows may repeat: the write the table
    names wins its row and the others write nowhere (row N is out of
    bounds: dropped), so the stored value and the write the backward
    pass pays are the same one."""
    n, b = cache.shape[0], rows.shape[0]
    with jax.named_scope("write"):
        order = jnp.arange(b, dtype=jnp.int32)
        pos = jnp.full((n,), b, jnp.int32).at[rows].set(order)
        won = jnp.take(pos, rows) == order
        new = cache.at[jnp.where(won, rows, n)].set(vals, mode="drop")
    with jax.named_scope("read"):
        out = jnp.take(new, read_rows, axis=0)
    return new, out, pos


@jax.custom_vjp
def _cache_write_then_read(cache: Array, rows: Array, vals: Array,
                           read_rows: Array) -> Tuple[Array, Array]:
    """(cache.at[rows].set(vals), that[read_rows]) as ONE differentiable
    operation: d vals[i] is the sum of the cotangents of the reads that
    saw write i, found through a rows-long position table, so neither
    pass builds anything of the cache's shape but the write itself.
    cache and vals share a dtype. The cache is state, not a function of
    the parameters: it takes and gives no cotangent."""
    return _write_read(cache, rows, vals, read_rows)[:2]


def _cache_write_then_read_fwd(cache, rows, vals, read_rows):
    new, out, pos = _write_read(cache.value, rows.value, vals.value,
                                read_rows.value)
    with jax.named_scope("read"):
        src = jnp.take(pos, read_rows.value)  # the write each read saw
    return (new, out), (src, rows.value)


# reads summed a loop turn in the backward pass (_sum_by_write)
_GRAD_CHUNK = 8192


def _sum_by_write(g: Array, src: Array, n_writes: int) -> Array:
    """[n_writes, dim]: the rows g[..., :] summed by the write each read
    saw (src, of g's leading shape; n_writes = saw none). Most reads see
    none, and a scatter-add over all of them costs the chip a sort, a
    gather and a scatter of EVERY row: so the reads are sorted by src
    once, which puts the hits first, and only the chunks that hold a hit
    are gathered and summed. How many that is is the batch's to say (a
    while loop), so the sum is exact for any batch, all reads hitting
    included."""
    n = src.size
    chunk = min(n, _GRAD_CHUNK)
    pad = -n % chunk + chunk   # a slice that starts inside never clamps
    hits = jnp.sum(src < n_writes)
    seg, read = jax.lax.sort_key_val(src.ravel(),
                                     jnp.arange(n, dtype=jnp.int32))
    seg = jnp.concatenate([seg, jnp.full((pad,), n_writes, seg.dtype)])
    read = jnp.concatenate([read, jnp.zeros((pad,), read.dtype)])

    def add_chunk(c, acc):
        at = jnp.unravel_index(
            jax.lax.dynamic_slice(read, (c * chunk,), (chunk,)), src.shape)
        # segment n_writes is out of range: dropped
        return acc + jax.ops.segment_sum(
            g[at], jax.lax.dynamic_slice(seg, (c * chunk,), (chunk,)),
            num_segments=n_writes, indices_are_sorted=True)

    return jax.lax.fori_loop(0, (hits + chunk - 1) // chunk, add_chunk,
                             jnp.zeros((n_writes, g.shape[-1]), g.dtype))


def _cache_write_then_read_bwd(res, cts):
    src, rows = res
    g_new, g_out = cts
    if not isinstance(g_new, SymbolicZero):
        raise NotImplementedError(
            "the activation cache is state: differentiate the rows read "
            "from it, not the table")
    with jax.named_scope("grad"):
        d_vals = _sum_by_write(g_out, src, rows.shape[0])
    return None, None, d_vals, None


_cache_write_then_read.defvjp(_cache_write_then_read_fwd,
                              _cache_write_then_read_bwd,
                              symbolic_zeros=True)


class _ScalableCache(nn.Module):
    """Per-node activation cache: [max_id+1, dim] rows in the 'cache'
    collection. One call stores the batch's fresh activations at its own
    ids (moving average with the old rows) and returns the rows of its
    neighbours from the cache AS WRITTEN, for the next layer.

    Write and read are one operation (_cache_write_then_read) because
    the gradient runs through the pair: a neighbour that is also a root
    of the step reads what this step wrote. Left to jax, the rule for a
    scatter that may repeat an index masks the whole table and the read's
    cotangent is scattered into a table of zeros: five [N+1, dim] passes
    a step for a term that touches a few thousand rows (PERF.md, PR 25).

    dtype picks the stored row precision: bfloat16 halves the HBM
    footprint AND the per-step read bytes at products scale (the whole
    point of the cache is replacing a bigger gather); reads are upcast
    to float32 before use."""

    max_id: int
    dim: int
    dtype: Any = jnp.float32
    decay: float = 0.9

    @nn.compact
    def __call__(self, ids: Array, fresh: Array, nbr_ids: Array) -> Array:
        """ids [B], fresh [B, dim], nbr_ids [B, K] -> [B, K, dim]."""
        cache = self.variable(
            "cache", "h",
            lambda: jnp.zeros((self.max_id + 1, self.dim), self.dtype))
        # rows are read neighbour-major, [K, B, dim]: the chip lays that
        # out as the gather wrote it, [B, K, dim] costs it a relayout
        nbr_rows = bucketize_ids(nbr_ids, self.max_id + 1).T
        if self.is_mutable_collection("cache"):
            rows = bucketize_ids(ids, self.max_id + 1)
            with jax.named_scope("read"):
                old = jnp.take(cache.value, rows, axis=0)
            if not self.is_initializing():
                # what a skipped step puts back (layers.undo_collection):
                # rows may repeat, every duplicate carries the same old row
                self.sow(undo_collection("cache"), "h", (rows, old),
                         reduce_fn=lambda _, record: record,
                         init_fn=lambda: None)
            with jax.named_scope("write"):
                upd = _ema_update(old.astype(jnp.float32), fresh,
                                  self.decay).astype(self.dtype)
            cache.value, nbr_h = _cache_write_then_read(cache.value, rows,
                                                        upd, nbr_rows)
        else:
            # eval/infer apply the module with the cache frozen; historical
            # activations are read-only there (reference ScalableGCNEncoder
            # only updates stores inside the training op).
            with jax.named_scope("read"):
                nbr_h = jnp.take(cache.value, nbr_rows, axis=0)
        return jnp.swapaxes(nbr_h, 0, 1).astype(jnp.float32)


def _store_then_neighbors(enc: nn.Module, layer: int, ids: Array,
                          fresh: Array, nbr_ids: Array) -> Array:
    """The batch's fresh layer-`layer` input activations go into
    cache_<layer>; its neighbours' [B, K, dim] come back out of it."""
    store = _ScalableCache(enc.max_id, enc.dim, dtype=enc.cache_dtype,
                           decay=enc.store_decay, name=f"cache_{layer}")
    if enc.is_mutable_collection("cache"):
        # traced_paths_total{path="act_cache_fused",detail=<encoder>}:
        # one a cache layer whose write and read are one operation
        from euler_tpu import obs

        obs.traced_path("act_cache_fused", type(enc).__name__)
    with jax.named_scope("cache"):
        return store(ids, fresh, nbr_ids)


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
        h_self, nbr_h = x, nbr_x
        for layer in range(self.num_layers):
            w = nn.Dense(self.dim, use_bias=False, name=f"w_{layer}")
            both = jnp.concatenate([h_self[:, None, :], nbr_h], axis=1)
            h_self = w(both.mean(axis=1))
            if layer < self.num_layers - 1:
                h_self = nn.relu(h_self)
                nbr_h = _store_then_neighbors(self, layer + 1, ids, h_self,
                                              nbr_ids)
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
        h_self, nbr_h = x, nbr_x
        for layer in range(self.num_layers):
            h_cat = jnp.concatenate([h_self, nbr_h.mean(axis=1)], axis=-1)
            h_self = nn.Dense(self.dim, name=f"w_{layer}")(h_cat)
            if layer < self.num_layers - 1:
                h_self = nn.relu(h_self)
                nbr_h = _store_then_neighbors(self, layer + 1, ids, h_self,
                                              nbr_ids)
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
    depth (LSTM gating) over a fanout. `neighbor_major` as SageEncoder's."""

    dim: int
    fanouts: Sequence[int]
    neighbor_major: bool = False

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
                nbr, axis = _hop_neighbors(hidden[hop + 1], x,
                                           self.neighbor_major)
                pooled = att(jnp.concatenate(
                    [jnp.expand_dims(x, axis), nbr], axis=axis), axis)
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
