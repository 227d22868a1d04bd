"""Dense neighborhood aggregators for the fanout/encoder path.

Parity: tf_euler/python/utils/aggregators.py:25-117 (Mean, MeanPool,
MaxPool, GCN aggregators). TPU-first: these operate on regular [B, K, D]
sampled-neighbor tensors — pure dense reductions + matmuls, no scatter at
all, which is the shape the MXU/VPU wants. This is the primary scalable
path (the reference's encoders use exactly these).

Every aggregator takes the neighbours as `encoders._hop_neighbors` views
them and reduces over the slot axis it names: `axis` 1 for [B, K, D]
(the default), 0 for the neighbour-major [K, B, D].
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

Array = jax.Array

__all__ = ["MeanAggregator", "MeanPoolAggregator", "MaxPoolAggregator",
           "GCNAggregator", "get_aggregator", "mean_with_self"]


def mean_with_self(x: Array, nbr: Array, axis: int = 1) -> Array:
    """The mean over a node's own row and its K slots, [B, D]: the slots
    summed along `axis`, so no [B, K+1, D] is built to hold both."""
    return (x + nbr.sum(axis=axis)) / (nbr.shape[axis] + 1)


class MeanAggregator(nn.Module):
    """concat(W_self x, W_nbr mean_k(nbr)) → [B, 2*dim] (or sum if concat=False)."""

    dim: int
    activation: str = "relu"
    concat: bool = True

    @nn.compact
    def __call__(self, x: Array, nbr: Array, axis: int = 1) -> Array:
        act = getattr(nn, self.activation) if self.activation else (lambda v: v)
        h_self = act(nn.Dense(self.dim, name="self")(x))
        h_nbr = act(nn.Dense(self.dim, name="nbr")(nbr.mean(axis=axis)))
        if self.concat:
            return jnp.concatenate([h_self, h_nbr], axis=-1)
        return h_self + h_nbr


class MeanPoolAggregator(nn.Module):
    """MLP per neighbor then mean-pool, concat with self transform."""

    dim: int
    activation: str = "relu"
    concat: bool = True

    @nn.compact
    def __call__(self, x: Array, nbr: Array, axis: int = 1) -> Array:
        act = getattr(nn, self.activation) if self.activation else (lambda v: v)
        h_self = act(nn.Dense(self.dim, name="self")(x))
        pooled = act(nn.Dense(self.dim, name="mlp")(nbr)).mean(axis=axis)
        h_nbr = act(nn.Dense(self.dim, name="nbr")(pooled))
        if self.concat:
            return jnp.concatenate([h_self, h_nbr], axis=-1)
        return h_self + h_nbr


class MaxPoolAggregator(nn.Module):
    """MLP per neighbor then max-pool, concat with self transform."""

    dim: int
    activation: str = "relu"
    concat: bool = True

    @nn.compact
    def __call__(self, x: Array, nbr: Array, axis: int = 1) -> Array:
        act = getattr(nn, self.activation) if self.activation else (lambda v: v)
        h_self = act(nn.Dense(self.dim, name="self")(x))
        pooled = act(nn.Dense(self.dim, name="mlp")(nbr)).max(axis=axis)
        h_nbr = act(nn.Dense(self.dim, name="nbr")(pooled))
        if self.concat:
            return jnp.concatenate([h_self, h_nbr], axis=-1)
        return h_self + h_nbr


class GCNAggregator(nn.Module):
    """W · mean(concat(x, nbr)) — single shared transform, GCN-style
    (`concat` is the other aggregators' field, SageEncoder's to pass:
    one transform has nothing to concatenate)."""

    dim: int
    activation: str = "relu"
    concat: bool = True

    @nn.compact
    def __call__(self, x: Array, nbr: Array, axis: int = 1) -> Array:
        act = getattr(nn, self.activation) if self.activation else (lambda v: v)
        return act(nn.Dense(self.dim, name="w")(
            mean_with_self(x, nbr, axis)))


_AGGREGATORS = {
    "mean": MeanAggregator,
    "meanpool": MeanPoolAggregator,
    "maxpool": MaxPoolAggregator,
    "gcn": GCNAggregator,
}


def get_aggregator(name: str):
    try:
        return _AGGREGATORS[name.lower()]
    except KeyError:
        raise ValueError(
            f"unknown aggregator {name!r}; options: {sorted(_AGGREGATORS)}"
        ) from None
