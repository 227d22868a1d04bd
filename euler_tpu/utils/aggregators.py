"""Dense neighborhood aggregators for the fanout/encoder path.

Parity: tf_euler/python/utils/aggregators.py:25-117 (Mean, MeanPool,
MaxPool, GCN aggregators). TPU-first: these operate on regular [B, K, D]
sampled-neighbor tensors — pure dense reductions + matmuls, no scatter at
all, which is the shape the MXU/VPU wants. This is the primary scalable
path (the reference's encoders use exactly these).

Every aggregator takes the neighbours as `encoders._hop_neighbors` views
them and reduces over the slot axis it names: `axis` 1 for [B, K, D]
(the default), 0 for the neighbour-major [K, B, D].

LANE PARTS. A hop that is only ever read as `nbr` (the deepest hop a
depth of `SageEncoder` writes) need not exist at its full width: with
`apart=True` a concatenating aggregator returns `(h_self, h_nbr)`, each
[B, dim], in place of their [B, 2*dim] concat. A tuple of parts stands
for the concat of its members along the last axis, in order, and only
`SageEncoder` hands one over, as the next depth's `nbr` (viewed part by
part by `_hop_neighbors`). A consumer that reduces `nbr` linearly over
the slots before anything else (`MeanAggregator`, `mean_with_self`)
reduces each part and concatenates the [B, D_part] results: the same
sums in the same slot order in every lane, and the K-times larger
concat is never written, read back, or given a cotangent. The pooling
aggregators' per-neighbour Dense needs whole rows: they concatenate the
parts first.
"""

from __future__ import annotations

from typing import Callable, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

Array = jax.Array
# one array, or the lane parts whose concat along the last axis it is
Parts = Union[Array, Tuple[Array, ...]]

__all__ = ["MeanAggregator", "MeanPoolAggregator", "MaxPoolAggregator",
           "GCNAggregator", "get_aggregator", "mean_with_self"]


def _over_slots(reduce: Callable, nbr: Parts, axis: int,
                aggregator: str) -> Array:
    """`reduce(nbr, axis=axis)` for a reduction that acts lane by lane:
    a tuple of lane parts is reduced part by part and the small results
    concatenated. Counted at trace time, one a depth whose deepest hop
    stayed in its lane parts:
    `traced_paths_total{path="sage_hop_parts",detail=<aggregator>}`."""
    if not isinstance(nbr, tuple):
        return reduce(nbr, axis=axis)
    from euler_tpu import obs

    obs.traced_path("sage_hop_parts", aggregator)
    return jnp.concatenate([reduce(p, axis=axis) for p in nbr], axis=-1)


def _whole(nbr: Parts) -> Array:
    """The array a tuple of lane parts stands for."""
    return jnp.concatenate(nbr, axis=-1) if isinstance(nbr, tuple) else nbr


def _join(h_self: Array, h_nbr: Array, concat: bool, apart: bool) -> Parts:
    """The two transforms as one output: their sum, their concat, or,
    where the caller will only reduce it over slots (`apart`), the
    concat's two lane parts."""
    if not concat:
        return h_self + h_nbr
    if apart:
        return h_self, h_nbr
    return jnp.concatenate([h_self, h_nbr], axis=-1)


def mean_with_self(x: Array, nbr: Parts, axis: int = 1) -> Array:
    """The mean over a node's own row and its K slots, [B, D]: the slots
    summed along `axis`, so no [B, K+1, D] is built to hold both. `nbr`
    may be a tuple of lane parts (module docstring): each is summed and
    the [B, D_part] sums concatenated."""
    k = jax.tree_util.tree_leaves(nbr)[0].shape[axis]
    return (x + _over_slots(jnp.sum, nbr, axis, "gcn")) / (k + 1)


class MeanAggregator(nn.Module):
    """concat(W_self x, W_nbr mean_k(nbr)) → [B, 2*dim] (or sum if
    concat=False). `nbr` is [B, K, D] / [K, B, D] or a tuple of lane
    parts of it, averaged part by part; `apart=True` returns the concat's
    parts `(h_self, h_nbr)` (module docstring; `SageEncoder` sets it from
    the hop's position, nobody else)."""

    dim: int
    activation: str = "relu"
    concat: bool = True

    @nn.compact
    def __call__(self, x: Array, nbr: Parts, axis: int = 1,
                 apart: bool = False) -> Parts:
        act = getattr(nn, self.activation) if self.activation else (lambda v: v)
        h_self = act(nn.Dense(self.dim, name="self")(x))
        h_nbr = act(nn.Dense(self.dim, name="nbr")(
            _over_slots(jnp.mean, nbr, axis, "mean")))
        return _join(h_self, h_nbr, self.concat, apart)


class MeanPoolAggregator(nn.Module):
    """MLP per neighbor then mean-pool, concat with self transform.
    `nbr` / `apart` as MeanAggregator's, but the MLP needs whole rows:
    lane parts are concatenated first."""

    dim: int
    activation: str = "relu"
    concat: bool = True

    @nn.compact
    def __call__(self, x: Array, nbr: Parts, axis: int = 1,
                 apart: bool = False) -> Parts:
        act = getattr(nn, self.activation) if self.activation else (lambda v: v)
        h_self = act(nn.Dense(self.dim, name="self")(x))
        pooled = act(nn.Dense(self.dim, name="mlp")(
            _whole(nbr))).mean(axis=axis)
        h_nbr = act(nn.Dense(self.dim, name="nbr")(pooled))
        return _join(h_self, h_nbr, self.concat, apart)


class MaxPoolAggregator(nn.Module):
    """MLP per neighbor then max-pool, concat with self transform.
    `nbr` / `apart` as MeanPoolAggregator's."""

    dim: int
    activation: str = "relu"
    concat: bool = True

    @nn.compact
    def __call__(self, x: Array, nbr: Parts, axis: int = 1,
                 apart: bool = False) -> Parts:
        act = getattr(nn, self.activation) if self.activation else (lambda v: v)
        h_self = act(nn.Dense(self.dim, name="self")(x))
        pooled = act(nn.Dense(self.dim, name="mlp")(
            _whole(nbr))).max(axis=axis)
        h_nbr = act(nn.Dense(self.dim, name="nbr")(pooled))
        return _join(h_self, h_nbr, self.concat, apart)


class GCNAggregator(nn.Module):
    """W · mean(concat(x, nbr)) — single shared transform, GCN-style
    (`concat` and `apart` are the other aggregators', SageEncoder's to
    pass: one transform has nothing to concatenate or keep apart)."""

    dim: int
    activation: str = "relu"
    concat: bool = True

    @nn.compact
    def __call__(self, x: Array, nbr: Parts, axis: int = 1,
                 apart: bool = False) -> Array:
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
