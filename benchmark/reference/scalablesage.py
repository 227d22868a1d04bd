"""Plain reference of the activation-cache GraphSAGE configuration
(upstream Euler's ScalableSageEncoder): ONE sampled hop; layer 0 sees
raw features, deeper layers read their neighbours' activations from a
per-node cache [N+1, dim] that this step's roots write first: a row never
written takes the fresh activation whole, a written row the moving
average decay * old + (1 - decay) * fresh. The cache is stored in the
dtype the configuration states; the gradient runs through the write.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common


def param_shapes(cfg: dict) -> dict:
    kw = cfg["model"]["kwargs"]
    dim = kw["dim"]
    shapes = {}
    for layer in range(kw["num_layers"]):
        width = cfg["feature_dim"] if layer == 0 else dim
        shapes[f"encoder/w_{layer}/kernel"] = (2 * width, dim)
        shapes[f"encoder/w_{layer}/bias"] = (dim,)
    shapes["out/kernel"] = (dim, cfg["num_classes"])
    shapes["out/bias"] = (cfg["num_classes"],)
    return shapes


def _cache_dtype(cfg):
    return jnp.dtype(cfg["model"]["kwargs"]["cache_dtype"])


def init_extra(cfg: dict, n_rows: int) -> dict:
    kw = cfg["model"]["kwargs"]
    return {f"cache/encoder/cache_{layer}/h":
            jnp.zeros((n_rows, kw["dim"]), _cache_dtype(cfg))
            for layer in range(1, kw["num_layers"])}


def loss(params, extra, tables, roots, sample_seed, cfg, uniform, dtype):
    kw = cfg["model"]["kwargs"]
    k, dim, decay = int(kw["fanout"]), kw["dim"], kw["store_decay"]
    b = roots.shape[0]
    nbr = common.draw(tables["nbr"], tables["cum"], roots, k,
                      common.step_key(sample_seed), uniform)
    x = common.dequantize(tables["q"], tables["scale"], roots, dtype)
    nbr_x = common.dequantize(tables["q"], tables["scale"], nbr, dtype)
    extra = dict(extra)
    h = x
    for layer in range(kw["num_layers"]):
        if layer == 0:
            nbr_h = nbr_x.reshape(b, k, -1)
        else:
            name = f"cache/encoder/cache_{layer}/h"
            nbr_h = jnp.take(extra[name], nbr, axis=0) \
                .astype(dtype).reshape(b, k, dim)
        h_cat = jnp.concatenate([h, nbr_h.mean(axis=1)], axis=-1)
        h = common.dense(h_cat, params, f"encoder/w_{layer}", dtype)
        if layer < kw["num_layers"] - 1:
            h = jax.nn.relu(h)
            name = f"cache/encoder/cache_{layer + 1}/h"
            old = jnp.take(extra[name], roots, axis=0).astype(dtype)
            seen = jnp.any(old != 0, axis=-1, keepdims=True)
            upd = jnp.where(seen, decay * old + (1 - decay) * h, h)
            extra[name] = extra[name].at[roots].set(
                upd.astype(extra[name].dtype))
    logits = common.dense(h, params, "out", dtype)
    classes = jnp.take(tables["cls"], roots)
    return common.softmax_xent(logits, classes), extra
