"""Plain reference of the device-sampled GraphSAGE configuration:
multi-hop neighbour draw, int8 feature rows dequantised, mean-aggregator
encoder (Hamilton et al. 2017, as OGB's / PyG's GraphSAGE baseline with
the concat variant upstream Euler's SageEncoder uses), dense output,
softmax cross-entropy. Reads the configuration's `model.kwargs`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common


def param_shapes(cfg: dict) -> dict:
    kw = cfg["model"]["kwargs"]
    dim, hops = kw["dim"], len(kw["fanouts"])
    shapes = {}
    for depth in range(hops):
        width = cfg["feature_dim"] if depth == 0 else 2 * dim
        for part in ("self", "nbr"):
            base = f"encoder/enc/agg_{depth}/{part}"
            shapes[base + "/kernel"] = (width, dim)
            shapes[base + "/bias"] = (dim,)
    shapes["out/kernel"] = (2 * dim, cfg["num_classes"])
    shapes["out/bias"] = (cfg["num_classes"],)
    return shapes


def init_extra(cfg: dict, n_rows: int) -> dict:
    return {}


def loss(params, extra, tables, roots, sample_seed, cfg, uniform, dtype):
    """-> (loss, extra). Hop h holds batch * prod(fanouts[:h]) rows;
    layer `depth` maps hop h's features and the mean of its hop h+1
    neighbours to concat(relu(W_self x), relu(W_nbr mean))."""
    fanouts = cfg["model"]["kwargs"]["fanouts"]
    key = common.step_key(sample_seed)
    rows, cur = [roots], roots
    for k in fanouts:
        key, sub = jax.random.split(key)
        cur = common.draw(tables["nbr"], tables["cum"], cur, int(k), sub,
                          uniform)
        rows.append(cur)
    hidden = [common.dequantize(tables["q"], tables["scale"], r, dtype)
              for r in rows]
    for depth in range(len(fanouts)):
        base = f"encoder/enc/agg_{depth}"
        nxt = []
        for hop in range(len(fanouts) - depth):
            x = hidden[hop]
            nb = hidden[hop + 1].reshape(x.shape[0], -1, x.shape[1])
            h_self = jax.nn.relu(common.dense(x, params, base + "/self",
                                              dtype))
            h_nbr = jax.nn.relu(common.dense(nb.mean(axis=1), params,
                                             base + "/nbr", dtype))
            nxt.append(jnp.concatenate([h_self, h_nbr], axis=-1))
        hidden = nxt
    logits = common.dense(hidden[0], params, "out", dtype)
    classes = jnp.take(tables["cls"], roots)
    return common.softmax_xent(logits, classes), extra
