"""Plain reference of the device-sampled graph-attention configuration:
multi-hop neighbour draw, int8 feature rows dequantised, three GATConv
layers with linear skips as PyG's examples/ogbn_products_gat.py stacks
them (Velickovic et al. 2018), softmax cross-entropy on the last layer's
own output. Reads the configuration's `model.kwargs` (`dim` = one head's
hidden width, `heads`, `fanouts`).

One layer, for a target i with its k sampled slots N(i), H heads of
width C, written head by head with no fused layout:

    z = x W                                   (no bias; sources and targets)
    e_ij^h = LeakyReLU_0.2(a_src^h . z_j^h + a_dst^h . z_i^h), j in N(i) U {i}
    alpha_i.^h = softmax_j e_ij^h
    o_i^h = sum_j alpha_ij^h z_j^h
    y_i = concat_h o_i^h + b, x_i' = ELU(y_i + x_i S + s)      (hidden layers)
    logits_i = mean_h o_i^h + b + x_i S + s                     (last layer)

applied with shared weights to every (hop h, hop h+1) pair the depth
needs. Departures from the PyG file, the same in the program:
  - dropout (features 0.5, attention 0) is off;
  - the target's own term is added in the dense layout, not as a
    self-loop edge; PyG's bipartite GATConv adds that edge too;
  - the draw is with replacement, so a neighbour drawn twice counts
    twice in the softmax and in the sum (PyG's NeighborSampler draws
    without replacement);
  - a pad slot (a neighbour of a node that has none) takes no weight;
  - log_softmax + nll is written as softmax cross-entropy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common

NEGATIVE_SLOPE = 0.2


def param_shapes(cfg: dict) -> dict:
    kw = cfg["model"]["kwargs"]
    dim, heads, hops = kw["dim"], kw["heads"], len(kw["fanouts"])
    shapes = {}
    for depth in range(hops):
        last = depth == hops - 1
        d_in = cfg["feature_dim"] if depth == 0 else heads * dim
        c = cfg["num_classes"] if last else dim
        d_out = c if last else heads * c
        base = f"encoder/enc/layer{depth}"
        shapes[base + "/proj/kernel"] = (d_in, heads * c)
        # leaves named `kernel` whose first axis is the head's width:
        # common.lecun_normal seeds by leaf name and zeroes the rest
        shapes[base + "/att_src/kernel"] = (c, heads)
        shapes[base + "/att_dst/kernel"] = (c, heads)
        shapes[base + "/bias"] = (d_out,)
        shapes[base + "/skip/kernel"] = (d_in, d_out)
        shapes[base + "/skip/bias"] = (d_out,)
    return shapes


def init_extra(cfg: dict, n_rows: int) -> dict:
    return {}


def layer(params, base: str, x_t, x_s, valid, heads: int, last: bool,
          dtype):
    """x_t [M, D] targets, x_s [M, k, D] their slots' rows, valid bool
    [M, k] (False: a pad slot) -> [M, H*C] through ELU, or the last
    layer's [M, C]."""
    w = params[base + "/proj/kernel"].astype(dtype)
    a_src = params[base + "/att_src/kernel"].astype(dtype)
    a_dst = params[base + "/att_dst/kernel"].astype(dtype)
    c = w.shape[1] // heads
    z_t, z_s = x_t @ w, x_s @ w
    heads_out = []
    for h in range(heads):
        zt_h = z_t[:, h * c:(h + 1) * c]                  # [M, C]
        zs_h = z_s[:, :, h * c:(h + 1) * c]               # [M, k, C]
        dst = zt_h @ a_dst[:, h]                          # [M]
        e_self = jax.nn.leaky_relu(zt_h @ a_src[:, h] + dst,
                                   NEGATIVE_SLOPE)
        e_nbr = jax.nn.leaky_relu(zs_h @ a_src[:, h] + dst[:, None],
                                  NEGATIVE_SLOPE)
        e = jnp.concatenate(
            [e_self[:, None], jnp.where(valid, e_nbr, -jnp.inf)], axis=1)
        alpha = jax.nn.softmax(e, axis=1)                 # [M, 1 + k]
        heads_out.append(alpha[:, :1] * zt_h
                         + (alpha[:, 1:, None] * zs_h).sum(axis=1))
    if last:
        y = sum(heads_out) / heads
    else:
        y = jnp.concatenate(heads_out, axis=-1)
    y = y + params[base + "/bias"].astype(dtype) \
        + common.dense(x_t, params, base + "/skip", dtype)
    return y if last else jax.nn.elu(y)


def loss(params, extra, tables, roots, sample_seed, cfg, uniform, dtype):
    """-> (loss, extra). Hop h holds batch * prod(fanouts[:h]) rows,
    hop h+1's rows m*k .. m*k+k-1 being the slots of hop h's row m."""
    kw = cfg["model"]["kwargs"]
    fanouts, heads = kw["fanouts"], kw["heads"]
    pad = tables["nbr"].shape[0] - 1
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
        last = depth == len(fanouts) - 1
        nxt = []
        for hop in range(len(fanouts) - depth):
            x_t = hidden[hop]
            m = x_t.shape[0]
            nxt.append(layer(
                params, f"encoder/enc/layer{depth}", x_t,
                hidden[hop + 1].reshape(m, -1, x_t.shape[1]),
                (rows[hop + 1] != pad).reshape(m, -1), heads, last, dtype))
        hidden = nxt
    classes = jnp.take(tables["cls"], roots)
    return common.softmax_xent(hidden[0], classes), extra
