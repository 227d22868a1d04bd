"""Plain reference of the device-sampled UniMP configuration (Shi et al.,
"Masked Label Prediction: Unified Message Passing Model for
Semi-Supervised Classification", arXiv:2009.03509): multi-hop neighbour
draw, int8 feature rows dequantised, the sampled neighbours' labels
added to their features where the step shows them (PyG's MaskLabel,
"add"), three TransformerConv(beta=True) layers with LayerNorm and ReLU
between them as PyG's examples/unimp_arxiv.py stacks them, softmax
cross-entropy on the last layer's own output. Reads the configuration's
`model.kwargs` (`dim` = one head's hidden width, `heads`, `fanouts`,
`label_rate`).

The input, for a sampled row r of hops 1..L (hop 0, the roots, gets no
label):

    h_r = x_r + s_r * (onehot(y_r) W_d)          W_d [classes, D], no bias
    s_r = 1 where the step shows r's label, else 0:
          mix(r XOR word) < floor(label_rate * 2**32), and r is no root
          of this step, and r is not the pad row
    word = 32 random bits of fold_in(the step's key, 0x1abe1)
    mix  = murmur3's 32-bit finaliser on uint32:
           x ^= x >> 16; x *= 0x85ebca6b; x ^= x >> 13; x *= 0xc2b2ae35;
           x ^= x >> 16

One layer, for a target i with its k sampled slots N(i), H heads of
width C, written head by head:

    q_i = W_q h_i + b_q;  k_j = W_k h_j + b_k;  v_j = W_v h_j + b_v
    alpha_ij^h = softmax_{j in N(i)} (q_i^h . k_j^h) / sqrt(C)
    m_i = concat_h sum_j alpha_ij^h v_j^h       (last layer: mean over h)
    r_i = W_r h_i + b_r
    beta_i = sigmoid(w_b . [r_i ; m_i ; r_i - m_i])
    o_i = beta_i r_i + (1 - beta_i) m_i
    h_i' = ReLU(LayerNorm(o_i))                 (last layer: logits_i = o_i)

applied with shared weights to every (hop h, hop h+1) pair the depth
needs. Departures from the paper and the PyG files, the same in the
program:
  - dropout (0.3) is off;
  - a label is shown a node and a step (the rule above) where the paper
    splits the training nodes once an epoch; a root's label is never
    shown to its own step, also where the root is drawn again as a
    neighbour;
  - the draw is with replacement, so a neighbour drawn twice counts
    twice in the softmax and in the sum;
  - a pad slot takes no weight, and a target whose slots are all pads
    aggregates zero (PyG: a node without in-edges does the same);
  - LayerNorm (eps 1e-5) keeps its gain as the offset from one:
    gain = 1 + gain_offset;
  - log_softmax + nll is written as softmax cross-entropy.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common

LABEL_STREAM = 0x1abe1
LN_EPS = 1e-5


def param_shapes(cfg: dict) -> dict:
    kw = cfg["model"]["kwargs"]
    dim, heads, hops = kw["dim"], kw["heads"], len(kw["fanouts"])
    # leaves named `kernel`: common.lecun_normal seeds by leaf name and
    # zeroes the rest (the biases, the norm's gain offset)
    shapes = {"encoder/label_emb/kernel":
              (cfg["num_classes"], cfg["feature_dim"])}
    for depth in range(hops):
        last = depth == hops - 1
        d_in = cfg["feature_dim"] if depth == 0 else heads * dim
        c = cfg["num_classes"] if last else dim
        d_out = c if last else heads * c
        base = f"encoder/enc/layer{depth}"
        for name, width in (("query", heads * c), ("key", heads * c),
                            ("value", heads * c), ("skip", d_out)):
            shapes[f"{base}/{name}/kernel"] = (d_in, width)
            shapes[f"{base}/{name}/bias"] = (width,)
        shapes[base + "/beta/kernel"] = (3 * d_out, 1)
        if not last:
            shapes[base + "/norm/gain_offset"] = (d_out,)
            shapes[base + "/norm/bias"] = (d_out,)
    return shapes


def init_extra(cfg: dict, n_rows: int) -> dict:
    return {}


def shown(rows, roots, word, rate: float, pad: int):
    """bool per sampled row: the step shows its label (the rule above)."""
    x = rows.astype(jnp.uint32) ^ word
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85ebca6b)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xc2b2ae35)
    x = x ^ (x >> 16)
    threshold = min(math.floor(rate * 2 ** 32), 2 ** 32 - 1)
    is_root = jnp.isin(rows, roots)
    return (x < jnp.uint32(threshold)) & ~is_root & (rows != pad)


def layer_norm(params, base: str, x, dtype):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    gain = 1.0 + params[base + "/gain_offset"].astype(dtype)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * gain \
        + params[base + "/bias"].astype(dtype)


def layer(params, base: str, x_t, x_s, valid, heads: int, last: bool,
          dtype):
    """x_t [M, D] targets, x_s [M, k, D] their slots' rows, valid bool
    [M, k] (False: a pad slot) -> [M, H*C] through LayerNorm and ReLU,
    or the last layer's [M, C]."""
    q = common.dense(x_t, params, base + "/query", dtype)
    k_s = common.dense(x_s, params, base + "/key", dtype)
    v_s = common.dense(x_s, params, base + "/value", dtype)
    c = q.shape[1] // heads
    heads_out = []
    for h in range(heads):
        lanes = slice(h * c, (h + 1) * c)
        e = (q[:, None, lanes] * k_s[:, :, lanes]).sum(axis=-1) \
            / math.sqrt(c)                                    # [M, k]
        # no term of the target's own; all pads: every weight 0
        e = jnp.where(valid, e, -jnp.inf)
        top = e.max(axis=1, keepdims=True)
        p = jnp.exp(e - jnp.where(jnp.isfinite(top), top, 0.0))
        total = p.sum(axis=1, keepdims=True)
        alpha = p / jnp.where(total > 0, total, 1.0)
        heads_out.append((alpha[:, :, None] * v_s[:, :, lanes]).sum(axis=1))
    if last:
        msg = sum(heads_out) / heads
    else:
        msg = jnp.concatenate(heads_out, axis=-1)
    r = common.dense(x_t, params, base + "/skip", dtype)
    w_b = params[base + "/beta/kernel"].astype(dtype)
    beta = jax.nn.sigmoid(jnp.concatenate([r, msg, r - msg], axis=-1) @ w_b)
    o = beta * r + (1.0 - beta) * msg
    return o if last else jax.nn.relu(layer_norm(params, base + "/norm",
                                                 o, dtype))


def loss(params, extra, tables, roots, sample_seed, cfg, uniform, dtype):
    """-> (loss, extra). Hop h holds batch * prod(fanouts[:h]) rows,
    hop h+1's rows m*k .. m*k+k-1 being the slots of hop h's row m."""
    kw = cfg["model"]["kwargs"]
    fanouts, heads, rate = kw["fanouts"], kw["heads"], kw["label_rate"]
    pad = tables["nbr"].shape[0] - 1
    step = common.step_key(sample_seed)
    word = jax.random.bits(jax.random.fold_in(step, LABEL_STREAM), (),
                           jnp.uint32)
    key = step
    rows, cur = [roots], roots
    for k in fanouts:
        key, sub = jax.random.split(key)
        cur = common.draw(tables["nbr"], tables["cum"], cur, int(k), sub,
                          uniform)
        rows.append(cur)
    hidden = [common.dequantize(tables["q"], tables["scale"], r, dtype)
              for r in rows]
    w_d = params["encoder/label_emb/kernel"].astype(dtype)
    for hop in range(1, len(rows)):
        r = rows[hop]
        onehot = jax.nn.one_hot(jnp.take(tables["cls"], r), w_d.shape[0],
                                dtype=dtype)
        s = shown(r, roots, word, rate, pad).astype(dtype)
        hidden[hop] = hidden[hop] + s[:, None] * (onehot @ w_d)
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
