"""Plain reference of the MAG240M baseline configuration: the
homogeneous neighbour-sampled GAT of OGB-LSC's
examples/lsc/mag240m/gnn.py (`--model gat`; Hu et al., arXiv:2103.09430):
multi-hop neighbour draw, int8 feature rows dequantised, GATConv layers
that CONCATENATE their heads, each with a linear skip, a BatchNorm1d and
ELU, an MLP head Linear-BatchNorm1d-ReLU-Linear, softmax cross-entropy.
Reads the configuration's `model.kwargs` (`dim` = one head's width,
`heads`, `fanouts`, `head_dim`). Nothing here imports euler_tpu.

Layer l, for a target i with its k sampled slots N(i), H heads of width
C, written head by head with no fused layout:

    z = h W                                   (no bias; sources and targets)
    e_ij^h = LeakyReLU_0.2(a_src^h . z_j^h + a_dst^h . z_i^h), j in N(i) U {i}
    alpha_i.^h = softmax_j e_ij^h
    m_i = concat_h sum_j alpha_ij^h z_j^h + b
    s_i = m_i + h_i S + c
    y_i = (1 + g) (s_i - mu) / sqrt(var + 1e-5) + beta
    h_i' = ELU(y_i)

applied with shared weights to every (hop h, hop h+1) pair the depth
needs; mu and var (biased) are per channel over ALL of the layer's
target rows, the pairs' outputs stacked into one array first, as PyG
normalises the one array that holds a layer's targets. Then

    logits = ReLU(BN(h_root W_a + b_a)) W_b + b_b      (BN over the roots)

The running statistics are the step's state (`extra`): mean <- 0.9 old
+ 0.1 mu, var <- 0.9 old + 0.1 var n / (n - 1), PyTorch's BatchNorm1d
at momentum 0.1. They do not enter a training step's loss.

Departures from the OGB file, the same in the program:
  - dropout (0.5 after every layer and in the head) is off;
  - a layer runs on every pair of hops, so the roots aggregate their 25
    hop-1 rows at both layers, where PyG's layer 0 hands the roots 15
    freshly drawn ones of the deepest adjacency;
  - the target's own term is added in the dense layout, not as a
    self-loop edge; the draw is with replacement, so a neighbour drawn
    twice counts twice in the softmax, in the sum and in the norm's
    statistics (it is two rows of the layer's output);
  - a pad slot (a neighbour of a node that has none) takes no weight,
    and its row is left out of the statistics;
  - the norm's gain is stored as its offset g from one (the harness
    zeroes every leaf that is not a `kernel`), and the running variance
    starts at 0 (the harness zeroes the state), PyTorch's at 1;
  - log_softmax + nll is written as softmax cross-entropy; the StepLR
    schedule is outside any window the benchmark runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common

NEGATIVE_SLOPE = 0.2
EPSILON = 1e-5
MOMENTUM = 0.1
ENC = "encoder/enc"
STATS = "batch_stats/" + ENC


def _norms(cfg: dict):
    """(name under the encoder, width) of every BatchNorm."""
    kw = cfg["model"]["kwargs"]
    wide = kw["heads"] * kw["dim"]
    return [(f"layer{d}/norm", wide) for d in range(len(kw["fanouts"]))] \
        + [("head/norm", kw["head_dim"])]


def param_shapes(cfg: dict) -> dict:
    kw = cfg["model"]["kwargs"]
    dim, heads, head_dim = kw["dim"], kw["heads"], kw["head_dim"]
    wide = heads * dim
    shapes = {}
    for depth in range(len(kw["fanouts"])):
        d_in = cfg["feature_dim"] if depth == 0 else wide
        base = f"{ENC}/layer{depth}"
        shapes[base + "/proj/kernel"] = (d_in, wide)
        # leaves named `kernel` whose first axis is the head's width:
        # common.lecun_normal seeds by leaf name and zeroes the rest
        shapes[base + "/att_src/kernel"] = (dim, heads)
        shapes[base + "/att_dst/kernel"] = (dim, heads)
        shapes[base + "/bias"] = (wide,)
        shapes[base + "/skip/kernel"] = (d_in, wide)
        shapes[base + "/skip/bias"] = (wide,)
    shapes[ENC + "/head/fc/kernel"] = (wide, head_dim)
    shapes[ENC + "/head/fc/bias"] = (head_dim,)
    shapes[ENC + "/head/out/kernel"] = (head_dim, cfg["num_classes"])
    shapes[ENC + "/head/out/bias"] = (cfg["num_classes"],)
    for name, width in _norms(cfg):
        shapes[f"{ENC}/{name}/gain_offset"] = (width,)
        shapes[f"{ENC}/{name}/bias"] = (width,)
    return shapes


def init_extra(cfg: dict, n_rows: int) -> dict:
    """The running statistics, all zero: the harness zeroes whatever the
    program's state carries beside its parameters, the variance too."""
    return {f"{STATS}/{name}/{stat}": jnp.zeros((width,), jnp.float32)
            for name, width in _norms(cfg) for stat in ("mean", "var")}


def batch_norm(params, extra, name: str, s, valid, dtype):
    """s [n, D] every target row of the layer, valid bool [n] (False: a
    pad row, left out of the statistics) -> (y [n, D], the norm's new
    running statistics)."""
    w = valid.astype(dtype)[:, None]
    n = valid.sum().astype(dtype)
    mu = (s * w).sum(axis=0) / n
    var = (jnp.square(s - mu) * w).sum(axis=0) / n
    gain = 1.0 + params[f"{ENC}/{name}/gain_offset"].astype(dtype)
    y = gain * (s - mu) / jnp.sqrt(var + EPSILON) \
        + params[f"{ENC}/{name}/bias"].astype(dtype)
    unbiased = var * n / jnp.maximum(n - 1.0, 1.0)
    new = {}
    for stat, batch in (("mean", mu), ("var", unbiased)):
        key = f"{STATS}/{name}/{stat}"
        new[key] = (1.0 - MOMENTUM) * extra[key] \
            + MOMENTUM * batch.astype(jnp.float32)
    return y, new


def pair(params, base: str, x_t, x_s, valid, heads: int, dtype):
    """x_t [M, D] targets, x_s [M, k, D] their slots' rows, valid bool
    [M, k] (False: a pad slot) -> s [M, H*C]: the heads concatenated,
    the layer's bias and the linear skip added, before the norm."""
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
    return jnp.concatenate(heads_out, axis=-1) \
        + params[base + "/bias"].astype(dtype) \
        + common.dense(x_t, params, base + "/skip", dtype)


def loss(params, extra, tables, roots, sample_seed, cfg, uniform, dtype,
         by_pair: bool = False):
    """-> (loss, the new running statistics). Hop h holds batch *
    prod(fanouts[:h]) rows, hop h+1's rows m*k .. m*k+k-1 being the
    slots of hop h's row m. `by_pair` is the planted fault of
    `benchmark/gat2bn_norm_by_pair.py`, never the reference: each pair's
    output normalised by its own statistics, one after the other."""
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
    real = [r != pad for r in rows]
    hidden = [common.dequantize(tables["q"], tables["scale"], r, dtype)
              for r in rows]
    extra = dict(extra)
    for depth in range(len(fanouts)):
        targets = list(range(len(fanouts) - depth))
        pre = []
        for hop in targets:
            x_t = hidden[hop]
            m = x_t.shape[0]
            pre.append(pair(
                params, f"{ENC}/layer{depth}", x_t,
                hidden[hop + 1].reshape(m, -1, x_t.shape[1]),
                real[hop + 1].reshape(m, -1), heads, dtype))
        # ONE normalisation over every target row the layer has
        hidden = []
        for group in ([[hop] for hop in targets] if by_pair else [targets]):
            y, stats = batch_norm(
                params, extra, f"layer{depth}/norm",
                jnp.concatenate([pre[hop] for hop in group]),
                jnp.concatenate([real[hop] for hop in group]), dtype)
            extra.update(stats)
            y = jax.nn.elu(y)
            for hop in group:
                hidden.append(y[:pre[hop].shape[0]])
                y = y[pre[hop].shape[0]:]
    h = common.dense(hidden[0], params, ENC + "/head/fc", dtype)
    h, stats = batch_norm(params, extra, "head/norm", h, real[0], dtype)
    extra.update(stats)
    logits = common.dense(jax.nn.relu(h), params, ENC + "/head/out", dtype)
    classes = jnp.take(tables["cls"], roots)
    return common.softmax_xent(logits, classes), extra
