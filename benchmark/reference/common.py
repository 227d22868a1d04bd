"""Plain pieces the references share: the stated storage arithmetic, the
two neighbour draws, softmax cross-entropy and Adam, in straightforward
numpy / jax.numpy. Nothing here imports euler_tpu.

`dtype` is the precision everything after the table reads is computed
in: float32 (under jax.default_matmul_precision("highest"), set by the
caller) for the reference, bfloat16 for the control.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

_BF16 = np.dtype(jnp.bfloat16)
_ROOT_KEY = 17   # the key every device-sampled model folds its seed into


def quantize_int8(feat: np.ndarray, chunk_rows: int = 262_144):
    """Features as the configuration states they are stored: rounded to
    bfloat16, then per-column symmetric int8 (scale = colmax|x| / 127,
    all-zero columns 1, q = rint(x / scale) clipped to +-127), the scale
    itself kept in bfloat16. Returns (q int8 [rows, D], scale bf16 [D]).
    Chunked: the transients stay chunk-sized."""
    starts = range(0, feat.shape[0], chunk_rows)

    def rounded(lo):
        return feat[lo:lo + chunk_rows].astype(_BF16).astype(np.float32)

    def fill(lo):
        q[lo:lo + chunk_rows] = np.clip(np.rint(rounded(lo) / scale),
                                        -127, 127)

    with ThreadPoolExecutor(8) as pool:
        top = np.max(list(pool.map(
            lambda lo: np.abs(rounded(lo)).max(axis=0), starts)), axis=0)
        scale = top.astype(np.float32) / np.float32(127.0)
        scale[scale == 0] = 1.0
        q = np.empty(feat.shape, np.int8)
        list(pool.map(fill, starts))
    return q, scale.astype(_BF16)


def dequantize(q_table, scale, rows, dtype):
    """q[rows] * scale: exact in float32 (7 bits times 8). The program
    serves the product in the scale's bfloat16, but XLA may keep excess
    precision through a fusion, so no rounding is stated here."""
    x = jnp.take(q_table, rows, axis=0).astype(jnp.float32)
    return (x * scale.astype(jnp.float32)).astype(dtype)


def step_key(sample_seed):
    return jax.random.fold_in(jax.random.key(_ROOT_KEY), sample_seed)


def draw(nbr_table, cum_table, rows, count: int, key, uniform: bool):
    """`count` neighbours of each of `rows`, with replacement: [n] ->
    [n * count]. uniform: the tables' rows carry unit weights, so the
    column is floor(u * degree), the degree being the row's non-pad
    slots. Otherwise inverse CDF over the row's inclusive cumulative
    weights: the column is the number of entries <= u * total. Rows of
    no weight give the pad row back (their neighbour entries are pad)."""
    n = rows.shape[0]
    pad = nbr_table.shape[0] - 1
    cap = nbr_table.shape[1]
    nbr = jnp.take(nbr_table, rows, axis=0)
    u = jax.random.uniform(key, (n, count))
    if uniform:
        deg = (nbr != pad).sum(-1)
        col = jnp.minimum(
            (u * deg[:, None].astype(jnp.float32)).astype(jnp.int32),
            jnp.maximum(deg[:, None] - 1, 0))
    else:
        cum = jnp.take(cum_table, rows, axis=0)
        u = u * cum[:, -1:]
        col = (cum[:, None, :] <= u[:, :, None]).sum(-1)
        col = jnp.clip(col, 0, cap - 1)
    return jnp.take_along_axis(nbr, col.astype(jnp.int32),
                               axis=1).reshape(-1)


def dense(x, params, name: str, dtype):
    return x @ params[name + "/kernel"].astype(dtype) \
        + params[name + "/bias"].astype(dtype)


def softmax_xent(logits, classes):
    """Mean over the batch of -log softmax(logits)[class], in the
    logits' own precision."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jnp.take_along_axis(logits, classes[:, None], axis=1)[:, 0]
    return (lse - hit).mean()


def lecun_normal(rng: np.random.Generator, shapes: dict) -> dict:
    """Seeded initial weights by leaf path: kernels N(0, 1/fan_in),
    biases zero, float32."""
    out = {}
    for path in sorted(shapes):
        shape = shapes[path]
        if path.endswith("/kernel"):
            out[path] = (rng.standard_normal(shape, dtype=np.float32)
                         / np.float32(np.sqrt(shape[0])))
        else:
            out[path] = np.zeros(shape, np.float32)
    return out


def adam_init(params: dict) -> dict:
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"t": jnp.zeros((), jnp.int32), "m": zeros, "v": dict(zeros)}


def adam_step(params: dict, grads: dict, opt: dict, lr: float,
              b1: float, b2: float, eps: float):
    t = opt["t"] + 1
    m = {k: b1 * opt["m"][k] + (1 - b1) * grads[k] for k in params}
    v = {k: b2 * opt["v"][k] + (1 - b2) * grads[k] ** 2 for k in params}
    new = {k: params[k] - lr * (m[k] / (1 - b1 ** t))
           / (jnp.sqrt(v[k] / (1 - b2 ** t)) + eps) for k in params}
    return new, {"t": t, "m": m, "v": v}
