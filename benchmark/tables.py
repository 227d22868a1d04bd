"""Graph tables made from the seed, on the host, in row chunks.

The device path of euler_tpu consumes four dense tables with a trailing
pad row N: neighbours [N+1, cap] int32, inclusive cumulative edge weights
[N+1, cap] float32, features [N+1, D] and one-hot labels [N+1, classes].
They are generated vectorised: power-law degrees clipped to the cap,
front-packed uniformly random neighbours, class-correlated gaussian
features (a weak signal, so the loss stays a number worth watching).
Every chunk has a generator of its own, keyed by (seed, chunk), so the
tables do not depend on how many threads fill them and the host holds
only chunk-sized transients beside them.

Copied in spirit from chip_smoke.make_tables (PR 21), which ran on the
chip; that one stays where it is for a later PR to delete.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 262_144
THREADS = 8   # numpy's generators and copies release the interpreter lock
_CENTER_STREAM = 1 << 20   # generator key of the class centres


def make_tables(seed: int, n_nodes: int, feat_dim: int, cap: int,
                num_classes: int, edge_weights: dict,
                chunk_rows: int = CHUNK_ROWS) -> dict:
    """edge_weights: {"kind": "unit"} or {"kind": "integer", "low": a,
    "high": b} (inclusive). Returns nbr, cum, feat (float32), cls (int32
    class of each real row), deg and edge_count; the one-hot label table
    is made by `one_hot_labels` when the program needs it."""
    n = int(n_nodes)
    kind = edge_weights["kind"]
    if kind not in ("unit", "integer"):
        raise ValueError(f"unknown edge weight kind {kind!r}")
    nbr = np.full((n + 1, cap), n, np.int32)
    cum = np.zeros((n + 1, cap), np.float32)
    feat = np.zeros((n + 1, feat_dim), np.float32)
    cls = np.zeros(n, np.int32)
    deg = np.zeros(n, np.int32)
    centers = 0.15 * np.random.default_rng(
        [int(seed), _CENTER_STREAM]).standard_normal(
            (num_classes, feat_dim), dtype=np.float32)
    cols = np.arange(cap)[None, :]

    def fill(c_lo):
        c, lo = c_lo
        hi = min(lo + chunk_rows, n)
        m = hi - lo
        rng = np.random.default_rng([int(seed), c])
        d = np.clip((rng.pareto(1.2, m) * 25).astype(np.int64) + 1, 1, cap)
        slot = cols < d[:, None]
        nbr[lo:hi] = np.where(
            slot, rng.integers(0, n, (m, cap), dtype=np.int32), np.int32(n))
        w = slot.astype(np.float32)
        if kind == "integer":
            w *= rng.integers(edge_weights["low"], edge_weights["high"] + 1,
                              (m, cap)).astype(np.float32)
        np.cumsum(w, axis=1, out=cum[lo:hi])
        k = rng.integers(0, num_classes, m).astype(np.int32)
        f = rng.standard_normal((m, feat_dim), dtype=np.float32)
        f += centers[k]
        feat[lo:hi] = f
        cls[lo:hi] = k
        deg[lo:hi] = d

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, enumerate(range(0, n, chunk_rows))))
    return {"nbr": nbr, "cum": cum, "feat": feat, "cls": cls, "deg": deg,
            "edge_count": int(deg.sum(dtype=np.int64))}


def one_hot_labels(cls: np.ndarray, num_classes: int) -> np.ndarray:
    """[N+1, classes] float32 one-hot rows, the pad row all zero: the
    label table as DeviceFeatureStore holds it."""
    n = cls.shape[0]
    label = np.zeros((n + 1, num_classes), np.float32)

    def fill(lo):
        hi = min(lo + CHUNK_ROWS, n)
        label[np.arange(lo, hi), cls[lo:hi]] = 1.0

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(0, n, CHUNK_ROWS)))
    return label
