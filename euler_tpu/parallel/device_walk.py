"""Device-resident random walks, skip-gram pair generation, and negative
sampling — the TPU-first input path for the walk/unsupervised model
family (DeepWalk / node2vec / LINE / unsupervised GraphSAGE).

The reference runs walks on the graph engine (random_walk_op.cc:34-172:
per-node neighbor queries + client-side p/q bias) and generates pairs on
the host (gen_pair_op.cc:28). On TPU that re-creates the host feeder
bottleneck the device sampler removed for the supervised path: measured
on v5e-1, the jitted skip-gram step runs orders of magnitude faster than
a 1-2 core host can walk. With the DeviceNeighborTable already in HBM, a
walk is just `walk_len` chained single-neighbor draws; pairs are static
index arithmetic; negatives are an inverse-CDF draw over a node-weight
table — all VPU work inside the jitted step, composing with lax.scan
(steps_per_loop) and pjit.

Fidelity notes:
  - walks draw from the capped neighbor table, so hub nodes walk over
    the same weighted C-subset the supervised device sampler uses;
  - node2vec's second-order p/q bias is computed EXACTLY over the capped
    table: membership of each candidate in the previous node's kept
    neighbor row (C x C compares on the VPU — the reference computes the
    same bias from two full-neighbor queries, random_walk_op.cc:70-110);
  - dead ends stick at pad_row, and pad-touching pairs are masked out of
    the loss (the host path trains default_id=0 on dead ends — the
    device path is strictly cleaner).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from euler_tpu.parallel.device_sampler import (
    sample_hop, slot_weights, stored_info, take_rows,
)


class DeviceNodeSampler:
    """Weighted global node sampling on device (negatives, root pools).

    The device transpose of the engine's per-type FastWeightedCollection
    (reference euler/common/fast_weighted_collection.h:28): a row pool +
    inclusive cumulative weights; draws are uniform * total -> one
    searchsorted (log N) per sample.
    """

    def __init__(self, graph, node_type: int = -1,
                 mesh: Optional[jax.sharding.Mesh] = None):
        ids = graph.all_node_ids()
        types = graph.get_node_type(ids)
        rows = np.arange(len(ids), dtype=np.int32)
        w = graph.all_node_weights()
        if node_type >= 0:
            keep = types == node_type
            rows, w = rows[keep], w[keep]
        self.pool = rows
        cum = np.cumsum(w, dtype=np.float32)
        from euler_tpu.parallel.placement import put_replicated

        self.rows = put_replicated(rows, mesh)
        self.cum = put_replicated(cum, mesh)

    @property
    def tables(self):
        return {"neg_rows": self.rows, "neg_cum": self.cum}


def sample_global_rows(pool_rows: jax.Array, pool_cum: jax.Array,
                       key, shape: Tuple[int, ...]) -> jax.Array:
    """Weighted draw of `shape` rows from a (pool, cum) node sampler."""
    total = pool_cum[-1]
    u = jax.random.uniform(key, shape) * total
    idx = jnp.searchsorted(pool_cum, u)
    idx = jnp.clip(idx, 0, pool_rows.shape[0] - 1)
    return jnp.take(pool_rows, idx)


def walk_rows(nbr_table: jax.Array, cum_table: jax.Array,
              roots: jax.Array, walk_len: int, key,
              p: float = 1.0, q: float = 1.0,
              gather=None, uniform: bool = False,
              alias_table=None) -> jax.Array:
    """[B] roots → [B, walk_len+1] row walks, column 0 = roots.

    p == q == 1: each step is one weighted neighbor draw (sample_hop);
    uniform=True routes those draws through the one-gather unit-weight
    path (DeviceNeighborTable.uniform_rows tables, replicated only);
    alias_table routes them through the O(1) alias draw — the walk
    family's chained count=1 draws are where the per-draw constant
    matters most. Otherwise node2vec
    second-order bias: candidate weights are scaled 1/p when returning
    to the previous node, 1 when the candidate is a kept neighbor of
    the previous node, 1/q otherwise — computed over the capped rows
    with C x C equality compares, no host round-trip (the biased path
    always reads the cum table: the bias math needs raw slot weights,
    so uniform/alias are ignored there).
    """
    C = stored_info(nbr_table).cap
    unif = uniform and gather is None and alias_table is None
    atab = alias_table if gather is None else None

    cols = [roots]
    key, sub = jax.random.split(key)
    cur = sample_hop(nbr_table, cum_table, roots, 1, sub, gather,
                     uniform=unif, alias_table=atab)
    cols.append(cur)
    prev = roots
    for _ in range(walk_len - 1):
        key, sub = jax.random.split(key)
        if p == 1.0 and q == 1.0:
            nxt = sample_hop(nbr_table, cum_table, cur, 1, sub, gather,
                             uniform=unif, alias_table=atab)
        else:
            cand = take_rows(nbr_table, cur, "nbr", gather)     # [B, C]
            w = slot_weights(
                take_rows(cum_table, cur, "cum", gather))       # [B, C]
            prev_nbr = take_rows(nbr_table, prev, "nbr", gather)
            is_prev = cand == prev[:, None]
            in_prev_nbr = (cand[:, :, None]
                           == prev_nbr[:, None, :]).any(-1)
            # pad candidates keep weight 0 regardless of bias
            bias = jnp.where(is_prev, 1.0 / p,
                             jnp.where(in_prev_nbr, 1.0, 1.0 / q))
            bw = w * bias
            bcum = jnp.cumsum(bw, axis=1)
            total = bcum[:, -1]
            u = jax.random.uniform(sub, (cand.shape[0],)) * total
            col = (bcum <= u[:, None]).sum(-1)
            col = jnp.clip(col, 0, C - 1).astype(jnp.int32)
            nxt = jnp.take_along_axis(cand, col[:, None], axis=1)[:, 0]
            # zero-total rows (dead end / pad): every candidate slot of
            # such a row already holds the table's DATA pad value (the
            # builder fills dead rows with pad), so cand[:, 0] is the
            # correct sentinel. Deriving it from stored_info's pad_row
            # would be wrong for row-sharded tables, whose row count is
            # padded up to the model-axis multiple (code-review r4).
            nxt = jnp.where(total > 0, nxt, cand[:, 0])
        cols.append(nxt)
        prev, cur = cur, nxt
    return jnp.stack(cols, axis=1)


def gen_pair_offsets(walk_cols: int, left_win: int,
                     right_win: int) -> Sequence[Tuple[int, int]]:
    """Static (center, context) index pairs for an L-column walk —
    boundary-clipped like ops.walk_ops.gen_pair."""
    out = []
    for i in range(walk_cols):
        for off in range(-left_win, right_win + 1):
            j = i + off
            if off == 0 or j < 0 or j >= walk_cols:
                continue
            out.append((i, j))
    return out


def gen_pair_rows(walks: jax.Array, left_win: int,
                  right_win: int) -> jax.Array:
    """[B, L] walks → [B, P, 2] skip-gram pairs (same pair order as the
    host gen_pair, so models are interchangeable across paths)."""
    L = walks.shape[1]
    offs = gen_pair_offsets(L, left_win, right_win)
    if not offs:
        return jnp.zeros((walks.shape[0], 0, 2), walks.dtype)
    ii = jnp.array([i for i, _ in offs])
    jj = jnp.array([j for _, j in offs])
    return jnp.stack([walks[:, ii], walks[:, jj]], axis=-1)
