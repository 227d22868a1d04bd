"""Device-resident layerwise (LADIES/FastGCN) sampling.

Completes the on-device input family: fanout (device_sampler.py) and
walks (device_walk.py) already run in-jit; this moves the third
sampling strategy — per-layer importance-sampled pools + dense
inter-pool adjacency (reference API_SAMPLE_L / sample_layer_op.cc:74 and
LayerwiseDataFlow, tf_euler/python/dataflow/layerwise_dataflow.py) —
into the jitted step as well. The host ships only root rows + a seed.

Per layer, over the capped HBM tables (DeviceNeighborTable layout):
  - pool candidates are the FRONTIER's neighbor slots — the previous
    layer's pool (the roots at layer 0) — with their edge weights
    (diff of the inclusive cum rows); drawing from the frontier only
    matches the host engine's layerwise sampler (SampleLayerwise,
    core/cc/ops.cc), which expands each layer from the nodes drawn in
    the previous one, not from the whole accumulated level (advisor
    r3: the concatenated-level draw skewed candidate mass toward
    earlier/duplicated nodes);
  - the pool is m_l WITH-REPLACEMENT draws ∝ slot weight (inverse-CDF
    over the flattened slot weights): P(neighbor) ∝ its total incident
    edge weight from the frontier — distributionally the engine's
    per-unique-neighbor accumulated-weight draw, with duplicates
    arising exactly as they do on the host path (each duplicate
    carries the full edge weight into the adjacency; _dense_adj does
    the same);
  - the next level is concat(current, pool) — the LADIES connectivity
    guarantee (each level contains the previous one, so self-loops
    always find a column), mirroring LayerwiseDataFlow.__call__;
  - the dense adjacency [n_l, n_{l+1}] is rebuilt on the VPU by
    comparing neighbor slots against the level columns, + self-loops,
    row-normalized — the same Â = A + I math as
    LayerwiseDataFlow._dense_adj.

Shapes are fully static: n_0 = B, n_{l+1} = n_l + m_l.

Envelope: the adjacency build materializes an [n_l, C, n_{l+1}] bool
hit tensor on the VPU — fine for the FastGCN/LADIES training regime
(batches 64-512, pools 128-512: ≤ ~50M elements), not for the
fanout-style batch-32k regime; giant batches belong to the fanout
sampler (device_sampler.py), whose cost is linear in drawn edges.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


from euler_tpu.parallel.device_sampler import (  # noqa: E402
    _alias_pick, slot_weights, stored_info, take_rows,
)


def sample_layerwise_rows(nbr_table: jax.Array, cum_table: jax.Array,
                          roots: jax.Array, layer_sizes: Sequence[int],
                          key, alias_table=None):
    """roots [B] int32 → (levels, adjs): levels[l] is an int32 row array
    (level 0 = roots, level l+1 = level l ++ pool of layer_sizes[l]);
    adjs[l] is the row-normalized dense [n_l, n_{l+1}] adjacency of
    Â = A + I restricted to the pools — exactly the batch geometry
    LayerwiseDataFlow produces and LayerEncoder consumes.

    alias_table (DeviceNeighborTable(alias=True)): the pool draw
    becomes two-stage — frontier node ∝ its total incident weight (an
    inverse-CDF over n_frontier row totals instead of n_frontier·C
    slots), then the O(1) alias draw inside the chosen row. P(node) ·
    P(slot|node) = (W_i/ΣW)·(w_ij/W_i) = w_ij/ΣW: distribution-
    identical to the flat slot draw, with the cumsum/searchsorted
    shrunk C×. The adjacency build is unchanged (it needs the raw slot
    weights either way)."""
    levels = [roots]
    adjs = []
    cur = roots
    n_frontier = roots.shape[0]  # frontier = last pool (roots at l=0)
    for m in layer_sizes:
        key, kg = jax.random.split(key)
        nbr = take_rows(nbr_table, cur, "nbr")          # [n, C] rows
        w = slot_weights(take_rows(cum_table, cur, "cum"))
        # pool draw expands the FRONTIER (a suffix of cur) only — the
        # host engine's layer-by-layer semantics; the full cur rows are
        # still needed below for the inter-level adjacency.
        # With-replacement inverse-CDF over the flat slot weights:
        # P(slot) ∝ w, zero-weight slots (pads, zero-weight edges) are
        # never hit while any real slot exists — without top-k's
        # shortfall when fewer than m positive slots exist
        nbr_f = nbr[-n_frontier:]
        if alias_table is not None:
            cur_f = cur[-n_frontier:]
            tot_cum = jnp.cumsum(w[-n_frontier:].sum(-1))   # [nf]
            u = jax.random.uniform(kg, (int(m),)) * tot_cum[-1]
            idx = jnp.searchsorted(tot_cum, u, side="right")
            idx = jnp.minimum(idx,
                              tot_cum.shape[0] - 1).astype(jnp.int32)
            arow = take_rows(alias_table, jnp.take(cur_f, idx),
                             "alias")                       # [m, C]
            key, ka = jax.random.split(key)
            ua = jax.random.uniform(ka, (2, int(m), 1))
            col, deg = _alias_pick(arow, ua[0], ua[1])      # [m, 1]
            pool = jnp.take_along_axis(jnp.take(nbr_f, idx, axis=0),
                                       col, axis=1)[:, 0]   # [m]
            # zero-total frontier rows carry no draw mass; if the WHOLE
            # frontier is dead every draw resolves to pad explicitly
            pool = jnp.where(deg > 0, pool,
                             stored_info(nbr_table).pad_row)
        else:
            flat_cum = jnp.cumsum(w[-n_frontier:].reshape(-1))
            total = flat_cum[-1]
            u = jax.random.uniform(kg, (int(m),)) * total
            idx = jnp.searchsorted(flat_cum, u, side="right")
            idx = jnp.minimum(idx,
                              flat_cum.shape[0] - 1).astype(jnp.int32)
            pool = jnp.take(nbr_f.reshape(-1), idx)         # [m]
        nxt = jnp.concatenate([cur, pool])              # [n + m]
        n_frontier = int(m)
        # dense Â = A + I between cur and nxt, row-normalized
        hit = (nbr[:, :, None] == nxt[None, None, :])   # [n, C, n+m]
        adj = (w[:, :, None] * hit).sum(axis=1)
        adj = adj + (cur[:, None] == nxt[None, :]).astype(adj.dtype)
        adj = adj / jnp.maximum(adj.sum(axis=1, keepdims=True), 1e-12)
        adjs.append(adj)
        levels.append(nxt)
        cur = nxt
    return levels, adjs
