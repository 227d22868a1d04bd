"""Ring all-to-all embedding exchange over ICI.

The GSPMD path in sharded_embedding lets XLA choose the collective for a
row-sharded table lookup (typically all-gather of hit rows). For very
large tables the all-gather of a big lookup batch can spike ICI + HBM;
the classic alternative is a ring exchange (the pattern ring attention
uses for KV blocks, applied here to embedding rows — SURVEY.md §5's
"optional ICI all-to-all embedding exchange"):

  each device holds rows [d·R/K, (d+1)·R/K) of the table and a shard of
  the lookup ids. In K steps, the id shard ppermutes around the ring;
  every device answers the ids that fall in its row range, accumulating
  into a result buffer that travels with the ids. Peak ICI traffic per
  step is 1/K of the all-gather, and each step's sends overlap the next
  lookup's compute.

ring_lookup runs under shard_map over a 1-d mesh axis; a pure-jnp
reference (same math, no collectives) backs the single-device path and
the tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def _local_answer(table_shard: Array, ids: Array, shard_lo: Array) -> Array:
    """Rows for ids that fall inside this shard's range, zeros elsewhere.
    The masked fill is a typed zero (not the float literal 0.0): the
    partitioned store runs int8-quantized tables through this path, and
    a weakly-typed float zero would silently promote the whole answer to
    f32 — breaking the byte-identity gate."""
    local = ids - shard_lo
    in_range = (local >= 0) & (local < table_shard.shape[0])
    rows = jnp.take(table_shard, jnp.clip(local, 0, table_shard.shape[0] - 1),
                    axis=0)
    return jnp.where(in_range[:, None], rows, jnp.zeros((), rows.dtype))


def ring_lookup(table: Array, ids: Array, mesh: Mesh,
                axis: str = "model") -> Array:
    """Distributed embedding lookup via a K-step ppermute ring.

    table: [R, D] row-sharded over `axis`; ids: [B] int32 in [0, R),
    sharded over `axis` too (each device starts with B/K ids). Returns
    [B, D] with the same sharding as ids.
    """
    k = mesh.shape[axis]
    rows_per = table.shape[0] // k

    def body(table_shard, ids_shard):
        me = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % k) for i in range(k)]

        def step(carry, _):
            cur_ids, acc = carry
            # answer the visiting ids that fall in this device's rows
            shard_lo = (me * rows_per).astype(cur_ids.dtype)
            acc = acc + _local_answer(table_shard, cur_ids, shard_lo)
            # pass ids + partial results to the next device in the ring
            cur_ids = jax.lax.ppermute(cur_ids, axis, perm)
            acc = jax.lax.ppermute(acc, axis, perm)
            return (cur_ids, acc), None

        acc0 = jnp.zeros((ids_shard.shape[0], table_shard.shape[1]),
                         table_shard.dtype)
        # shard_map tracks per-axis varyingness: the carry must enter
        # the scan already device-varying because ppermute makes it so
        # on the way out
        acc0 = jax.lax.pcast(acc0, axis, to="varying")
        (_, acc), _ = jax.lax.scan(step, (ids_shard, acc0), None, length=k)
        # after k hops every id shard (and its answers) is home again
        return acc

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(axis)),
        out_specs=P(axis, None),
    )
    return fn(table, ids)


def allgather_lookup(table: Array, ids: Array, mesh: Mesh,
                     axis: str = "model") -> Array:
    """The one-collective alternative to ring_lookup: all-gather the id
    shards over `axis`, answer the ids that fall in this device's rows,
    then reduce-scatter the summed answers back so each device keeps its
    own B/K slice. Same calling convention and the same bytes-exact
    output as ring_lookup (every id has exactly one owning shard, so the
    sum has one nonzero contributor per row — exact for float AND int8).

    Tradeoff vs the ring (the cost model in pick_lookup_strategy):
    2 collective launches instead of 2K ppermutes — wins when the batch
    is small/latency-bound — but it materializes the full [B, D] answer
    buffer on every chip before the scatter, so peak per-chip memory and
    ICI burst scale with B·D·K where the ring stays at B·D/K per step.
    """
    k = mesh.shape[axis]
    rows_per = table.shape[0] // k

    def body(table_shard, ids_shard):
        me = jax.lax.axis_index(axis)
        all_ids = jax.lax.all_gather(ids_shard, axis).reshape(-1)   # [B]
        shard_lo = (me * rows_per).astype(all_ids.dtype)
        ans = _local_answer(table_shard, all_ids, shard_lo)         # [B, D]
        # one owner per id → the scatter-sum reassembles exact rows
        return jax.lax.psum_scatter(ans, axis, scatter_dimension=0,
                                    tiled=True)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(axis)),
        out_specs=P(axis, None),
    )
    return fn(table, ids)


# Per-chip byte budget below which the all-gather variant's full [B, D]
# answer buffer (replicated K ways before the scatter) is considered
# cheap: under it the 2-launch all-gather wins on dispatch latency, over
# it the ring's 1/K peak footprint wins. Tuned for ~v4/v5e VMEM-adjacent
# staging; override per call site when measured on-chip.
ALLGATHER_MAX_BYTES = 64 << 20


def pick_lookup_strategy(n_ids: int, k: int, dim: int,
                         elem_bytes: int = 4,
                         allgather_max_bytes: int = ALLGATHER_MAX_BYTES
                         ) -> str:
    """Per-step lookup-strategy pick on batch ids shipped × K.

    n_ids is the id count that actually enters the exchange — the full
    batch today (neither variant deduplicates; pass the deduplicated
    count iff a dedup stage runs upstream). Both variants move the same
    total row bytes over ICI; what differs is launch count (all-gather:
    2 collectives; ring: 2K ppermutes) vs peak footprint (all-gather
    stages the full n_ids·D·elem answer on EVERY chip — a K-way
    replicated burst — where the ring holds 1/K of that per step). So:
    small batches on big meshes are launch-bound → 'allgather'; once
    n_ids·K·D·elem crosses the budget the burst dominates → 'ring'.
    K <= 1 means the table isn't partitioned at all → 'local' (plain
    take, no collective)."""
    if k <= 1:
        return "local"
    if n_ids * k * dim * elem_bytes <= allgather_max_bytes:
        return "allgather"
    return "ring"


def reference_lookup(table: Array, ids: Array) -> Array:
    """Single-device equivalent: plain take (the numbers ring_lookup and
    allgather_lookup must reproduce byte-for-byte)."""
    return jnp.take(table, ids, axis=0)
