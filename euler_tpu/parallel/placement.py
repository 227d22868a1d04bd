"""Shared device placement for HBM-resident input tables
(DeviceFeatureStore, DeviceNeighborTable).

Two policies, one helper module so the table classes cannot diverge:

- put_replicated: every chip holds the full table; per-step gathers stay
  local, no collective per step. Right when the graph fits one chip's
  HBM — the single-chip bench configuration.
- put_row_sharded: rows split over the mesh's 'model' axis (the
  reference's PS-sharded embedding capability, tf_euler/python/utils/
  layers.py:119-171): per-chip memory shrinks ~linearly with the model
  axis, and gathers become a masked local take + psum over 'model'
  (device_sampler.make_table_gather). Right when the graph outgrows one
  chip.

`placement_stage` times what the two classes do to a table on its way to
the device: a span and an observation in placement_ms{table,stage}, so
that set-up can be split from inside the program (PERF.md section 3).
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np


def placement_stage(stage: str, table: str, **attrs):
    """`with placement_stage("transfer", "nbr"):` around one stage of a
    table's placement: span `stage` (attribute `table`, and `attrs`) and
    its length in placement_ms{table,stage}. A constructor's parent span
    (`place_features`, `place_neighbors`) is a stage under its own name.
    The span times what the host thread does: a transfer the code leaves
    in flight is timed as its enqueue."""
    from euler_tpu import obs

    hist = obs.histogram(
        "placement_ms",
        "host time of each stage of placing a table on the device (pad, "
        "cast, quantize, detect_uniform_rows, store_rows, transfer) and, "
        "under the constructor's own name, of the whole of it",
        ("table", "stage"), buckets=obs.SETUP_MS_BUCKETS)
    return obs.timed_span(stage, hist.labels(table=table, stage=stage),
                          table=table, **attrs)


def _global_put(x: np.ndarray, sharding) -> jax.Array:
    """device_put that also works when `sharding` spans OTHER hosts'
    devices (multi-process mesh): every process calls this with the
    same full array and contributes its addressable shards."""
    if all(d.process_index == jax.process_index()
           for d in sharding.device_set):
        return jax.device_put(x, sharding)
    x = np.asarray(x)
    return jax.make_array_from_callback(x.shape, sharding,
                                        lambda idx: x[idx])


def put_replicated(x: np.ndarray,
                   mesh: Optional[jax.sharding.Mesh] = None) -> jax.Array:
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        return _global_put(x, NamedSharding(mesh, PartitionSpec()))
    return jax.device_put(x)


def put_row_sharded(x: np.ndarray, mesh: Optional[jax.sharding.Mesh],
                    axis: str = "model") -> jax.Array:
    """Rows over `axis`; falls back to replication when the mesh has no
    (or a trivial) model axis. Rows are zero-padded up to a multiple of
    the axis size — the pad rows sit PAST the table's own trailing
    pad_row, so no live index ever reaches them."""
    if mesh is None or dict(mesh.shape).get(axis, 1) <= 1:
        return put_replicated(x, mesh)
    from jax.sharding import NamedSharding, PartitionSpec

    mp = dict(mesh.shape)[axis]
    pad = (-x.shape[0]) % mp
    if pad:
        x = np.concatenate(
            [x, np.zeros((pad,) + x.shape[1:], x.dtype)])
    spec = PartitionSpec(axis, *([None] * (x.ndim - 1)))
    return _global_put(x, NamedSharding(mesh, spec))
