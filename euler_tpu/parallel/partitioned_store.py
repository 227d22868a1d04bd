"""Partitioned device feature tables with a hub-aware replication cache.

The giant-graph tier (ROADMAP item 4): the canonical products config
(2.45M nodes / 122M edges) is one order of magnitude from outgrowing a
single chip's HBM, and the measured degree skew (hub_frac ≈ 0.996 at
cap 32) means a tiny replicated hot-set can absorb most gathers. This
module replaces the all-or-nothing placement choice (replicated vs
plain row-sharded) with a three-tier layout:

  hub cache   top hub_cache_frac highest-degree rows, REPLICATED on
              every chip — gathers route cache-first, so the hot mass
              never crosses ICI;
  partition   each chip holds a contiguous 1/K row shard of the table
              (plus the pad sentinel), cold gathers cross ICI via
              ring_exchange.ring_lookup or its all-gather variant,
              picked per step by a cost model on batch-unique ids × K;
  host        rows past an optional device budget stay in host RAM,
              served through CachedGraphEngine behind the existing
              degrade/retry machinery.

The load-bearing trick is a HUB-FIRST ROW PERMUTATION: rows are
relabeled in descending-degree order (degree ranking comes from the
graph engine at build time), so hub membership is simply `row < H` —
no device-resident membership map, and the hub cache is literally the
table's first H rows. The same permutation is the degree-sorted
locality layout bench.py already A/Bs (_degree_sort_tables), so the
neighbor tables compose by `apply_permutation`.

Correctness contract: `gather()` on the mesh is byte-identical to
`ring_exchange.reference_lookup` on the unpartitioned table for every
dtype the store supports (float32 and int8-quantized) — hub rows come
from a verbatim replicated copy, cold rows from the masked
single-owner exchange, and the combine is a select, never arithmetic.
"""

from __future__ import annotations

import itertools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from euler_tpu import obs as _obs
from euler_tpu.parallel.feature_store import quantize_int8
from euler_tpu.parallel.ring_exchange import (
    allgather_lookup,
    pick_lookup_strategy,
    ring_lookup,
)

__all__ = ["PartitionedFeatureStore", "hub_routed_take"]

_STORE_IDS = itertools.count()


def hub_routed_take(base_take, hub_cache: jax.Array):
    """Wrap a table gather with cache-first hub routing.

    `base_take(table, rows)` is the cold-leg gather (plain take for a
    replicated table, make_table_gather's masked-take+psum or the
    ring/all-gather exchange for a partitioned one). Rows below the
    hub-cache height H are served from the replicated `hub_cache` (the
    table's first H rows verbatim — the hub-first permutation makes
    membership a compare, not a map); only the cold tail reaches
    `base_take`, with hub positions routed to the table's trailing zero
    row so a hub row NEVER rides the remote leg. The final combine is a
    select, so output bytes equal an unrouted gather exactly (int8
    included)."""
    H = int(hub_cache.shape[0])
    if H == 0:
        return base_take

    def take(table, rows):
        is_hub = rows < H
        cached = jnp.take(hub_cache, jnp.minimum(rows, H - 1), axis=0)
        cold = base_take(
            table, jnp.where(is_hub, table.shape[0] - 1, rows))
        return jnp.where(is_hub[..., None], cached, cold)

    return take


class PartitionedFeatureStore:
    """Mesh-partitioned node feature table + replicated hub cache.

    Device-row layout (after the hub-first degree permutation):
      [0, H)            hub rows — the first rows of the partition AND
                        replicated verbatim as `hub_cache`
      [H, dev_rows)     cold rows, contiguous 1/K shards over `axis`
      dev_rows          the all-zero pad sentinel (unknown ids, sampling
                        pads) — the DeviceFeatureStore convention
      > dev_rows        put_row_sharded zero padding; no live index
                        reaches it

    Rank space past dev_rows (host_rows of them) is the host-RAM
    overflow tier: those rows never upload; lookup_with_overflow flags
    them and fetch_host_rows serves them through CachedGraphEngine.

    Usage mirrors DeviceFeatureStore:
        store = PartitionedFeatureStore(graph, ["feature"], mesh=mesh,
                                        hub_cache_frac=0.01)
        rows = store.lookup(ids_u64)          # host: ids → device rows
        out = store.make_gather()(rows_dev)   # on-mesh, parity-exact
    plus `tables` for the estimator static_batch and `apply_permutation`
    for remapping neighbor/label tables into the same row space.
    """

    def __init__(self, graph, feature_ids: Sequence, *,
                 mesh: jax.sharding.Mesh, axis: str = "model",
                 hub_cache_frac: float = 0.0,
                 device_rows: Optional[int] = None,
                 dtype=jnp.float32, quantize: Optional[str] = None,
                 host_cache_bytes: int = 64 << 20,
                 name: Optional[str] = None):
        ids = graph.all_node_ids()
        feats = graph.get_dense_feature(ids, list(feature_ids))
        if isinstance(feats, list):
            feats = np.concatenate(feats, axis=1)
        feats = feats.astype(np.dtype(dtype), copy=False)
        # degree ranking from the engine at build time: the hub set is
        # the measured skew, not a guess
        offs = graph.get_full_neighbor(ids)[0].astype(np.int64)
        degrees = np.diff(offs)
        self._init_from(feats, degrees, mesh=mesh, axis=axis,
                        hub_cache_frac=hub_cache_frac,
                        device_rows=device_rows, quantize=quantize,
                        scale_dtype=dtype, name=name)
        self._graph = graph
        self._feature_ids = list(feature_ids)
        # host overflow reads go through the immutable-graph client
        # cache — and whatever degrade/retry machinery the wrapped
        # engine already carries (RemoteGraphEngine's RetryPolicy)
        self._host_engine = None
        if self.host_rows > 0:
            from euler_tpu.graph import CachedGraphEngine

            self._host_engine = CachedGraphEngine(
                graph, budget_bytes=int(host_cache_bytes),
                name=f"{self.name}_host")

    @classmethod
    def from_arrays(cls, features: np.ndarray, degrees: np.ndarray, *,
                    mesh: jax.sharding.Mesh, axis: str = "model",
                    hub_cache_frac: float = 0.0,
                    quantize: Optional[str] = None,
                    scale_dtype=jnp.float32,
                    name: Optional[str] = None):
        """Rehydrate from a prebuilt [N+1, D] table (trailing pad row,
        the builders' convention) + per-node degrees [N] — the bench
        cache path. Node ids are taken to BE original table rows
        (dense-id graphs). No graph engine → no host-overflow tier."""
        self = cls.__new__(cls)
        self._graph = None
        self._feature_ids = None
        self._host_engine = None
        self._init_from(np.asarray(features), np.asarray(degrees),
                        mesh=mesh, axis=axis,
                        hub_cache_frac=hub_cache_frac,
                        device_rows=None, quantize=quantize,
                        scale_dtype=scale_dtype, name=name)
        return self

    # -- build -------------------------------------------------------------
    def _init_from(self, feats: np.ndarray, degrees: np.ndarray, *,
                   mesh, axis, hub_cache_frac, device_rows, quantize,
                   scale_dtype, name):
        from euler_tpu.parallel.placement import (
            put_replicated, put_row_sharded,
        )

        n = int(degrees.shape[0])
        if feats.shape[0] == n:          # engine path: pad row not yet
            feats = np.concatenate(
                [feats, np.zeros((1, feats.shape[1]), feats.dtype)])
        if feats.shape[0] != n + 1:
            raise ValueError(
                f"features must be [N, D] or [N+1, D] for N={n} degrees,"
                f" got {feats.shape}")
        self.mesh = mesh
        self.axis = axis
        self.k = int(dict(mesh.shape).get(axis, 1))
        self.name = name or f"ptable{next(_STORE_IDS)}"
        if not 0.0 <= float(hub_cache_frac) < 1.0:
            raise ValueError(
                f"hub_cache_frac must be in [0, 1), got {hub_cache_frac}")
        self.hub_size = int(round(float(hub_cache_frac) * n))
        self.dev_rows = n if device_rows is None else int(device_rows)
        self.dev_rows = max(min(self.dev_rows, n), self.hub_size)
        self.host_rows = n - self.dev_rows
        self.pad_row = self.dev_rows
        # hub-first permutation, old row → device row. Host-resident
        # ranks shift +1 past the pad sentinel (which takes device row
        # dev_rows), so no rank collides with it.
        order = np.argsort(-degrees, kind="stable").astype(np.int64)
        rank = np.arange(n, dtype=np.int32)
        perm = np.empty(n + 1, np.int32)
        perm[order] = np.where(rank < self.dev_rows, rank, rank + 1)
        perm[n] = self.dev_rows                   # old pad → sentinel
        self.permutation = perm                   # old row → new row
        self.order = order                        # degree rank → old row
        # hub mass: the share of total degree the cached rows carry —
        # the expected gather-traffic reduction on a degree-biased
        # batch (a random edge endpoint is proportionally a hub)
        tot = float(degrees.sum())
        self.hub_mass = float(
            degrees[order[:self.hub_size]].sum() / tot) if tot else 0.0
        self.degree_max = int(degrees.max()) if n else 0
        self.degree_mean = float(degrees.mean()) if n else 0.0

        self.feature_scale = None
        if quantize == "int8":
            # scale computed over the FULL table so hub cache, shard and
            # reference share one quantization — parity stays byte-exact
            feats, scale = quantize_int8(feats)
            self.feature_scale = put_replicated(
                scale.astype(np.dtype(scale_dtype), copy=False), mesh)
        elif quantize is not None:
            raise ValueError(f"unknown quantize mode {quantize!r}")
        dev = np.empty((self.dev_rows + 1, feats.shape[1]), feats.dtype)
        np.take(feats, order[:self.dev_rows], axis=0, out=dev[:-1])
        dev[-1] = 0                               # pad sentinel row
        self.hub_cache = put_replicated(
            np.ascontiguousarray(dev[:self.hub_size]), mesh)
        self.features = put_row_sharded(dev, mesh, axis=axis)
        # optional replicated label table in the SAME permuted row space
        # (callers set it via apply_permutation + put_replicated — labels
        # are label_dim-wide, not worth sharding)
        self.labels = None
        self.dim = int(dev.shape[1])
        self._elem_bytes = dev.dtype.itemsize
        # per-chip byte accounting (the memory_plan formulas, live)
        shard_rows = -(-int(self.features.shape[0]) // max(self.k, 1))
        self.per_chip_bytes = (
            shard_rows * self.dim * self._elem_bytes
            + self.hub_size * self.dim * self._elem_bytes
            + (self.dim * 4 if self.feature_scale is not None else 0))
        self._wire_obs()

    def _wire_obs(self):
        reg = _obs.default_registry()
        lab = {"store": self.name}
        self._ctr = {
            leg: reg.counter(
                f"table_gather_{leg}_rows_total",
                h, ("store",)).labels(**lab)
            for leg, h in (
                ("local", "gathered rows owned by the requesting shard"),
                ("cached", "gathered rows served by the hub cache"),
                ("remote", "gathered rows crossing ICI (cold, non-local)"),
                ("host", "gathered rows served from host RAM overflow"),
            )}
        self._ctr_hub_hits = reg.counter(
            "hub_cache_hits_total",
            "table gathers answered by the replicated hub cache",
            ("store",)).labels(**lab)
        self._ctr_hub_misses = reg.counter(
            "hub_cache_misses_total",
            "table gathers past the hub cache (local + remote + host)",
            ("store",)).labels(**lab)
        self._g_hbm = reg.gauge(
            "table_hbm_bytes",
            "per-chip HBM bytes held by the partitioned table tier "
            "(shard + hub cache + scale)", ("store",)).labels(**lab)
        self._g_hbm.set(self.per_chip_bytes)
        _obs.register_health(self.name, self.cache_stats)

    # -- host side ---------------------------------------------------------
    def lookup(self, ids) -> np.ndarray:
        """u64 node ids → int32 DEVICE rows (hub-first space). Unknown
        ids map to the pad sentinel. Ids whose rows were evicted to the
        host tier are refused here — route them through
        lookup_with_overflow / fetch_host_rows instead (a silent pad
        would train on zeros where data exists)."""
        rows, host = self.lookup_with_overflow(ids)
        if host.any():
            raise ValueError(
                f"{int(host.sum())} of {host.size} ids resolve to "
                "host-overflow rows; use lookup_with_overflow() + "
                "fetch_host_rows() on this store (device_rows="
                f"{self.dev_rows} < {self.dev_rows + self.host_rows})")
        return rows

    def lookup_with_overflow(self, ids):
        """(device_rows int32, host_mask bool): host-resident ids come
        back with the pad sentinel in `device_rows` and True in
        `host_mask`; fetch their features with fetch_host_rows(ids)."""
        ids = np.asarray(ids, np.uint64).ravel()
        if self._graph is not None:
            old = self._graph.node_rows(
                ids, missing=len(self.permutation) - 1)
        else:
            old = np.minimum(ids.astype(np.int64),
                             len(self.permutation) - 1)
        new = self.permutation[np.asarray(old, np.int64)]
        host = new > self.dev_rows  # shifted ranks past the sentinel
        return (np.where(host, self.pad_row, new).astype(np.int32),
                host)

    def fetch_host_rows(self, ids) -> np.ndarray:
        """Dense features for host-overflow ids, via the
        CachedGraphEngine tier (deterministic reads cached client-side;
        retries/degrade per the wrapped engine). Counted as the 'host'
        gather leg."""
        if self._host_engine is None:
            raise ValueError("store has no host tier "
                             "(device_rows covers every row)")
        ids = np.asarray(ids, np.uint64).ravel()
        feats = self._host_engine.get_dense_feature(
            ids, list(self._feature_ids))
        if isinstance(feats, list):
            feats = np.concatenate(feats, axis=1)
        self._ctr["host"].inc(int(ids.size))
        self._ctr_hub_misses.inc(int(ids.size))
        return feats

    def apply_permutation(self, table: np.ndarray,
                          remap_values: bool = False) -> np.ndarray:
        """Permute a [N+1, ...] companion table (neighbor/cum/label
        rows in the ORIGINAL row space, trailing pad row) into this
        store's hub-first row space, so one set of int32 device rows
        indexes every table. remap_values=True additionally rewrites
        int32 row VALUES (neighbor ids) — the _degree_sort_tables
        contract. Host-overflow stores refuse: a neighbor value
        pointing at an evicted row has no device representation."""
        if self.host_rows:
            raise ValueError(
                "apply_permutation needs a fully device-resident store "
                f"(host_rows={self.host_rows}): companion tables cannot "
                "reference host-evicted rows")
        n = len(self.permutation) - 1
        if table.shape[0] != n + 1:
            raise ValueError(
                f"companion table has {table.shape[0]} rows, store row "
                f"space is {n + 1}")
        out = np.empty_like(table)
        np.take(table, self.order, axis=0, out=out[:-1])
        out[-1] = table[-1]                       # pad row kept verbatim
        if remap_values:
            np.take(self.permutation, out, out=out)
        return out

    def route_batch(self, rows) -> dict:
        """Deterministic per-batch traffic split for device rows [B]
        (duplicates count — the gather issues every row). Ring
        semantics: the flat batch splits into K contiguous position
        blocks (shard_map's P(axis) layout); a cold row is 'local' when
        its owner shard is the requesting block, 'remote' otherwise.
        The all-gather variant physically moves every non-hub row
        through the collective, so 'remote' is the hardware-traffic
        proxy both variants are judged by."""
        rows = np.asarray(rows).ravel()
        b = int(rows.size)
        hub = rows < self.hub_size
        if self.k <= 1:
            local = int((~hub).sum())
            remote = 0
        else:
            rows_per = int(self.features.shape[0]) // self.k
            owner = np.minimum(rows // max(rows_per, 1), self.k - 1)
            block = np.arange(b) * self.k // max(b, 1)
            local = int(((~hub) & (owner == block)).sum())
            remote = int(((~hub) & (owner != block)).sum())
        # strategy fed the SAME input make_gather('auto') uses (total
        # rows shipped — the exchanges don't deduplicate, and the
        # all-gather burst scales with B, not unique ids), so the
        # recorded strategy always matches the executed one
        return {"rows": b, "cached": int(hub.sum()), "local": local,
                "remote": remote,
                "strategy": pick_lookup_strategy(
                    b, self.k, self.dim, self._elem_bytes)}

    def observe_batch(self, rows) -> dict:
        """route_batch + bump the obs counters (the per-step
        table_gather_* split bench.py's detail.obs captures)."""
        r = self.route_batch(rows)
        self._ctr["cached"].inc(r["cached"])
        self._ctr["local"].inc(r["local"])
        self._ctr["remote"].inc(r["remote"])
        self._ctr_hub_hits.inc(r["cached"])
        self._ctr_hub_misses.inc(r["local"] + r["remote"])
        return r

    def cache_stats(self) -> dict:
        """Registry-backed stats view (the /healthz provider — same
        pattern as CachedGraphEngine.cache_stats)."""
        hits = int(self._ctr_hub_hits.value)
        misses = int(self._ctr_hub_misses.value)
        return {
            "k_shards": self.k,
            "hub_size": self.hub_size,
            "hub_mass": round(self.hub_mass, 6),
            "dev_rows": self.dev_rows,
            "host_rows": self.host_rows,
            "degree_max": self.degree_max,
            "degree_mean": round(self.degree_mean, 3),
            "per_chip_bytes": self.per_chip_bytes,
            "hub_hits": hits,
            "hub_misses": misses,
            "hit_rate": round(hits / max(hits + misses, 1), 6),
            "gather_rows": {
                leg: int(c.value) for leg, c in self._ctr.items()},
        }

    # -- device side -------------------------------------------------------
    @property
    def tables(self) -> dict:
        """static_batch keys: the row-sharded table, the replicated hub
        cache (gather_feature_rows routes cache-first when present) and
        the int8 scale."""
        out = {"feature_table": self.features}
        if self.hub_size > 0:
            out["hub_cache"] = self.hub_cache
        if self.feature_scale is not None:
            out["feature_scale"] = self.feature_scale
        if self.labels is not None:
            out["label_table"] = self.labels
        return out

    def make_gather(self, strategy: str = "auto",
                    n_ids_hint: Optional[int] = None):
        """gather(rows) → rows' features on the mesh, byte-identical to
        reference_lookup on the unpartitioned table.

        strategy: 'allgather' (masked-answer + reduce-scatter, 2
        collective launches), 'ring' (K-step ppermute, 1/K peak
        footprint), or 'auto' — the pick_lookup_strategy cost model on
        batch ids shipped × K (n_ids_hint, else resolved per call from
        the row count; route_batch records the same pick). An unpartitioned store (K == 1) always takes
        the plain local path. Hub rows are routed cache-first in every
        strategy. Rows are padded to a multiple of K with the pad
        sentinel (sliced back off), so any batch length works; each
        strategy jit-compiles once and is cached."""
        if strategy not in ("auto", "allgather", "ring"):
            raise ValueError(f"unknown gather strategy {strategy!r}")
        if self.k <= 1:
            routed = hub_routed_take(
                lambda t, r: jnp.take(t, r, axis=0), self.hub_cache)
            return lambda rows: jax.jit(routed)(self.features, rows)

        def exchange(kind):
            fn = ring_lookup if kind == "ring" else allgather_lookup

            def base(table, rows):
                b = rows.shape[0]
                pad = (-b) % self.k
                if pad:
                    rows = jnp.concatenate(
                        [rows, jnp.full((pad,), self.pad_row,
                                        rows.dtype)])
                # pin REPLICATED before shard_map: on a mesh with a
                # non-trivial data axis, GSPMD may shard this in-jit
                # intermediate over 'data' and the implicit reshard to
                # P(axis) reads wrong values on jax without pvary/pcast
                # (observed on 0.4.37); no-op when already replicated
                from jax.sharding import NamedSharding, PartitionSpec

                rows = jax.lax.with_sharding_constraint(
                    rows, NamedSharding(self.mesh, PartitionSpec()))
                out = fn(table, rows, self.mesh, self.axis)
                return out[:b] if pad else out

            return hub_routed_take(base, self.hub_cache)

        jitted = {}

        def gather(rows):
            kind = strategy
            if kind == "auto":
                n = n_ids_hint or int(rows.shape[0])
                kind = pick_lookup_strategy(n, self.k, self.dim,
                                            self._elem_bytes)
            if kind not in jitted:
                jitted[kind] = jax.jit(exchange(kind))
            return jitted[kind](self.features, rows)

        return gather
