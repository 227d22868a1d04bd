"""Device-resident neighbor sampling: the TPU-first answer to the host
sampling bottleneck.

The reference's whole input design exists to amortize CPU-side neighbor
sampling (one-RPC chained fanout, tf_euler/kernels/sample_fanout_op.cc:
36-48). On TPU that leaves the chip idle: measured on v5e-1, the jitted
GraphSAGE train step sustains 11-24 steps/s while a 2-core host produces
at most ~3 fanout batches/s — the accelerator waits on the feeder 4-10×
over. When the graph fits in HBM the right design is to move sampling
itself onto the device:

  - neighbor rows [N, C] (int32, capped at C per node) and inclusive
    cumulative weights [N, C] (float32) live in HBM — the
    CompactWeightedCollection layout (reference
    euler/common/compact_weighted_collection.h:55) transposed into two
    dense tables an XLA gather can hit. They are STORED as their bytes,
    int8 [N, 4C] in byte planes (store_rows; the alias words too): the
    runtime lays a C-wide 32-bit table out with the nodes on the lanes,
    a row in C/8 tiles (36 ns a gathered row on v5e), and a 4C-byte
    int8 row along the lanes of one tile (9 ns). Same bytes in HBM;
    every row read goes through take_rows, which gives the words back
    bit for bit, and the tables' logical facts come from stored_info;
  - per hop, sampling is: uniform draw → per-row inverse-CDF over C
    cumulative weights (C compares on the VPU) → gather neighbor rows.
    Pure XLA inside the jitted train step; composes with lax.scan
    (steps_per_loop) and pjit;
  - the host ships ONLY root rows (~131KB for batch 32768) — everything
    else (sampling, feature gather, labels) reads HBM-resident tables.

Memory: 8 bytes × N × C (e.g. 200k nodes × C=32 → 51MB) next to the
DeviceFeatureStore feature table.

Fidelity: nodes with degree ≤ C sample exactly the host engine's
weighted-with-replacement distribution. Nodes with degree > C sample
from a C-subset drawn once at build time (weighted, without
replacement) — the standard neighbor-cap approximation (GraphSAGE §3.1
uses fixed-size uniform subsets the same way). Pass cap >= max degree
for exact parity.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from euler_tpu.parallel.placement import (
    placement_stage as _stage, put_replicated, put_row_sharded,
)


class DeviceNeighborTable:
    """Builds the HBM neighbor/cum-weight tables from a graph engine.

    Row order matches `graph.all_node_ids()` (the DeviceFeatureStore
    convention) so the same int32 rows index features, labels, and
    adjacency. Row N (= pad_row) is an all-pad row: sampling from it
    yields pad_row again, mirroring the host sampler's default_id pads.

    `neighbors`, `cum_weights` and `alias_table` (and `tables`) hold the
    STORED form, int8 [N+1, 4C] (store_rows): read rows of them with
    take_rows, the whole logical table with logical_rows. The fused
    table keeps its [N+1, 2C] int32 form; `host_tables` stay logical.

    alias=True additionally builds the per-row Vose alias table
    (build_alias_tables): one packed int32 word per slot, enabling the
    O(1) alias draw in sample_hop(alias_table=...) — the device
    transpose of the reference's euler/common/alias_method.h. Replicated
    split tables only (raises with fused/shard_rows): the alias draw
    derives per-row degree from the words themselves and pad from the
    table shape, neither of which survives the fused bitcast layout or
    the row-sharded shape padding.
    """

    def __init__(self, graph, cap: int = 32, edge_types=None,
                 seed: int = 0,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 keep_host: bool = False, shard_rows: bool = False,
                 fused: bool = False, alias: bool = False):
        self.shard_rows = bool(shard_rows)
        self.fused = bool(fused)
        self.alias = bool(alias)
        _check_alias_layout(self.alias, self.fused, self.shard_rows)
        # retained for patch_rows: a delta patch must re-derive dirty
        # rows under the SAME edge-type filter and draw keys
        self._edge_types = edge_types
        self._seed = int(seed)
        self._mesh = mesh
        ids = graph.all_node_ids()
        n = len(ids)
        self.cap = int(cap)
        self.pad_row = n
        with _stage("place_neighbors", "neighbors", rows=n + 1,
                    row_bytes=4 * self.cap, shard_rows=self.shard_rows):
            with _stage("read_graph", "neighbors"):
                offs, nbrs, ws, _ = graph.get_full_neighbor(ids, edge_types)
                offs = offs.astype(np.int64)
                deg = np.diff(offs)
                nbr_rows = graph.node_rows(nbrs, missing=n).astype(np.int32)
                del nbrs
                ws = ws.astype(np.float32)
            with _stage("build_tables", "neighbors"):
                nbr_tab, cum, alias_tab = self._build_tables(
                    n, deg, nbr_rows, ws, seed)
            # host copies are opt-in (cache writers like bench): pinning
            # them by default would double host RAM for every training
            # caller
            self.host_tables = (nbr_tab, cum) if keep_host else None
            self._place(nbr_tab, cum, mesh, alias_tab)

    @classmethod
    def from_arrays(cls, nbr_tab: np.ndarray, cum_tab: np.ndarray,
                    stats: Optional[dict] = None,
                    mesh: Optional[jax.sharding.Mesh] = None,
                    shard_rows: bool = False, fused: bool = False,
                    alias: bool = False):
        """Rehydrate from prebuilt [N+1, C] tables (e.g. a bench/dataset
        cache) without a live graph engine. alias=True rebuilds the
        alias table from the cum rows (chunked — caches carry only
        nbr/cum)."""
        self = cls.__new__(cls)
        self.shard_rows = bool(shard_rows)
        self.fused = bool(fused)
        self.alias = bool(alias)
        _check_alias_layout(self.alias, self.fused, self.shard_rows)
        # rehydrated tables carry no build provenance: patch_rows against
        # a live graph assumes the cache was built with seed 0 and no
        # edge-type filter (the bench/dataset cache convention)
        self._edge_types = None
        self._seed = 0
        self._mesh = mesh
        self.cap = int(nbr_tab.shape[1])
        self.pad_row = int(nbr_tab.shape[0]) - 1
        for k in ("hub_frac", "edge_keep_frac", "max_degree"):
            setattr(self, k, (stats or {}).get(k))
        with _stage("place_neighbors", "neighbors", rows=self.pad_row + 1,
                    row_bytes=4 * self.cap, shard_rows=self.shard_rows):
            # caches written before the round-5 uniform lever carry no
            # uniform_rows stat — recompute from the tables (the slot
            # weights are exactly recoverable from the inclusive cumsum).
            # Chunked: a full-table astype + diff would hold two ~3.5GB
            # transients at products scale (advisor r5)
            u = (stats or {}).get("uniform_rows")
            if u is None:
                with _stage("detect_uniform_rows", "neighbors"):
                    u = True
                    pad = self.pad_row
                    for lo in range(0, cum_tab.shape[0], _CHUNK_ROWS):
                        cc = np.asarray(cum_tab[lo:lo + _CHUNK_ROWS]) \
                            .astype(np.float32, copy=False)
                        w = np.diff(cc, axis=1,
                                    prepend=np.zeros((cc.shape[0], 1),
                                                     np.float32))
                        if not _detect_uniform_rows(
                                np.asarray(nbr_tab[lo:lo + _CHUNK_ROWS]), w,
                                pad=pad):
                            u = False
                            break
            self.uniform_rows = bool(u)
            self.host_tables = None
            alias_tab = None
            if self.alias:
                with _stage("build_alias", "neighbors"):
                    alias_tab = build_alias_tables(
                        np.asarray(nbr_tab), cum_tab=np.asarray(cum_tab))
            with _stage("cast", "neighbors"):
                nbr_tab = np.ascontiguousarray(nbr_tab)
                cum_tab = np.ascontiguousarray(cum_tab)
            self._place(nbr_tab, cum_tab, mesh, alias_tab)
        return self

    def _build_tables(self, n, deg, nbr_rows, ws, seed):
        C = self.cap
        nbr_tab = np.full((n + 1, C), n, dtype=np.int32)
        w_tab = np.zeros((n + 1, C), dtype=np.float32)
        _fill_table_rows(C, n, np.arange(n, dtype=np.int64), deg,
                         nbr_rows, ws, seed,
                         out_nbr=nbr_tab[:n], out_w=w_tab[:n])

        # truncation telemetry (bench reports these: VERDICT r2 weak #2)
        hubs = deg > C
        self.hub_frac = float(hubs.mean()) if n else 0.0
        kept = np.minimum(deg, C).sum()
        self.edge_keep_frac = float(kept / max(len(nbr_rows), 1))
        self.max_degree = int(deg.max()) if n else 0
        self.uniform_rows = _detect_uniform_rows(nbr_tab, w_tab)

        # alias table built from the exact slot weights BEFORE they are
        # folded into the cumsum (no f32 diff round-trip on this path)
        alias_tab = build_alias_tables(nbr_tab, w_tab=w_tab) \
            if getattr(self, "alias", False) else None
        cum = np.cumsum(w_tab, axis=1, dtype=np.float32)
        return nbr_tab, cum, alias_tab

    def _place(self, nbr_tab, cum, mesh, alias_tab=None):
        """The tables' stored form and its transfer, table by table, each
        under its span (placement.placement_stage: `store_rows`,
        `transfer`; `fuse` in fused mode). Every transfer is left in
        flight: the span holds the enqueue, and the first step that
        draws from the table waits for it."""
        def place(tab, table, put):
            with _stage("store_rows", table):
                stored = store_rows(tab, table)
            with _stage("transfer", table):
                return put(stored, mesh)

        if getattr(self, "fused", False):
            # one [N+1, 2C] i32 table (ids + bitcast cum): one row gather
            # per hop in sample_hop_fused. Split views are not uploaded —
            # fused mode exists to cut HBM gathers, not to double memory.
            # Composes with shard_rows: the fused rows split over 'model'
            # exactly like the split tables (the masked-take+psum gather
            # is dtype-exact for the bitcast f32 lanes — the one owning
            # shard contributes the bits, all others contribute i32
            # zeros), so the HBM-capacity lever and the gather-count
            # lever stack.
            put = put_row_sharded if self.shard_rows else put_replicated
            with _stage("fuse", "nbrcum"):
                fused_tab = fuse_tables_host(nbr_tab, cum)
            with _stage("transfer", "nbrcum"):
                self.fused_table = put(fused_tab, mesh)
            self.neighbors = None
            self.cum_weights = None
        else:
            put = put_row_sharded if self.shard_rows else put_replicated
            self.neighbors = place(nbr_tab, "nbr", put)
            self.cum_weights = place(cum, "cum", put)
        self.alias_table = place(alias_tab, "alias", put_replicated) \
            if alias_tab is not None else None

    @property
    def tables(self):
        """Arrays to merge into the estimator's static_batch."""
        if getattr(self, "fused", False):
            return {"nbrcum_table": self.fused_table}
        out = {"nbr_table": self.neighbors, "cum_table": self.cum_weights}
        if getattr(self, "alias_table", None) is not None:
            out["alias_table"] = self.alias_table
        return out

    def patch_rows(self, graph, dirty_ids) -> dict:
        """O(dirty) table maintenance after graph.apply_delta(...):
        re-derive ONLY the dirty rows (one neighbor query over the dirty
        ids, one _fill_table_rows block, one per-row Vose rebuild for
        the alias words) instead of rebuilding all N rows — the chunked
        per-row-chunk build machinery applied to exactly one chunk. New
        nodes (engine rows past the old pad) grow the tables; old pad
        sentinels are remapped to the new pad id in one vectorized pass
        (a memory pass, not a rebuild — 0 rows re-derived by it).

        The patched table is byte-identical to a from-scratch build on
        the final edge set: row content is row-local by construction
        (see _fill_table_rows), untouched rows are bit-copied, and the
        engine's append-only row identity keeps neighbor row ids valid.

        Replicated split tables only (fused/shard_rows layouts raise —
        same constraint family as alias=True). O(dirty) end to end on
        the common path: when the delta adds no nodes (no table growth)
        and the tables are not mesh-placed, the device copies are
        updated with an `.at[rows].set` row scatter — no O(N) host
        round-trip, no full re-upload. Growth (shape change) or a mesh
        placement falls back to the full re-place; stats["upload"] says
        which path ran ("row_scatter" / "replace" / "none"). Counted on
        the obs registry: alias_rows_patched_total (vs
        alias_rows_rebuilt_total for full builds). Returns
        {rows_patched, rows_total, grown_rows, rebuild_frac, upload}."""
        if self.fused or self.shard_rows:
            raise ValueError(
                "patch_rows supports replicated split tables only — the "
                "fused bitcast layout and row-sharded shape padding "
                "would both need a full re-place anyway; rebuild those "
                "tables instead")
        dirty_ids = np.asarray(dirty_ids, dtype=np.uint64).ravel()
        old_pad = self.pad_row
        n_new = int(graph.node_count)
        if n_new < old_pad:
            raise ValueError(
                f"graph shrank ({n_new} nodes < table's {old_pad}) — "
                "deltas are append-only; rebuild the table")
        C = self.cap
        grown = n_new - old_pad
        has_alias = getattr(self, "alias_table", None) is not None
        # device-side row scatter: no growth (table shapes unchanged)
        # and no mesh placement (a scatter on a mesh-sharded array would
        # reshard under jit defaults) — the dirty rows go straight onto
        # the device arrays with .at[rows].set, no O(N) host pull and no
        # full re-upload. Growth or a mesh falls back to re-place.
        scatter = grown == 0 and self._mesh is None
        nbr = cum = alias_tab = None
        if not scatter:
            if self.host_tables is not None:
                nbr = np.array(self.host_tables[0], copy=True)
                cum = np.array(self.host_tables[1], copy=True)
            else:
                nbr = logical_rows(self.neighbors, "nbr")
                cum = logical_rows(self.cum_weights, "cum")
            alias_tab = (logical_rows(self.alias_table, "alias")
                         if has_alias else None)
        if grown:
            g_nbr = np.full((n_new + 1, C), n_new, dtype=np.int32)
            g_cum = np.zeros((n_new + 1, C), dtype=np.float32)
            # old pad sentinels point at the MOVED pad row: remap in one
            # compare+where pass (alias words are column-relative and
            # need none)
            old_rows = nbr[:old_pad]
            g_nbr[:old_pad] = np.where(old_rows == old_pad, n_new,
                                       old_rows)
            g_cum[:old_pad] = cum[:old_pad]
            nbr, cum = g_nbr, g_cum
            if alias_tab is not None:
                g_alias = np.full((n_new + 1, C), ALIAS_SENTINEL,
                                  dtype=np.int32)
                g_alias[:old_pad] = alias_tab[:old_pad]
                alias_tab = g_alias
        # dirty ids → engine rows, resolved ONCE; ids this shard/graph
        # does not know (foreign dsts in a broadcast delta) resolve to
        # the pad row and drop out
        all_rows = graph.node_rows(dirty_ids, missing=n_new) \
            .astype(np.int64)
        ok = all_rows < n_new
        order = np.argsort(all_rows[ok], kind="stable")
        sorted_rows = all_rows[ok][order]
        keep_first = np.ones(sorted_rows.size, bool)
        keep_first[1:] = sorted_rows[1:] != sorted_rows[:-1]
        rows = sorted_rows[keep_first]      # unique, ascending
        stats = {"rows_patched": int(rows.size), "rows_total": n_new,
                 "grown_rows": int(grown),
                 "rebuild_frac": float(rows.size / max(n_new, 1)),
                 "upload": ("none" if scatter and rows.size == 0
                            else "row_scatter" if scatter else "replace")}
        if rows.size:
            # the dirty ids in ROW order (dedup'd) so the CSR block from
            # get_full_neighbor lines up 1:1 with `rows`
            ids = dirty_ids[ok][order][keep_first]
            offs, nbrs, ws, _ = graph.get_full_neighbor(
                ids, self._edge_types)
            offs = offs.astype(np.int64)
            deg = np.diff(offs)
            nbr_rows = graph.node_rows(nbrs, missing=n_new).astype(np.int32)
            blk_nbr, blk_w = _fill_table_rows(
                C, n_new, rows, deg, nbr_rows, ws.astype(np.float32),
                self._seed)
            blk_cum = np.cumsum(blk_w, axis=1, dtype=np.float32)
            blk_alias = (_alias_rows_block(blk_nbr, blk_w, n_new)
                         if has_alias else None)
            if scatter:
                # host copies (when kept) mutate in place — the device
                # arrays own their own memory, so this cannot alias
                if self.host_tables is not None:
                    self.host_tables[0][rows] = blk_nbr
                    self.host_tables[1][rows] = blk_cum
                self.neighbors = self.neighbors.at[rows].set(
                    store_rows(blk_nbr, "nbr"))
                self.cum_weights = self.cum_weights.at[rows].set(
                    store_rows(blk_cum, "cum"))
                if has_alias:
                    self.alias_table = self.alias_table.at[rows].set(
                        store_rows(blk_alias, "alias"))
            else:
                nbr[rows] = blk_nbr
                cum[rows] = blk_cum
                if alias_tab is not None:
                    alias_tab[rows] = blk_alias
            # stats stay correct conservatively: uniform_rows may only
            # turn False (correctness-neutral — False just keeps the
            # general inverse-CDF path); hub telemetry tracks the max
            self.uniform_rows = bool(
                getattr(self, "uniform_rows", False)
                and _detect_uniform_rows(blk_nbr, blk_w, pad=n_new))
            if deg.size:
                self.max_degree = max(
                    int(getattr(self, "max_degree", 0) or 0),
                    int(deg.max()))
        self.pad_row = n_new
        if not scatter:
            if self.host_tables is not None:
                self.host_tables = (nbr, cum)
            self._place(nbr, cum, self._mesh, alias_tab)
        _alias_patch_counter("patched").inc(stats["rows_patched"])
        return stats


def _edge_uniforms(seed: int, rows: np.ndarray,
                   pos: np.ndarray) -> np.ndarray:
    """Stateless per-edge uniforms in [0, 1): a splitmix64 finalizer
    over (seed, global row, position-within-row). Replacing the shared
    rng stream makes every table row's hub draw a pure function of
    (seed, row, its edge list) — the property that lets patch_rows
    rebuild ONLY dirty rows and still match a from-scratch build on the
    final edge set byte-for-byte (a sequential stream would shift every
    row's draws whenever any earlier row's degree changed)."""
    with np.errstate(over="ignore"):
        x = (rows.astype(np.uint64) << np.uint64(32)) \
            ^ pos.astype(np.uint64)
        x ^= np.uint64(seed & 0xFFFFFFFFFFFFFFFF) * \
            np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
    return (x >> np.uint64(11)).astype(np.float64) * (2.0 ** -53)


def _fill_table_rows(C: int, pad: int, global_rows: np.ndarray,
                     deg: np.ndarray, nbr_rows: np.ndarray,
                     ws: np.ndarray, seed: int,
                     out_nbr: np.ndarray = None,
                     out_w: np.ndarray = None):
    """[k, C] (nbr, weight) table rows for the k selected nodes, from
    their concatenated CSR neighbor lists. Shared by the full build
    (global_rows = arange(n)) and patch_rows (global_rows = the dirty
    rows): every row's content depends only on (seed, its global row
    id, its own edge list) — row-local by construction, so a patched
    row is byte-identical to the same row in a from-scratch build.

    Rows with degree <= C front-pack their edges; hubs draw a weighted
    C-subset without replacement (vectorized Efraimidis–Spirakis over
    the stateless per-edge uniforms: the C largest keys u^(1/w) per row
    ARE such a draw; zero-weight edges get keys in (-2,-1] so they only
    fill slots left over after every positive-weight edge; rows whose
    total weight is <= 0 stay all-pad, the zero-degree convention).

    out_nbr/out_w: optional pre-initialized (pad / zero) destination
    views — the full build fills its final tables IN PLACE through
    them, avoiding a whole extra (N, C) transient pair at table scale
    (this file's standing memory contract)."""
    k = int(len(deg))
    nbr_tab = out_nbr if out_nbr is not None \
        else np.full((k, C), pad, dtype=np.int32)
    w_tab = out_w if out_w is not None \
        else np.zeros((k, C), dtype=np.float32)
    if k == 0:
        return nbr_tab, w_tab
    deg = np.asarray(deg, dtype=np.int64)
    edge_node = np.repeat(np.arange(k, dtype=np.int64), deg)
    offs0 = np.concatenate([[0], np.cumsum(deg)])
    pos_in_row = (np.arange(len(nbr_rows), dtype=np.int64)
                  - np.repeat(offs0[:-1], deg))
    small = deg <= C
    if small.any():
        keep = small[edge_node]
        nbr_tab[edge_node[keep], pos_in_row[keep]] = nbr_rows[keep]
        w_tab[edge_node[keep], pos_in_row[keep]] = ws[keep]
        del keep
    hubs = ~small
    if hubs.any():
        hub_edge = hubs[edge_node]
        he_node = edge_node[hub_edge]
        he_w = ws[hub_edge].astype(np.float64)
        he_nbr = nbr_rows[hub_edge]
        u = _edge_uniforms(seed, np.asarray(global_rows)[he_node],
                           pos_in_row[hub_edge])
        with np.errstate(divide="ignore", over="ignore"):
            key = np.where(he_w > 0,
                           np.exp(np.log(np.maximum(u, 1e-300)) /
                                  np.maximum(he_w, 1e-300)),
                           u - 2.0)
        del u
        # lexsort ≡ (row asc, key desc) at FULL key precision. The old
        # composite trick (row*4.0 − key in one f64) absorbed keys
        # smaller than the row index's ulp, so a row's subset silently
        # depended on its numeric index scale — patch blocks (small
        # local indices) and full builds (large global indices) would
        # tie-break differently and byte parity broke. Equal keys
        # (underflowed tiny weights) still break by within-row edge
        # order, which is row-local in both paths.
        order = np.lexsort((-key, he_node))
        del key
        he_node = he_node[order]
        # rank within row = position − first position of that row
        counts = np.bincount(he_node, minlength=k).astype(np.int64)
        starts = np.concatenate([[0], np.cumsum(counts)])
        rank = np.arange(he_node.size, dtype=np.int64) - starts[he_node]
        top = rank < C
        rows_t, cols_t = he_node[top], rank[top]
        sel = order[top]  # gather only kept entries — a full
        # he_*[order] copy would peak ~1GB transient at bench scale
        nbr_tab[rows_t, cols_t] = he_nbr[sel]
        w_tab[rows_t, cols_t] = he_w[sel].astype(np.float32)
        # rows with zero total weight revert to all-pad
        tot_by_row = np.bincount(edge_node[hub_edge],
                                 weights=ws[hub_edge], minlength=k)
        dead = hubs & (tot_by_row <= 0)
        if dead.any():
            nbr_tab[dead] = pad
            w_tab[dead] = 0.0
    return nbr_tab, w_tab


def _detect_uniform_rows(nbr_tab: np.ndarray, w_tab: np.ndarray,
                         pad: Optional[int] = None) -> bool:
    """True iff every row's positive-weight slots carry ONE equal weight
    and the positive slots are exactly the non-pad slots — the unweighted
    -graph case (cora/pubmed/ogbn-products and the bench graph all build
    with default edge weight 1.0). Under this condition the inverse-CDF
    draw is distribution-identical to a uniform draw over the row's
    degree, and sample_hop(uniform=True) may skip the cum-row gather
    entirely. Any weighted row (or an edge whose endpoint was missing
    and mapped to pad while keeping weight) clears the flag — a false
    positive would silently change the sampling distribution.

    The non-pad slots must additionally be FRONT-PACKED (contiguous in
    columns [0, deg)): the uniform draw's col = floor(u·deg) only ever
    reads that prefix, so an externally built from_arrays table with an
    interior pad slot would otherwise pass detection and silently sample
    pad rows while skipping real neighbors (advisor r5). Every in-repo
    builder front-packs; this guards the public rehydrate API.

    pad: the pad row id — pass it when nbr_tab is a ROW CHUNK of a
    larger table (from_arrays' chunked recompute), where shape[0] - 1
    is not the pad id. Chunk-wise conjunction is exact: every condition
    here is row-local."""
    if pad is None:
        pad = nbr_tab.shape[0] - 1
    C = nbr_tab.shape[1]
    nonpad = nbr_tab != pad
    pos = w_tab > 0
    if not (pos == nonpad).all():
        return False
    deg = nonpad.sum(axis=1)
    if not (nonpad == (np.arange(C) < deg[:, None])).all():
        return False
    rmax = w_tab.max(axis=1, keepdims=True)
    return bool(((w_tab == 0) | (w_tab == rmax)).all())


# Row-chunk size for table-scale host passes: bounds transients to
# chunk-sized arrays instead of full-table copies (~3.5GB at products
# scale, advisor r5). The uniform recompute holds ~2 f32 arrays per
# chunk; the Vose build holds ~8 f64/i64 working arrays per chunk, so
# it chunks finer to stay under one full-table f32 copy at any scale
# (the products-scale memory smoke pins this).
_CHUNK_ROWS = 262_144
_ALIAS_CHUNK_ROWS = 32_768

# Packed alias word layout (one int32 per slot; the layout contract for
# build_alias_tables and _alias_pick):
#   bits 16..30: alias column index (C <= 255 → 8 bits used)
#   bits  0..15: acceptance probability, quantized to uint16
#                (P(keep) = prob / 65535 — exact at 0 and 1)
# Pad/inactive slots and dead rows (total weight <= 0) hold -1: the
# sign bit doubles as the sentinel, so the device derives per-row
# active-column count as (word >= 0).sum(-1). Max packed value is
# 254<<16 | 65535 = 2^24 - 65537 < 2^24, so words always ride an f32
# lane exactly and _pick_cols' masked lane-sum applies unconditionally.
ALIAS_SENTINEL = np.int32(-1)
_ALIAS_PROB_MAX = 65535


def _check_alias_layout(alias: bool, fused: bool, shard_rows: bool):
    if alias and fused:
        raise ValueError(
            "DeviceNeighborTable(alias=True) needs the split nbr/cum "
            "layout — the fused [N+1, 2C] table has no slot for the "
            "alias words. Build with fused=False.")
    if alias and shard_rows:
        raise ValueError(
            "DeviceNeighborTable(alias=True) supports replicated tables "
            "only: the alias draw derives pad from the table shape, "
            "which row-sharding pads to the model-axis multiple. Use "
            "the weighted inverse-CDF path with row-sharded tables.")


def _vose_rows(w: np.ndarray, active: np.ndarray) -> np.ndarray:
    """Vectorized per-row Vose alias construction.

    w [R, C] float slot weights, active [R, C] bool (the columns the
    draw can land on: col0 = floor(u·K) with K = active.sum(row)) →
    packed int32 words [R, C] (layout above). Rows whose active weight
    totals <= 0 come back all-sentinel — the draw side resolves them to
    pad (the zero-degree convention).

    Two-pointer robin hood over per-row sorted scaled probabilities:
    each iteration finalizes exactly one column per live row (a small
    against the current large, a depleted large against the next one,
    or the terminal column), so the loop runs at most C+1 times with
    O(R) work per step — O(R·C) overall, no per-row Python loop."""
    R, C = w.shape
    out = np.full((R, C), ALIAS_SENTINEL, dtype=np.int32)
    if R == 0:
        return out
    w = np.where(active, w, 0.0).astype(np.float64)
    K = active.sum(axis=1).astype(np.int64)                 # [R]
    W = w.sum(axis=1)                                       # [R]
    live = (K > 0) & (W > 0)
    if not live.any():
        return out
    with np.errstate(invalid="ignore", divide="ignore"):
        p = w * (K[:, None] / W[:, None])                   # target 1.0
    # inactive columns sort to the far right and are never entered
    # (l starts at K-1); dead rows are skipped entirely
    p = np.where(active & live[:, None], p, np.inf)
    order = np.argsort(p, axis=1, kind="stable")            # ascending
    p_ord = np.take_along_axis(p, order, axis=1)            # [R, C]
    prob = np.ones((R, C))          # final prob, by sorted position
    alias = order.copy()            # final alias TARGET COLUMN, ditto
    s = np.zeros(R, dtype=np.int64)                 # next small (left)
    l = np.maximum(K - 1, 0)                        # current large
    rem = np.take_along_axis(p_ord, l[:, None], axis=1)[:, 0]
    done = ~live
    for _ in range(C + 1):
        a = np.flatnonzero(~done)
        if a.size == 0:
            break
        fin = s[a] >= l[a]
        f = a[fin]
        if f.size:
            # terminal column: mass conservation leaves rem ≈ 1 here
            prob[f, l[f]] = np.clip(rem[f], 0.0, 1.0)
            done[f] = True
        r = a[~fin]
        if r.size:
            sm = rem[r] >= 1.0
            rs = r[sm]          # finalize the next small against l
            if rs.size:
                ps = p_ord[rs, s[rs]]
                prob[rs, s[rs]] = np.clip(ps, 0.0, 1.0)
                alias[rs, s[rs]] = order[rs, l[rs]]
                rem[rs] += ps - 1.0
                s[rs] += 1
            rd = r[~sm]         # current large depleted: it becomes a
            if rd.size:         # small, finalized against the next one
                prob[rd, l[rd]] = np.clip(rem[rd], 0.0, 1.0)
                alias[rd, l[rd]] = order[rd, l[rd] - 1]
                l[rd] -= 1
                rem[rd] = p_ord[rd, l[rd]] + rem[rd] - 1.0
    q = np.rint(prob * _ALIAS_PROB_MAX).astype(np.int64)
    words = (alias.astype(np.int64) << 16) | q
    # scatter back from sorted position to actual column, live active
    # slots only — everything else keeps the sentinel
    keep = live[:, None] & (np.arange(C)[None, :] < K[:, None])
    ri, pi = np.nonzero(keep)
    out[ri, order[ri, pi]] = words[ri, pi].astype(np.int32)
    return out


def _alias_patch_counter(kind: str):
    """alias_rows_{patched,rebuilt}_total: rows whose Vose alias words
    were re-derived incrementally (patched — O(dirty) delta maintenance)
    vs by a full-table build (rebuilt). The streaming-mutation bench
    gates on patched/rebuilt staying ≤ 10% for a 1% delta."""
    from euler_tpu import obs

    helps = {
        "patched": "alias/table rows re-derived by incremental patching",
        "rebuilt": "alias table rows built by full-table builds",
    }
    return obs.default_registry().counter(
        f"alias_rows_{kind}_total", helps[kind])


def _alias_rows_block(nb: np.ndarray, w: np.ndarray,
                      pad: int) -> np.ndarray:
    """Packed alias words for one row block (explicit pad id — the
    block need not carry the table's trailing pad row). Per row the
    active draw columns are the front-packed non-pad prefix [0, deg)
    when the row IS front-packed, else all C columns (pad slots then
    carry prob 0 and alias into a real slot)."""
    C = nb.shape[1]
    cols = np.arange(C)
    nonpad = nb != pad
    deg = nonpad.sum(axis=1)
    front = (nonpad == (cols < deg[:, None])).all(axis=1)
    active = np.where(front[:, None], cols < deg[:, None], True)
    return _vose_rows(w, active)


def build_alias_tables(nbr_tab: np.ndarray,
                       cum_tab: Optional[np.ndarray] = None,
                       w_tab: Optional[np.ndarray] = None,
                       chunk_rows: int = _ALIAS_CHUNK_ROWS) -> np.ndarray:
    """[N+1, C] neighbor table (+ slot weights, given directly or as the
    inclusive cumsum) → [N+1, C] packed int32 alias table (word layout
    at ALIAS_SENTINEL above) — the device transpose of the reference's
    euler/common/alias_method.h, built once per table like the
    CompactWeightedCollection cum rows.

    Per row the active draw columns are the front-packed non-pad prefix
    [0, deg) when the row IS front-packed, else all C columns (pad slots
    then carry prob 0 and alias into a real slot) — either way the
    device-side count of non-sentinel words equals the builder's K, so
    col = floor(u·K) is always in range and never skips a real slot,
    even for externally built from_arrays tables with interior pads.

    Chunked over rows: peak transient is O(chunk_rows · C) floats, never
    a full-table f32 copy (the products-scale memory contract, pinned by
    the slow alias-build smoke)."""
    if (cum_tab is None) == (w_tab is None):
        raise ValueError(
            "build_alias_tables needs exactly one of cum_tab / w_tab")
    n_rows, C = nbr_tab.shape
    if C > 255:
        raise ValueError(
            f"alias words pack the column index into 8 bits — cap C "
            f"must be <= 255, got {C}")
    pad = n_rows - 1
    out = np.empty((n_rows, C), dtype=np.int32)
    for lo in range(0, n_rows, max(int(chunk_rows), 1)):
        hi = min(lo + max(int(chunk_rows), 1), n_rows)
        nb = np.asarray(nbr_tab[lo:hi])
        if w_tab is not None:
            w = np.asarray(w_tab[lo:hi]).astype(np.float32, copy=False)
        else:
            cc = np.asarray(cum_tab[lo:hi]).astype(np.float32,
                                                   copy=False)
            w = np.diff(cc, axis=1,
                        prepend=np.zeros((cc.shape[0], 1), np.float32))
        out[lo:hi] = _alias_rows_block(nb, w, pad)
    _alias_patch_counter("rebuilt").inc(n_rows)
    return out


def _pick_cols(row: jax.Array, col: jax.Array, exact_f32: bool):
    """row [n, C], col [n, k] → row[i, col[i, j]] [n, k].

    take_along_axis lowers to an n·k single-element gather on TPU —
    element-count-bound exactly like the retired flat pick (round-5
    probes: 4.9M picks ≈ 40ms inside the 90ms hop-2 sample while the
    row gather itself is 22ms). When ids fit f32 exactly (table rows
    <= 2^24) the pick is instead a masked lane-sum over the C columns
    already staged by the row gather — fused VPU work, no gather."""
    if not exact_f32:
        return jnp.take_along_axis(row, col, axis=1)
    C = row.shape[1]
    iota = jnp.arange(C, dtype=jnp.int32)
    ind = iota[None, None, :] == col[:, :, None]          # [n, k, C]
    return (row[:, None, :].astype(jnp.float32) * ind).sum(-1) \
        .astype(row.dtype)


# The stored form of the [N+1, C] row tables, defined ONCE here: int8
# [N+1, 4C], the rows' own bytes in BYTE PLANES — stored[:, b*C + j] is
# byte b (little-endian) of word j. Same bytes in HBM as the words; a
# row lies along the lanes of one tile, and putting the words back
# together is four contiguous lane slices, shifts and ors (an
# interleaved view would need a strided pick a word, which the chip's
# compiler turned into whole-array relayouts: PERF.md, PR 29). The
# logical dtype of each table is part of the contract, not of the
# stored array.
_ROW_DTYPES = {"nbr": np.dtype("<i4"), "cum": np.dtype("<f4"),
               "alias": np.dtype("<i4")}


class StoredInfo(NamedTuple):
    """What a stored row table says of the logical one. pad_row is the
    id of the trailing all-pad row of a REPLICATED table (row-sharding
    pads the row count up to the model-axis multiple: those consumers
    take the pad from the rows' content); ids_exact_f32: every row id
    rides an f32 lane exactly (_pick_cols' masked lane-sum)."""
    cap: int
    pad_row: int
    ids_exact_f32: bool


def stored_info(stored) -> StoredInfo:
    return StoredInfo(cap=stored.shape[1] // 4,
                      pad_row=stored.shape[0] - 1,
                      ids_exact_f32=stored.shape[0] <= (1 << 24))


def store_rows(tab: np.ndarray, table: str) -> np.ndarray:
    """Host [n, C] rows of table `table` ("nbr" | "cum" | "alias") →
    their stored form, int8 [n, 4C] (one pass over the bytes)."""
    tab = np.ascontiguousarray(tab, dtype=_ROW_DTYPES[table])
    n, cap = tab.shape
    planes = tab.view(np.int8).reshape(n, cap, 4).transpose(0, 2, 1)
    return np.ascontiguousarray(planes).reshape(n, 4 * cap)


def logical_rows(stored, table: str) -> np.ndarray:
    """A stored table (device or host) → its logical [n, C] rows on
    the host, bit for bit (store_rows' inverse), in memory of their own."""
    stored = np.asarray(stored)
    n, cap = stored.shape[0], stored.shape[1] // 4
    words = stored.reshape(n, 4, cap).transpose(0, 2, 1)
    return np.array(words, order="C").view(_ROW_DTYPES[table]) \
        .reshape(n, cap)


def take_rows(stored: jax.Array, rows: jax.Array, table: str,
              gather=None) -> jax.Array:
    """stored [N+1, 4C] int8, rows [n] in [0, N] → the logical
    table[rows], [n, C] in the table's dtype, bit for bit: ONE gather of
    4C-byte rows (the feature gather's kernel), then the byte planes
    put back together as words. Row ids are clipped to the table, not
    tested and filled (a second pass over the gathered bytes). gather
    (make_table_gather) routes the read of a row-sharded table: its
    masked take + psum is exact on bytes (the owner's plus zeros).
    Counted at trace time:
    traced_paths_total{path="table_rows_stored",detail=<table>}."""
    from euler_tpu import obs

    if stored.dtype != jnp.int8:
        raise TypeError(
            f"take_rows reads a STORED table (store_rows: int8 "
            f"[N+1, 4C]), got {stored.dtype}{list(stored.shape)}: a "
            "logical [N+1, C] table would be read as garbage")
    obs.traced_path("table_rows_stored", table)
    if gather is None:
        got = jnp.take(stored, rows, axis=0, mode="clip")  # [n, 4C] i8
    else:
        got = gather(stored, rows)
    cap = got.shape[1] // 4
    b = got.astype(jnp.int32)                  # sign-extended bytes
    words = ((b[:, :cap] & 0xff)
             | ((b[:, cap:2 * cap] & 0xff) << 8)
             | ((b[:, 2 * cap:3 * cap] & 0xff) << 16)
             | (b[:, 3 * cap:] << 24))
    return jax.lax.bitcast_convert_type(words, _ROW_DTYPES[table])


def fuse_tables_host(nbr_tab: np.ndarray, cum_tab: np.ndarray) -> np.ndarray:
    """Host-side fuse_tables (numpy view bitcast, no device transfer) —
    the layout contract is defined ONCE here; fuse_tables mirrors it on
    device and a unit test pins the two equal bit-for-bit."""
    return np.concatenate(
        [np.asarray(nbr_tab).astype(np.int32, copy=False),
         np.asarray(cum_tab).astype(np.float32, copy=False)
            .view(np.int32)], axis=1)


def fuse_tables(nbr_tab, cum_tab):
    """Interleave neighbor ids and cumulative weights into one
    [N+1, 2C] int32 table (cum bitcast to i32): sample_hop then reads a
    node's full sampling state with ONE 2C-wide row gather instead of a
    cum-row gather plus a separate flattened neighbor-id gather. At
    products scale the per-hop gathers are the step's dominant cost, so
    halving the gather count on the sampling side is a direct win; the
    f32 bits ride an i32 lane and are bitcast back in-jit (exact).
    Layout contract shared with fuse_tables_host."""
    import jax.numpy as jnp

    nbr = jnp.asarray(nbr_tab)
    cum_bits = jax.lax.bitcast_convert_type(
        jnp.asarray(cum_tab, jnp.float32), jnp.int32)
    return jnp.concatenate([nbr.astype(jnp.int32), cum_bits], axis=1)


def draw_scope(hop: int):
    """The name the device program's ops of hop `hop`'s neighbour draw
    carry (`draw/hop<h>`, h from 1), whichever draw it takes: uniform,
    inverse-CDF, alias or fused. The trace's per-kernel metrics find the
    draw by it (benchmark/scope_readers.py)."""
    return jax.named_scope(f"draw/hop{hop}")


def sample_hop_fused(fused_table: jax.Array, rows: jax.Array,
                     count: int, key, gather=None) -> jax.Array:
    """sample_hop over a fuse_tables() layout: one row gather yields
    both the C neighbor ids and the C cumulative weights; the chosen
    column is then picked locally with take_along_axis (operand already
    in registers/VMEM — no second HBM gather).

    gather (make_table_gather) routes the row read for row-sharded fused
    tables: one masked local take + psum per hop — still half the
    collectives of the split-sharded path."""
    C = fused_table.shape[1] // 2
    n = rows.shape[0]
    if gather is None:
        row = jnp.take(fused_table, rows, axis=0)          # [n, 2C]
    else:
        row = gather(fused_table, rows)                    # [n, 2C]
    nbr = row[:, :C]
    cum = jax.lax.bitcast_convert_type(row[:, C:], jnp.float32)
    total = cum[:, -1]
    u = jax.random.uniform(key, (n, count)) * total[:, None]
    col = (cum[:, None, :] <= u[:, :, None]).sum(-1)
    col = jnp.clip(col, 0, C - 1).astype(jnp.int32)
    return jnp.take_along_axis(nbr, col, axis=1).reshape(-1)


def sample_fanout_rows_fused(fused_table: jax.Array, roots: jax.Array,
                             fanouts: Sequence[int], key, gather=None):
    """sample_fanout_rows over a fuse_tables() layout."""
    layers = [roots]
    cur = roots
    for hop, k in enumerate(fanouts, 1):
        key, sub = jax.random.split(key)
        with draw_scope(hop):
            cur = sample_hop_fused(fused_table, cur, int(k), sub, gather)
        layers.append(cur)
    return layers


def is_model_sharded(mesh: Optional[jax.sharding.Mesh],
                     axis: str = "model") -> bool:
    """True when `mesh` has a non-trivial model axis — i.e. HBM tables
    built against it are actually row-sharded and reads must go through
    make_table_gather's masked-take+psum path. The single definition of
    the triviality rule (models and make_table_gather both use it)."""
    return mesh is not None and dict(mesh.shape).get(axis, 1) > 1


def make_table_gather(mesh: Optional[jax.sharding.Mesh] = None,
                      axis: str = "model", data_axis: str = "data",
                      hub_cache=None):
    """gather(table, rows) → table[rows] for HBM-resident tables.

    Replicated tables (mesh None / trivial model axis) → a plain local
    take. Row-sharded tables (placement.put_row_sharded) → the classic
    TPU sharded-embedding lookup: each chip takes its local row slice
    with out-of-range rows masked to zero, then one psum over the
    'model' axis reassembles full rows. One collective per gather, rides
    ICI; per-chip table memory stays 1/mp. rows must be shardable over
    the 'data' axis (batch and hop widths are multiples of it).

    hub_cache (a replicated [H, ...] copy of the table's first H rows —
    the PartitionedFeatureStore hub-first layout) wraps the gather in
    cache-first routing: rows < H are served from the local replica and
    never enter the psum leg (partitioned_store.hub_routed_take). Only
    meaningful for tables sharing that layout; pass per-table, since
    each table has its own cache."""
    if not is_model_sharded(mesh, axis):
        base = lambda tab, rows: jnp.take(tab, rows, axis=0)  # noqa: E731
        if hub_cache is not None:
            from euler_tpu.parallel.partitioned_store import (
                hub_routed_take,
            )

            return hub_routed_take(base, hub_cache)
        return base
    from functools import partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mp = dict(mesh.shape)[axis]

    def gather(tab, rows):
        if tab.shape[0] % mp:
            raise ValueError(
                f"make_table_gather: table has {tab.shape[0]} rows, not "
                f"divisible by the '{axis}' axis size {mp}. Row-sharded "
                "tables must be placed with placement.put_row_sharded "
                "(which pads rows to a multiple of the axis); a "
                "replicated table should use the local-take path "
                "(model table_mesh=None / shard_rows=False throughout)")
        per = tab.shape[0] // mp
        shape = rows.shape
        rows_flat = rows.reshape(-1)
        nd = tab.ndim - 1

        @partial(shard_map, mesh=mesh,
                 in_specs=(P(axis, *([None] * nd)), P(data_axis)),
                 out_specs=P(data_axis, *([None] * nd)))
        def _g(tab_loc, r_loc):
            lo = jax.lax.axis_index(axis) * per
            loc = r_loc - lo
            ok = (loc >= 0) & (loc < per)
            loc = jnp.clip(loc, 0, per - 1)
            out = jnp.take(tab_loc, loc, axis=0)
            mask = ok.reshape(ok.shape + (1,) * nd)
            out = jnp.where(mask, out, jnp.zeros((), out.dtype))
            return jax.lax.psum(out, axis)

        return _g(tab, rows_flat).reshape(shape + tab.shape[1:])

    if hub_cache is not None:
        from euler_tpu.parallel.partitioned_store import hub_routed_take

        # flatten before routing: hub_routed_take's [..., None] mask
        # broadcast and the pad redirect both operate on flat rows,
        # exactly like the sharded gather itself
        routed = hub_routed_take(gather, hub_cache)

        def gather_hub(tab, rows):
            shape = rows.shape
            out = routed(tab, rows.reshape(-1))
            return out.reshape(shape + tab.shape[1:])

        return gather_hub
    return gather


def slot_weights(cum_rows: jax.Array) -> jax.Array:
    """Inclusive cumulative-weight rows [n, C] → per-slot edge weights
    [n, C]. The inverse of the cumsum in DeviceNeighborTable's layout —
    defined HERE, next to the layout contract, and shared by every
    consumer that needs raw slot weights (device_walk's node2vec bias,
    device_layerwise's pool draws)."""
    return jnp.diff(cum_rows, axis=1,
                    prepend=jnp.zeros_like(cum_rows[:, :1]))


def _alias_pick(alias_rows: jax.Array, u1: jax.Array, u2: jax.Array):
    """alias_rows [n, C] packed words, u1/u2 [n, k] uniforms →
    (col [n, k] int32, deg [n] int32): the O(1) alias draw.

    col0 = floor(u1·deg) over the row's active columns (deg = count of
    non-sentinel words — C compares on data the row gather already
    staged, the same trick the uniform path uses for pad counting),
    then ONE word read decides: keep col0 with P = prob/65535, else
    jump to the packed alias column. No [n, k, C] f32 broadcast-compare
    and no per-draw dependence on C — the inverse-CDF's cum-row scan is
    what the round-5 profile fingered inside the 90ms hop-2 draw. The
    word read uses _pick_cols' masked lane-sum (packed words always fit
    f32 exactly — see the layout note at ALIAS_SENTINEL).

    Dead rows (all-sentinel: pad row, zero-degree, zero-total-weight)
    come back with deg = 0 and col = 0 — callers resolve them to the
    pad row."""
    C = alias_rows.shape[1]
    deg = (alias_rows >= 0).sum(-1).astype(jnp.int32)          # [n]
    col0 = jnp.minimum(
        (u1 * deg[:, None].astype(jnp.float32)).astype(jnp.int32),
        jnp.maximum(deg[:, None] - 1, 0))                      # [n, k]
    word = _pick_cols(alias_rows, col0, True)                  # [n, k]
    prob = jnp.bitwise_and(word, _ALIAS_PROB_MAX)
    ali = jnp.right_shift(word, 16)                # arithmetic: -1 → -1
    keep = u2 * float(_ALIAS_PROB_MAX) < prob.astype(jnp.float32)
    col = jnp.where(keep, col0, ali)
    return jnp.clip(col, 0, C - 1).astype(jnp.int32), deg


def sample_hop(nbr_table: jax.Array, cum_table: jax.Array,
               rows: jax.Array, count: int, key,
               gather=None, uniform: bool = False,
               alias_table=None) -> jax.Array:
    """One weighted neighbor draw per (row, slot): [n] → [n * count].

    Inverse-CDF over each row's C inclusive cumulative weights — the
    device transpose of CompactWeightedCollection's binary search (C is
    small and fixed, so C vectorized compares beat a gather-heavy
    log-search). Zero-degree rows (total weight 0) resolve to the pad
    slot, whose neighbor entry is pad_row.

    nbr_table / cum_table / alias_table are the STORED tables
    (store_rows; DeviceNeighborTable.tables); every row read is
    take_rows. TPU gather cost here is bound by the number of gathered
    ROWS, not by bytes, so the whole [n, C] neighbor row is read once
    per node and the count columns are picked locally (_pick_cols: a
    masked lane-sum when ids fit f32, because take_along_axis lowers to
    an element-count-bound gather), whatever the count: n rows for n
    nodes, where a flat pick of single elements would issue n·count.

    uniform=True (tables whose rows are unit-weight —
    DeviceNeighborTable.uniform_rows) skips the cum-row gather
    entirely: ONE neighbor-row gather per hop, degree derived from the
    row's pad count, column = floor(u·deg). Distribution-identical to
    the inverse-CDF draw on such tables (not draw-for-draw — different
    u consumption). Replicated tables only: the row-sharded layout pads
    the row count up to the model-axis multiple, so pad cannot be
    derived from shape there (walk_rows has the same constraint).

    alias_table (DeviceNeighborTable(alias=True) /
    build_alias_tables): the Vose alias draw — O(1) per draw via one
    packed-word read instead of the C-wide inverse-CDF scan, at the
    same gather element count (the alias row gather replaces the
    cum-row gather). Distribution-identical to the inverse-CDF draw up
    to the uint16 prob quantization (< 1e-5 per slot; chi-squared
    pinned in tests), NOT draw-for-draw (different u consumption).
    Replicated split tables only, and exclusive with uniform=True —
    callers resolve precedence explicitly.

    gather (make_table_gather) routes table reads for row-sharded
    tables; that path always has the full rows and picks locally."""
    info = stored_info(nbr_table)
    n = rows.shape[0]
    if alias_table is not None:
        if gather is not None:
            raise ValueError(
                "sample_hop(alias_table=...) supports replicated tables "
                "only: the alias draw resolves dead rows to the pad id "
                "derived from the table shape, which row-sharding pads "
                "to the model-axis multiple. Use the weighted path "
                "(alias_table=None) with row-sharded tables.")
        if uniform:
            raise ValueError(
                "sample_hop: uniform=True and alias_table are exclusive "
                "— resolve the precedence at the call site (the alias "
                "draw already covers unit-weight tables)")
        arow = take_rows(alias_table, rows, "alias")   # [n, C]
        u = jax.random.uniform(key, (2, n, count))
        col, deg = _alias_pick(arow, u[0], u[1])
        nbr = take_rows(nbr_table, rows, "nbr")        # [n, C]
        out = _pick_cols(nbr, col, info.ids_exact_f32)
        # dead rows (zero degree / zero total weight) resolve to pad
        return jnp.where(deg[:, None] > 0, out, info.pad_row).reshape(-1)
    if uniform:
        if gather is not None:
            raise ValueError(
                "sample_hop(uniform=True) supports replicated tables "
                "only: a row-sharded table's row count is padded to the "
                "model-axis multiple, so the pad id cannot be derived "
                "from its shape. Use the weighted path (uniform=False) "
                "with row-sharded tables.")
        nbr = take_rows(nbr_table, rows, "nbr")        # [n, C]
        deg = (nbr != info.pad_row).sum(-1).astype(jnp.float32)    # [n]
        u = jax.random.uniform(key, (n, count))
        col = jnp.minimum((u * deg[:, None]).astype(jnp.int32),
                          jnp.maximum(
                              deg[:, None].astype(jnp.int32) - 1, 0))
        return _pick_cols(nbr, col, info.ids_exact_f32).reshape(-1)
    cum = take_rows(cum_table, rows, "cum", gather)    # [n, C]
    total = cum[:, -1]
    u = jax.random.uniform(key, (n, count)) * total[:, None]   # [n, k]
    col = (cum[:, None, :] <= u[:, :, None]).sum(-1)   # [n, k]
    col = jnp.clip(col, 0, info.cap - 1).astype(jnp.int32)
    nbr = take_rows(nbr_table, rows, "nbr", gather)    # [n, C]
    return _pick_cols(nbr, col, info.ids_exact_f32).reshape(-1)


def sample_fanout_rows(nbr_table: jax.Array, cum_table: jax.Array,
                       roots: jax.Array, fanouts: Sequence[int], key,
                       gather=None, uniform: bool = False,
                       alias_table=None):
    """Multi-hop on-device fanout: returns [roots, hop1, hop2, ...] row
    arrays (layer h has roots.shape[0] * prod(fanouts[:h]) entries) —
    the shape contract of FanoutDataFlow, produced without touching the
    host. uniform=True → the one-gather unit-weight path per hop;
    alias_table → the O(1) alias draw per hop (see sample_hop)."""
    layers = [roots]
    cur = roots
    for hop, k in enumerate(fanouts, 1):
        key, sub = jax.random.split(key)
        with draw_scope(hop):
            cur = sample_hop(nbr_table, cum_table, cur, int(k), sub,
                             gather, uniform=uniform,
                             alias_table=alias_table)
        layers.append(cur)
    return layers
