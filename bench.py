"""Throughput benchmark: GraphSAGE training over an ogbn-products-shaped
synthetic graph. Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Baseline (BASELINE.json): GraphSAGE on ogbn-products >= 1M edges/sec/chip.
"edges/sec" counts message-passing edges aggregated per training step
(sum over hops of batch * prod(fanouts[:h+1])), the standard sampled-GNN
throughput accounting.

Modes:
  python bench.py            # full bench: requires the TPU, exits non-zero
                             # and prints no metric without one
  python bench.py --smoke    # small/fast CPU sanity run (what the tests use)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# Resolved steps_per_loop when --steps_per_loop is unset on TPU. 32 since
# the round-5 on-chip A/B (28.81M vs 28.27M edges/s at spl=16 under the
# int8 default; stacking degsort+pad on top added only +0.2% — PERF.md).
TPU_STEPS_PER_LOOP = 32


def build_products_like(n_nodes: int, avg_degree: int, feat_dim: int,
                        num_classes: int, seed: int = 0):
    """Synthetic graph with ogbn-products-like statistics (power-lawish
    degrees, class-correlated features)."""
    from euler_tpu.dataset.base_dataset import synthetic_citation

    data = synthetic_citation(
        "bench", n=n_nodes, d=feat_dim, num_classes=num_classes,
        intra_degree=avg_degree * 0.75, inter_degree=avg_degree * 0.25,
        signal=1.0, seed=seed,
        train_per_class=max(20, n_nodes // (num_classes * 10)),
        val=n_nodes // 20, test=n_nodes // 10)
    return data


def _degree_sort_tables(nbr, cum, feat, label):
    """Permute node rows so high-degree nodes occupy the lowest row
    numbers. Gathered rows are degree-biased (a random edge endpoint is
    proportionally a hub), so packing hubs into a compact prefix of the
    HBM tables turns scattered reads into a hot region — a pure
    relabeling (quality- and distribution-neutral: roots are uniform
    over rows either way). Telemetry flag --degree_sorted; A/B probe
    for the products-scale gather locality loss (57M small-graph vs
    27.5M products, PERF.md)."""
    n = nbr.shape[0] - 1                      # trailing pad row stays
    deg = (nbr[:n] != n).sum(axis=1)
    order = np.argsort(-deg, kind="stable")   # old rows, hot first
    inv = np.empty(n + 1, np.int32)
    inv[order] = np.arange(n, dtype=np.int32)
    inv[n] = n                                # pad maps to pad

    def permute(x, remap=False):
        # true one-copy-per-table: np.take with out= avoids the
        # fancy-indexing temporary, and the nbr remap rewrites the
        # permuted buffer in place — multi-GB tables at products scale
        # must not hold extra transient copies during setup
        out = np.empty_like(x)
        np.take(x, order, axis=0, out=out[:n])
        out[n] = x[n]                         # pad row kept verbatim
        if remap:
            np.take(inv, out, out=out)
        return out

    return (permute(nbr, remap=True), permute(cum),
            permute(feat), permute(label))


def _uniform_effective(args, sampler) -> bool:
    """Resolve the --uniform_path tri-state against the table: default
    (None) auto-enables on unit-weight tables (the one-gather sampling
    path, round-5 on-chip win); forcing it ON over a weighted table is
    refused — it would silently change the sampling distribution.
    Forcing it ON when the path can't apply at all (--fused_sampler /
    --host_sampler / --alias_sampler) is refused the same way: a
    silently-recorded uniform_path=False would mislabel the A/B leg
    (advisor r5)."""
    if sampler is None or getattr(sampler, "fused", False) \
            or getattr(sampler, "alias", False):
        if args.uniform_path:
            # explicit force on an inapplicable config: refuse rather
            # than silently record uniform_path=False on the artifact
            reason = "--host_sampler leaves no device table" \
                if sampler is None else (
                    "--fused_sampler keeps the weighted fused draw"
                    if getattr(sampler, "fused", False)
                    else "--alias_sampler selects the alias draw")
            print(f"bench: --uniform_path forced but inapplicable "
                  f"({reason}) — drop one of the flags", file=sys.stderr)
            sys.exit(2)
        return False
    detected = bool(getattr(sampler, "uniform_rows", False))
    if args.uniform_path is None:
        return detected
    if args.uniform_path and not detected:
        print("bench: --uniform_path forced on a weighted table "
              "(uniform_rows=False) — refusing; the uniform draw would "
              "not match the table's weights", file=sys.stderr)
        sys.exit(2)
    return bool(args.uniform_path)


def _sampler_variant(args, sampler, has_uniform_path: bool = True) -> str:
    """The draw algorithm the measured run actually used — recorded in
    detail JSON so A/B leg artifacts are self-describing (the 'sampler'
    key only says host/device/device_fused). has_uniform_path=False for
    modes whose draw never consults the uniform lever (layerwise's pool
    draw) — recording 'uniform' there would mislabel the artifact."""
    if sampler is None:
        return "host_pipelined" if int(
            getattr(args, "host_pipeline", 0) or 0) > 1 else "host"
    if getattr(sampler, "fused", False):
        return "fused"
    if getattr(sampler, "alias", False):
        return "alias"
    if not has_uniform_path:
        return "inverse_cdf"
    return "uniform" if _uniform_effective(args, sampler) \
        else "inverse_cdf"


class _CachedGraph:
    """Minimal engine facade over the bench table cache: dense ids
    (row == id), uniform unit node weights — so sample_node(-1) matches
    the real engine's draw. The cache does not carry per-node types, so
    a typed draw (node_type >= 0) would silently change the measured
    workload between cache states — refuse it instead (the bench always
    trains with train_node_type=-1)."""

    def __init__(self, n_nodes: int, edge_count: int, seed: int = 17):
        self.node_count = int(n_nodes)
        self.edge_count = int(edge_count)
        self._rng = np.random.default_rng(seed)

    def sample_node(self, count: int, node_type: int = -1) -> np.ndarray:
        if node_type >= 0:
            raise ValueError(
                "_CachedGraph has no node types; run with "
                "train_node_type=-1 or --no_cache")
        return self._rng.integers(
            0, self.node_count, count).astype(np.uint64)


def _partition_from_hosts(args, nbr_h, cum_h, feat_h, label_h, stats,
                          dt, quant, fused, alias, lookup_graph=None):
    """--partition K: mesh-partitioned feature store (hub-first row
    relabeling, PartitionedFeatureStore) + the neighbor/label tables
    remapped into the same row space. Neighbor tables stay REPLICATED
    (their bytes are cap-bounded); the feature table is the capacity
    lever, split 1/K over the 'model' axis with the top
    --hub_cache_frac degree-ranked rows replicated in front.

    Degree ranking here comes from the capped neighbor table (the
    cache carries no raw degrees) — a ranking proxy: rows above the
    cap tie, so WHICH saturated hubs fill the cache is arbitrary but
    the cache height and routing are exact. The engine-true ranking
    A/B lives in tools/bench_host.py --mode table."""
    import jax
    from jax.sharding import Mesh

    from euler_tpu.parallel import (
        DeviceNeighborTable, PartitionedFeatureStore,
    )
    from euler_tpu.parallel.placement import put_replicated

    k = int(args.partition)
    devs = np.asarray(jax.devices()[:k]).reshape(1, k)
    mesh = Mesh(devs, ("data", "model"))
    n = nbr_h.shape[0] - 1
    deg = (np.asarray(nbr_h[:n]) != n).sum(axis=1).astype(np.int64)
    store = PartitionedFeatureStore.from_arrays(
        np.asarray(feat_h).astype(np.dtype(dt), copy=False), deg,
        mesh=mesh, hub_cache_frac=float(args.hub_cache_frac),
        quantize=quant, scale_dtype=dt)
    if lookup_graph is not None:
        # real engine: ids are NOT dense rows — lookup() must translate
        # through the engine's row order before the hub-first perm
        store._graph = lookup_graph
    nbr_p = store.apply_permutation(np.asarray(nbr_h),
                                    remap_values=True)
    cum_p = store.apply_permutation(np.asarray(cum_h))
    lab_p = store.apply_permutation(np.asarray(label_h))
    store.labels = put_replicated(
        lab_p.astype(np.float32, copy=False), mesh)
    sampler = DeviceNeighborTable.from_arrays(
        nbr_p, cum_p, stats=stats, mesh=mesh, fused=fused, alias=alias)
    return store, sampler


def setup_tables(args, n_nodes, avg_degree, feat_dim, num_classes,
                 use_cache: bool):
    """Build (or load from the local cache) the HBM-resident bench
    tables. The cache only skips host-side SETUP — the measured training
    loop is identical either way; detail.graph_cache records provenance."""
    import jax.numpy as jnp

    from euler_tpu.parallel import DeviceFeatureStore, DeviceNeighborTable

    dt = jnp.bfloat16 if args.bf16 else jnp.float32
    # walk models (DeviceSampledSkipGram → walk_rows) read the split
    # nbr/cum tables; the fused layout only serves the fanout path
    fused = args.fused_sampler and not args.walk and not args.layerwise
    # the alias draw serves all three families (fanout/walk/layerwise);
    # conflicts vs fused/host are refused up front in run_bench
    alias = bool(args.alias_sampler)
    if args.fused_sampler and args.walk:
        print("bench: --fused_sampler ignored in --walk mode "
              "(walk_rows reads the split tables)", file=sys.stderr)
    if args.fused_sampler and args.layerwise:
        print("bench: --fused_sampler ignored in --layerwise mode "
              "(pool weights come from the split cum table)",
              file=sys.stderr)
    pad_features = args.pad_features and not args.walk
    if args.pad_features and args.walk:
        print("bench: --pad_features ignored in --walk mode (the skip-"
              "gram model embeds ids, no feature table)", file=sys.stderr)
    # int8 is default-on; in --walk mode it is a silent no-op (the
    # skip-gram model embeds ids, no feature table)
    quant = "int8" if (args.int8_features and not args.walk) else None
    cache_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             ".bench_cache")
    # precision rides the key: a bf16-written cache holds bf16-quantized
    # features and must not serve an --fp32 run
    key = (f"g_n{n_nodes}_d{avg_degree}_f{feat_dim}_c{num_classes}"
           f"_cap{args.cap}_{'bf16' if args.bf16 else 'fp32'}_v1.npz")
    path = os.path.join(cache_dir, key)
    if use_cache and os.path.exists(path):
        z = np.load(path)
        stats = {k: z[k].item() for k in
                 ("hub_frac", "edge_keep_frac", "max_degree")}
        if "uniform_rows" in z.files:  # absent in pre-round-5 caches →
            # from_arrays recomputes from the tables
            stats["uniform_rows"] = bool(z["uniform_rows"].item())
        nbr_h, cum_h = z["nbr"], z["cum"]
        feat_h, label_h = z["feat"], z["label"]
        if args.degree_sorted:
            # host_sampler runs never reach this branch (they always
            # rebuild: use_cache=False in run_bench)
            nbr_h, cum_h, feat_h, label_h = _degree_sort_tables(
                nbr_h, cum_h, feat_h, label_h)
        if args.partition:
            store, sampler = _partition_from_hosts(
                args, nbr_h, cum_h, feat_h, label_h, stats, dt, quant,
                fused, alias)
            return (_CachedGraph(n_nodes, int(z["edge_count"])), store,
                    sampler, "hit")
        sampler = None if args.host_sampler else \
            DeviceNeighborTable.from_arrays(nbr_h, cum_h, stats=stats,
                                            fused=fused, alias=alias)
        store = DeviceFeatureStore.from_arrays(
            feat_h.astype(np.dtype(dt), copy=False), label_h,
            pad_dim_to=128 if pad_features else None,
            quantize=quant, scale_dtype=dt)
        graph = _CachedGraph(n_nodes, int(z["edge_count"]))
        return graph, store, sampler, "hit"
    if args.degree_sorted:
        print("bench: --degree_sorted applies only to cache-served runs "
              "(this is a rebuild/smoke/host path) — measured UNSORTED",
              file=sys.stderr)
    data = build_products_like(n_nodes, avg_degree, feat_dim, num_classes)
    graph = data.engine
    if args.partition:
        # rebuild path: host tables built once (keep_host), then
        # relabeled hub-first and re-placed partitioned
        sampler_h = DeviceNeighborTable(graph, cap=args.cap,
                                        keep_host=True)
        ids = graph.all_node_ids()
        feats = graph.get_dense_feature(ids, ["feature"])
        if isinstance(feats, list):
            feats = np.concatenate(feats, axis=1)
        feats = np.concatenate(
            [feats, np.zeros((1, feats.shape[1]), feats.dtype)])
        labels = graph.get_dense_feature(ids, "label", num_classes)
        labels = np.concatenate(
            [labels, np.zeros((1, labels.shape[1]), labels.dtype)])
        nbr_h, cum_h = sampler_h.host_tables
        stats = {k: getattr(sampler_h, k) for k in
                 ("hub_frac", "edge_keep_frac", "max_degree",
                  "uniform_rows")}
        store, sampler = _partition_from_hosts(
            args, nbr_h, cum_h, feats, labels, stats, dt, quant,
            fused, alias, lookup_graph=graph)
        return graph, store, sampler, "miss"
    sampler = None if args.host_sampler else DeviceNeighborTable(
        graph, cap=args.cap, keep_host=use_cache, fused=fused,
        alias=alias)
    if pad_features:
        print("bench: --pad_features applies only to cache-served runs; "
              "rebuild path stores the raw dim", file=sys.stderr)
    store = DeviceFeatureStore(graph, ["feature"], label_fid="label",
                               label_dim=num_classes, dtype=dt,
                               keep_host=use_cache, quantize=quant)
    if use_cache and sampler is not None and store.host_arrays is not None:
        try:
            os.makedirs(cache_dir, exist_ok=True)
            nbr, cum = sampler.host_tables
            feat, label = store.host_arrays
            tmp = path + ".tmp.npz"  # savez appends .npz unless present
            np.savez(tmp, nbr=nbr, cum=cum,
                     feat=np.asarray(feat, np.float32), label=label,
                     edge_count=np.int64(graph.edge_count),
                     hub_frac=sampler.hub_frac,
                     edge_keep_frac=sampler.edge_keep_frac,
                     max_degree=sampler.max_degree,
                     uniform_rows=sampler.uniform_rows)
            os.replace(tmp, path)
        except OSError as e:
            print(f"bench: cache write failed (ignored): {e}",
                  file=sys.stderr)
    if sampler is not None:
        sampler.host_tables = None  # free ~600MB host copies
    store.host_arrays = None
    return graph, store, sampler, "miss"


def run_walk_bench(args, graph, sampler, cache_state, setup_secs,
                   n_nodes, batch, steps, spl):
    """--walk mode: DeepWalk skip-gram throughput, device-sampled
    (walks + pairs + negatives in-jit, DeviceSampledSkipGram) vs
    --host_sampler (engine random_walk + host gen_pair + host negatives
    — the reference random_walk_op.cc topology)."""
    import jax

    from euler_tpu.estimator import BaseEstimator
    from euler_tpu.estimator.base_estimator import _to_device_tree
    from euler_tpu.estimator.prefetch import Prefetcher
    from euler_tpu.models import DeepWalk, DeviceSampledSkipGram

    walk_len, lwin, rwin, num_negs = 5, 1, 1, 5
    if sampler is not None:
        model = DeviceSampledSkipGram(
            num_rows=sampler.pad_row, dim=128, walk_len=walk_len,
            left_win=lwin, right_win=rwin, num_negs=num_negs,
            uniform_sampling=_uniform_effective(args, sampler))
        est = BaseEstimator(model, dict(
            learning_rate=0.01, log_steps=1 << 30, checkpoint_steps=0,
            steps_per_loop=spl))
        # bench graph node weights are uniform 1.0 → the device negative
        # sampler is a dense pool with a unit-weight cumsum
        import jax.numpy as jnp
        est.static_batch.update({
            **sampler.tables,
            "neg_rows": jax.device_put(
                np.arange(n_nodes, dtype=np.int32)),
            "neg_cum": jax.device_put(
                np.arange(1, n_nodes + 1, dtype=np.float32)),
        })
        seed_box = [0]

        def gen():
            while True:
                roots = graph.sample_node(batch, -1).astype(np.int64)
                seed_box[0] += 1
                yield {"rows": [roots.astype(np.int32)],
                       "sample_seed": np.uint32(seed_box[0])}
    else:
        from euler_tpu.ops.walk_ops import gen_pair

        model = DeepWalk(max_id=n_nodes - 1, dim=128)
        est = BaseEstimator(model, dict(
            learning_rate=0.01, log_steps=1 << 30, checkpoint_steps=0,
            max_id=n_nodes - 1, steps_per_loop=spl))

        def one_batch():
            # one independent host-walk batch — thread-safe, so
            # --host_pipeline N can build N of them concurrently
            roots = graph.sample_node(batch, -1)
            walks = graph.random_walk(roots, walk_len)
            pairs = gen_pair(walks, lwin, rwin)
            flat = pairs.reshape(-1, 2)
            negs = graph.sample_node(
                flat.shape[0] * num_negs, -1).reshape(-1, num_negs)
            return {"src": flat[:, 0], "pos": flat[:, 1], "negs": negs}

        def gen():
            while True:
                yield one_batch()

    def to_dev(b):
        return jax.device_put(_to_device_tree(b, est.max_id))

    from euler_tpu.estimator.prefetch import make_feeder

    w = int(getattr(args, "host_pipeline", 0) or 0)
    if sampler is None and w > 1:
        it = make_feeder(one_batch, workers=w, depth=max(3, w),
                         transform=to_dev)
    else:
        if w > 1:
            print("bench: --host_pipeline is a host-feeder lever; the "
                  "device-sampled walk path keeps its ordered seed "
                  "stream (serial feeder)", file=sys.stderr)
        it = Prefetcher(gen(), depth=3, transform=to_dev)
    warmup = spl + 2 if spl > 1 else 3
    est.train(iter([next(it) for _ in range(warmup)]), max_steps=warmup)
    _obs_region_start()
    t0 = time.time()
    res = est.train(it, max_steps=warmup + steps)
    dt = time.time() - t0
    _close_iter(it)
    done = res["global_step"] - warmup
    n_pairs = len([1 for i in range(walk_len + 1)
                   for off in (-1, 1) if 0 <= i + off <= walk_len])
    pairs_per_sec = done * batch * n_pairs / dt
    value = pairs_per_sec / max(jax.device_count(), 1)
    return {
        "metric": "deepwalk_train_pairs_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "pairs/s/chip",
        "vs_baseline": round(value / 1_000_000, 4),
        "detail": {
            "backend": jax.default_backend(),
            "nodes": n_nodes,
            "graph_edges": int(graph.edge_count),
            "batch_size": batch,
            "walk_len": walk_len,
            "num_negs": num_negs,
            "steps": done,
            "steps_per_sec": round(done / dt, 2),
            "sampler": "host" if sampler is None else (
                "device_fused" if getattr(sampler, "fused", False)
                else "device"),
            "sampler_variant": _sampler_variant(args, sampler),
            "alias_sampler": bool(args.alias_sampler),
            "degree_sorted": bool(args.degree_sorted
                                  and cache_state == "hit"),
            "uniform_path": _uniform_effective(args, sampler),
            "steps_per_loop": spl,
            "graph_cache": cache_state,
            "setup_secs": round(setup_secs, 1),
            "host_pipeline": int(getattr(args, "host_pipeline", 0) or 0),
            "cache": _cache_detail(graph),
            "health": _bench_health(graph, res),
        },
    }


def run_layerwise_bench(args, graph, store, sampler, cache_state,
                        setup_secs, n_nodes, steps, spl, num_classes):
    """--layerwise mode: device-resident LADIES/FastGCN training rate
    (in-jit pools + dense adjacency, DeviceSampledLayerwiseGCN). The
    host feeder ceiling to compare against is tools/bench_host.py
    --mode layerwise (engine pools + python adjacency assembly)."""
    import jax

    from euler_tpu.estimator import NodeEstimator
    from euler_tpu.estimator.prefetch import Prefetcher
    from euler_tpu.models import DeviceSampledLayerwiseGCN

    if sampler is None:
        raise ValueError(
            "--layerwise has no --host_sampler mode in bench.py; the "
            "host layerwise feeder ceiling is measured by "
            "tools/bench_host.py --mode layerwise")
    batch = args.batch_size or (64 if args.smoke else 512)
    sizes = (8, 8) if args.smoke else (512, 512)
    # num_classes comes from run_bench (the label-table dimension the
    # tables were built with) — a hardcoded copy here would break
    # silently if the canonical value changed (advisor r3)
    model = DeviceSampledLayerwiseGCN(
        num_classes=num_classes, multilabel=False, dim=128,
        layer_sizes=sizes)
    est = NodeEstimator(
        model,
        dict(batch_size=batch, learning_rate=0.01, label_dim=num_classes,
             log_steps=1 << 30, checkpoint_steps=0, train_node_type=-1,
             steps_per_loop=spl),
        graph, None, label_fid="label", label_dim=num_classes,
        feature_store=store, device_sampler=sampler)

    it = _make_bench_feeder(est, args, _make_to_dev(est))
    warmup = spl + 2 if spl > 1 else 3
    est.train(iter([next(it) for _ in range(warmup)]), max_steps=warmup)
    _obs_region_start()
    t0 = time.time()
    res = est.train(it, max_steps=warmup + steps)
    dt = time.time() - t0
    _close_iter(it)
    done = res["global_step"] - warmup
    nodes_per_sec = done * (batch + sum(sizes)) / dt
    value = nodes_per_sec / max(jax.device_count(), 1)
    return {
        "metric": "layerwise_train_pool_nodes_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "pool-nodes/s/chip",
        "vs_baseline": round(value / 1_000_000, 4),
        "detail": {
            "backend": jax.default_backend(),
            "nodes": n_nodes,
            "graph_edges": int(graph.edge_count),
            "batch_size": batch,
            "layer_sizes": list(sizes),
            "steps": done,
            "steps_per_sec": round(done / dt, 2),
            "final_loss": res["loss"],
            "sampler": "device",
            "sampler_variant": _sampler_variant(args, sampler,
                                                has_uniform_path=False),
            "alias_sampler": bool(args.alias_sampler),
            "degree_sorted": bool(args.degree_sorted
                                  and cache_state == "hit"),
            "steps_per_loop": spl,
            "graph_cache": cache_state,
            "setup_secs": round(setup_secs, 1),
            "host_pipeline": int(getattr(args, "host_pipeline", 0) or 0),
            "cache": _cache_detail(graph),
            "health": _bench_health(graph, res),
        },
    }


# registry snapshot taken when the measured region starts (post-warmup):
# detail.obs_measured diffs the final snapshot against this, so compile-
# dominated warmup observations can't masquerade as measured step time
_OBS_REGION_BASE = None


def _obs_region_start():
    """Mark the start of the measured region: drop setup/warmup spans
    (--trace exports exactly the region) and snapshot the registry so
    detail.obs_measured can report region-only metric deltas (obs
    import is stdlib-only/cheap)."""
    global _OBS_REGION_BASE
    from euler_tpu import obs

    obs.clear_trace()
    _OBS_REGION_BASE = obs.snapshot()


def _bench_health(graph, res=None):
    """detail.health: the graph client's retry/degraded counters (None
    for engines without a health() surface — embedded / _CachedGraph)
    plus the train loop's nonfinite-skip count, so a perf artifact shows
    whether the measured run degraded (a padded-batch or skipped-step
    run is not comparable to a clean one)."""
    h = getattr(graph, "health", None)
    out = {"graph": h() if callable(h) else None}
    if res is not None:
        out["skipped_steps"] = int(res.get("skipped_steps", 0))
        out["skipped_batches"] = int(res.get("skipped_batches", 0))
    return out


def _make_to_dev(est):
    """Prefetch-thread transform: strip host-only keys, device_put —
    ONE definition so every bench mode measures the same input path."""
    import jax

    from euler_tpu.estimator.base_estimator import _to_device_tree

    def to_dev(b):
        return jax.device_put(_to_device_tree(
            {k: v for k, v in b.items() if k != "infer_ids"}, est.max_id))

    return to_dev


def _make_bench_feeder(est, args, transform, depth=3):
    """The bench input iterator: the single prefetch thread, or — with
    --host_pipeline N — the multi-worker feeder over the estimator's
    thread-safe batch factory. Modes without a factory (device-sampler
    paths, whose per-batch seed stream is ordered) fall back to
    serialized next() with a stderr note rather than silently changing
    the measured semantics."""
    from euler_tpu.estimator.prefetch import make_feeder

    w = int(getattr(args, "host_pipeline", 0) or 0)
    if w > 1:
        src = est._train_batch_factory()
        if src is None:
            print("bench: --host_pipeline has no thread-safe batch "
                  "factory in this mode — K workers share one "
                  "serialized input stream (transform/prefetch still "
                  "overlap)", file=sys.stderr)
            src = est.train_input_fn()
        return make_feeder(src, workers=w, depth=max(depth, w),
                           transform=transform)
    return make_feeder(est.train_input_fn(), workers=0, depth=depth,
                       transform=transform)


def _close_iter(it) -> None:
    """Reclaim a bench feeder's worker thread(s) right after the timed
    section: an abandoned feeder keeps issuing graph RPCs during the
    post-run health/obs snapshot (and into any later leg in the same
    process), and the prefetchers' contract is close-or-with."""
    closer = getattr(it, "close", None)
    if callable(closer):
        closer()


def _cache_detail(graph):
    """detail.cache: client-cache counters when --client_cache wrapped
    the engine (None otherwise) — the artifact must show whether the
    measured run was cache-served and how warm it ran."""
    stats = getattr(graph, "cache_stats", None)
    return stats() if callable(stats) else None


def run_bench(args):
    import jax

    # --alias_sampler conflicts fail BEFORE any table build: a leg that
    # silently dropped the flag would be mislabeled in the sweep
    if args.alias_sampler:
        if args.host_sampler:
            print("bench: --alias_sampler needs the device sampler "
                  "(incompatible with --host_sampler)", file=sys.stderr)
            sys.exit(2)
        if args.fused_sampler:
            print("bench: --alias_sampler needs the split nbr/cum "
                  "layout (incompatible with --fused_sampler — the "
                  "fused [N+1, 2C] table has no alias words)",
                  file=sys.stderr)
            sys.exit(2)
        if args.uniform_path:
            print("bench: --alias_sampler and --uniform_path select "
                  "different draw algorithms — run them as separate "
                  "A/B legs", file=sys.stderr)
            sys.exit(2)
    # --partition levers fail BEFORE any table build, like the alias
    # conflicts above: a leg that silently dropped the flag would be
    # mislabeled in the sweep
    if args.hub_cache_frac and args.partition < 2:
        print("bench: --hub_cache_frac needs --partition >= 2 (a "
              "replicated table has no remote leg for the hub cache "
              "to absorb)", file=sys.stderr)
        sys.exit(2)
    if args.partition:
        if args.partition < 2:
            print("bench: --partition must be >= 2 (1 is the replicated "
                  "layout — just drop the flag)", file=sys.stderr)
            sys.exit(2)
        for flag, on in (("--host_sampler", args.host_sampler),
                         ("--walk", args.walk),
                         ("--layerwise", args.layerwise),
                         ("--act_cache", args.act_cache),
                         ("--remat", args.remat),
                         # the partitioned store has no pad_dim_to path
                         # yet — refusing beats stamping pad_features=
                         # true on a leg that measured an unpadded table
                         ("--pad_features", args.pad_features)):
            if on:
                print(f"bench: --partition applies to the device fanout "
                      f"feature path only (incompatible with {flag})",
                      file=sys.stderr)
                sys.exit(2)
        if not 0.0 <= args.hub_cache_frac < 1.0:
            print("bench: --hub_cache_frac must be in [0, 1)",
                  file=sys.stderr)
            sys.exit(2)
        if jax.device_count() < args.partition:
            print(f"bench: --partition {args.partition} needs that many "
                  f"devices; backend has {jax.device_count()} (CPU runs "
                  "force the virtual device count in main — pass "
                  "--platform cpu or --smoke)", file=sys.stderr)
            sys.exit(2)
    # --client_cache intercepts the deterministic host reads
    # (get_full_neighbor / get_dense_feature) — only the host feeder
    # path issues any; wrapping a device-sampler run would stamp a
    # dead cache onto the artifact
    if args.client_cache and not args.host_sampler:
        print("bench: --client_cache needs the host feeder path "
              "(--host_sampler); device-sampler modes fetch features "
              "from HBM tables, not the graph service", file=sys.stderr)
        sys.exit(2)
    if args.client_cache and args.layerwise:
        print("bench: --layerwise has no host feeder mode for "
              "--client_cache to intercept", file=sys.stderr)
        sys.exit(2)
    # a forced --uniform_path on a config with no uniform path must die
    # HERE, not at detail-record time after the measured run completed
    # (the in-_uniform_effective refusal is the backstop for tools that
    # bypass run_bench)
    if args.uniform_path and (args.host_sampler or args.fused_sampler
                              or args.layerwise):
        which = "--host_sampler" if args.host_sampler else (
            "--fused_sampler" if args.fused_sampler else "--layerwise")
        print(f"bench: --uniform_path forced but inapplicable with "
              f"{which} — drop one of the flags", file=sys.stderr)
        sys.exit(2)

    if args.smoke:
        n_nodes = args.nodes or 2000
        batch = args.batch_size or 64
        fanouts = [int(x) for x in args.fanouts.split(",")] if args.fanouts \
            else [5, 5]
        steps = args.steps or 20
        feat_dim = args.feat_dim or 32
        avg_degree = args.avg_degree or 10
        warmup = 3
    else:
        # measured sweet spot on v5e-1: batch 32768 + bf16 features
        # (batch 65536 OOMs HBM, 49152 regresses). Graph shape defaults
        # to ogbn-products scale (BASELINE.md: 2.45M nodes, avg degree
        # ~50 → ~120M directed edges), built through the real engine.
        n_nodes = args.nodes or 2_450_000
        batch = args.batch_size or 32768
        fanouts = [int(x) for x in args.fanouts.split(",")] if args.fanouts \
            else [15, 10]
        steps = args.steps or 30
        feat_dim = args.feat_dim or 100
        avg_degree = args.avg_degree or 50
        warmup = 5
        if not args.fp32:
            args.bf16 = True

    from euler_tpu.dataflow import FanoutDataFlow
    from euler_tpu.estimator import NodeEstimator
    from euler_tpu.estimator.base_estimator import _to_device_tree
    from euler_tpu.estimator.prefetch import Prefetcher
    from euler_tpu.models import DeviceSampledGraphSage, SupervisedGraphSage

    num_classes = 16
    setup_t0 = time.time()
    # TPU-first input path: features live in HBM (DeviceFeatureStore) and
    # — unless --host_sampler — the fanout is sampled ON DEVICE
    # (DeviceNeighborTable): the host ships only root rows per step, so
    # the feeder leaves the critical path (measured: the jitted step
    # sustains 11-24 steps/s while a 2-core host samples ~3 batches/s)
    graph, store, sampler, cache_state = setup_tables(
        args, n_nodes, avg_degree, feat_dim, num_classes,
        use_cache=not (args.no_cache or args.smoke or args.host_sampler))
    setup_secs = time.time() - setup_t0
    if args.client_cache:
        from euler_tpu.graph import CachedGraphEngine

        graph = CachedGraphEngine(
            graph, budget_bytes=int(args.client_cache) << 20)
    spl_walk = args.steps_per_loop or (1 if args.smoke else 8)
    if args.walk:
        return run_walk_bench(args, graph, sampler, cache_state,
                              setup_secs, n_nodes, batch, steps, spl_walk)
    if args.layerwise:
        return run_layerwise_bench(args, graph, store, sampler,
                                   cache_state, setup_secs, n_nodes,
                                   steps, spl_walk, num_classes)
    if args.remat and (args.act_cache or sampler is None):
        # a silently-ignored flag would stamp remat=true on an artifact
        # whose model never ran remat — fail loudly like --act_cache
        print("bench: --remat applies to the device fanout model only "
              "(incompatible with --act_cache / --host_sampler)",
              file=sys.stderr)
        sys.exit(2)
    if sampler is None:
        if args.act_cache:
            print("bench: --act_cache needs the device sampler "
                  "(incompatible with --host_sampler)", file=sys.stderr)
            sys.exit(2)
        model = SupervisedGraphSage(
            num_classes=num_classes, multilabel=False, dim=128,
            fanouts=tuple(fanouts))
    elif args.act_cache:
        import jax.numpy as jnp

        from euler_tpu.models import DeviceSampledScalableSage
        model = DeviceSampledScalableSage(
            num_classes=num_classes, multilabel=False, dim=128,
            fanout=fanouts[0], num_layers=len(fanouts),
            max_id=int(store.features.shape[0]) - 1,
            cache_dtype=jnp.bfloat16 if args.bf16 else None,
            uniform_sampling=_uniform_effective(args, sampler))
    else:
        model = DeviceSampledGraphSage(
            num_classes=num_classes, multilabel=False, dim=128,
            fanouts=tuple(fanouts), remat=args.remat,
            uniform_sampling=_uniform_effective(args, sampler))
    flow = None if isinstance(graph, _CachedGraph) else FanoutDataFlow(
        graph, fanouts, with_features=False)
    spl = args.steps_per_loop or (1 if args.smoke
                                  else TPU_STEPS_PER_LOOP)
    est = NodeEstimator(
        model,
        dict(batch_size=batch, learning_rate=0.01, optimizer="adam",
             label_dim=num_classes, log_steps=1 << 30, checkpoint_steps=0,
             train_node_type=-1, steps_per_loop=spl,
             # the opt-in partitioned-tier knobs (validated at
             # construction; the store itself is built in setup_tables)
             table_partition=int(args.partition),
             hub_cache_frac=float(args.hub_cache_frac)),
        graph, flow, label_fid="label", label_dim=num_classes,
        feature_store=store, device_sampler=sampler)

    # the estimator already trims store-mode batches to rows (+
    # infer_ids, host-only); transfer in the prefetch thread so the
    # main loop never waits on the link
    it = _make_bench_feeder(est, args, _make_to_dev(est))

    # warmup (compile) then timed steps. The headline value is the
    # AGGREGATE rate over all measured steps; per-window rates (and the
    # peak) ride in detail because a one-chip machine shares its host's
    # CPU cores and runs drift. With steps_per_loop > 1 the warmup must
    # compile BOTH dispatch paths: one full scanned window + a tail.
    if spl > 1:
        warmup = spl + 2
    est.train(iter([next(it) for _ in range(warmup)]), max_steps=warmup)
    _obs_region_start()
    per_window = max(steps // 3, spl, 1)
    window_rates = []
    done_before = warmup
    total_dt = 0.0
    for _ in range(3):
        t0 = time.time()
        res = est.train(it, max_steps=done_before + per_window)
        dt = time.time() - t0
        total_dt += dt
        window_rates.append((res["global_step"] - done_before) / dt)
        done_before = res["global_step"]
    _close_iter(it)

    if args.act_cache:
        # each of the len(fanouts) layers aggregates the SAME sampled
        # [B, k1] neighborhood (deeper layers via the activation cache):
        # count edges actually aggregated, not the fanout-equivalent —
        # cross-config comparison goes by detail.nodes_per_sec
        edges_per_step = len(fanouts) * batch * fanouts[0]
    else:
        edges_per_step = 0
        m = batch
        for k in fanouts:
            m *= k
            edges_per_step += m
    steps_done = done_before - warmup
    edges_per_sec = edges_per_step * steps_done / total_dt
    n_chips = jax.device_count()
    value = edges_per_sec / max(n_chips, 1)
    return {
        "metric": "graphsage_train_edges_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "edges/s/chip",
        "vs_baseline": round(value / 1_000_000, 4),
        "detail": {
            "backend": jax.default_backend(),
            "devices": n_chips,
            "nodes": n_nodes,
            "avg_degree": avg_degree,
            "graph_edges": int(graph.edge_count),
            "batch_size": batch,
            "fanouts": fanouts,
            "steps": steps_done,
            "steps_per_sec": round(steps_done / total_dt, 2),
            "window_steps_per_sec": [round(r, 2) for r in window_rates],
            "peak_edges_per_sec": round(edges_per_step * max(window_rates)),
            "final_loss": res["loss"],
            "sampler": "host" if sampler is None else (
                "device_fused" if getattr(sampler, "fused", False)
                else "device"),
            "sampler_variant": _sampler_variant(args, sampler),
            "feat_dim_stored": store.dim,
            "feat_table_dtype": str(store.features.dtype),
            "degree_sorted": bool(args.degree_sorted
                                  and cache_state == "hit"),
            # self-describing lever flags: window artifacts
            # (.bench_cache/out_*.json) must carry their own config so a
            # stage rename or default flip can never mislabel a
            # historical measurement (advisor r4)
            "int8_features": bool(args.int8_features),
            "fused_sampler": bool(args.fused_sampler),
            "alias_sampler": bool(args.alias_sampler),
            "pad_features": bool(args.pad_features),
            "act_cache": bool(args.act_cache),
            "remat": bool(args.remat),
            # partitioned-table tier (--partition K --hub_cache_frac f):
            # per-chip bytes + the local/cached/remote gather-row split
            # the run actually incurred (store.cache_stats is the same
            # registry view /healthz serves)
            "partition": None if not args.partition else {
                "k": int(args.partition),
                "hub_cache_frac": float(args.hub_cache_frac),
                "degree_ranking": "capped_nbr_table",
                # device-sampler mode draws hop rows in-jit, so these
                # counters cover the ROOT rows the host shipped; the
                # full-fanout counted split is tools/bench_host.py
                # --mode table
                "counted_rows": "roots_only",
                "store": store.cache_stats(),
            },
            "uniform_path": _uniform_effective(args, sampler),
            # config-independent training rate (root nodes consumed/s):
            # the honest cross-config axis when edge accounting differs
            # (--act_cache aggregates ~5x fewer edges per step by design)
            "nodes_per_sec": round(batch * steps_done / total_dt),
            "sampler_cap": None if sampler is None else sampler.cap,
            # cap-truncation telemetry (VERDICT r2 weak #2): what share
            # of nodes exceed the cap and what share of edges the HBM
            # table retains
            "hub_frac": None if sampler is None else sampler.hub_frac,
            "edge_keep_frac":
                None if sampler is None else sampler.edge_keep_frac,
            "max_degree": None if sampler is None else sampler.max_degree,
            "steps_per_loop": spl,
            "graph_cache": cache_state,
            "setup_secs": round(setup_secs, 1),
            "host_pipeline": int(getattr(args, "host_pipeline", 0) or 0),
            "cache": _cache_detail(graph),
            "health": _bench_health(graph, res),
        },
    }


def build_argparser():
    """The bench flag set; tools that re-use setup_tables derive their
    config from this parser's defaults (one source of truth for
    default-flip decisions like the round-4 int8 win)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="small CPU run")
    ap.add_argument("--nodes", type=int, default=0)
    ap.add_argument("--batch_size", type=int, default=0)
    ap.add_argument("--fanouts", default="")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--feat_dim", type=int, default=0)
    ap.add_argument("--avg_degree", type=int, default=0,
                    help="0 = auto (50 full — ogbn-products shape, 10 "
                         "smoke/CPU)")
    ap.add_argument("--no_cache", action="store_true", default=False,
                    help="always rebuild the graph + tables from scratch "
                         "(the cache only skips setup, never measurement)")
    ap.add_argument("--bf16", action="store_true", default=False)
    ap.add_argument("--cap", type=int, default=32,
                    help="device-sampler neighbor cap C (HBM table width)")
    ap.add_argument("--host_sampler", action="store_true", default=False,
                    help="sample fanouts on the host engine (the "
                         "reference topology) instead of on device")
    ap.add_argument("--fused_sampler", action="store_true", default=False,
                    help="fused [N+1, 2C] sampling table: one row gather "
                         "per hop (candidate config)")
    ap.add_argument("--alias_sampler", action="store_true", default=False,
                    help="O(1) Vose alias-method neighbor draws over a "
                         "packed [N+1, C] int32 alias table (one extra "
                         "row gather per hop replaces the cum-row "
                         "gather, no C-wide inverse-CDF scan per draw — "
                         "the reference's alias_method.h moved on "
                         "device). Applies to fanout, --walk and "
                         "--layerwise; incompatible with "
                         "--fused_sampler / --host_sampler / a forced "
                         "--uniform_path (candidate config)")
    ap.add_argument("--degree_sorted", action="store_true", default=False,
                    help="permute table rows hub-first (gather-locality "
                         "A/B; cache-served runs only)")
    ap.add_argument("--uniform_path", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="one-gather uniform sampling on unit-weight "
                         "tables (skips the cum-row gather per hop; "
                         "round-5 on-chip win). Default: auto — on when "
                         "the table reports uniform_rows; --no-uniform_"
                         "path A/Bs the weighted inverse-CDF draw on "
                         "the same table")
    ap.add_argument("--int8_features", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="store the HBM feature table int8-quantized "
                         "(per-column scale): halves gather bytes and "
                         "table memory; dequant after the gather. DEFAULT "
                         "since the round-4 on-TPU A/B (28.06M vs 26.97M "
                         "edges/s bf16; quality pinned by the "
                         "graphsage-dev-int8 row). --no-int8_features "
                         "reverts to the bf16 table")
    ap.add_argument("--pad_features", action="store_true", default=False,
                    help="zero-pad the HBM feature table to 128 lanes so "
                         "each gathered row is one aligned tile "
                         "(candidate config; cache-served runs only)")
    ap.add_argument("--remat", action="store_true", default=False,
                    help="recompute gather+encode in the backward pass "
                         "(jax.checkpoint): the hop-2 feature layer "
                         "never lives across the backward, unlocking "
                         "bigger batches (batch 65536 OOMs without it; "
                         "pair with --batch_size 65536 for the A/B — "
                         "candidate config)")
    ap.add_argument("--act_cache", action="store_true", default=False,
                    help="historical-activation config "
                         "(DeviceSampledScalableSage): sample ONE hop and "
                         "read deeper-layer neighbor activations from an "
                         "HBM cache updated in-jit — removes the hop-2 "
                         "raw-feature gather that dominates the products-"
                         "scale step (PERF.md). Same model depth; edges/s "
                         "counts actually-aggregated edges, so compare "
                         "configs by detail.nodes_per_sec (candidate "
                         "config)")
    ap.add_argument("--host_pipeline", type=int, default=0,
                    help="N > 1 runs the multi-worker host feeder (N "
                         "sampler threads over a thread-safe batch "
                         "factory, ordered delivery); 0/1 keeps the "
                         "single prefetch thread. Recorded as "
                         "detail.host_pipeline (host modes also flip "
                         "detail.sampler_variant to host_pipelined)")
    ap.add_argument("--client_cache", type=int, default=0,
                    help="MB > 0 wraps the host graph engine in the "
                         "immutable-graph client cache "
                         "(CachedGraphEngine): deterministic neighbor/"
                         "feature reads served client-side, only "
                         "misses over the wire; stats recorded as "
                         "detail.cache. Needs --host_sampler (the only "
                         "path issuing host feature reads); the feeder "
                         "A/B proper is tools/bench_host.py --mode "
                         "feeder")
    ap.add_argument("--partition", type=int, default=0,
                    help="K >= 2 partitions the HBM feature table into "
                         "1/K row shards over a K-wide 'model' mesh axis "
                         "(PartitionedFeatureStore): per-chip table "
                         "memory drops ~Kx, cold gathers cross ICI. "
                         "Rows are relabeled hub-first (the degree-"
                         "sorted layout) and the neighbor tables are "
                         "remapped to match. Device fanout mode only; "
                         "recorded as detail.partition (candidate "
                         "config)")
    ap.add_argument("--hub_cache_frac", type=float, default=0.0,
                    help="with --partition: replicate this fraction of "
                         "highest-degree rows on every chip and route "
                         "gathers cache-first, so only the cold tail "
                         "crosses ICI (the measured degree skew means a "
                         "tiny cache absorbs most gathers); counted in "
                         "detail.partition.store gather_rows")
    ap.add_argument("--steps_per_loop", type=int, default=0,
                    help="0 = auto (32 on TPU since the round-5 on-chip "
                         "A/B, 1 in smoke/CPU mode): lax.scan window per "
                         "device dispatch")
    ap.add_argument("--fp32", action="store_true", default=False,
                    help="keep float32 features in the full bench")
    ap.add_argument("--layerwise", action="store_true", default=False,
                    help="measure device-resident layerwise (LADIES) "
                         "training instead of fanout GraphSAGE")
    ap.add_argument("--walk", action="store_true", default=False,
                    help="DeepWalk skip-gram throughput instead of "
                         "GraphSAGE (pairs/s; combine with "
                         "--host_sampler for the host-walk topology)")
    ap.add_argument("--platform", default="",
                    choices=["", "auto", "tpu", "cpu"],
                    help="default: cpu for --smoke; the full bench "
                         "always requires tpu")
    ap.add_argument("--serve", action="store_true", default=False,
                    help="after the training bench, run the serving "
                         "smoke (tools/bench_serve.serve_smoke): a "
                         "batch1-vs-micro-batched p50/p99 pair over "
                         "the real InferenceServer/ServingClient "
                         "stack with injected per-flush latency; "
                         "recorded as detail.serve")
    ap.add_argument("--trace", default="",
                    help="write a chrome://tracing JSON of the measured "
                         "region (per-step input_wait/device_step/hook "
                         "spans, graph rpc spans) to this path; view "
                         "with chrome://tracing, ui.perfetto.dev, or "
                         "tools/trace_dump.py")
    ap.add_argument("--rpc_mux", action="store_true", default=False,
                    help="after the training bench, run the mux-"
                         "transport smoke (tools/bench_host.py --mode "
                         "rpc): counted pool-vs-mux-vs-mux+dedup+"
                         "compression A/B under 10ms injected RTT over "
                         "a live 2-shard cluster; recorded as "
                         "detail.rpc")
    return ap


def main(argv=None):
    args = build_argparser().parse_args(argv)

    # The full bench is a device measurement: it requires the TPU and a
    # missing chip is a failure (non-zero exit, no metric line). --smoke
    # is the CPU sanity run the tests use. The backend is initialized in
    # THIS process (a chip belongs to one process at a time) and never
    # retargeted after a failure.
    from euler_tpu.platform import init_platform

    if args.smoke:
        platform = args.platform or "cpu"
    elif args.platform == "cpu":
        print("bench: the full bench requires the TPU (--platform cpu is "
              "only valid with --smoke)", file=sys.stderr)
        return 2
    else:
        platform = "tpu"
    # CPU runs need a virtual multi-device backend for the K-wide
    # 'model' axis of --partition
    n_cpu = max(int(args.partition), 2) if args.partition > 1 else None
    init_platform(platform, n_cpu)

    result = run_bench(args)
    # every mode's artifact carries the full registry snapshot (process
    # lifetime: includes setup/warmup/compile) PLUS the measured-region
    # delta — read the host/device split off obs_measured, not obs
    # (ISSUE 3: a degraded or input-bound run is visible in the artifact
    # itself)
    from euler_tpu import obs

    if isinstance(result.get("detail"), dict):
        final = obs.snapshot()
        result["detail"]["obs"] = final
        if _OBS_REGION_BASE is not None:
            result["detail"]["obs_measured"] = obs.snapshot_delta(
                _OBS_REGION_BASE, final)
        tools_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools")
        if args.serve:
            # serving smoke AFTER the measured region: its servers/
            # clients must not pollute the training artifact's
            # obs_measured delta
            sys.path.insert(0, tools_dir)
            from bench_serve import serve_smoke

            result["detail"]["serve"] = serve_smoke()
        if args.rpc_mux:
            # mux-transport smoke AFTER the measured region, same rule
            # as --serve
            sys.path.insert(0, tools_dir)
            from bench_host import rpc_smoke

            result["detail"]["rpc"] = rpc_smoke()
    if args.trace:
        obs.dump_trace(args.trace)
        print(f"bench: chrome trace written to {args.trace} "
              "(load in chrome://tracing / ui.perfetto.dev)",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
