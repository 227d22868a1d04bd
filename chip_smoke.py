"""Chip smoke: the device-sampled GraphSAGE trainer on the TPU, through
the entry points a user calls, in ONE process.

  python chip_smoke.py            # one chip: phases A, B, C
  python chip_smoke.py --chips 4  # four chips: ONLY the row-sharded phase
                                  # and the replicated run it is compared
                                  # with

  A  canonical trainer at full width (bench.py's canonical config:
     dim 128, fanouts 15,10, batch 32768, 100-dim int8 features, cap 32,
     steps_per_loop 32, 2.45M-row tables) through NodeEstimator.train
     with the prefetch feeder. Tables are generated vectorised from
     --seed and placed with the public from_arrays constructors.
  B  engine -> tables -> train -> export_bundle -> InferenceServer ->
     ServingClient embed/score/kNN, same process (README "train ->
     export -> query online").
  C  the same jitted device-sampled step, tiny and float32, once on the
     TPU and once on this process's CPU device: identical sampled rows,
     first-step loss within a stated tolerance.

This is a smoke, not a benchmark: no number it prints is a benchmark
result. It requires the TPU — main() has no option that lets it pass on
another platform — and any failing phase makes the exit code non-zero
(nothing here catches a phase's failure and carries on). It reads
nothing generated (.bench_cache/, accept_out/, .jax_cache/ contents are
only jax's own compile cache).

Every stdout line is one JSON object naming device_kind; the LAST line is
exactly {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# bench.py's canonical configuration (bench.py run_bench, non-smoke branch)
CANON = dict(n_nodes=2_450_000, feat_dim=100, cap=32, num_classes=16,
             dim=128, fanouts=(15, 10), batch=32768, steps_per_loop=32)
WINDOWS = 3        # post-warm-up scanned windows in phase A
NODES_B = 100_000  # phase B graph: the engine builds 100k nodes / 5M
                   # edges in ~4 s in the CPU sandbox


def say(phase: str, **kv) -> None:
    import jax

    print(json.dumps({"phase": phase,
                      "device_kind": jax.devices()[0].device_kind, **kv}),
          flush=True)


class CompileWatch:
    """Counts executables jax builds (and the seconds it spends building
    or fetching them from the persistent cache) via jax.monitoring."""

    _EVENT = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.count, self.secs, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if event == self._EVENT:
            self.count += 1
            self.secs += duration

    def _ev(self, event, **kw):
        if event == self._HIT:
            self.hits += 1

    def mark(self):
        return self.count, self.secs, self.hits

    def since(self, mark):
        return {"compiles": self.count - mark[0],
                "compile_secs": round(self.secs - mark[1], 2),
                "persistent_cache_hits": self.hits - mark[2]}


def peak_memory(device) -> dict:
    stats = device.memory_stats()
    if not stats:
        return {"peak_bytes_in_use": "not reported by this backend"}
    return {k: int(stats[k]) for k in
            ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")
            if k in stats}


def make_tables(seed: int, n_nodes: int, feat_dim: int, cap: int,
                num_classes: int, weighted: bool = False) -> dict:
    """The capped [N+1, C] neighbour / cumulative-weight tables and the
    [N+1, D] feature / [N+1, classes] label tables the device path
    consumes (trailing pad row = N), generated vectorised from `seed`:
    power-law degrees clipped to the cap, front-packed random neighbours,
    class-correlated features. weighted=False gives unit weights (the
    products-like bench graph; uniform_rows), True small integer weights
    (the inverse-CDF draw)."""
    rng = np.random.default_rng(seed)
    n = int(n_nodes)
    deg = np.clip((rng.pareto(1.2, n) * 25).astype(np.int64) + 1, 1, cap)
    slot = np.arange(cap)[None, :] < deg[:, None]
    nbr = np.full((n + 1, cap), n, np.int32)
    nbr[:n] = np.where(slot, rng.integers(0, n, (n, cap), dtype=np.int32),
                       np.int32(n))
    w = slot.astype(np.float32)
    if weighted:
        w *= rng.integers(1, 4, (n, cap)).astype(np.float32)
    cum = np.zeros((n + 1, cap), np.float32)
    np.cumsum(w, axis=1, out=cum[:n])
    cls = rng.integers(0, num_classes, n)
    # weak class signal: the loss has to stay a real number to watch,
    # not collapse to 0.0 within the warm-up
    centers = 0.15 * rng.standard_normal((num_classes, feat_dim),
                                         dtype=np.float32)
    feat = np.zeros((n + 1, feat_dim), np.float32)
    feat[:n] = rng.standard_normal((n, feat_dim), dtype=np.float32)
    feat[:n] += centers[cls]
    label = np.zeros((n + 1, num_classes), np.float32)
    label[np.arange(n), cls] = 1.0
    stats = {"hub_frac": float((deg >= cap).mean()), "edge_keep_frac": 1.0,
             "max_degree": int(deg.max()), "uniform_rows": not weighted}
    return {"nbr": nbr, "cum": cum, "feat": feat, "label": label,
            "deg": deg, "stats": stats, "edge_count": int(deg.sum())}


def check_placed(tree, devices, what: str) -> int:
    """Every array leaf of `tree` lives on exactly `devices`."""
    import jax

    want = set(devices)
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    for path, leaf in leaves:
        got = leaf.devices()
        if got != want:
            raise AssertionError(
                f"{what}{jax.tree_util.keystr(path)} is on {got}, "
                f"expected {want}")
    return len(leaves)


def check_row_sharded(name: str, arr, mesh, axis: str = "model") -> dict:
    """Each device of the mesh holds exactly 1/K of `arr`'s rows, on
    len(mesh.devices) DISTINCT devices (code that has only met virtual
    devices may put everything on the first)."""
    k = dict(mesh.shape)[axis]
    shards = arr.addressable_shards
    devs = {s.device for s in shards}
    if devs != set(mesh.devices.flat) or len(devs) != mesh.devices.size:
        raise AssertionError(f"{name}: shards on {devs}, mesh has "
                             f"{set(mesh.devices.flat)}")
    per = arr.shape[0] // k
    starts = set()
    for s in shards:
        if s.data.shape[0] != per or s.data.shape[1:] != arr.shape[1:]:
            raise AssertionError(
                f"{name}: device {s.device} holds {s.data.shape}, "
                f"expected ({per}, ...) = 1/{k} of {arr.shape}")
        starts.add(s.index[0].start or 0)
    if starts != {i * per for i in range(k)}:
        raise AssertionError(f"{name}: row blocks {sorted(starts)} do "
                             f"not tile {arr.shape[0]} rows over {k}")
    return {"table": name, "rows": int(arr.shape[0]), "k": k,
            "rows_per_device": per, "devices": len(devs)}


def build_estimator(graph, store, sampler, *, dim, fanouts, num_classes,
                    batch, steps_per_loop, uniform, table_mesh=None,
                    flow=None, optimizer="adam"):
    """NodeEstimator over DeviceSampledGraphSage, configured as bench.py
    configures its canonical run."""
    from euler_tpu.estimator import NodeEstimator
    from euler_tpu.models import DeviceSampledGraphSage

    model = DeviceSampledGraphSage(
        num_classes=num_classes, multilabel=False, dim=dim,
        fanouts=tuple(fanouts), uniform_sampling=uniform,
        table_mesh=table_mesh)
    return NodeEstimator(
        model,
        dict(batch_size=batch, learning_rate=0.01, optimizer=optimizer,
             label_dim=num_classes, log_steps=1 << 30, checkpoint_steps=0,
             train_node_type=-1, steps_per_loop=steps_per_loop),
        graph, flow, label_fid="label", label_dim=num_classes,
        feature_store=store, device_sampler=sampler)


def train_windows(est, steps_per_loop: int, windows: int,
                  watch: CompileWatch, tail: bool = True) -> dict:
    """bench.py's train sequence: the prefetch feeder with device_put in
    its thread, a warm-up that compiles both dispatch paths (one full
    scanned window + a 2-step tail; tail=False warms the scanned window
    only), then `windows` more windows. Each window is timed around
    block_until_ready and must compile nothing."""
    import jax

    import bench
    from euler_tpu.estimator.prefetch import make_feeder

    spl = int(steps_per_loop)
    per_window = max(spl, 1)
    it = make_feeder(est.train_input_fn(), workers=0, depth=3,
                     transform=bench._make_to_dev(est))
    try:
        warmup = (spl + 2 if tail else spl) if spl > 1 else 3
        mark, t0 = watch.mark(), time.perf_counter()
        res = est.train(iter([next(it) for _ in range(warmup)]),
                        max_steps=warmup)
        jax.block_until_ready(est.state.params)
        out = {"warmup_steps": warmup,
               "warmup_secs": round(time.perf_counter() - t0, 2),
               **watch.since(mark)}
        losses = [res["loss"]]
        before = jax.device_get(est.state.params)
        done, window_secs = warmup, []
        for _ in range(windows):
            mark, t0 = watch.mark(), time.perf_counter()
            res = est.train(it, max_steps=done + per_window)
            jax.block_until_ready(est.state.params)
            window_secs.append(time.perf_counter() - t0)
            compiled = watch.since(mark)["compiles"]
            if compiled:
                raise AssertionError(
                    f"{compiled} compile(s) inside a post-warm-up window")
            if res["global_step"] != done + per_window:
                raise AssertionError(f"window stopped at {res}")
            done = res["global_step"]
            losses.append(res["loss"])
    finally:
        it.close()
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite loss: {losses}")
    if res["skipped_steps"]:
        raise AssertionError(f"nonfinite guard skipped steps: {res}")
    after = jax.device_get(est.state.params)
    moved = max(float(np.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(after), jax.tree_util.tree_leaves(before)))
    if not moved > 0:
        raise AssertionError("params did not move in the timed windows")
    out.update(
        steps=done, losses=losses,
        window_secs=[round(s, 3) for s in window_secs],
        step_ms=[round(1e3 * s / per_window, 2) for s in window_secs],
        param_max_abs_change=moved)
    return out


def place_tables(t: dict, *, mesh=None, shard_rows: bool = False):
    """Host tables -> (store, sampler) through the public from_arrays
    constructors, int8 features with bf16 scales as bench.py stores
    them."""
    import jax.numpy as jnp

    from euler_tpu.parallel import DeviceFeatureStore, DeviceNeighborTable

    dt = np.dtype(jnp.bfloat16)
    store = DeviceFeatureStore.from_arrays(
        t["feat"].astype(dt, copy=False), t["label"], mesh=mesh,
        shard_rows=shard_rows,
        quantize="int8", scale_dtype=jnp.bfloat16)
    sampler = DeviceNeighborTable.from_arrays(
        t["nbr"], t["cum"], stats=t["stats"], mesh=mesh,
        shard_rows=shard_rows)
    return store, sampler


# --------------------------------------------------------------------------
# Phase A — canonical trainer at full width
# --------------------------------------------------------------------------
def phase_a(*, seed, n_nodes, feat_dim, cap, num_classes, dim, fanouts,
            batch, steps_per_loop, windows, device, watch) -> dict:
    import bench

    say("A.config", n_nodes=n_nodes, feat_dim=feat_dim, feat_dtype="int8",
        scale_dtype="bfloat16", cap=cap, num_classes=num_classes, dim=dim,
        fanouts=list(fanouts), batch=batch, steps_per_loop=steps_per_loop,
        windows=windows, seed=seed)
    t0 = time.perf_counter()
    t = make_tables(seed, n_nodes, feat_dim, cap, num_classes)
    gen_secs = time.perf_counter() - t0
    store, sampler = place_tables(t)
    graph = bench._CachedGraph(n_nodes, t["edge_count"])
    est = build_estimator(
        graph, store, sampler, dim=dim, fanouts=fanouts,
        num_classes=num_classes, batch=batch,
        steps_per_loop=steps_per_loop, uniform=sampler.uniform_rows)
    n_tables = check_placed(est.static_batch, [device], "table ")
    say("A.setup", table_gen_secs=round(gen_secs, 2),
        setup_secs=round(time.perf_counter() - t0, 2),
        tables={k: [list(v.shape), str(v.dtype)]
                for k, v in est.static_batch.items()},
        tables_on_device=n_tables, uniform_rows=sampler.uniform_rows,
        graph_edges=t["edge_count"])
    del t
    out = train_windows(est, steps_per_loop, windows, watch)
    out["param_leaves_on_device"] = check_placed(
        est.state.params, [device], "param ")
    out.update(peak_memory(device))
    say("A.train", **out)
    return out


# --------------------------------------------------------------------------
# Phase B — engine -> tables -> train -> export -> serve
# --------------------------------------------------------------------------
def phase_b(*, seed, n_nodes, avg_degree, feat_dim, cap, num_classes, dim,
            fanouts, batch, steps, n_queries, device, watch) -> dict:
    import jax
    import jax.numpy as jnp

    import bench
    from euler_tpu.core import lib as core_lib
    from euler_tpu.dataflow import FanoutDataFlow
    from euler_tpu.parallel import DeviceFeatureStore, DeviceNeighborTable
    from euler_tpu.serving import InferenceServer, ServingClient
    from euler_tpu.tools.knn import brute_force

    say("B.config", n_nodes=n_nodes, avg_degree=avg_degree,
        feat_dim=feat_dim, cap=cap, dim=dim, fanouts=list(fanouts),
        batch=batch, steps=steps, n_queries=n_queries, seed=seed)
    t0 = time.perf_counter()
    data = bench.build_products_like(n_nodes, avg_degree, feat_dim,
                                     num_classes, seed=seed)
    graph = data.engine
    build_secs = time.perf_counter() - t0
    # the rebuild path of bench.setup_tables
    sampler = DeviceNeighborTable(graph, cap=cap)
    store = DeviceFeatureStore(graph, ["feature"], label_fid="label",
                               label_dim=num_classes, dtype=jnp.bfloat16,
                               quantize="int8")
    est = build_estimator(
        graph, store, sampler, dim=dim, fanouts=fanouts,
        num_classes=num_classes, batch=batch, steps_per_loop=1,
        uniform=sampler.uniform_rows,
        flow=FanoutDataFlow(graph, list(fanouts), with_features=False))
    check_placed(est.static_batch, [device], "table ")
    say("B.setup", engine_stamp=core_lib.build_stamp(),
        engine_build_graph_secs=round(build_secs, 2),
        setup_secs=round(time.perf_counter() - t0, 2),
        graph_nodes=int(graph.node_count),
        graph_edges=int(graph.edge_count),
        hub_frac=sampler.hub_frac, uniform_rows=sampler.uniform_rows)

    mark, t0 = watch.mark(), time.perf_counter()
    res = est.train(est.train_input_fn, max_steps=steps)
    jax.block_until_ready(est.state.params)
    if not np.isfinite(res["loss"]) or res["global_step"] != steps:
        raise AssertionError(f"phase B training failed: {res}")
    check_placed(est.state.params, [device], "param ")
    say("B.train", steps=steps, secs=round(time.perf_counter() - t0, 2),
        loss=round(res["loss"], 5), **watch.since(mark))

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        mark, t0 = watch.mark(), time.perf_counter()
        bundle_dir = os.path.join(tmp, "bundle")
        bundle = est.export_bundle(bundle_dir)
        emb_dim = bundle.embeddings.shape[1]  # concat sage: 2 * dim
        if len(bundle.ids) != graph.node_count or emb_dim % dim \
                or not np.isfinite(bundle.embeddings).all():
            raise AssertionError(
                f"bundle embeddings {bundle.embeddings.shape} are not "
                f"finite rows for all {graph.node_count} nodes")
        say("B.export", secs=round(time.perf_counter() - t0, 2),
            embeddings=list(bundle.embeddings.shape), **watch.since(mark))

        mark, t0 = watch.mark(), time.perf_counter()
        rng = np.random.default_rng(seed)
        rows = np.sort(rng.choice(len(bundle.ids), n_queries, replace=False))
        qids, want = bundle.ids[rows], bundle.embeddings[rows]
        with InferenceServer(bundle_dir, max_batch=64) as srv, \
                ServingClient(
                    endpoints=f"hosts:127.0.0.1:{srv.port}") as cli:
            warm_secs = time.perf_counter() - t0
            info = cli.info()
            if info["dim"] != emb_dim or info["count"] != len(bundle.ids):
                raise AssertionError(f"server info {info}")
            emb = cli.embed(qids)
            if not np.array_equal(emb, want):
                raise AssertionError("served embed rows != bundle rows")
            score = cli.score(qids, qids[::-1])
            np.testing.assert_allclose(
                score, (want * want[::-1]).sum(-1), rtol=1e-4, atol=1e-5)
            got_n, got_s = cli.knn(qids, k=5)
            want_n, want_s = brute_force(bundle.embeddings, bundle.ids,
                                         want, 5)
            if not np.array_equal(got_n, want_n):
                raise AssertionError("served kNN ids != brute force")
            np.testing.assert_allclose(got_s, want_s, rtol=1e-5, atol=1e-6)
            health = srv.health()
            if health["shed"] or health["errors"]:
                raise AssertionError(f"server degraded: {health}")
        say("B.serve", server_warm_secs=round(warm_secs, 2),
            secs=round(time.perf_counter() - t0, 2),
            requests=health["requests"], **watch.since(mark))
    return res


# --------------------------------------------------------------------------
# Phase C — chip vs CPU agreement, tiny
# --------------------------------------------------------------------------
def phase_c(*, seed, device, cpu_device, n_nodes=1000, feat_dim=32, cap=8,
            num_classes=4, dim=32, fanouts=(5, 3), batch=64,
            loss_rtol=1e-4, loss_rtol_default_precision=2e-2) -> dict:
    """The device-sampled step (float32 tables, value_and_grad of the
    model loss) on `device` and on `cpu_device`, same seed. Sampled rows
    must be IDENTICAL (threefry is backend-independent); the first-step
    loss must agree to loss_rtol at highest matmul precision and to
    loss_rtol_default_precision at the default (bf16-pass) precision the
    trainer runs."""
    import jax

    from euler_tpu.models import DeviceSampledGraphSage
    from euler_tpu.parallel.device_sampler import (
        sample_fanout_rows, store_rows,
    )

    out = {"n_nodes": n_nodes, "feat_dim": feat_dim, "cap": cap, "dim": dim,
           "fanouts": list(fanouts), "batch": batch,
           "loss_rtol": loss_rtol,
           "loss_rtol_default_precision": loss_rtol_default_precision,
           "compared_with": str(cpu_device)}
    rng = np.random.default_rng(seed)
    roots = rng.integers(0, n_nodes, batch).astype(np.int32)
    for variant, weighted in (("inverse_cdf", True), ("uniform", False)):
        t = make_tables(seed, n_nodes, feat_dim, cap, num_classes,
                        weighted=weighted)
        uniform = not weighted
        host = {"rows": [roots], "sample_seed": np.uint32(1),
                "feature_table": t["feat"], "label_table": t["label"],
                "nbr_table": store_rows(t["nbr"], "nbr"),
                "cum_table": store_rows(t["cum"], "cum")}
        model = DeviceSampledGraphSage(
            num_classes=num_classes, multilabel=False, dim=dim,
            fanouts=tuple(fanouts), uniform_sampling=uniform)

        def draw(nbr, cum, r, key):
            return sample_fanout_rows(nbr, cum, r, tuple(fanouts), key,
                                      uniform=uniform)

        def loss_fn(params, b):
            return model.apply(params, b).loss

        params = jax.device_get(jax.jit(model.init)(
            jax.random.key(seed), jax.device_put(host, cpu_device)))
        got = {}
        for name, dev in (("chip", device), ("cpu", cpu_device)):
            b = jax.device_put(host, dev)
            p = jax.device_put(params, dev)
            key = jax.device_put(jax.random.key(seed + 7), dev)
            hops = jax.jit(draw)(b["nbr_table"], b["cum_table"],
                                 b["rows"][0], key)
            step = jax.jit(jax.value_and_grad(loss_fn))
            with jax.default_matmul_precision("highest"):
                loss_hi, grads = step(p, b)
            loss_def, _ = step(p, b)  # default (bf16-pass) precision
            if loss_hi.devices() != {dev}:
                raise AssertionError(f"step ran on {loss_hi.devices()}, "
                                     f"not {dev}")
            got[name] = ([np.asarray(h) for h in hops], float(loss_hi),
                         float(loss_def), jax.device_get(grads))
        for h, (a, b) in enumerate(zip(got["chip"][0], got["cpu"][0])):
            if not np.array_equal(a, b):
                raise AssertionError(
                    f"{variant}: hop {h} sampled rows differ between "
                    f"{device} and {cpu_device} "
                    f"({int((a != b).sum())} of {a.size})")
        l_chip, l_cpu = got["chip"][1], got["cpu"][1]
        np.testing.assert_allclose(l_chip, l_cpu, rtol=loss_rtol)
        np.testing.assert_allclose(got["chip"][2], l_cpu,
                                   rtol=loss_rtol_default_precision)
        gdiff = max(float(np.abs(a - b).max()) for a, b in zip(
            jax.tree_util.tree_leaves(got["chip"][3]),
            jax.tree_util.tree_leaves(got["cpu"][3])))
        out[variant] = {
            "sampled_rows_identical": True,
            "rows_compared": int(sum(a.size for a in got["chip"][0])),
            "loss_chip": l_chip, "loss_cpu": l_cpu,
            "loss_chip_default_precision": got["chip"][2],
            "grad_max_abs_diff": gdiff}
    say("C.agreement", **out)
    return out


# --------------------------------------------------------------------------
# --chips 4 — row-sharded tables on a ('data', 'model') mesh
# --------------------------------------------------------------------------
def phase_multichip(*, seed, devices, n_nodes, feat_dim, cap, num_classes,
                    dim, fanouts, batch, steps_per_loop, windows, watch,
                    hub_cache_frac=0.01, sgd_loss_rtol=1e-5,
                    sgd_param_atol=1e-6, adam_loss_rtol=1e-3) -> dict:
    """The canonical widths on a 2x2 ('data', 'model') mesh with feature,
    label and neighbour tables row-sharded over 'model', compared with
    the replicated single-device run on the same seed (both draw with
    the inverse-CDF sampler — the sharded layout has no uniform
    shortcut); plus ring_lookup / allgather_lookup and the
    PartitionedFeatureStore gather, exact against a plain take.

    Each layout trains the same scanned windows twice through
    NodeEstimator.train on the same placed tables: with the canonical
    adam, and with sgd. The sgd run is the sharp check that the sharded
    in-step gather and draw are right: same batches and the same
    gradients leave params within sgd_param_atol and the window losses
    within sgd_loss_rtol. Under adam the window losses are held to
    adam_loss_rtol only and the param drift is a printed reading: adam
    divides by sqrt(v), which amplifies rounding in near-zero-gradient
    coordinates (PERF.md section 7)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import bench
    from euler_tpu.parallel import PartitionedFeatureStore
    from euler_tpu.parallel.ring_exchange import (
        allgather_lookup, reference_lookup, ring_lookup,
    )

    devices = list(devices)
    if len(devices) != 4 or len(set(devices)) != 4:
        raise AssertionError(f"need 4 distinct devices, got {devices}")
    mesh = Mesh(np.asarray(devices).reshape(2, 2), ("data", "model"))
    say("X.config", mesh=dict(mesh.shape), devices=[str(d) for d in devices],
        n_nodes=n_nodes, feat_dim=feat_dim, cap=cap, dim=dim,
        fanouts=list(fanouts), batch=batch, steps_per_loop=steps_per_loop,
        windows=windows, hub_cache_frac=hub_cache_frac, seed=seed,
        sgd_loss_rtol=sgd_loss_rtol,
        sgd_param_atol=sgd_param_atol, adam_loss_rtol=adam_loss_rtol)
    t0 = time.perf_counter()
    t = make_tables(seed, n_nodes, feat_dim, cap, num_classes)
    # bf16-rounded once for all three placements below
    t["feat"] = t["feat"].astype(np.dtype(jnp.bfloat16))

    def run(label, mesh_):
        shard = mesh_ is not None
        on = devices if shard else devices[:1]
        store, sampler = place_tables(t, mesh=mesh_, shard_rows=shard)
        tables = {"feature_table": store.features,
                  "label_table": store.labels, **sampler.tables}
        if shard:
            placed = [check_row_sharded(k, v, mesh_)
                      for k, v in tables.items()]
        else:
            placed = f"{check_placed(tables, on, 'table ')} tables " \
                f"replicated on {devices[0]}"
        say(f"X.{label}.setup", placement=placed,
            secs_since_phase_start=round(time.perf_counter() - t0, 2))

        def train(optimizer):
            # a fresh graph facade per estimator: same root batches
            est = build_estimator(
                bench._CachedGraph(n_nodes, t["edge_count"]), store,
                sampler, dim=dim, fanouts=fanouts,
                num_classes=num_classes, batch=batch,
                steps_per_loop=steps_per_loop, uniform=False,
                table_mesh=mesh_, optimizer=optimizer)
            # scanned windows only: the single-step tail would be one
            # more SPMD compile of the same step on four chips' time
            out = train_windows(est, steps_per_loop, windows, watch,
                                tail=False)
            check_placed(est.state.params, on, "param ")
            out["peak_memory"] = [peak_memory(d) for d in on]
            say(f"X.{label}.{optimizer}", **out)
            return out, jax.device_get(est.state.params)

        return (store, *train("adam"), *train("sgd"))

    def max_abs_diff(pa, pb):
        return max(float(np.abs(a - b).max()) for a, b in zip(
            jax.tree_util.tree_leaves(pa), jax.tree_util.tree_leaves(pb)))

    store_sh, adam_sh, padam_sh, sgd_sh, psgd_sh = run("sharded", mesh)
    store_rep, adam_rep, padam_rep, sgd_rep, psgd_rep = run(
        "replicated", None)
    sgd_pdiff = max_abs_diff(psgd_sh, psgd_rep)
    say("X.compare", sgd_losses_sharded=sgd_sh["losses"],
        sgd_losses_replicated=sgd_rep["losses"],
        sgd_param_max_abs_diff=sgd_pdiff,
        adam_losses_sharded=adam_sh["losses"],
        adam_losses_replicated=adam_rep["losses"],
        adam_param_max_abs_diff=max_abs_diff(padam_sh, padam_rep))
    np.testing.assert_allclose(sgd_sh["losses"], sgd_rep["losses"],
                               rtol=sgd_loss_rtol)
    if not sgd_pdiff <= sgd_param_atol:
        raise AssertionError(
            f"sharded vs replicated params differ by {sgd_pdiff} after "
            f"{sgd_sh['steps']} sgd steps (stated {sgd_param_atol})")
    np.testing.assert_allclose(adam_sh["losses"], adam_rep["losses"],
                               rtol=adam_loss_rtol)

    # the exchanges, exact against a plain take of the replicated table
    rng = np.random.default_rng(seed + 1)
    ids = rng.integers(0, n_nodes, batch).astype(np.int32)
    want = np.asarray(reference_lookup(store_rep.features,
                                       jnp.asarray(ids)))
    ids_sh = jax.device_put(ids, NamedSharding(mesh, P("model")))
    checked = []
    for fn in (ring_lookup, allgather_lookup):
        got = jax.jit(lambda tab, i, fn=fn: fn(tab, i, mesh, "model"))(
            store_sh.features, ids_sh)
        if not np.array_equal(np.asarray(got), want):
            raise AssertionError(f"{fn.__name__} != take")
        checked.append(fn.__name__)
    pstore = PartitionedFeatureStore.from_arrays(
        t["feat"], t["deg"], mesh=mesh,
        hub_cache_frac=hub_cache_frac, quantize="int8",
        scale_dtype=jnp.bfloat16)
    placed = check_row_sharded("partitioned.features", pstore.features,
                               mesh)
    prow = jnp.asarray(pstore.lookup(ids.astype(np.uint64)))
    for strategy in ("ring", "allgather"):
        got = pstore.make_gather(strategy)(prow)
        if not np.array_equal(np.asarray(got), want):
            raise AssertionError(f"PartitionedFeatureStore {strategy} "
                                 "gather != take")
        checked.append(f"partitioned_store.{strategy}")
    say("X.exchange", exact_vs_take=checked, ids=int(ids.size),
        table=[list(store_sh.features.shape),
               str(store_sh.features.dtype)],
        partitioned=placed, hub_size=pstore.hub_size)
    return adam_sh


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 runs ONLY the row-sharded phase and the "
                         "replicated run it is compared with")
    args = ap.parse_args(argv)

    from euler_tpu.platform import compile_cache_dir, init_platform

    init_platform("tpu")  # raises unless the TPU is there
    import jax

    devices = jax.devices()
    dev = devices[0]
    if len(devices) != args.chips:
        raise RuntimeError(f"--chips {args.chips} but jax sees "
                           f"{len(devices)} devices")
    watch = CompileWatch()
    t_start = time.perf_counter()
    say("start", platform=dev.platform, count=len(devices),
        jax=jax.__version__, compile_cache_dir=compile_cache_dir(),
        seed=args.seed, chips=args.chips)
    if args.chips == 4:
        phase_multichip(seed=args.seed, devices=devices, windows=1,
                        watch=watch, **CANON)
    else:
        phase_a(seed=args.seed, windows=WINDOWS, device=dev,
                watch=watch, **CANON)
        # same model widths; graph, batch and step count cut to a size
        # whose engine build stays in seconds
        widths = {k: CANON[k] for k in (
            "feat_dim", "cap", "num_classes", "dim", "fanouts")}
        phase_b(seed=args.seed, n_nodes=NODES_B, avg_degree=50,
                batch=4096, steps=8, n_queries=16, device=dev,
                watch=watch, **widths)
        phase_c(seed=args.seed, device=dev,
                cpu_device=jax.devices("cpu")[0])
    say("done", total_secs=round(time.perf_counter() - t_start, 1),
        **watch.since((0, 0.0, 0)))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
