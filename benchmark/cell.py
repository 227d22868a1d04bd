"""One cell's program side: the tables placed through the program's public
constructors, the model named by the configuration's file, NodeEstimator
over the prefetch feeder, the first steps whose results are compared, the
warm-up and the timed window. From euler_tpu this takes the system under
test and its histograms, nothing of bench.py, chip_smoke.py or tools/.
"""

from __future__ import annotations

import importlib
import json
import os
import time

import numpy as np

from . import tables as tables_lib
from .traffic import RootSource

CHECK_STEPS = 3      # single steps whose loss, gradient and change compare
SCAN_CHECKED = 2     # leading losses of the first scanned dispatch compared


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(dotted: str):
    mod, _, name = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), name)


def load_config(bench_dir: str, name: str) -> dict:
    return load_json(os.path.join(bench_dir, "configs", name + ".json"))


class CompileWatch:
    """Counts the executables jax builds or fetches from its persistent
    cache (jax.monitoring); a copy of chip_smoke.CompileWatch."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event, duration, **kw):
        if event == self._EVENT:
            self.count += 1
            self.secs += duration


def flatten(tree, prefix: str = "") -> dict:
    """Nested dicts of leaves -> {"a/b/c": leaf}."""
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else str(k)
        if hasattr(v, "items"):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def unflatten(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return out


class Program:
    """The estimator of one cell with its feed. `records[i]` holds the
    roots and the sample seed of the i-th batch the feeder produced."""

    def __init__(self, cfg: dict, traffic: dict, host: dict, seed: int,
                 chips: int):
        import jax
        import jax.numpy as jnp

        from euler_tpu.estimator import NodeEstimator
        from euler_tpu.estimator.prefetch import make_feeder
        from euler_tpu.parallel import (
            DeviceFeatureStore, DeviceNeighborTable,
        )

        if chips != 1:
            raise NotImplementedError(
                "chips: 4 (mesh from the configuration's file, tables "
                "placed with shard_rows) is not built yet: PERF.md, "
                "Open questions")
        if cfg["feature_storage"] != "int8":
            raise ValueError("only int8 feature storage is built")
        self.cfg, self.traffic = cfg, traffic
        self.batch = int(traffic["root_batch"])
        self.spl = int(cfg["steps_per_loop"])
        n, classes = cfg["num_nodes"], cfg["num_classes"]
        store = DeviceFeatureStore.from_arrays(
            host["feat"].astype(np.dtype(jnp.bfloat16)),
            tables_lib.one_hot_labels(host["cls"], classes),
            quantize="int8", scale_dtype=jnp.dtype(cfg["scale_dtype"]))
        # no stats handed over: the program itself finds out whether the
        # rows carry unit weights, as it does for a user's tables
        sampler = DeviceNeighborTable.from_arrays(host["nbr"], host["cum"])
        self.uniform = bool(sampler.uniform_rows)
        facts = {"uniform_rows": self.uniform, "num_nodes": n}
        kwargs = dict(cfg["model"]["kwargs"])
        for key, fact in cfg["model"].get("from_run", {}).items():
            kwargs[key] = facts[fact]
        for key, val in kwargs.items():
            if key.endswith("_dtype"):
                kwargs[key] = jnp.dtype(val)
            elif isinstance(val, list):
                kwargs[key] = tuple(val)
        model = resolve(cfg["model"]["class"])(**kwargs)
        opt = cfg["optimizer"]
        self.est = NodeEstimator(
            model,
            dict(batch_size=self.batch, learning_rate=opt["learning_rate"],
                 optimizer=opt["name"], label_dim=classes,
                 log_steps=1 << 30, checkpoint_steps=0,
                 train_node_type=-1, steps_per_loop=self.spl),
            RootSource(n, host["edge_count"], seed), None,
            label_fid="label", label_dim=classes, feature_store=store,
            device_sampler=sampler)
        # the state init's batch, the single steps, the first dispatch
        self.recorded = 1 + CHECK_STEPS + self.spl
        self.records: list = []
        self.scan_losses: list = []
        self.done = 0

        def to_dev(b):
            if len(self.records) < self.recorded:
                self.records.append((np.array(b["rows"][0], np.int32),
                                     int(b["sample_seed"])))
            return jax.device_put(
                {k: v for k, v in b.items() if k != "infer_ids"})

        self.feed = make_feeder(self.est.train_input_fn(), workers=0,
                                depth=int(traffic["feeder_depth"]),
                                transform=to_dev)

    def close(self):
        self.feed.close()

    # -- set-up ------------------------------------------------------------
    def install_weights(self, weights: dict) -> None:
        """Let the program build its own state (one batch, no step), then
        put the seed-made weights in place of its initial ones and zero
        whatever else the state carries (flax's init pass has already
        written the activation cache, with weights of its own). The leaf
        paths and shapes have to be the reference's."""
        import jax
        import jax.numpy as jnp

        self.est.train(iter([next(self.feed)]), max_steps=0)
        state = self.est.state
        have = {p: tuple(v.shape) for p, v in flatten(state.params).items()}
        want = {p: tuple(v.shape) for p, v in weights.items()}
        if have != want:
            raise RuntimeError(
                f"the program's parameters {have} are not the "
                f"reference's {want}")
        dev = next(iter(jax.tree_util.tree_leaves(state.params))).devices()
        placed = jax.device_put(unflatten(weights), next(iter(dev)))
        zeroed = jax.tree_util.tree_map(jnp.zeros_like,
                                        state.extra_vars or {})
        self.est.state = state.replace(params=placed, extra_vars=zeroed)

    def _watch_scanned(self) -> None:
        """Keep the per-step losses each scanned dispatch returns (device
        arrays, no wait): est.train reports only the window's last."""
        loop = self.est._build_train_loop()

        def watched(state, batches, static_batch):
            state, losses, metrics = loop(state, batches, static_batch)
            self.scan_losses.append(losses)
            return state, losses, metrics

        self.est._train_loop = watched

    def _second_moment(self) -> dict:
        import jax

        nu = [s.nu for s in jax.tree_util.tree_leaves(
            self.est.state.opt_state, is_leaf=lambda s: hasattr(s, "nu"))
            if hasattr(s, "nu")]
        if len(nu) != 1:
            raise RuntimeError("no second moment in the optimizer state")
        return {p: np.asarray(v, np.float64)
                for p, v in flatten(jax.device_get(nu[0])).items()}

    def _extra_norms(self) -> dict:
        import jax.numpy as jnp

        return {
            p: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for p, v in flatten(self.est.state.extra_vars or {}).items()}

    def first_steps(self) -> dict:
        """CHECK_STEPS single steps and one scanned dispatch through
        est.train and the feeder: what they leave for the comparison."""
        import jax

        est = self.est
        out = {"loss": [], "b1": self.cfg["optimizer"]["b1"]}
        p0 = flatten(jax.device_get(est.state.params))
        for step in range(1, CHECK_STEPS + 1):
            res = est.train(self.feed, max_steps=step)
            if res["global_step"] != step:
                raise RuntimeError(f"step {step} stopped at {res}")
            out["loss"].append(res["loss"])
            if step == 1:
                mu = [s.mu for s in jax.tree_util.tree_leaves(
                    est.state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
                    if hasattr(s, "mu")]
                if len(mu) != 1:
                    raise RuntimeError("no first moment in the optimizer "
                                       "state to read the gradient from")
                out["grad1"] = {
                    p: np.asarray(v, np.float64) / (1.0 - out["b1"])
                    for p, v in flatten(jax.device_get(mu[0])).items()}
        p3 = flatten(jax.device_get(est.state.params))
        out["dparam"] = {p: np.asarray(p3[p], np.float64)
                         - np.asarray(p0[p], np.float64) for p in p0}
        out["extra_norm"] = self._extra_norms()
        self._watch_scanned()
        res = est.train(self.feed, max_steps=CHECK_STEPS + self.spl)
        jax.block_until_ready(est.state.params)
        self.done = res["global_step"]
        if self.done != CHECK_STEPS + self.spl or len(self.scan_losses) != 1:
            raise RuntimeError(f"the scanned dispatch stopped at {res}")
        first = np.asarray(self.scan_losses[0], np.float64)
        out["scan_loss"] = [float(x) for x in first[:SCAN_CHECKED]]
        p_end = flatten(jax.device_get(est.state.params))
        out["scan_dparam"] = {p: np.asarray(p_end[p], np.float64)
                              - np.asarray(p3[p], np.float64) for p in p3}
        out["scan_mom2"] = self._second_moment()
        out["scan_extra_norm"] = self._extra_norms()
        return out

    # -- the timed window --------------------------------------------------
    def window(self, seconds: float, watch: CompileWatch, tracer=None) -> dict:
        """Whole scanned dispatches through est.train until `seconds`
        have passed, each ended by block_until_ready. `tracer`, if given,
        is started before the second dispatch and stopped after the
        third, both outside the dispatches' own times: a traced stretch
        of two. `dispatch_parts_ms` splits each dispatch by the program's
        own histograms: its wait for the 32 batches, its stack and
        enqueue, and the rest (the device's work and the loss fetch)."""
        import jax

        from euler_tpu import obs

        def phases():
            snap = obs.snapshot()
            return [sum(float(c["sum"]) for c in
                        snap.get(h, {}).get("values", {}).values())
                    for h in ("estimator_input_wait_ms",
                              "estimator_device_step_ms")]

        est, spl = self.est, self.spl
        start_step, skipped0 = self.done, int(est.state.skipped_steps)
        seen = len(self.scan_losses)
        hist0 = obs.snapshot()
        compiles0 = watch.count
        secs, parts, traced, tracing = [], [], None, False
        before = phases()
        t0 = time.perf_counter()
        while True:
            i = len(secs)
            if tracer is not None and i == 1:
                tracer.start()
                tracing = True
            ts = time.perf_counter()
            if ts - t0 >= seconds:
                break
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                res = est.train(self.feed, max_steps=self.done + spl)
                jax.block_until_ready(est.state.params)
            te = time.perf_counter()
            if tracing and i == 2:
                traced, tracing = tracer.stop(), False
            secs.append(te - ts)
            after = phases()
            wait, enqueue = (a - b for a, b in zip(after, before))
            parts.append([wait, enqueue, 1e3 * secs[-1] - wait - enqueue])
            before = after
            self.done = res["global_step"]
        if tracing:
            tracer.stop()
        elapsed = te - t0 if secs else 0.0
        if tracer is not None and traced is None:
            raise RuntimeError(
                f"the window held {len(secs)} dispatches: too few to "
                "trace two of them after the first")
        losses = np.concatenate(
            [np.asarray(x) for x in self.scan_losses[seen:]]) \
            if len(self.scan_losses) > seen else np.zeros(0)
        return {
            "dispatch_secs": secs, "dispatch_parts_ms": parts,
            "elapsed": elapsed,
            "steps_expected": len(secs) * spl,
            "steps_done": self.done - start_step,
            "steps_skipped": int(est.state.skipped_steps) - skipped0,
            "losses_nonfinite": int((~np.isfinite(losses)).sum()),
            "losses_seen": int(losses.size),
            "compiles": watch.count - compiles0,
            "obs_before": hist0, "obs_after": obs.snapshot(),
            "trace": traced,
            "batch": self.batch, "spl": spl,
        }

    def free(self) -> None:
        """Drop everything the program holds on the device."""
        import jax

        self.close()
        est = self.est
        big = list(est.static_batch.values()) + jax.tree_util.tree_leaves(
            (est.state.params, est.state.opt_state, est.state.extra_vars))
        est.static_batch.clear()
        est.state = None
        self.scan_losses.clear()
        for a in big:
            if hasattr(a, "delete"):
                a.delete()
        jax.clear_caches()
