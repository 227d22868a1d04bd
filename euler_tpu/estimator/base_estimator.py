"""Training drivers: train / evaluate / infer / train_and_evaluate.

Parity: euler_estimator/python/base_estimator.py:27-180 (BaseEstimator on
tf.estimator: train loop with LoggingTensorHook + ProfilerHook, evaluate,
infer writing embedding_*.npy / ids_*.npy, checkpointing to model_dir).

TPU-first redesign: a functional train loop — flax TrainState + optax,
one jitted train_step (donate-argnums on state so HBM buffers are
reused), orbax checkpointing, jax.profiler for the profiling hook, and an
optional jax.sharding.Mesh for SPMD data parallelism (batch sharded over
the 'data' axis; parameters replicated — see euler_tpu.parallel for the
embedding-sharded variant).

The model contract is ModelOutput (embedding, loss, metric_name, metric);
input_fn is a host-side iterator of numpy batch dicts with STATIC shapes.
"""

from __future__ import annotations

import itertools
import os
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.training import train_state

from euler_tpu import obs as _obs
from euler_tpu.obs import first_calls as _first_calls
from euler_tpu.utils import optimizers as opt_lib
from euler_tpu.utils.layers import undo_collection

# the spans' second sink: while a jax.profiler session runs, every
# obs.span is also an "euler.<name>" event on that session's host plane,
# on the device plane's clock (obs itself imports no jax)
_obs.install_profiler_annotation(jax.profiler.TraceAnnotation)
# what each jitted function's first call costs (trace, lower, compile or
# cache fetch), booked to the function the estimator says it is calling
# (`_calling`): obs/first_calls.py. Silent once every shape is warm.
jax.monitoring.register_event_duration_secs_listener(
    _first_calls.on_duration)
jax.monitoring.register_event_listener(_first_calls.on_event)
_calling = _first_calls.calling
# the device program's kernels are found in a profile by their
# jax.named_scope names (draw/hop<h>, gather/hop<h>, cache, update ...),
# which are op metadata. jax leaves metadata out of the compile cache's
# key by default, so an executable cached before a scope was added or
# renamed would carry the old names into every later profile (seen on the
# chip, PR 24: a cache that came with the machine served the unnamed
# program). The price: an edit that only moves lines recompiles once.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

# per-process estimator numbering: the label value distinguishing N
# estimators' children on the shared estimator_* metrics
_EST_IDS = itertools.count()


class TrainState(train_state.TrainState):
    """TrainState + mutable variable collections (scalable-encoder caches)
    + the nonfinite-guard skip counter (device scalar so the guarded step
    stays a single jitted dispatch)."""

    extra_vars: Dict[str, Any] = None
    skipped_steps: Any = None


def _to_device_tree(batch: Dict, max_id: int = 0) -> Dict:
    """numpy batch → jnp pytree. uint64 id arrays become int32 rows
    (bucketized by max_id+1 when provided) because TPU jit runs with x64
    disabled; all other arrays pass through."""

    def conv(v):
        if isinstance(v, np.ndarray) and v.dtype == np.uint64:
            if max_id > 0:
                v = (v % np.uint64(max_id + 1))
            return v.astype(np.int32)
        return v

    return jax.tree_util.tree_map(conv, batch)


# a leaf of K device-resident batches stacked by ONE dispatch. Eager
# jnp.stack is K reshapes and a concatenate, K + 1 dispatches a leaf, and
# the runtime holds a dispatch back while too many are in flight: called
# while a scanned window runs, it came back only when the window was done,
# and its operations then ran with the device idle (~10 ms a window on the
# chip, PERF.md, PR 35)
_stack_on_device = jax.jit(lambda *xs: jnp.stack(xs))


def _merged(batch: Dict, static_batch: Dict) -> Dict:
    return {**batch, **static_batch} if static_batch else batch


def _put_back(table, rows, old, ok):
    """`table` as given where the step is sound (`ok`), with `old` back
    at `rows` where it is skipped. A loop of no turn or one: its carry is
    updated in place and a sound step pays nothing, where the identity
    branch of a lax.cond copies the table and a scatter whose updates
    are all dropped still walks every row (PERF.md, PR 27)."""
    return jax.lax.fori_loop(0, jnp.where(ok, 0, 1),
                             lambda _, t: t.at[rows].set(old), table)


def _last_finite(vals) -> float:
    """Most recent finite scalar in `vals` (NaN when none): run
    summaries report the last REAL loss, not a guard-skipped step's
    NaN."""
    for v in reversed(vals):
        f = float(v)
        if np.isfinite(f):
            return f
    return float("nan")


def _match_placement(new_tree, like_tree):
    """Re-place each restored leaf with the CURRENT leaf's sharding:
    snapshot/checkpoint restores go through host numpy, which would
    silently replicate a deliberately sharded leaf (e.g. a
    shard_act_cache'd activation cache) — re-inflating per-chip memory
    by mp with no error."""
    def place(new, like):
        sh = getattr(like, "sharding", None)
        if sh is not None:
            try:
                return jax.device_put(new, sh)
            except Exception:  # shape changed / mesh gone: plain array
                pass
        return jnp.asarray(new)

    try:
        return jax.tree_util.tree_map(place, new_tree, like_tree)
    except ValueError:  # tree structures differ (e.g. fresh collection)
        return jax.tree_util.tree_map(jnp.asarray, new_tree)


class BaseEstimator:
    """Drives a flax model with the ModelOutput contract.

    params dict (mirrors the reference's params into estimators):
      optimizer: name (default 'adam'), learning_rate, batch_size,
      log_steps, checkpoint_steps, max_id (for id bucketization),
      profiling (bool).
    """

    def __init__(self, model, params: Dict, model_dir: Optional[str] = None,
                 mesh: Optional[jax.sharding.Mesh] = None):
        self.model = model
        self.params_cfg = dict(params or {})
        self.model_dir = model_dir
        self.mesh = mesh
        self.tx = opt_lib.get(
            self.params_cfg.get("optimizer", "adam"),
            self.params_cfg.get("learning_rate", 0.01),
            weight_decay=float(self.params_cfg.get("weight_decay", 0.0)),
        )
        self.max_id = int(self.params_cfg.get("max_id", 0))
        # >1 → lax.scan over that many host batches per device dispatch
        # (the TPUEstimator iterations_per_loop idea): amortizes dispatch
        # and host↔device round-trip latency
        self.steps_per_loop = int(self.params_cfg.get("steps_per_loop", 1))
        self.log_steps = int(self.params_cfg.get("log_steps", 20))
        self.ckpt_steps = int(self.params_cfg.get("checkpoint_steps", 1000))
        self.profiling = bool(self.params_cfg.get("profiling", False))
        # resilient input path: transient input-pipeline failures (a
        # flaky graph service) are retried with backoff; past the
        # retries, up to skip_batch_budget batches may be abandoned
        # (counted) before the error is treated as unrecoverable — at
        # which point an emergency checkpoint is written and the error
        # re-raises.
        self.input_retries = int(self.params_cfg.get("input_retries", 3))
        self.input_backoff_s = float(
            self.params_cfg.get("input_backoff_s", 0.1))
        self._skip_budget = int(
            self.params_cfg.get("skip_batch_budget", 0))
        # multi-worker host feeder (ISSUE 4): feeder_workers > 1 wraps
        # train()'s input stream in a ParallelPrefetcher — K sampler
        # threads feeding an ordered bounded queue. Estimators whose
        # batches are independent (NodeEstimator host mode) expose a
        # thread-safe per-batch factory so sampling itself runs in
        # parallel; otherwise only transform/prefetch overlap. Batch
        # ORDER stays deterministic per feeder, but which random roots
        # land in which position is not bit-reproducible vs serial.
        self.feeder_workers = int(self.params_cfg.get("feeder_workers", 0))
        self.feeder_depth = int(
            self.params_cfg.get("feeder_depth", 0)) or None
        # partitioned device-table tier (opt-in knobs, ISSUE 6): callers
        # that build the feature store from estimator params read these —
        # table_partition = K mesh shards for the feature table (0/1 =
        # replicated), hub_cache_frac = fraction of highest-degree rows
        # replicated on every chip in front of the partition
        # (PartitionedFeatureStore). Validated here so a typo'd config
        # fails at construction, not after a day of training.
        self.table_partition = int(self.params_cfg.get("table_partition", 0))
        if self.table_partition < 0:
            raise ValueError(
                f"table_partition must be >= 0, got {self.table_partition}")
        self.hub_cache_frac = float(
            self.params_cfg.get("hub_cache_frac", 0.0))
        if not 0.0 <= self.hub_cache_frac < 1.0:
            raise ValueError(
                f"hub_cache_frac must be in [0, 1), got "
                f"{self.hub_cache_frac}")
        if self.hub_cache_frac > 0 and self.table_partition <= 1:
            raise ValueError(
                "hub_cache_frac needs a partitioned table "
                "(table_partition >= 2): a replicated table has no "
                "remote leg for the hub cache to absorb")
        self._live_feeder = None
        self._input_factory = None
        # what the last train call took from a caller's iterator and did
        # not train on: (the iterator, its raw batches in order, their
        # stacked window if one was made, whether the stream ended)
        self._ahead = None
        # input-path counters live on the obs registry (children labeled
        # by estimator instance); input_health / health() are VIEWS over
        # them — the same numbers a /metrics scrape reports
        self._obs_name = f"estimator{next(_EST_IDS)}"
        reg = _obs.default_registry()
        lab = {"estimator": self._obs_name}
        self._ctr_input_failures = reg.counter(
            "estimator_input_failures_total",
            "input batches that raised", ("estimator",)).labels(**lab)
        self._ctr_input_retries = reg.counter(
            "estimator_input_retries_total",
            "input-pipeline retry sleeps", ("estimator",)).labels(**lab)
        self._ctr_skipped_batches = reg.counter(
            "estimator_skipped_batches_total",
            "input batches abandoned under skip_batch_budget",
            ("estimator",)).labels(**lab)
        batches = reg.counter(
            "estimator_window_batches_total",
            "batches a train window consumed, by how it got them: ahead "
            "(in hand before the window began: read while the device ran "
            "the window before, or carried from the last call on the same "
            "iterator) or waited (pulled with nothing in flight)",
            ("estimator", "how"))
        self._ctr_batches_ahead = batches.labels(how="ahead", **lab)
        self._ctr_batches_waited = batches.labels(how="waited", **lab)
        self._hist_input_wait = reg.histogram(
            "estimator_input_wait_ms",
            "per-step host wait for the next batch (sampling + RPC + "
            "host→device conversion)", ("estimator",)).labels(**lab)
        self._hist_device_step = reg.histogram(
            "estimator_device_step_ms",
            "host time to ENQUEUE one train dispatch (a single step, or "
            "a scanned window of steps_per_loop): the call is "
            "asynchronous, the device's time is not in it",
            ("estimator",)).labels(**lab)
        self._hist_result_wait = reg.histogram(
            "estimator_result_wait_ms",
            "blocking fetch of a scanned window's losses: the host "
            "waiting for the device to finish the window",
            ("estimator",)).labels(**lab)
        self._hist_hook = reg.histogram(
            "estimator_hook_ms",
            "per-step logging/checkpoint hooks", ("estimator",)
        ).labels(**lab)
        self._hist_train_call = reg.histogram(
            "estimator_train_call_ms",
            "one call of train(), whole: the first batch, state init and "
            "first calls where it pays them, its steps or dispatches, the "
            "closing fetches", ("estimator",),
            buckets=_obs.SETUP_MS_BUCKETS).labels(**lab)
        self._g_steps_per_sec = reg.gauge(
            "estimator_steps_per_sec", "train-loop throughput",
            ("estimator",)).labels(**lab)
        self._g_skipped_steps = reg.gauge(
            "estimator_skipped_steps",
            "nonfinite-guard skipped device steps",
            ("estimator",)).labels(**lab)
        self._g_global_step = reg.gauge(
            "estimator_global_step", "last reported global step",
            ("estimator",)).labels(**lab)
        # non-counter health fields (strings / one-shot markers) stay
        # instance-side; the input_health view merges them back in
        self._input_meta: Dict[str, Any] = {
            "emergency_checkpoint_step": None, "last_input_error": None}
        _obs.register_health(self._obs_name, self.health)
        self.state: Optional[TrainState] = None
        self._train_step = None
        self._train_loop = None
        self._eval_step = None
        self._ckpt_mgr = None
        # device-resident arrays merged into every batch (e.g. a
        # DeviceFeatureStore table): same jax.Array object each step, so
        # jit sees a cached on-device arg — no per-step transfer
        self.static_batch: Dict[str, Any] = {}
        # called with this estimator right before every interleaved and
        # final evaluation in train_and_evaluate (e.g. a full-coverage
        # activation-cache refresh, models/graphsage.refresh_act_cache)
        self.pre_eval_hook = None

    # -- setup -------------------------------------------------------------
    def _init_state(self, batch: Dict, rng=None) -> None:
        span = self._span
        with span("init_state"), _calling("init"):
            with span("model_init"):
                rng = rng if rng is not None else jax.random.key(
                    int(self.params_cfg.get("seed", 0)))
                # jitted: op-by-op eager init made the canonical warm-up
                # 112 compiles on one chip and 279 on a 2x2 mesh (18 on
                # both now; ~20 s and ~40 s of warm-up — PR 21 chip_smoke
                # readings) and held every unfused full-batch intermediate
                # in HBM at once. The init runs the same __call__ the
                # jitted train step traces, so whatever that step accepts
                # as a batch, this does.
                variables = dict(jax.jit(self.model.init)(rng, batch))
            with span("create_state"):
                params = variables.pop("params")
                self.state = TrainState.create(
                    apply_fn=self.model.apply, params=params, tx=self.tx,
                    extra_vars=dict(variables),
                    skipped_steps=jnp.zeros((), jnp.int32),
                )
            # the mesh the state lives on: the estimator's own, else the
            # one the feature store was told to place its tables on
            mesh = self.mesh
            if mesh is None:
                mesh = getattr(getattr(self, "feature_store", None),
                               "mesh", None)
            if mesh is not None:
                # commit the fresh state REPLICATED on that mesh. As
                # created, some of its leaves are uncommitted, while every
                # jitted step returns it committed — so without this the
                # first scanned window compiles for "unspecified" state
                # shardings and the second one compiles the whole window
                # again (found by chip_smoke's no-compile-after-warm-up
                # check, PR 21)
                from jax.sharding import NamedSharding, PartitionSpec

                with span("commit_state"):
                    self.state = jax.device_put(
                        self.state, NamedSharding(mesh, PartitionSpec()))

    def _first_state(self, batch: Dict) -> None:
        """A fresh state from `batch`, then the newest checkpoint over
        it where there is one."""
        self._init_state(batch)
        with self._span("restore_checkpoint"):
            self.restore_checkpoint()

    def _make_one_step(self):
        """The single SGD step shared by the per-step jit and the scanned
        loop — one definition so the two dispatch paths cannot drift."""
        mutable_keys = [k for k in (self.state.extra_vars or {})]
        undo_keys = [undo_collection(k) for k in mutable_keys]
        dropout_key = jax.random.key(
            int(self.params_cfg.get("seed", 0)) + 1)

        def one_step(state: TrainState, batch):
            # per-step dropout rng; eval applies without rngs → dropout
            # layers run deterministic there
            rngs = {"dropout": jax.random.fold_in(dropout_key, state.step)}

            def loss_fn(p):
                variables = {"params": p, **(state.extra_vars or {})}
                if mutable_keys:
                    out, new_vars = state.apply_fn(
                        variables, batch, mutable=mutable_keys + undo_keys,
                        rngs=rngs)
                    new_vars = dict(new_vars)
                    undo = {k: new_vars.pop(u)
                            for k, u in zip(mutable_keys, undo_keys)
                            if u in new_vars}
                else:
                    out = state.apply_fn(variables, batch, rngs=rngs)
                    new_vars, undo = {}, {}
                return out.loss, (out, new_vars, undo)

            (loss, (out, new_vars, undo)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)
            guarded = state.skipped_steps is not None
            # a collection the apply wrote by rows, with a record of its
            # variables' old rows (layers.undo_collection), is taken as
            # written and rolled back by rows: through the lax.cond both
            # the old and the new table would have to be alive, and the
            # compiler copies the whole table twice a step (PERF.md, PR 27)
            by_rows = undo if guarded else {}
            for k in by_rows:
                # the variables of collection k rolled back by rows,
                # counted each time a train step is traced
                _obs.traced_path(
                    "guard_row_rollback", k,
                    len(jax.tree_util.tree_leaves(new_vars[k])))
            if by_rows:
                state = state.replace(extra_vars={
                    k: v for k, v in state.extra_vars.items()
                    if k not in by_rows})
            in_cond = {k: v for k, v in new_vars.items() if k not in by_rows}

            def apply_update(_):
                with jax.named_scope("update"):
                    s2 = state.apply_gradients(grads=grads)
                if new_vars:
                    s2 = s2.replace(extra_vars=in_cond)
                return s2

            def skip_update(_):
                # bad batch: keep params/opt_state/extra_vars, advance
                # the step (so dropout rng / schedules move on) and
                # count the skip — the donated buffers survive intact
                return state.replace(
                    step=state.step + 1,
                    skipped_steps=state.skipped_steps + 1)

            if guarded:
                # guard the GRADS too: overflow in the backward pass can
                # yield NaN grads under a finite loss, which would poison
                # the donated params with skipped_steps still reading 0
                with jax.named_scope("guard"):
                    ok = jnp.isfinite(loss)
                    for g in jax.tree_util.tree_leaves(grads):
                        ok &= jnp.all(jnp.isfinite(g))
                    state = jax.lax.cond(ok, apply_update, skip_update,
                                         None)
                    if by_rows:
                        state = state.replace(extra_vars={
                            **state.extra_vars,
                            **{k: jax.tree_util.tree_map(
                                lambda t, rec: _put_back(t, *rec, ok),
                                new_vars[k], records)
                               for k, records in by_rows.items()}})
            else:
                state = apply_update(None)
            return state, loss, out.metric

        return one_step

    def _build_train_step(self):
        train_step = self._make_one_step()

        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(self.mesh, P())
            data = NamedSharding(self.mesh, P("data"))
            self._data_sharding = data
            train_step = jax.jit(
                train_step,
                donate_argnums=(0,),
            )
        else:
            train_step = jax.jit(train_step, donate_argnums=(0,))
        return train_step

    def _build_train_loop(self):
        """K steps per dispatch: scan the single-step body over a batch
        pytree stacked on axis 0. static_batch rides as an explicit arg
        so the feature table isn't baked into the jaxpr as a constant."""
        one_step = self._make_one_step()

        def train_loop(state: TrainState, batches, static_batch):
            def body(s, b):
                s, loss, metric = one_step(s, _merged(b, static_batch))
                return s, (loss, metric)

            state, (losses, metrics) = jax.lax.scan(body, state, batches)
            return state, losses, metrics

        return jax.jit(train_loop, donate_argnums=(0,))

    def _build_eval_step(self):
        def eval_step(state: TrainState, batch):
            variables = {"params": state.params, **(state.extra_vars or {})}
            out = state.apply_fn(variables, batch)
            return out.loss, out.metric, out.embedding

        return jax.jit(eval_step)

    def _checkpoint_manager(self):
        if self._ckpt_mgr is None and self.model_dir:
            import orbax.checkpoint as ocp

            path = os.path.abspath(os.path.join(self.model_dir, "checkpoints"))
            os.makedirs(path, exist_ok=True)
            self._ckpt_mgr = ocp.CheckpointManager(
                path, options=ocp.CheckpointManagerOptions(max_to_keep=3))
        return self._ckpt_mgr

    def save_checkpoint(self, step: int) -> None:
        mgr = self._checkpoint_manager()
        if mgr is None:
            return
        import orbax.checkpoint as ocp

        payload = {"params": self.state.params,
                   "opt_state": self.state.opt_state,
                   "extra_vars": self.state.extra_vars or {},
                   # persisted explicitly (not only as the checkpoint
                   # label) so a resumed run restarts at the right step
                   # instead of 0 and re-overwriting earlier checkpoints
                   "step": int(self.state.step)}
        mgr.save(step, args=ocp.args.StandardSave(payload))

    def finalize_checkpoints(self) -> None:
        """Block until async orbax saves commit — called at the end of
        every train path so a process exiting right after train() never
        leaves a half-written checkpoint (observed as futures-after-
        shutdown errors at exit). Mid-training saves stay async."""
        if self._ckpt_mgr is not None:
            self._ckpt_mgr.wait_until_finished()

    def restore_checkpoint(self) -> Optional[int]:
        mgr = self._checkpoint_manager()
        if mgr is None or mgr.latest_step() is None:
            return None
        import orbax.checkpoint as ocp

        step = mgr.latest_step()
        payload = {"params": self.state.params,
                   "opt_state": self.state.opt_state,
                   "extra_vars": self.state.extra_vars or {},
                   "step": int(self.state.step)}
        try:
            restored = mgr.restore(step,
                                   args=ocp.args.StandardRestore(payload))
        except Exception as first_err:
            # pre-step-persisting checkpoint layout: retry without the
            # step entry and fall back to the checkpoint label. If the
            # legacy-layout retry ALSO fails, the checkpoint is broken
            # for some other reason — re-raise the ORIGINAL error so the
            # real diagnosis isn't masked by a missing-key complaint.
            payload.pop("step")
            try:
                restored = mgr.restore(
                    step, args=ocp.args.StandardRestore(payload))
            except Exception:
                raise first_err
        resume_step = int(restored.get("step", step))
        self.state = self.state.replace(
            params=restored["params"], opt_state=restored["opt_state"],
            step=jnp.asarray(resume_step, dtype=jnp.int32),
            extra_vars=_match_placement(restored.get("extra_vars") or {},
                                        self.state.extra_vars or {}))
        return resume_step

    # -- resilient input path ----------------------------------------------
    def _skipped_steps(self) -> int:
        """Nonfinite-guard skip count from device state (0 pre-init)."""
        if self.state is None or self.state.skipped_steps is None:
            return 0
        return int(jax.device_get(self.state.skipped_steps))

    @property
    def input_health(self) -> Dict[str, Any]:
        """Input-path counters — a compatibility VIEW over this
        estimator's obs registry children (plus the instance-side
        last-error / emergency-checkpoint markers); mutate the counters
        through the registry children, not this dict."""
        return {
            "input_failures": int(self._ctr_input_failures.value),
            "input_retries": int(self._ctr_input_retries.value),
            "skipped_batches": int(self._ctr_skipped_batches.value),
            **self._input_meta,
        }

    def health(self) -> Dict[str, Any]:
        """Input-path + train-step degradation counters, merged with the
        graph client's health() when the estimator's graph exposes one —
        a single surface for 'did this run degrade?'.

        skipped_steps comes from the obs GAUGE (refreshed by the train
        thread at every log hook and at train()'s end), NOT from device
        state: health() runs on the /healthz scrape thread, and a
        device_get there could touch buffers the in-flight train step
        has already donated. Mid-train the value is at most one log
        window stale; after train() it is exact."""
        out = dict(self.input_health)
        out["skipped_steps"] = int(self._g_skipped_steps.value)
        graph_health = getattr(getattr(self, "graph", None), "health", None)
        if callable(graph_health):
            out["graph"] = graph_health()
        # partitioned feature-store tier (NodeEstimator feature_store=
        # PartitionedFeatureStore): degree stats + the hub-cache
        # hit/miss and gather-leg counters, same pattern as the client
        # cache's cache_stats()
        store_stats = getattr(getattr(self, "feature_store", None),
                              "cache_stats", None)
        if callable(store_stats):
            out["feature_store"] = store_stats()
        return out

    def _phase(self, name: str, hist):
        """Span + histogram for one train-loop phase (input_wait /
        device_step / result_wait / hook). obs.timed_span never swallows
        exceptions — a StopIteration from the input iterator propagates
        to the loops' break handlers unchanged."""
        return _obs.timed_span(name, hist, estimator=self._obs_name)

    def _span(self, name: str, **attrs):
        """A span of this estimator's that no histogram keeps."""
        return _obs.span(name, estimator=self._obs_name, **attrs)

    def _emergency_checkpoint(self, err: BaseException) -> None:
        """Best-effort checkpoint before an unrecoverable input error
        re-raises — the run dies, the progress doesn't. Never masks the
        original error."""
        if self.state is None:
            return
        step = int(self.state.step)
        try:
            self.save_checkpoint(step)
            self.finalize_checkpoints()
            if self.model_dir:
                self._input_meta["emergency_checkpoint_step"] = step
                print(f"emergency checkpoint at step {step} before "
                      f"re-raising input error: {err}", flush=True)
        except Exception as ce:  # pragma: no cover - disk-full etc.
            print(f"emergency checkpoint failed ({ce}); "
                  f"re-raising original input error", flush=True)

    # -- multi-worker feeder -----------------------------------------------
    def _train_batch_factory(self):
        """Thread-safe zero-arg one-batch callable for the multi-worker
        feeder, or None when the input stream must stay serialized
        (subclass hook — see NodeEstimator)."""
        return None

    def _wrap_feeder(self, input_fn, use_factory: bool = True):
        """ParallelPrefetcher over the train input: the subclass batch
        factory when one exists AND the caller passed the estimator's
        own train_input_fn (parallel sampling); a CUSTOM input_fn's
        stream is never substituted — it wraps with serialized next()
        so its schedule (e.g. a chaos kill script) is preserved."""
        from euler_tpu.estimator.prefetch import ParallelPrefetcher

        src = self._train_batch_factory() if use_factory else None
        if src is None:
            src = input_fn() if callable(input_fn) else input_fn
        f = ParallelPrefetcher(src, workers=self.feeder_workers,
                               depth=self.feeder_depth,
                               name=f"{self._obs_name}_train")
        self._live_feeder = f
        return f

    def _close_live_feeder(self) -> None:
        f, self._live_feeder = self._live_feeder, None
        if f is not None:
            f.close()

    def _next_input(self, it):
        """next(it) with transient-failure retry (exponential backoff)
        and the skip-batch budget. Returns (raw_batch, it) — the
        iterator may have been recreated from the train input_fn after a
        failure (a generator that raised is dead). StopIteration passes
        through; an unrecoverable error checkpoints then re-raises.

        Contract: retry/skip assumes input_fn() returns a STATELESS
        (infinite random-sampler) stream — the estimator convention; all
        built-in input_fns qualify — because recreation restarts the
        stream. A finite deterministic stream would replay its head on
        every recreation, so pass those as plain iterators instead: with
        no factory every input failure is treated as unrecoverable
        (emergency checkpoint + re-raise), never silently replayed."""
        attempts = 0
        while True:
            try:
                return next(it), it
            except StopIteration:
                raise
            except Exception as e:
                from euler_tpu.graph.remote import retryable_error

                # retry needs a recreatable source: a generator that
                # raised is dead (next() would yield StopIteration and
                # silently END training) — without the input_fn factory
                # every failure is unrecoverable. A RESILIENT feeder
                # (ParallelPrefetcher) survives its own errors, so it
                # is retryable even when passed as a bare iterator.
                transient = ((self._input_factory is not None
                              or getattr(it, "resilient", False))
                             and (retryable_error(e)
                                  or isinstance(e, OSError)))
                self._ctr_input_failures.inc()
                self._input_meta["last_input_error"] = str(e)
                if not transient:
                    self._emergency_checkpoint(e)
                    raise
                if attempts < self.input_retries:
                    attempts += 1
                    self._ctr_input_retries.inc()
                    with _obs.span("input_retry_backoff",
                                   estimator=self._obs_name,
                                   attempt=attempts):
                        time.sleep(min(
                            self.input_backoff_s * (2 ** (attempts - 1)),
                            2.0))
                elif self._skip_budget > 0:
                    # retries exhausted for this batch: abandon it and
                    # move on (countable degraded event, not a job kill)
                    self._skip_budget -= 1
                    self._ctr_skipped_batches.inc()
                    attempts = 0
                else:
                    self._emergency_checkpoint(e)
                    raise
                if self._input_factory is not None and not getattr(
                        it, "resilient", False):
                    # the raised iter is dead — close it first (a feeder
                    # holds worker threads; a generator's close() is a
                    # no-op) then recreate. A resilient feeder
                    # (ParallelPrefetcher) delivers the error in-stream
                    # and keeps producing: just call next() again.
                    closer = getattr(it, "close", None)
                    if callable(closer):
                        try:
                            closer()
                        except Exception:
                            pass
                    it = self._input_factory()

    def _pull(self, it, buf) -> Iterator:
        """One more batch from `it` (through `_next_input`) onto `buf`, as
        the device takes it; returns the iterator, which a retry may have
        made anew."""
        raw, it = self._next_input(it)
        buf.append(_to_device_tree(raw, self.max_id))
        return it

    # -- drivers -----------------------------------------------------------
    def train(self, input_fn: Callable[[], Iterator[Dict]],
              max_steps: int = 1000) -> Dict[str, float]:
        """Train until the global step reaches `max_steps`; step s trains
        on the s-th batch the input gave.

        `input_fn` is a callable that makes the iterator, or an iterator.
        With steps_per_loop > 1 the next window's batches are read, and
        stacked, while the device runs the current one. A callable's
        iterator dies with the call, so exactly the batches the call
        trains on are taken from it. An ITERATOR is the caller's and
        outlives the call: up to steps_per_loop batches may be taken from
        it beyond `max_steps`. They are kept on the estimator and trained
        on first by the next call that passes the same object (a call
        that passes another one drops them)."""
        # one parent span a call: everything below is its child, so
        # set-up's calls (state init, the first steps, the first scanned
        # dispatch) and the window's lie on one timeline, each whole
        with _obs.timed_span("train", self._hist_train_call,
                             estimator=self._obs_name, max_steps=max_steps):
            if self.feeder_workers > 1 and callable(input_fn):
                # multi-worker feeder: K sampler threads over the input
                # stream; it owns worker threads, so train() reclaims it
                # on every exit path and recreation-on-failure rebuilds it
                use_factory = input_fn == getattr(self, "train_input_fn",
                                                  None)
                it = self._wrap_feeder(input_fn, use_factory)
                self._input_factory = lambda: self._wrap_feeder(
                    input_fn, use_factory)
                try:
                    return self._train_impl(it, max_steps)
                finally:
                    self._close_live_feeder()
            it = input_fn() if callable(input_fn) else input_fn
            self._input_factory = input_fn if callable(input_fn) else None
            return self._train_impl(it, max_steps)

    def _take_ahead(self, it):
        """(raw batches, their stacked window or None, stream ended) the
        last call read from `it` and did not train on; nothing when that
        call read another iterator."""
        ahead, self._ahead = self._ahead, None
        if ahead is not None and ahead[0] is it:
            return ahead[1:]
        return [], None, False

    def _keep_ahead(self, it, buf, stacked, exhausted) -> None:
        """Leave what this call took from a caller's iterator and did not
        train on to the next call on it. An iterator made from a callable
        dies with the call, and nothing beyond the call was read from
        it."""
        if self._input_factory is None and (buf or exhausted):
            self._ahead = (it, buf, stacked, exhausted)

    def _train_impl(self, it, max_steps: int) -> Dict[str, float]:
        buf, stacked, exhausted = self._take_ahead(it)
        carried = len(buf)
        if not buf:
            if exhausted:
                raise StopIteration
            with self._phase("input_wait", self._hist_input_wait):
                it = self._pull(it, buf)
        first = _merged(buf[0], self.static_batch)
        if self.state is None:
            self._first_state(first)
        if self._train_step is None:
            with self._span("build_fn", fn="train_step"), \
                    _calling("train_step"):
                self._train_step = self._build_train_step()
        if self.profiling and self.model_dir:
            jax.profiler.start_trace(os.path.join(self.model_dir, "prof"))
        if self.steps_per_loop > 1:
            # pass the UNMERGED batches: the looped path stacks raw
            # batches and merges static_batch inside the scanned body
            return self._run_looped(it, buf, stacked, exhausted, carried,
                                    max_steps)
        step = int(self.state.step)
        start_step = step
        losses, metrics = [], []
        # monotonic everywhere in the loop: an NTP step during a long
        # run must not corrupt rates (same bug class PR 2 fixed in
        # FileBarrier.wait)
        t0 = time.monotonic()
        last_log = t0
        while step < max_steps and buf:
            with _obs.span("train_step", estimator=self._obs_name,
                           step=step):
                with self._phase("device_step", self._hist_device_step), \
                        _calling("train_step"):
                    self.state, loss, metric = self._train_step(
                        self.state,
                        _merged(buf.pop(0), self.static_batch))
                step += 1
                losses.append(loss)
                metrics.append(metric)
                do_log = step % self.log_steps == 0
                do_ckpt = self.ckpt_steps and step % self.ckpt_steps == 0
                if do_log or do_ckpt:
                    with self._phase("hook", self._hist_hook):
                        if do_log:
                            # nanmean: a guard-skipped step's NaN
                            # loss/metric must not turn the whole
                            # window's log line into nan
                            lv = float(jnp.nanmean(jnp.stack(
                                losses[-self.log_steps:])))
                            mv = float(jnp.nanmean(jnp.stack(
                                metrics[-self.log_steps:])))
                            now = time.monotonic()
                            rate = self.log_steps / max(now - last_log,
                                                        1e-9)
                            last_log = now
                            self._g_steps_per_sec.set(rate)
                            # train thread owns the state buffers here
                            # (between dispatches) — safe sync point to
                            # refresh the gauge health() reads
                            self._g_skipped_steps.set(
                                self._skipped_steps())
                            print(f"step {step}: loss={lv:.4f} "
                                  f"metric={mv:.4f} "
                                  f"({rate:.1f} steps/s)", flush=True)
                        if do_ckpt:
                            self.save_checkpoint(step)
                if step < max_steps and not buf and not exhausted:
                    try:
                        with self._phase("input_wait",
                                         self._hist_input_wait):
                            it = self._pull(it, buf)
                    except StopIteration:
                        exhausted = True
        self._keep_ahead(it, buf, None, exhausted)
        # the closing checkpoint and the fetches of the summary: on this
        # path the one place the host waits for the steps it enqueued
        with self._span("train_finish"):
            if self.ckpt_steps:
                self.save_checkpoint(step)
            self.finalize_checkpoints()
            if self.profiling and self.model_dir:
                jax.profiler.stop_trace()
            rate = (step - start_step) / max(time.monotonic() - t0, 1e-9)
            skipped = self._skipped_steps()
            self._g_steps_per_sec.set(rate)
            self._g_skipped_steps.set(skipped)
            self._g_global_step.set(step)
            return {
                # guard-skipped steps report NaN loss/metric; exclude
                # them from the summary so one bad batch doesn't blank
                # the run's headline numbers (the skip itself is in
                # skipped_steps)
                "loss": _last_finite(losses),
                "metric": float(jnp.nanmean(jnp.stack(metrics)))
                if metrics else 0.0,
                "steps_per_sec": rate,
                "global_step": step,
                "skipped_steps": skipped,
                "skipped_batches": self.input_health["skipped_batches"],
            }

    def _stack(self, buf):
        """The K raw batches of `buf` as one pytree stacked on axis 0,
        what the scanned window takes."""
        def stack(*xs):
            if isinstance(xs[0], np.ndarray):
                return np.stack(xs)
            return _stack_on_device(*xs)

        with _obs.span("stack", estimator=self._obs_name):
            return jax.tree_util.tree_map(stack, *buf)

    def _read_ahead(self, it, in_flight, step: int, want: int):
        """Up to `want` batches of the window that starts at `step`,
        pulled while the device runs the window whose losses `in_flight`
        are, and stacked where a whole window of them is in hand. Returns
        (raw batches, their stacked form or None, the iterator, stream
        ended).

        Never later than a loop that reads nothing ahead: between pulls
        the in-flight result is asked whether it is done, and once it is,
        reading stops where it stands and the next window waits for the
        rest as `input_wait`. A StopIteration ends the stream for the
        next window, not the one in flight."""
        buf, stacked, exhausted = [], None, False
        if in_flight.is_ready():
            return buf, stacked, it, exhausted
        with self._span("read_ahead", step=step, K=want) as sp:
            with self._phase("input_wait", self._hist_input_wait):
                while len(buf) < want and not in_flight.is_ready():
                    try:
                        it = self._pull(it, buf)
                    except StopIteration:
                        exhausted = True
                        break
            if len(buf) == self.steps_per_loop \
                    and not in_flight.is_ready():
                stacked = self._stack(buf)
            sp.set(got=len(buf))
        return buf, stacked, it, exhausted

    def _run_looped(self, it, buf, stacked, exhausted: bool, carried: int,
                    max_steps: int) -> Dict[str, float]:
        """steps_per_loop > 1 train path: full K-step windows dispatch as
        one scanned device call; a tail shorter than K falls back to the
        single-step function (no partial-scan recompile).

        The loop is software-pipelined on this thread: once a window is
        enqueued, and before its losses are fetched, the next window's
        batches are pulled and stacked while the device works
        (`_read_ahead`). `buf` holds the raw batches in hand, in order
        (the first `carried` of them left by the last call on this
        iterator), `stacked` their stacked form where one was made."""
        K = self.steps_per_loop
        step = int(self.state.step)
        start_step = step
        loop_losses, loop_metrics = [], []
        last_loss = float("nan")
        t0 = time.monotonic()
        last_log = t0
        logged_at = step
        # batches of `buf` that were in hand before their window began
        ahead = carried
        while step < max_steps and (buf or not exhausted):
            want = min(K, max_steps - step)
            # one parent per window; input_wait, stack, device_step,
            # read_ahead, result_wait and hook follow each other under it
            # with nothing between them, so a device-idle gap inside a
            # window always lies under one named phase
            with _obs.span("train_dispatch", estimator=self._obs_name,
                           step=step, K=want):
                if len(buf) < want and not exhausted:
                    with self._phase("input_wait", self._hist_input_wait):
                        while len(buf) < want and not exhausted:
                            try:
                                it = self._pull(it, buf)
                            except StopIteration:
                                exhausted = True
                if not buf:
                    break
                done = min(len(buf), want)
                self._ctr_batches_ahead.inc(min(ahead, done))
                self._ctr_batches_waited.inc(done - min(ahead, done))
                if done == K:
                    if self._train_loop is None:
                        with self._span("build_fn", fn="train_loop"), \
                                _calling("train_loop"):
                            self._train_loop = self._build_train_loop()
                    if stacked is None:
                        stacked = self._stack(buf)
                    with self._phase("device_step",
                                     self._hist_device_step), \
                            _calling("train_loop"):
                        self.state, l_arr, m_arr = self._train_loop(
                            self.state, stacked, self.static_batch)
                    # a caller's iterator outlives the call, and the next
                    # call's window is read from it; one made from a
                    # callable dies with the call: nothing is read from it
                    # that this call does not train on
                    buf, stacked = [], None
                    room = K if self._input_factory is None else min(
                        K, max_steps - step - K)
                    if room > 0 and not exhausted:
                        buf, stacked, it, exhausted = self._read_ahead(
                            it, l_arr, step + K, room)
                    with self._phase("result_wait",
                                     self._hist_result_wait):
                        # nanmean / last-finite: guard-skipped steps
                        # inside the scanned window report NaN and must
                        # not poison the window aggregate or the
                        # reported final loss
                        loop_losses.append((jnp.nanmean(l_arr), K))
                        loop_metrics.append((jnp.nanmean(m_arr), K))
                        # the one place the host waits for the chip
                        fin = np.asarray(l_arr)
                    fin = fin[np.isfinite(fin)]
                    if fin.size:
                        last_loss = float(fin[-1])
                else:
                    # tail shorter than K: single-step dispatches (the
                    # jit was built in train() before this path was
                    # entered), nothing read ahead; what a carried
                    # window holds beyond the tail stays in hand, raw
                    for b in buf[:done]:
                        with self._phase("device_step",
                                         self._hist_device_step), \
                                _calling("train_step"):
                            self.state, l, m = self._train_step(
                                self.state,
                                _merged(b, self.static_batch))
                        loop_losses.append((l, 1))
                        loop_metrics.append((m, 1))
                        with self._phase("result_wait",
                                         self._hist_result_wait):
                            fin = float(l)
                        if np.isfinite(fin):
                            last_loss = fin
                    buf, stacked = buf[done:], None
                ahead = len(buf)
                prev = step
                step += done
                do_log = step - logged_at >= self.log_steps
                do_ckpt = self.ckpt_steps and \
                    step // self.ckpt_steps > prev // self.ckpt_steps
                if do_log or do_ckpt:
                    with self._phase("hook", self._hist_hook):
                        if do_log:
                            now = time.monotonic()
                            rate = (step - logged_at) / max(
                                now - last_log, 1e-9)
                            self._g_steps_per_sec.set(rate)
                            self._g_skipped_steps.set(
                                self._skipped_steps())
                            print(f"step {step}: "
                                  f"loss={float(loop_losses[-1][0]):.4f} "
                                  f"metric="
                                  f"{float(loop_metrics[-1][0]):.4f} "
                                  f"({rate:.1f} steps/s)", flush=True)
                            last_log, logged_at = now, step
                        if do_ckpt:
                            self.save_checkpoint(step)
        self._keep_ahead(it, buf, stacked, exhausted)
        # the closing checkpoint and the summary's fetches (the windows'
        # metrics, the skip counter)
        with self._span("train_finish"):
            if self.ckpt_steps:
                self.save_checkpoint(step)
            self.finalize_checkpoints()
            if self.profiling and self.model_dir:
                jax.profiler.stop_trace()
            # step-weighted mean so the reported train metric matches what
            # the same run would report with steps_per_loop=1; NaN entries
            # (guard-skipped steps / all-skipped windows) drop out with
            # their weight
            if loop_metrics:
                w = np.asarray([c for _, c in loop_metrics], np.float64)
                vals = np.asarray([float(v) for v, _ in loop_metrics])
                keep = np.isfinite(vals)
                metric = float(
                    np.dot(vals[keep], w[keep] / w[keep].sum())) \
                    if keep.any() else float("nan")
            else:
                metric = 0.0
            rate = (step - start_step) / max(time.monotonic() - t0, 1e-9)
            skipped = self._skipped_steps()
            self._g_steps_per_sec.set(rate)
            self._g_skipped_steps.set(skipped)
            self._g_global_step.set(step)
            return {
                "loss": float(last_loss),
                "metric": metric,
                "steps_per_sec": rate,
                "global_step": step,
                "skipped_steps": skipped,
                "skipped_batches": self.input_health["skipped_batches"],
            }

    def evaluate(self, input_fn, steps: int = 100) -> Dict[str, float]:
        it = input_fn() if callable(input_fn) else input_fn
        if self._eval_step is None:
            self._eval_step = self._build_eval_step()
        losses, metrics, weights = [], [], []
        for _ in range(steps):
            try:
                raw = next(it)
            except StopIteration:
                break
            batch = _to_device_tree(raw, self.max_id)
            if self.state is None:
                self._first_state(_merged(batch, self.static_batch))
                self._eval_step = self._build_eval_step()
            with _calling("eval_step"):
                loss, metric, _ = self._eval_step(
                    self.state, _merged(batch, self.static_batch))
            losses.append(float(loss))
            metrics.append(float(metric))
            # masked batches (graph packing / node eval sweeps) report
            # per-batch means over n_real entries; weight them so a short
            # final sweep batch doesn't count like a full one
            mask = None
            if isinstance(raw, dict):
                mask = raw.get("graph_mask")
                if mask is None:
                    mask = raw.get("metric_mask")
            weights.append(float(np.sum(mask)) if mask is not None else 1.0)
        if not losses:
            return {"loss": float("nan"), "metric": float("nan")}
        w = np.asarray(weights)
        w = w / w.sum()
        return {"loss": float(np.dot(losses, w)),
                "metric": float(np.dot(metrics, w))}

    def infer(self, input_fn, steps: int = 100,
              id_key: str = "infer_ids") -> Dict[str, str]:
        """Writes embedding_0.npy / ids_0.npy under model_dir (parity:
        reference infer artifacts base_estimator.py:157-180)."""
        it = input_fn() if callable(input_fn) else input_fn
        if self._eval_step is None:
            self._eval_step = self._build_eval_step()
        embs, ids = [], []
        for _ in range(steps):
            try:
                raw = next(it)
            except StopIteration:
                break
            batch = _to_device_tree(raw, self.max_id)
            if self.state is None:
                self._first_state(_merged(batch, self.static_batch))
                self._eval_step = self._build_eval_step()
            with _calling("eval_step"):
                _, _, emb = self._eval_step(
                    self.state, _merged(batch, self.static_batch))
            embs.append(np.asarray(emb))
            key = id_key if id_key in raw else ("ids" if "ids" in raw else None)
            if key is not None:
                v = raw[key]
                v = v[0] if isinstance(v, list) else v
                ids.append(np.asarray(v).ravel()[: emb.shape[0]])
        out_dir = self.model_dir or "."
        os.makedirs(out_dir, exist_ok=True)
        emb_path = os.path.join(out_dir, "embedding_0.npy")
        np.save(emb_path, np.concatenate(embs) if embs else np.zeros((0,)))
        id_path = os.path.join(out_dir, "ids_0.npy")
        if ids:
            np.save(id_path, np.concatenate(ids))
        return {"embedding": emb_path, "ids": id_path}

    def export_bundle(self, out_dir: str, input_fn=None,
                      steps: int = 1_000_000, nlist: int = 64,
                      nprobe: int = 8, index: bool = True,
                      shards: int = 1, version: Optional[str] = None,
                      extra_meta: Optional[Dict[str, Any]] = None):
        """Export a versioned serving bundle (euler_tpu.serving): the
        trained parameter pytree, the full node-embedding matrix from a
        batched `embed_all` inference pass over `input_fn` (default:
        this estimator's infer_input_fn sweep), and an IVFFlat index
        over it — everything the InferenceServer needs, checksummed in
        a manifest so corruption is detected at load. `shards > 1`
        writes the partitioned fleet layout instead (contiguous 1/N row
        shards, per-shard IVFFlat, one manifest) for a sharded serving
        fleet; `version` stamps the bundle_version the hot-swap
        protocol reports (default: the training step). Returns the
        ModelBundle (already written to out_dir)."""
        import dataclasses

        import jax.tree_util as jtu

        from euler_tpu.serving.export import ModelBundle, embed_all
        from euler_tpu.tools.knn import IVFFlatIndex

        ids, emb = embed_all(self, input_fn, steps)
        leaves = jtu.tree_flatten_with_path(self.state.params)[0]
        params = {jtu.keystr(path): np.asarray(jax.device_get(leaf))
                  for path, leaf in leaves}
        spec: Dict[str, Any] = {"model_class": type(self.model).__name__}
        if dataclasses.is_dataclass(self.model):
            for f in dataclasses.fields(self.model):
                if f.name in ("parent", "name"):
                    continue
                v = getattr(self.model, f.name, None)
                if isinstance(v, (str, int, float, bool)) or v is None:
                    spec[f.name] = v
        meta = {"global_step": int(self.state.step), **(extra_meta or {})}
        if version is not None:
            meta["bundle_version"] = str(version)
        index_state = None
        if index and shards == 1 and len(ids) >= 2:
            # the global index only serves the unsharded layout;
            # save_sharded trains one per shard instead
            idx = IVFFlatIndex(nlist=nlist, nprobe=nprobe)
            idx.train_add(emb, ids)
            index_state = idx.state_dict()
        bundle = ModelBundle(params, emb, ids, index_state, spec, meta)
        if shards > 1:
            bundle.save_sharded(out_dir, shards, nlist=nlist,
                                nprobe=nprobe, index=index)
        else:
            bundle.save(out_dir)
        return bundle

    def train_and_evaluate(self, train_input_fn, eval_input_fn,
                           max_steps: int = 1000,
                           eval_steps: int = 50,
                           eval_every: int = 0,
                           keep_best: bool = False) -> Dict[str, float]:
        """Train with optional interleaved evaluation.

        eval_every > 0 evaluates on eval_input_fn every that many train
        steps (the reference's tf.estimator.train_and_evaluate interleaves
        the same way); keep_best additionally snapshots the parameters at
        the best interleaved eval metric and restores them before the
        final evaluation — the standard early-stopping protocol for the
        citation benchmarks, whose small train splits overfit long before
        a fixed step budget ends.
        """
        if eval_every <= 0:
            train_res = self.train(train_input_fn, max_steps)
            if self.pre_eval_hook:
                self.pre_eval_hook(self)
            eval_res = self.evaluate(eval_input_fn, eval_steps)
            return {**{f"train_{k}": v for k, v in train_res.items()},
                    **{f"eval_{k}": v for k, v in eval_res.items()}}

        owned_feeder = self.feeder_workers > 1 and callable(train_input_fn)
        if owned_feeder:
            # one feeder spans every train segment (segments pass it as
            # a bare iterator, so train() doesn't wrap or close it)
            it = self._wrap_feeder(
                train_input_fn,
                train_input_fn == getattr(self, "train_input_fn", None))
        else:
            it = train_input_fn() if callable(train_input_fn) \
                else train_input_fn
        best_metric, best_step, best_snap = -float("inf"), 0, None
        train_res: Dict[str, float] = {}
        step = 0
        # segments checkpoint once at the end (at the restored-best
        # weights), not once per segment
        saved_ckpt_steps, self.ckpt_steps = self.ckpt_steps, 0
        try:
            while step < max_steps:
                target = min(step + eval_every, max_steps)
                try:
                    seg = self.train(it, max_steps=target)
                except StopIteration:
                    break  # train iterator exhausted at a segment edge
                train_res = seg
                step = seg["global_step"]
                if self.pre_eval_hook:
                    self.pre_eval_hook(self)
                ev = self.evaluate(eval_input_fn, eval_steps)
                m = ev["metric"]
                if keep_best and (best_snap is None or m > best_metric):
                    best_metric, best_step = m, step
                    best_snap = jax.device_get(
                        {"params": self.state.params,
                         "extra_vars": self.state.extra_vars or {}})
                if step < target:
                    break  # train iterator exhausted mid-segment
        finally:
            self.ckpt_steps = saved_ckpt_steps
            if owned_feeder:
                self._close_live_feeder()
            if callable(train_input_fn):
                # the segments' iterator was made here and dies here,
                # with what the last segment read ahead from it
                self._ahead = None
        if keep_best and best_snap is not None:
            self.state = self.state.replace(
                params=jax.tree_util.tree_map(jnp.asarray,
                                              best_snap["params"]),
                extra_vars=_match_placement(
                    best_snap["extra_vars"],
                    self.state.extra_vars or {}) or {})
        if self.ckpt_steps and self.state is not None:
            self.save_checkpoint(step)  # disk matches the reported weights
            self.finalize_checkpoints()
        if self.pre_eval_hook:
            # the restored-best snapshot's cache was refreshed before
            # its eval, but keep_best=False (or a first-segment
            # StopIteration) reaches here without any refresh at all
            self.pre_eval_hook(self)
        eval_res = self.evaluate(eval_input_fn, eval_steps)
        out = {**{f"train_{k}": v for k, v in train_res.items()},
               **{f"eval_{k}": v for k, v in eval_res.items()}}
        if keep_best:
            out["best_step"] = best_step
        return out
