"""SPMD training step over a device mesh.

The multi-chip training path (SURVEY.md §2.4 mapping): batch arrays are
sharded over the 'data' axis, embedding tables over 'model', everything
else replicated; the jitted step lets XLA GSPMD insert gradient
all-reduces over ICI. Used by the estimator (mesh=...), bench.py's
multi-chip mode, and __graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax.sharding import Mesh

from euler_tpu import obs as _obs
from euler_tpu.parallel.mesh import shard_batch
from euler_tpu.parallel.sharded_embedding import apply_param_shardings


def spmd_init(model: nn.Module, tx: optax.GradientTransformation,
              sample_batch: Dict, mesh: Mesh, seed: int = 0) -> Dict[str, Any]:
    """Initializes sharded train state: params placed per their
    partitioning metadata (embedding rows over 'model'), optimizer state
    mirrors the param placement."""
    rng = jax.random.key(seed)
    batch = shard_batch(sample_batch, mesh)
    # one compiled program, as BaseEstimator._init_state dispatches it:
    # op by op, every primitive of the forward pass is a compile of its
    # own (some 200 for a device-sampled model over row-sharded tables)
    variables = jax.jit(model.init)(rng, batch)
    variables = apply_param_shardings(variables, mesh)
    params = variables.pop("params")
    opt_state = tx.init(params)
    return {"params": params, "opt_state": opt_state,
            "extra_vars": variables, "step": jnp.zeros((), jnp.int32),
            "skipped_steps": jnp.zeros((), jnp.int32)}


def make_spmd_train_step(model: nn.Module,
                         tx: optax.GradientTransformation,
                         mutable_keys: Tuple[str, ...] = (),
                         table_store=None,
                         table_rows_key: str = "rows") -> Callable:
    """Jitted (state, batch) → (state, loss, metric). State buffers are
    donated so HBM is reused across steps — which is exactly why the
    step is guarded: one NaN loss applied to donated buffers destroys
    the only copy of the params. A bad step keeps the old
    params/opt_state and bumps state['skipped_steps'].

    table_store (a PartitionedFeatureStore) turns on per-step gather
    accounting in the HOST wrapper: each dispatch's table rows
    (batch[table_rows_key], a row array or list of per-hop row arrays)
    are routed through store.observe_batch before the device call, so
    the table_gather_{local,cached,remote}_rows counters track exactly
    the dispatched steps. Pass HOST row arrays — a device-resident
    array here costs a blocking device→host fetch per step. (The
    estimator path does its own counting in NodeEstimator._node_batch/
    _sampler_batch; this hook serves raw spmd-loop callers.)"""

    def train_step(state, batch):
        # states built before spmd_init grew the counter (hand-rolled
        # dicts) can't be guarded — structure of both cond branches must
        # match the input pytree
        has_ctr = "skipped_steps" in state

        def loss_fn(p):
            variables = {"params": p, **state["extra_vars"]}
            if mutable_keys:
                out, new_vars = model.apply(variables, batch,
                                            mutable=list(mutable_keys))
            else:
                out = model.apply(variables, batch)
                new_vars = state["extra_vars"]
            return out.loss, (out.metric, new_vars)

        (loss, (metric, new_vars)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])

        def apply_update(_):
            updates, opt_state = tx.update(grads, state["opt_state"],
                                           state["params"])
            params = optax.apply_updates(state["params"], updates)
            new = {"params": params, "opt_state": opt_state,
                   "extra_vars": new_vars, "step": state["step"] + 1}
            if has_ctr:
                new["skipped_steps"] = state["skipped_steps"]
            return new

        def skip_update(_):
            new = dict(state)
            new["step"] = state["step"] + 1
            if has_ctr:
                new["skipped_steps"] = state["skipped_steps"] + 1
            return new

        if has_ctr:
            # loss AND grads: backward-pass overflow can produce NaN
            # grads under a finite loss
            ok = jnp.isfinite(loss)
            for g in jax.tree_util.tree_leaves(grads):
                ok &= jnp.all(jnp.isfinite(g))
            new_state = jax.lax.cond(ok, apply_update, skip_update, None)
        else:
            new_state = apply_update(None)
        return new_state, loss, metric

    jitted = jax.jit(train_step, donate_argnums=(0,))
    reg = _obs.default_registry()
    c_steps = reg.counter("spmd_steps_total",
                          "SPMD train-step dispatches")
    h_dispatch = reg.histogram(
        "spmd_dispatch_ms",
        "host-side SPMD train-step dispatch latency (async: excludes "
        "device time the host did not wait for)")

    def stepped(state, batch):
        t0 = time.monotonic()
        if table_store is not None and table_rows_key in batch:
            rows = batch[table_rows_key]
            for r in (rows if isinstance(rows, (list, tuple)) else [rows]):
                table_store.observe_batch(np.asarray(r))
        with _obs.span("spmd_train_step"):
            out = jitted(state, batch)
        c_steps.inc()
        h_dispatch.observe((time.monotonic() - t0) * 1000.0)
        return out

    return stepped
