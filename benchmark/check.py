"""How `correct` is decided: the reference follows the program's first
steps on the same tables, weights and roots, and every number compared
has a limit of its own in the configuration's file.

Numbers (all gaps are relative, program against reference):
  loss1..3    the loss of each of the three single steps
  grad1       the first gradient as Adam got it (its first moment after
              one step over 1 - b1): the worst leaf's gap of NORMS, over
              the larger of that leaf's reference norm and the median
              leaf's
  dparam3     the change of the parameters over the three steps, by the
              same measure, leaves left out whose reference gradient is
              under a thousandth of the median leaf's
  state3      the same for what else the train state carries (the
              activation cache), where there is such state
and of the first SCANNED dispatch, the compiled program the window
times (steps 4 to 3 + steps_per_loop), against the reference carried on
through all of it:
  scan_loss   its first two losses (the later ones carry Adam's
              sign-flip noise: PERF.md)
  scan_dparam the change of the parameters over the dispatch, by the
              measure of dparam3 (a dispatch that leaves them reads 1)
  scan_mom2   Adam's second moment after the dispatch (the decayed sum
              of its steps' squared gradients, so rows left out of a mean
              or a gradient scaled show here): the MEDIAN leaf's gap of
              norms; scan_mom2_worst is the worst leaf's, which a
              configuration whose later steps are noise names under
              `not_compared` (PERF.md has the look)
  scan_state  the norm of the other state (the cache) after it
and, exact: the steps the window's dispatches did not make, the steps
its non-finite guard skipped and its non-finite losses.

A number for which neither the control nor a fault gives an upper reading
is named, with the reason, under `not_compared` in the configuration's
file and is left out (PERF.md gives the readings).
"""

from __future__ import annotations

import importlib
import statistics
import sys

import numpy as np

from .cell import CHECK_STEPS, SCAN_CHECKED, resolve
from .reference import common

_WEIGHT_STREAM = 1 << 22
ZERO_GRAD_SHARE = 1e-3


def make_weights(cfg: dict, seed: int) -> dict:
    """The seed's initial weights, {leaf path: float32 array}: the
    reference's own `init_weights(cfg, rng, shapes)` where its module
    defines one, else common.lecun_normal, from the same stream."""
    mod = cfg["reference"]
    shapes = resolve(mod + ".param_shapes")(cfg)
    rng = np.random.default_rng([int(seed), _WEIGHT_STREAM])
    init = getattr(importlib.import_module(mod), "init_weights", None)
    if init is None:
        return common.lecun_normal(rng, shapes)
    return init(cfg, rng, shapes)


def place_tables(cfg: dict, traffic: dict, host: dict) -> dict:
    """The reference's own device tables, from the host tables: features
    quantised by its own arithmetic, classes as integers (the cross-
    entropy of a one-hot row is that of its class)."""
    import jax.numpy as jnp

    uniform = traffic["edge_weights"]["kind"] == "unit"
    q, scale = common.quantize_int8(host["feat"])
    return {"nbr": jnp.asarray(host["nbr"]),
            "cum": jnp.asarray(host["cum"] if not uniform
                               else host["cum"][:1]),
            "q": jnp.asarray(q), "scale": jnp.asarray(scale),
            "cls": jnp.asarray(host["cls"])}


def free_tables(tables: dict) -> None:
    for a in tables.values():
        a.delete()


def run_reference(cfg: dict, traffic: dict, tables: dict, records: list,
                  weights: dict, precision: str = "float32",
                  batch_share: float = 1.0, frozen: bool = False,
                  fault_from: int = 1) -> dict:
    """The reference over records[1 : 1 + CHECK_STEPS + steps_per_loop]
    (records[0] went into the program's state init and made no step):
    the three single steps and the whole first scanned dispatch.
    precision "bfloat16" is the control; batch_share 0.5 plants the
    half-batch fault (the mean taken over the first half); frozen plants
    the step that returns its state unchanged; either from step
    `fault_from` on (CHECK_STEPS + 1: inside the scanned dispatch only)."""
    import jax
    import jax.numpy as jnp

    mod = cfg["reference"]
    loss_fn, init_extra = resolve(mod + ".loss"), resolve(mod + ".init_extra")
    uniform = traffic["edge_weights"]["kind"] == "unit"
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[precision]
    o = cfg["optimizer"]

    @jax.jit
    def step(params, opt, extra, tabs, roots, seed):
        def of_params(p):
            return loss_fn(p, extra, tabs, roots, seed, cfg, uniform, dtype)

        (loss, new_extra), grads = jax.value_and_grad(
            of_params, has_aux=True)(params)
        new_params, new_opt = common.adam_step(
            params, grads, opt, o["learning_rate"], o["b1"], o["b2"],
            o["eps"])
        return loss, grads, new_params, new_opt, new_extra

    def host(tree):
        return {k: np.asarray(v, np.float64) for k, v in tree.items()}

    def extra_norms():
        return {k: float(jnp.sqrt(jnp.sum(jnp.square(
            v.astype(jnp.float32))))) for k, v in extra.items()}

    params = {k: jnp.asarray(v) for k, v in weights.items()}
    p0 = host(weights)
    extra = init_extra(cfg, cfg["num_nodes"] + 1)
    opt = common.adam_init(params)
    out = {"loss": [], "scan_loss": []}
    n_steps = CHECK_STEPS + int(cfg["steps_per_loop"])
    with jax.default_matmul_precision("highest"):
        for i in range(1, n_steps + 1):
            roots, seed = records[i]
            faulty = i >= fault_from
            if faulty:
                roots = roots[:max(1, int(len(roots) * batch_share))]
            loss, grads, new_params, new_opt, new_extra = step(
                params, opt, extra, tables, jnp.asarray(roots),
                jnp.uint32(seed))
            if not (frozen and faulty):
                params, opt, extra = new_params, new_opt, new_extra
            if i <= CHECK_STEPS:
                out["loss"].append(float(loss))
            elif i <= CHECK_STEPS + SCAN_CHECKED:
                out["scan_loss"].append(float(loss))
            if i == 1:
                out["grad1"] = host(grads)
            if i == CHECK_STEPS:
                p3 = host(params)
                out["dparam"] = {k: p3[k] - p0[k] for k in p0}
                out["extra_norm"] = extra_norms()
    p_end = host(params)
    out["scan_dparam"] = {k: p_end[k] - p3[k] for k in p3}
    out["scan_mom2"] = host(opt["v"])
    out["scan_extra_norm"] = extra_norms()
    for a in extra.values():
        a.delete()
    return out


def norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in tree.items()}


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Each leaf's |norm_program - norm_reference| over the larger of the
    reference's norm of that leaf and of its median leaf."""
    floor = statistics.median(ref.values())
    return {k: abs(prog[k] - r) / max(r, floor, 1e-30)
            for k, r in ref.items() if keep is None or k in keep}


def leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """The worst leaf's gap."""
    return max(leaf_gaps(prog, ref, keep).values(), default=0.0)


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def first_step_numbers(prog: dict, ref: dict) -> dict:
    """The numbers compared, from the program's first steps (or whatever
    stands in its place) and the reference's."""
    out = {}
    for i in range(CHECK_STEPS):
        out[f"loss{i + 1}"] = _rel(prog["loss"][i], ref["loss"][i])
    g_ref = norms(ref["grad1"])
    out["grad1"] = leaf_gap(norms(prog["grad1"]), g_ref)
    floor = ZERO_GRAD_SHARE * statistics.median(g_ref.values())
    moving = {k for k, g in g_ref.items() if g >= floor}
    out["dparam3"] = leaf_gap(norms(prog["dparam"]), norms(ref["dparam"]),
                              keep=moving)
    if ref["extra_norm"]:
        out["state3"] = leaf_gap(prog["extra_norm"], ref["extra_norm"])
    out["scan_loss"] = max(_rel(p, r) for p, r in
                           zip(prog["scan_loss"], ref["scan_loss"]))
    out["scan_dparam"] = leaf_gap(norms(prog["scan_dparam"]),
                                  norms(ref["scan_dparam"]), keep=moving)
    mom2 = leaf_gaps(norms(prog["scan_mom2"]), norms(ref["scan_mom2"]),
                     keep=moving)
    out["scan_mom2"] = statistics.median(mom2.values())
    out["scan_mom2_worst"] = max(mom2.values())
    if ref["scan_extra_norm"]:
        out["scan_state"] = leaf_gap(prog["scan_extra_norm"],
                                     ref["scan_extra_norm"])
    return out


def window_numbers(win: dict) -> dict:
    return {"steps_missing": win["steps_expected"] - win["steps_done"],
            "steps_skipped": win["steps_skipped"],
            "losses_nonfinite": win["losses_nonfinite"]}


def judge(numbers: dict, limits: dict, not_compared=()):
    """-> (correct, {name: [value, limit]}). A number that has neither a
    limit nor a stated reason for having none (the configuration's
    `not_compared`) is an error, not a pass."""
    compared, ok = {}, True
    for name, value in numbers.items():
        if name in not_compared:
            continue
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the configuration's "
                           "file")
        limit = limits[name]
        compared[name] = [value, limit]
        if not (np.isfinite(value) and value <= limit):
            ok = False
    return ok, compared


def report(compared: dict, correct: bool) -> None:
    """The numbers beside their limits, as the last lines of stderr."""
    for name, (value, limit) in compared.items():
        mark = "ok" if np.isfinite(value) and value <= limit else "OVER"
        print(f"compared {name} {value!r} limit {limit!r} {mark}",
              file=sys.stderr)
    print(f"correct {correct}", file=sys.stderr, flush=True)
