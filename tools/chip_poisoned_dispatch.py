"""The non-finite guard on the chip, at a benchmark cell's own size.

  chiprun -- python3 tools/chip_poisoned_dispatch.py [--seed N]

Builds the activation-cache cell's program (benchmark/cell.Program: the
tables, the model and the feeder of scalablesage-papers100m-s32.
unit-b32768), warms it with sound steps, plants a NaN in ONE row of the
label table and makes that row a root of the steps to poison. Then, bit
for bit on the device:

  single  one poisoned single step (the per-step jit) leaves the cache,
          the parameters and the optimizer state as they were;
  all     a scanned dispatch of 32 poisoned steps does too;
  mixed   a scanned dispatch with steps 5, 17 and 18 poisoned: the cache
          rows that only those steps wrote are as they were, the sound
          steps' rows moved, and the same dispatch with OTHER batches in
          the poisoned places ends in the same bits (what a skipped step
          read, drew and wrote leaves no trace).

Exit 2 without a TPU (--cells_dir / --workload let the CPU run it at the
test size of tests/benchmark_checks/tiny). The last line of stdout is
the result, {"ok": true, ...} when every check held.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

POISONED = (5, 17, 18)   # of a dispatch's steps; (1, 2) where it has fewer


def _bits(a):
    import jax
    import jax.numpy as jnp

    if jnp.issubdtype(a.dtype, jnp.floating):
        return jax.lax.bitcast_convert_type(
            a, {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize])
    return a


def _compare(a, b, rows=None):
    """Over the leaves of two trees (their `rows` alone where given):
    whether every bit agrees, and how many rows differ in the leaf where
    the fewest do. One fused reduction a leaf: a table is never copied."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def leaf(x, y):
        if rows is not None:
            x, y = jnp.take(x, rows, axis=0), jnp.take(y, rows, axis=0)
        differs = (_bits(x) != _bits(y)).reshape(x.shape[:1] + (-1,))
        return jnp.sum(jnp.any(differs, axis=-1))

    rows_differ = [int(leaf(x, y)) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b),
        strict=True)]
    return {"same_bits": not any(rows_differ),
            "fewest_rows_differ": min(rows_differ)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2700000071)
    ap.add_argument("--workload",
                    default="scalablesage-papers100m-s32.unit-b32768")
    ap.add_argument("--cells_dir", default=os.path.join(ROOT, "benchmark"))
    ap.add_argument("--cpu", action="store_true",
                    help="accept a platform other than the TPU (test size)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import check, run, tables
    from benchmark.cell import Program, load_config
    from benchmark.traffic import load_traffic

    run.place_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu:
        print(f"no TPU: jax initialised {dev.platform!r}", file=sys.stderr)
        return 2
    config, _, mix = args.workload.partition(".")
    cfg = load_config(args.cells_dir, config)
    traffic = load_traffic(args.cells_dir, mix)
    host = tables.make_tables(args.seed, cfg["num_nodes"],
                              cfg["feature_dim"], cfg["cap"],
                              cfg["num_classes"], traffic["edge_weights"])
    prog = Program(cfg, traffic, host, args.seed, 1)
    prog.install_weights(check.make_weights(cfg, args.seed))
    est, spl = prog.est, prog.spl
    poisoned = POISONED if spl > max(POISONED) else (1, 2)

    def take(n):
        return [next(prog.feed) for _ in range(n)]

    def run_batches(batches):
        est.train(iter(batches), max_steps=int(est.state.step) + len(batches))
        jax.block_until_ready(est.state)

    def snapshot():
        s = est.state
        return jax.tree_util.tree_map(
            jnp.copy, (s.params, s.opt_state, s.extra_vars))

    def held():
        s = est.state
        return (s.params, s.opt_state, s.extra_vars)

    # sound steps first: non-zero cache rows, non-zero moments, both
    # programs compiled
    run_batches(take(1))
    run_batches(take(spl))
    single, all_bad, mixed = take(1), take(spl), take(spl)
    roots = [np.asarray(b["rows"][0]) for b in single + all_bad + mixed]
    used = np.unique(np.concatenate(roots))
    bad_row = int(np.setdiff1d(np.arange(1, cfg["num_nodes"]), used)[0])
    est.static_batch["label_table"] = jax.jit(
        lambda t: t.at[bad_row].set(jnp.nan), donate_argnums=0)(
            est.static_batch["label_table"])

    def poison(b):
        rows = np.array(b["rows"][0])
        rows[0] = bad_row
        return {**b, "rows": [jax.device_put(rows)]}

    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "workload": args.workload, "seed": args.seed,
           "bad_row": bad_row}
    cache_rows = sum(int(np.prod(v.shape[:1])) for v in
                     jax.tree_util.tree_leaves(est.state.extra_vars))
    out["cache_rows"] = cache_rows

    def skipped_and_step():
        return int(est.state.skipped_steps), int(est.state.step)

    # -- single --------------------------------------------------------------
    before, (sk0, st0) = snapshot(), skipped_and_step()
    run_batches([poison(single[0])])
    sk1, st1 = skipped_and_step()
    out["single"] = {"same_bits": _compare(held(), before)["same_bits"],
                     "skipped": sk1 - sk0, "steps": st1 - st0}
    print(json.dumps({"single": out["single"]}), file=sys.stderr, flush=True)
    # -- all -----------------------------------------------------------------
    run_batches([poison(b) for b in all_bad])
    sk2, st2 = skipped_and_step()
    out["all"] = {"same_bits": _compare(held(), before)["same_bits"],
                  "skipped": sk2 - sk1, "steps": st2 - st1}
    print(json.dumps({"all": out["all"]}), file=sys.stderr, flush=True)
    # -- mixed ---------------------------------------------------------------
    run_batches([poison(b) if i in poisoned else b
                 for i, b in enumerate(mixed)])
    sk3, st3 = skipped_and_step()
    got = snapshot()
    mixed_roots = roots[1 + spl:]
    sound = np.unique(np.concatenate(
        [r for i, r in enumerate(mixed_roots) if i not in poisoned]))
    only_bad = np.setdiff1d(np.concatenate(
        [mixed_roots[i][1:] for i in poisoned]), sound)
    out["mixed"] = {
        "skipped": sk3 - sk2, "steps": st3 - st2,
        "rows_only_poisoned_steps_wrote": int(only_bad.size),
        "those_rows_same_bits": _compare(
            got[2], before[2], jnp.asarray(only_bad))["same_bits"],
        "sound_rows": int(sound.size),
        "sound_rows_moved": _compare(
            got[2], before[2], jnp.asarray(sound))["fewest_rows_differ"],
        "all_finite": all(bool(jnp.all(jnp.isfinite(x)))
                          for x in jax.tree_util.tree_leaves(got)),
    }
    print(json.dumps({"mixed": out["mixed"]}), file=sys.stderr, flush=True)
    # the same start and sound batches, other batches poisoned
    est.state = est.state.replace(
        params=before[0], opt_state=before[1], extra_vars=before[2])
    del before
    run_batches([poison(all_bad[i]) if i in poisoned else b
                 for i, b in enumerate(mixed)])
    out["mixed"]["same_bits_with_other_poisoned_batches"] = _compare(
        held(), got)["same_bits"]
    prog.free()

    m = out["mixed"]
    out["ok"] = bool(
        out["single"] == {"same_bits": True, "skipped": 1, "steps": 1}
        and out["all"] == {"same_bits": True, "skipped": spl, "steps": spl}
        and m["skipped"] == len(poisoned) and m["steps"] == spl
        and m["rows_only_poisoned_steps_wrote"] > 0
        and m["those_rows_same_bits"] and m["all_finite"]
        and m["sound_rows_moved"] > 0.9 * m["sound_rows"]
        and m["same_bits_with_other_poisoned_batches"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "poisoned_dispatch.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
