"""The readings the limits are set from, and the control's: for each seed
the program's first steps against the reference, and for the first
--controls seeds also the reference in bfloat16 put in the program's
place (the control) and the two faults planted in the reference: half of
each batch left out, and a step that leaves its state unchanged, each
from the first step on and, again, inside the scanned dispatch only.
Every stand-in is judged by the cell's own limits, and the numbers it
fails are printed beside its gaps: the control and each fault have to
fail one. No measured window: training's readings need none.

  python3 benchmark/readings.py --workload <name> --seeds 11,12,13 --controls 3

One process, TPU required; one JSON line per seed on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def read_seed(cell: dict, seed: int, controls: bool,
              cells_dir: str = BENCH_DIR) -> dict:
    from benchmark import check, tables
    from benchmark.cell import CHECK_STEPS, Program, load_config
    from benchmark.traffic import load_traffic

    cfg = load_config(cells_dir, cell["config"])
    traffic = load_traffic(cells_dir, cell["traffic"])
    host = tables.make_tables(
        seed, cfg["num_nodes"], cfg["feature_dim"], cfg["cap"],
        cfg["num_classes"], traffic["edge_weights"])
    weights = check.make_weights(cfg, seed)
    prog = Program(cfg, traffic, host, seed, cell["chips"])
    try:
        prog.install_weights(weights)
        firsts = prog.first_steps()
        records = prog.records
    finally:
        prog.free()

    def judged(stand_in):
        """The numbers of whatever stands in the program's place, judged
        by the cell's limits, with the numbers it fails and, leaf by
        leaf, the second moment's gap after the scanned dispatch (the
        look at scan_mom2)."""
        numbers = check.first_step_numbers(stand_in, ref)
        ok, compared = check.judge(numbers, cfg["limits"],
                                   cfg.get("not_compared", ()))
        return {"correct": ok, "numbers": numbers,
                "over": sorted(n for n, (v, lim) in compared.items()
                               if not v <= lim),
                "scan_mom2_leaves": check.leaf_gaps(
                    check.norms(stand_in["scan_mom2"]),
                    check.norms(ref["scan_mom2"]))}

    t0 = time.perf_counter()
    tabs = check.place_tables(cfg, traffic, host)
    t1 = time.perf_counter()
    ref = check.run_reference(cfg, traffic, tabs, records, weights)
    out = {"workload": cell["name"], "seed": seed,
           "program": judged(firsts),
           "loss": firsts["loss"],
           "reference_s": {"tables": t1 - t0,
                           "steps": time.perf_counter() - t1}}
    if controls:
        scan_only = {"fault_from": CHECK_STEPS + 1}
        for name, kw in (
                ("control_bfloat16", {"precision": "bfloat16"}),
                ("fault_half_batch", {"batch_share": 0.5}),
                ("fault_state_unchanged", {"frozen": True}),
                ("fault_half_batch_scan", {"batch_share": 0.5, **scan_only}),
                ("fault_state_unchanged_scan", {"frozen": True, **scan_only})):
            stand_in = check.run_reference(cfg, traffic, tabs, records,
                                           weights, **kw)
            out[name] = judged(stand_in)
    check.free_tables(tabs)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=0)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    from benchmark.run import find_cell, place_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    place_compile_cache()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = find_cell(json.load(f), args.workload)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        print(json.dumps(read_seed(cell, seed, i < args.controls)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
