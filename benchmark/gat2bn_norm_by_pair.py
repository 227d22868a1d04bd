"""The planted fault of the MAG240M GAT configuration's own: the
reference with each layer's BatchNorm statistics taken PAIR BY PAIR (the
roots' output normalised by the roots' own mean and variance, hop 1's by
hop 1's, the running statistics moved once a pair) instead of over every
row the layer writes, put in the program's place and judged by the
cell's limits against the reference as it is. It has to fail a limit on
every seed: a program that normalises inside its loop over the pairs,
the obvious place to put the call, must not come out `correct`.
Reference against reference, so any roots do: they are drawn as the
cell's feeder draws them, from the seed; no program is built and nothing
is timed. This module is also the stand-in's `reference` (`loss`,
`init_extra`): `check.run_reference` resolves both by name.

  python3 benchmark/gat2bn_norm_by_pair.py --workload <name> --seeds 11,12,13

One process, TPU required (the readings the limits are judged by are
the chip's); one JSON line per seed on stdout.
"""

from __future__ import annotations

import argparse
import copy
import functools
import json
import os
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import gat2bn  # noqa: E402

loss = functools.partial(gat2bn.loss, by_pair=True)
init_extra = gat2bn.init_extra


def norm_by_pair(cfg: dict, traffic: dict, tabs: dict, records: list,
                 weights: dict, ref: dict) -> dict:
    """The reference with pair-by-pair statistics over `records`, judged
    by the configuration's limits against `ref`, the reference as it is
    over the same records: what it fails is under `over`."""
    from benchmark import check

    off = copy.deepcopy(cfg)
    off["reference"] = "benchmark.gat2bn_norm_by_pair"
    stand_in = check.run_reference(off, traffic, tabs, records, weights)
    numbers = check.first_step_numbers(stand_in, ref)
    ok, compared = check.judge(numbers, cfg["limits"],
                               cfg.get("not_compared", ()))
    return {"correct": ok, "numbers": numbers,
            "over": sorted(n for n, (v, lim) in compared.items()
                           if not v <= lim)}


def read_seed(cell: dict, seed: int, cells_dir: str = BENCH_DIR) -> dict:
    from benchmark import check, tables
    from benchmark.cell import CHECK_STEPS, load_config
    from benchmark.traffic import RootSource, load_traffic

    cfg = load_config(cells_dir, cell["config"])
    traffic = load_traffic(cells_dir, cell["traffic"])
    host = tables.make_tables(
        seed, cfg["num_nodes"], cfg["feature_dim"], cfg["cap"],
        cfg["num_classes"], traffic["edge_weights"])
    weights = check.make_weights(cfg, seed)
    roots = RootSource(cfg["num_nodes"], host["edge_count"], seed)
    records = [(roots.sample_node(int(traffic["root_batch"]))
                .astype(np.int32), i)
               for i in range(1 + CHECK_STEPS + int(cfg["steps_per_loop"]))]
    tabs = check.place_tables(cfg, traffic, host)
    ref = check.run_reference(cfg, traffic, tabs, records, weights)
    fault = norm_by_pair(cfg, traffic, tabs, records, weights, ref)
    check.free_tables(tabs)
    return {"workload": cell["name"], "seed": seed,
            "fault_norm_by_pair": fault}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import jax

    from benchmark.run import find_cell, place_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    place_compile_cache()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = find_cell(json.load(f), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(read_seed(cell, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
