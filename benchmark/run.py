"""One run of one cell of BENCHMARK.json.

  python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process on the machine it is started on; a TPU with as many chips as
the cell asks for is required (no other platform is accepted: exit 2 and
no result). The last line of stdout is the result. Everything a cell is
made of is data found by name: configs/<config>.json with the model's
dotted path and its reference, traffic/<traffic>.json, and for each
per-layer metric metrics/<metric>.json with its reader.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def place_compile_cache() -> str:
    """jax's persistent cache: where the environment says, else the fixed
    <checkout>/.jax_cache; every program is kept, however quick."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _log(what: str) -> None:
    print(f"[{time.perf_counter() - _T0:8.2f} s] {what}", file=sys.stderr,
          flush=True)


def find_cell(bench: dict, workload: str):
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")


def metrics_of(bench: dict, group: str, workload: str):
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def run_cell(bench: dict, args, cells_dir: str = BENCH_DIR,
             require_tpu: bool = True, trace_dir: str = TRACE_DIR,
             planted=None) -> dict:
    """The whole run; returns the result line as a dict. cells_dir holds
    configs/ and traffic/. require_tpu is False only in the CPU tests;
    `planted(program)` lets them break the timed path underneath before
    its first step."""
    import jax

    import euler_tpu.estimator  # noqa: F401 - absent program: fail now
    from benchmark import check, reduce_trace, tables
    from benchmark.cell import (
        CompileWatch, Program, bind_model_fields, load_config, load_json,
        resolve, resolve_functions,
    )
    from benchmark.traffic import load_traffic

    cell = find_cell(bench, args.workload)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        print(f"no TPU: jax initialised {dev.platform!r}", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < cell["chips"]:
        print(f"{cell['chips']} chips asked, {len(devices)} found",
              file=sys.stderr)
        raise SystemExit(2)
    cfg = load_config(cells_dir, cell["config"])
    bind_model_fields(cfg)
    resolve_functions(cfg)
    traffic = load_traffic(cells_dir, cell["traffic"])
    peaks_all = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if require_tpu and dev.device_kind not in peaks_all:
        raise SystemExit(f"no peaks for device kind {dev.device_kind!r}")
    watch = CompileWatch()

    host = tables.make_tables(
        args.seed, cfg["num_nodes"], cfg["feature_dim"], cfg["cap"],
        cfg["num_classes"], traffic["edge_weights"])
    _log(f"tables made: {cfg['num_nodes']} rows, "
         f"{host['edge_count']} edges")
    weights = check.make_weights(cfg, args.seed)
    prog = Program(cfg, traffic, host, args.seed, cell["chips"])
    try:
        _log(f"tables placed, estimator built (uniform rows: "
             f"{prog.uniform})")
        prog.install_weights(weights)
        if planted is not None:
            planted(prog)
        firsts = prog.first_steps()
        setup_s = time.perf_counter() - _T0
        _log(f"first steps and warm-up done: {watch.count} programs "
             f"built or fetched in {watch.secs:.1f} s")
        tracer = None
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            tracer = reduce_trace.Tracer(trace_dir)
        win = prog.window(args.seconds, watch, tracer)
        stats = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
        peak = max((int(s.get("peak_bytes_in_use", 0)) for s in stats),
                   default=0)
        records = prog.records
        _log(f"window closed: {len(win['dispatch_secs'])} dispatches in "
             f"{win['elapsed']:.2f} s")
        for i, (d, (wait, enq, rest)) in enumerate(
                zip(win["dispatch_secs"], win["dispatch_parts_ms"])):
            print(f"dispatch {i}: {d:.4f} s = input wait {wait:.1f} ms + "
                  f"stack and enqueue {enq:.1f} ms + device and fetch "
                  f"{rest:.1f} ms", file=sys.stderr)
    finally:
        prog.free()

    ref_tables = check.place_tables(cfg, traffic, host)
    ref = check.run_reference(cfg, traffic, ref_tables, records, weights)
    check.free_tables(ref_tables)
    _log("reference done")
    numbers = check.first_step_numbers(firsts, ref)
    numbers.update(check.window_numbers(win))
    correct, compared = check.judge(numbers, cfg["limits"],
                                    cfg.get("not_compared", ()))

    steps_per_s = win["steps_done"] / win["elapsed"] if win["elapsed"] else 0
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"], "memory_peak_bytes": peak}
    result = {"correct": bool(correct),
              "attempted": win["steps_expected"],
              "failed": (win["steps_expected"] - win["steps_done"])
              + win["steps_skipped"] + win["losses_nonfinite"]
              + (win["steps_expected"] if win["compiles"] else 0)}
    metrics = {}
    if args.trace:
        reduced = reduce_trace.reduce(
            reduce_trace.read_planes(reduce_trace.find_xplane(win["trace"])))
        ctx = {"cfg": cfg, "traffic": traffic, "window": win,
               "trace": reduced, "peaks": peaks_all.get(dev.device_kind)}
        for m in metrics_of(bench, "per_layer", args.workload):
            spec = load_json(os.path.join(BENCH_DIR, "metrics",
                                          m["name"] + ".json"))
            value = resolve(spec["reader"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        values = {"train_nodes_per_s": steps_per_s * win["batch"],
                  "setup_s": setup_s}
        for m in metrics_of(bench, "end_to_end", args.workload):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result.update(metrics=metrics, device=device, compared=compared)
    if win["compiles"]:
        print(f"{win['compiles']} compile(s) inside the measured window",
              file=sys.stderr)
    check.report(compared, result["correct"])
    return result


def main(argv=None) -> int:
    args = _args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    place_compile_cache()
    result = run_cell(bench, args)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
