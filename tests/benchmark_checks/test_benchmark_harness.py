"""CPU checks of the benchmark harness (benchmark/, BENCHMARK.json), at
tiny sizes: no chip, no described topology, nothing compiled for a TPU.

The run tests go through benchmark.run.run_cell with require_tpu=False,
the only thing that differs from a run on the chip besides the sizes
(tiny/ holds test-size copies of the two configurations and of the
mixes). The control (the reference in bfloat16, in the program's place)
and the two faults a one-chip training cell can have (a step that leaves
its state unchanged; half of the batch left out), planted under every
step or inside the scanned dispatch alone, have to come out as not
correct here as they do on the chip at the cells' own size (PERF.md).
"""

import argparse
import contextlib
import importlib
import json
import numbers
import os
import re
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import (  # noqa: E402
    check, readers, reduce_trace, run, tables, work,
)
from benchmark.cell import (  # noqa: E402
    load_config, resolve, resolve_functions,
)
from benchmark.reference import common  # noqa: E402
from benchmark.traffic import RootSource, load_traffic  # noqa: E402

TINY = str(Path(__file__).resolve().parent / "tiny")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TINY_CELLS = {
    "sage3-tiny.unit-b64": ("sage3-tiny", "unit-b64"),
    "sage3-tiny.weighted-b64": ("sage3-tiny", "weighted-b64"),
    "scalablesage-tiny.unit-b64": ("scalablesage-tiny", "unit-b64"),
}
SEED = 2147483699   # past 2**31, as the driver's are


def _tiny_bench():
    b = dict(BENCH)
    b["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                       "why": "test size"}
                      for n, (c, t) in TINY_CELLS.items()]
    return b


def _run(workload, tmp, trace=0, planted=None):
    args = argparse.Namespace(workload=workload, seed=SEED, seconds=0.3,
                              trace=trace)
    return run.run_cell(_tiny_bench(), args, cells_dir=TINY,
                        require_tpu=False,
                        trace_dir=os.path.join(tmp, "trace"),
                        planted=planted)


# -- a whole run ------------------------------------------------------------
@pytest.mark.parametrize("workload,trace", [
    ("sage3-tiny.unit-b64", 0), ("sage3-tiny.weighted-b64", 1),
    ("scalablesage-tiny.unit-b64", 0)])
def test_run_prints_a_well_formed_correct_line(workload, trace, tmp_path):
    r = json.loads(json.dumps(_run(workload, str(tmp_path), trace)))
    assert list(r)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(r)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(r["device"])
    for name, (value, limit) in r["compared"].items():
        assert value <= limit, name
    group = "per_layer" if trace else "end_to_end"
    known = {m["name"]: m["unit"] for m in BENCH[group]}
    assert r["metrics"], "no metric reported"
    for name, m in r["metrics"].items():
        assert m["unit"] == known[name] and np.isfinite(m["value"])
    if trace:
        assert "breakdown" in r and "busy_s" in r["device"]
        # no device plane on the CPU: the shares of peaks and the idle
        # share are left out, never reported as 0
        assert "device_idle_pct" not in r["metrics"]
        assert "step_mfu_pct" not in r["metrics"]
        assert r["metrics"]["compiles_in_window"]["value"] == 0
        assert r["metrics"]["input_wait_ms"]["value"] > 0
    else:
        assert set(r["metrics"]) == {"train_nodes_per_s", "setup_s"}
        assert r["metrics"]["train_nodes_per_s"]["value"] > 0


def _state_unchanged(prog):
    import optax

    prog.est.state = prog.est.state.replace(tx=optax.set_to_zero())


def _half_batch(prog):
    apply_fn = prog.est.state.apply_fn

    def on_half(variables, batch, **kw):
        rows = batch["rows"][0]
        return apply_fn(variables,
                        {**batch, "rows": [rows[:rows.shape[0] // 2]]},
                        **kw)

    prog.est.state = prog.est.state.replace(apply_fn=on_half)


def _in_scan_only(broken_loop):
    """Plants a fault in the scanned dispatch's program alone: the
    single steps before it stay sound."""
    def plant(prog):
        build = prog.est._build_train_loop
        prog.est._build_train_loop = lambda: broken_loop(build())
    return plant


def _scan_on_half(loop):
    def on_half(state, batches, static_batch):
        rows = batches["rows"][0]
        return loop(state, {**batches,
                            "rows": [rows[:, :rows.shape[1] // 2]]},
                    static_batch)
    return on_half


def _scan_keeps_state(loop):
    import jax
    import jax.numpy as jnp

    def kept(state, batches, static_batch):
        old = jax.tree_util.tree_map(jnp.copy, state)   # loop donates it
        new, losses, metrics = loop(state, batches, static_batch)
        return old.replace(step=new.step), losses, metrics
    return kept


@pytest.mark.parametrize("workload,fault,caught_by", [
    ("sage3-tiny.unit-b64", _state_unchanged,
     {"grad1", "dparam3", "scan_dparam"}),
    ("sage3-tiny.unit-b64", _half_batch, {"grad1", "scan_mom2"}),
    ("sage3-tiny.unit-b64", _in_scan_only(_scan_on_half), {"scan_mom2"}),
    ("sage3-tiny.unit-b64", _in_scan_only(_scan_keeps_state),
     {"scan_dparam", "scan_mom2"}),
    ("scalablesage-tiny.unit-b64", _in_scan_only(_scan_on_half),
     {"scan_mom2", "scan_state"}),
    ("scalablesage-tiny.unit-b64", _in_scan_only(_scan_keeps_state),
     {"scan_dparam", "scan_state"})],
    ids=["state_unchanged", "half_batch", "half_batch_in_scan",
         "state_unchanged_in_scan", "cache_half_batch_in_scan",
         "cache_state_unchanged_in_scan"])
def test_a_broken_timed_path_is_not_correct(workload, fault, caught_by,
                                            tmp_path):
    r = _run(workload, str(tmp_path), planted=fault)
    assert r["correct"] is False
    over = {n for n, (v, lim) in r["compared"].items() if not v <= lim}
    assert caught_by <= over, r["compared"]


def test_the_bfloat16_control_is_not_correct():
    """The reference in bfloat16, put in the program's place, fails a
    limit of each tiny configuration."""
    for config, mix in (("sage3-tiny", "unit-b64"),
                        ("scalablesage-tiny", "unit-b64")):
        cfg, traffic = load_config(TINY, config), load_traffic(TINY, mix)
        host = tables.make_tables(SEED, cfg["num_nodes"],
                                  cfg["feature_dim"], cfg["cap"],
                                  cfg["num_classes"],
                                  traffic["edge_weights"])
        weights = check.make_weights(cfg, SEED)
        src = RootSource(cfg["num_nodes"], 0, SEED)
        records = [(src.sample_node(64).astype(np.int32), i)
                   for i in range(1 + 3 + cfg["steps_per_loop"])]
        tabs = check.place_tables(cfg, traffic, host)
        ref = check.run_reference(cfg, traffic, tabs, records, weights)
        ctl = check.run_reference(cfg, traffic, tabs, records, weights,
                                  precision="bfloat16")
        numbers = check.first_step_numbers(ctl, ref)
        ok, compared = check.judge(numbers, cfg["limits"],
                                   cfg.get("not_compared", ()))
        assert not ok, compared
        same = check.first_step_numbers(ref, ref)
        assert max(same.values()) == 0.0


class _TablesAsked(Exception):
    """Raised where a run makes its tables: it got that far."""


def cells_dir_with(tmp: Path, cfg: dict) -> str:
    """A cells directory that holds `cfg` as its configuration's file
    beside the benchmark's traffic mixes."""
    (tmp / "configs").mkdir(parents=True)
    (tmp / "configs" / (cfg["name"] + ".json")).write_text(json.dumps(cfg))
    shutil.copytree(ROOT / "benchmark" / "traffic", tmp / "traffic")
    return str(tmp)


def tables_made_by_a_run(workload: str, cells_dir: str, monkeypatch,
                         raises=_TablesAsked, match=None) -> int:
    """Runs a cell of BENCHMARK.json from `cells_dir` up to the making
    of its tables, which raises in their place; how often it got there."""
    asked = []

    def make_tables(*args, **kw):
        asked.append(args)
        raise _TablesAsked

    monkeypatch.setattr(tables, "make_tables", make_tables)
    with pytest.raises(raises, match=match):
        run.run_cell(BENCH, argparse.Namespace(
            workload=workload, seed=SEED, seconds=1, trace=0),
            cells_dir=cells_dir, require_tpu=False)
    return len(asked)


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_the_model_fields_are_bound_before_any_table_is_made(
        config, tmp_path, monkeypatch):
    """Each configuration's fields bind to the program's model class, so
    its run comes to its tables; a field no model class takes, handed as
    a keyword or as a fact of the run, stops the run before them."""
    workload = next(w["name"] for w in BENCH["workloads"]
                    if w["config"] == config)
    assert tables_made_by_a_run(workload, str(ROOT / "benchmark"),
                                monkeypatch) == 1
    for group, field, value in (("kwargs", "a_field_no_model_has", 1),
                                ("from_run", "a_fact_no_model_takes",
                                 "num_nodes")):
        cfg = load_config(str(ROOT / "benchmark"), config)
        cfg["model"].setdefault(group, {})[field] = value
        assert tables_made_by_a_run(
            workload, cells_dir_with(tmp_path / group, cfg), monkeypatch,
            raises=TypeError, match=field) == 0


@pytest.mark.parametrize("key,value,match", [
    ("kernels", None, "names no kernels"),
    ("kernels", "benchmark.kernel_work.no_such_count", "no_such_count"),
    ("kernels", "benchmark.no_such_module.sage", "no_such_module"),
    ("work", "benchmark.work.no_such_work", "no_such_work"),
    ("reference", "benchmark.reference.common", "param_shapes")],
    ids=["kernels_missing", "kernels_no_function", "kernels_no_module",
         "work_no_function", "reference_without_param_shapes"])
def test_the_named_functions_are_resolved_before_any_table_is_made(
        key, value, match, tmp_path, monkeypatch):
    """A function the configuration's file names that does not exist
    (its table kernels' counts, its work counts, its reference's
    functions) stops the run before its tables, not in a traced run's
    readers after them."""
    cfg = load_config(str(ROOT / "benchmark"), "sage3-papers100m-s32")
    if value is None:
        del cfg[key]
    else:
        cfg[key] = value
    workload = next(w["name"] for w in BENCH["workloads"]
                    if w["config"] == cfg["name"])
    assert tables_made_by_a_run(
        workload, cells_dir_with(tmp_path, cfg), monkeypatch,
        raises=LookupError, match=match) == 0


def test_no_tpu_means_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.run_cell(BENCH, argparse.Namespace(
            workload=BENCH["workloads"][0]["name"], seed=1, seconds=1,
            trace=0))
    assert e.value.code == 2
    assert capsys.readouterr().out == ""


# -- the yardstick's arithmetic --------------------------------------------
def test_work_counts_match_hand_sums():
    cfg = {"feature_dim": 8, "num_classes": 3, "cap": 4,
           "feature_storage": "int8",
           "model": {"kwargs": {"dim": 2, "fanouts": [2, 3]}}}
    # hops 5, 10, 30 rows; layer 0 on hops 0 and 1 (input is data: x2),
    # layer 1 on hop 0 (x3), output (x3)
    l0 = 2 * (2 * (5 + 10) * 8 * 2) * 2
    l1 = 2 * (2 * 5 * 4 * 2) * 3
    out = (2 * 5 * 4 * 3) * 3
    w = work.sage(cfg, 5, weighted=False)
    assert w["flops"] == l0 + l1 + out
    n_params = 2 * (8 * 2 + 2) + 2 * (4 * 2 + 2) + 4 * 3 + 3
    acts = (2 * (5 + 10) * 2 + 2 * 5 * 2 + 5 * 3) * 4 * 2
    moved = (5 + 10) * 4 * 4 + (5 + 10 + 30) * 8 * 1 + 5 * 3 * 4
    assert w["bytes"] == moved + acts + n_params * 4 * 4 * 2
    assert work.sage(cfg, 5, weighted=True)["bytes"] - w["bytes"] \
        == (5 + 10) * 4 * 4
    cfg["model"]["kwargs"] = {"dim": 2, "fanout": 3, "num_layers": 2,
                              "cache_dtype": "bfloat16"}
    s = work.scalablesage(cfg, 5, weighted=False)
    assert s["flops"] == (2 * 5 * 16 * 2) * 2 + (2 * 5 * 4 * 2) * 3 \
        + (2 * 5 * 2 * 3) * 3
    cache = (5 * 3 + 2 * 5) * 2 * 2
    acts = (5 * 2 + 5 * 2 + 5 * 3) * 4 * 2
    n_params = (16 * 2 + 2) + (4 * 2 + 2) + (2 * 3 + 3)
    moved = 5 * 4 * 4 + 5 * 4 * 8 + 5 * 3 * 4
    assert s["bytes"] == moved + cache + acts + n_params * 32


def _planes():
    ms = 1e6
    ops = [("%while.1", 10 * ms, 80 * ms),          # encloses the next two
           ("%fusion.gather", 12 * ms, 30 * ms),
           ("%fusion.dense", 50 * ms, 20 * ms),
           ("%copy.1", 102 * ms, 6 * ms),
           ("%fusion.gather", 130 * ms, 50 * ms)]
    return {
        "/device:TPU:0": {"XLA Ops": ops, "Steps": [("0", 0.0, 1.0)]},
        "/host:CPU": {"python3": [
            ("bench.dispatch", 0.0, 95 * ms),
            ("bench.dispatch", 110 * ms, 90 * ms)]},
        "/device:CUSTOM:Megascale Trace": {"XLA Ops": [("x", 0.0, 1e9)]},
    }


def test_trace_reduction_busy_idle_gaps_and_self_time():
    r = reduce_trace.reduce(_planes())
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.200)
    assert r["busy_s"] == pytest.approx(0.080 + 0.006 + 0.050)
    ops = dict(r["device_ops"])
    assert ops["%fusion.gather"] == pytest.approx(0.080)
    assert ops["%while.1"] == pytest.approx(0.030)   # 80 - 30 - 20
    gaps = r["idle_gaps"]
    assert gaps[0][1] == pytest.approx(0.022)        # 108 -> 130 ms
    assert gaps[0][0].startswith("dispatch 1: before")
    assert {g[0] for g in gaps} >= {"between dispatches (runner loop)"}
    assert sum(g[1] for g in gaps) == pytest.approx(0.064)
    empty = reduce_trace.reduce({"/host:CPU": {}})
    assert empty["busy_s"] is None and empty["device_ops"] == []
    # the shares of the peaks: the traced dispatches' steps over the time
    # the device was busy, whatever the host's clock read
    assert r["dispatches"] == 2
    ctx = {"trace": r, "window": {"spl": 4}, "traffic": load_traffic(
        TINY, "unit-b64"), "cfg": load_config(TINY, "sage3-tiny"),
        "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}}
    per_step = work.sage(ctx["cfg"], 64, weighted=False)
    assert readers.step_mfu_pct(ctx) == pytest.approx(
        100 * per_step["flops"] * (8 / 0.136) / 1e9)
    assert readers.step_hbm_pct(ctx) == pytest.approx(
        100 * per_step["bytes"] * (8 / 0.136) / 1e9)
    assert readers.step_mfu_pct({**ctx, "trace": empty}) is None
    assert reduce_trace.union_seconds(
        [(0, 5e9), (1e9, 2e9), (4e9, 7e9), (9e9, 10e9)]) == 8.0


def test_a_recorded_trace_is_read(tmp_path):
    import jax
    import jax.numpy as jnp

    tracer = reduce_trace.Tracer(str(tmp_path))
    tracer.start()
    with jax.profiler.TraceAnnotation(reduce_trace.DISPATCH_SPAN):
        jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64))) \
            .block_until_ready()
    planes = reduce_trace.read_planes(
        reduce_trace.find_xplane(tracer.stop()))
    spans = [ev for lines in planes.values() for evs in lines.values()
             for ev in evs if ev[0] == reduce_trace.DISPATCH_SPAN]
    assert len(spans) == 1 and spans[0][2] > 0
    # the CPU backend has no device plane: nothing to read, nothing made up
    assert reduce_trace.reduce(planes)["busy_s"] is None


CONFIG_FILES = sorted((ROOT / "benchmark" / "configs").glob("*.json")) \
    + sorted((Path(TINY) / "configs").glob("*.json"))


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_the_weights_are_lecun_normal_where_the_reference_seeds_none(path):
    """No reference of these configurations defines `init_weights`: the
    weights are common.lecun_normal's from the seed's weight stream, bit
    for bit what they were before a reference could seed its own."""
    cfg = json.loads(path.read_text())
    shapes = resolve(cfg["reference"] + ".param_shapes")(cfg)
    want = common.lecun_normal(np.random.default_rng([SEED, 1 << 22]),
                               shapes)
    got = check.make_weights(cfg, SEED)
    assert "init_weights" not in resolve_functions(cfg)
    assert list(got) == list(want)
    for leaf, w in want.items():
        assert got[leaf].dtype == w.dtype == np.float32, leaf
        assert np.array_equal(got[leaf], w), leaf


def test_a_reference_that_seeds_its_own_weights_is_used(tmp_path):
    root = _later_tree(tmp_path)
    bench = _add_pools_cell(root, json.loads(json.dumps(BENCH)))
    cfg = json.loads((root / bench["configs"][-1]["file"]).read_text())
    with modules_of(root):
        ref = importlib.import_module(cfg["reference"])
        shapes = ref.param_shapes(cfg)
        want = ref.init_weights(
            cfg, np.random.default_rng([SEED, 1 << 22]), shapes)
        got = check.make_weights(cfg, SEED)
        assert list(got) == list(want)
        for leaf, w in want.items():
            assert np.array_equal(got[leaf], w), leaf
        # ... which is not what common.lecun_normal would make
        assert np.any(got["out/bias"] != 0)


# -- the data the harness is driven by ----------------------------------------
def test_tables_do_not_depend_on_the_threads(monkeypatch):
    kw = dict(n_nodes=3000, feat_dim=8, cap=4, num_classes=5,
              edge_weights={"kind": "integer", "low": 1, "high": 3},
              chunk_rows=512)
    a = tables.make_tables(SEED, **kw)
    monkeypatch.setattr(tables, "THREADS", 1)
    b = tables.make_tables(SEED, **kw)
    for k in ("nbr", "cum", "feat", "cls", "deg"):
        assert np.array_equal(a[k], b[k]), k
    assert not np.array_equal(a["feat"],
                              tables.make_tables(SEED + 1, **kw)["feat"])
    n = kw["n_nodes"]
    assert (a["nbr"][n] == n).all() and (a["cum"][n] == 0).all()
    deg = (a["nbr"][:n] != n).sum(1)
    assert np.array_equal(deg, a["deg"]) and deg.min() >= 1
    w = np.diff(a["cum"][:n], axis=1, prepend=0)
    assert set(np.unique(w)) <= {0.0, 1.0, 2.0, 3.0}
    label = tables.one_hot_labels(a["cls"], 5)
    assert label.shape == (n + 1, 5) and label[n].sum() == 0
    assert np.array_equal(label[:n].argmax(1), a["cls"])


def test_roots_are_distinct_and_repeat_from_the_seed():
    a = RootSource(100, 0, SEED)
    b = RootSource(100, 0, SEED)
    for _ in range(5):
        r = a.sample_node(64)
        assert len(set(r.tolist())) == 64 and r.max() < 100
        assert np.array_equal(r, b.sample_node(64))


def test_reference_quantisation_is_the_stores():
    from euler_tpu.parallel.feature_store import quantize_int8
    import jax.numpy as jnp

    from benchmark.reference import common

    f = np.random.default_rng(3).standard_normal((5000, 16),
                                                 dtype=np.float32)
    f[:, 3] = 0
    q, scale = common.quantize_int8(f, chunk_rows=700)
    bf16 = np.dtype(jnp.bfloat16)
    q2, s2 = quantize_int8(np.asarray(f.astype(bf16), np.float32))
    assert np.array_equal(q, q2)
    assert np.array_equal(scale, s2.astype(bf16))


@contextlib.contextmanager
def modules_of(root: Path):
    """The modules a later tree at `root` adds under `benchmark/` and
    `benchmark/reference/` importable by their dotted names while it
    lasts, after the repo's own, as a checkout of that tree has them;
    dropped again at its end."""
    if root.resolve() == ROOT:
        yield
        return
    import benchmark.reference

    pkgs = {benchmark: root / "benchmark",
            benchmark.reference: root / "benchmark" / "reference"}
    for pkg, d in pkgs.items():
        pkg.__path__.append(str(d))
    importlib.invalidate_caches()
    before = set(sys.modules)
    try:
        yield
    finally:
        for pkg, d in pkgs.items():
            pkg.__path__.remove(str(d))
        for name in set(sys.modules) - before:
            file = getattr(sys.modules[name], "__file__", None)
            if name.startswith("benchmark.") and file and Path(
                    file).resolve().is_relative_to(root.resolve()):
                del sys.modules[name]
                parent, _, leaf = name.rpartition(".")
                if getattr(sys.modules[parent], leaf, None) is not None:
                    delattr(sys.modules[parent], leaf)


def keeps_to_the_contract(bench: dict, root: Path) -> None:
    """The rules BENCHMARK.json keeps, as a function of its dict and of
    the tree its files lie in (`root`), so that the file as a later PR
    leaves it, with a metric or a cell appended, is held to them as
    they stand; the functions its files name are looked up in that
    tree."""
    with modules_of(root):
        _keeps_to_the_contract(bench, root)


def _positive_int(v) -> bool:
    return isinstance(v, numbers.Integral) and not isinstance(v, bool) \
        and v > 0


def _keeps_to_the_contract(bench: dict, root: Path) -> None:
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = {}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.setdefault(group in ("end_to_end", "per_layer")
                             and "metric" or group, []).append(e["name"])
    for group, got in names.items():
        assert len(got) == len(set(got)), group
    cells = set(names["workloads"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        # a list of cells names cells of this benchmark, and some
        assert set(m.get("workloads", cells)) <= cells, m["name"]
        assert m.get("workloads", cells), m["name"]
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    metrics = root / "benchmark" / "metrics"
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        spec = json.loads((metrics / (m["name"] + ".json")).read_text())
        for k in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert callable(resolve(spec["reader"]))
    # a metric's file and its entry come together: no file is left over
    assert {f.stem for f in metrics.glob("*.json")} \
        == {m["name"] for m in bench["per_layer"]}
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and w["config"] in configs
        assert (root / "benchmark" / "traffic"
                / (w["traffic"] + ".json")).is_file()
        used.add(w["config"])
    assert used == set(configs)
    for c in bench["configs"]:
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        cfg = json.loads((root / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] == ["num_nodes"]
        assert cfg["source"] == c["source"]
        # no width is cut: the published ones stand in the file
        for k in ("feature_dim", "num_classes"):
            assert cfg[k] == cfg["published"][k]
        # one chip's row share of the published graph: 1/32 unless the
        # configuration states its own
        assert cfg["published"]["num_nodes"] // cfg.get("row_share", 32) \
            == cfg["num_nodes"]
        assert {"assumed", "deployment", "limits"} <= set(cfg)
        # every function the file names exists; the table kernels' counts
        # are rows and row bytes at each of its cells' batch and weights
        kernels = resolve_functions(cfg)["kernels"]
        for w in bench["workloads"]:
            if w["config"] != c["name"]:
                continue
            mix = load_traffic(str(root / "benchmark"), w["traffic"])
            counts = kernels(cfg, int(mix["root_batch"]),
                             mix["edge_weights"]["kind"] != "unit")
            for k in ("gather", "draw"):
                rows, row_bytes = counts[k]
                assert _positive_int(rows) and _positive_int(row_bytes), \
                    (c["name"], k, counts[k])
    for p in bench["paths"]:
        for f in (root / p).rglob("*"):
            if "__pycache__" in f.parts or f.suffix == ".pyc":
                continue
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+",
                                str(f.relative_to(root))), f


def test_benchmark_json_keeps_to_the_contract():
    keeps_to_the_contract(BENCH, ROOT)


LATER_CELL = "sagelstm2-papers100m-s8.unit-b1024"


def _later_tree(tmp: Path) -> Path:
    """A copy of the benchmark's files, as a later PR starts from."""
    shutil.copytree(ROOT / "benchmark", tmp / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp


def _add_metric(root: Path, bench: dict, name: str, cells: list,
                entry: bool = True, file: bool = True) -> dict:
    spec = {"name": name, "unit": "ms/step", "better": "lower",
            "source": "device_trace",
            "layer": bench["per_layer"][-1]["layer"],
            "moves": "train_nodes_per_s"}
    if file:
        (root / "benchmark" / "metrics" / (name + ".json")).write_text(
            json.dumps({**spec, "reader": "benchmark.lstm_readers.lstm_ms"}))
    if not entry:
        return bench
    return {**bench, "per_layer": bench["per_layer"]
            + [{**spec, "workloads": cells}]}


def _add_cell(root: Path, bench: dict, row_share) -> dict:
    """A configuration that is another share of a graph the benchmark
    already holds, and its cell."""
    (base,) = [c for c in bench["configs"]
               if c["name"] == "sagelstm2-papers100m-s32"]
    cfg = json.loads((root / base["file"]).read_text())
    cfg["name"] = LATER_CELL.split(".")[0]
    cfg["num_nodes"] = cfg["published"]["num_nodes"] // 8
    if row_share is not None:
        cfg["row_share"] = row_share
    file = f"benchmark/configs/{cfg['name']}.json"
    (root / file).write_text(json.dumps(cfg))
    return {**bench,
            "configs": bench["configs"] + [{**base, "name": cfg["name"],
                                            "file": file}],
            "workloads": bench["workloads"] + [{
                "name": LATER_CELL, "config": cfg["name"],
                "traffic": "unit-b1024", "chips": 1, "why": "test"}]}


POOLS_CELL = "pools-papers100m-s32.unit-b1024"
# what a later PR would add as files of its own: the table kernels' counts
# and the work counts of a draw that is no fanout (a pool of sampled nodes
# a layer), and a reference that seeds its own weights
POOLS_WORK = '''"""Counts of the pools configuration: a pool of sampled
nodes a layer, no fanout."""


def kernels(cfg, batch, weighted):
    pools = [batch, *cfg["model"]["kwargs"]["layer_sizes"]]
    draw_row = cfg["cap"] * 4 * (2 if weighted else 1)
    return {"gather": (sum(pools), cfg["feature_dim"]),
            "draw": (sum(pools[:-1]), draw_row)}


def work(cfg, batch, weighted):
    return {"flops": 1, "bytes": 1}
'''
POOLS_REFERENCE = '''"""The pools configuration's reference: it seeds its
own weights, biases too."""

import numpy as np


def param_shapes(cfg):
    dim = cfg["model"]["kwargs"]["dim"]
    return {"encoder/layer_0/kernel": (cfg["feature_dim"], dim),
            "encoder/layer_0/bias": (dim,),
            "out/kernel": (dim, cfg["num_classes"]),
            "out/bias": (cfg["num_classes"],)}


def init_weights(cfg, rng, shapes):
    return {p: rng.uniform(-0.1, 0.1, shapes[p]).astype(np.float32)
            for p in sorted(shapes)}


def init_extra(cfg, rows):
    return {}


def loss(params, extra, tables, roots, sample_seed, cfg, uniform, dtype):
    raise NotImplementedError
'''


def _add_pools_cell(root: Path, bench: dict, kernels=True) -> dict:
    """A configuration whose model takes no `fanouts`, with its own
    `kernels` function and a reference with `init_weights` in files of
    their own, and its cell. `kernels`: True names the function, None
    leaves the key out, a string is named in its place."""
    (base,) = [c for c in bench["configs"]
               if c["name"] == "sage3-papers100m-s32"]
    cfg = json.loads((root / base["file"]).read_text())
    cfg["name"] = POOLS_CELL.split(".")[0]
    cfg["model"] = {"class": "euler_tpu.models.DeviceSampledLayerwiseGCN",
                    "kwargs": {"dim": 256, "layer_sizes": [512, 512],
                               "num_classes": 172}}
    (root / "benchmark" / "work_pools_in_test.py").write_text(POOLS_WORK)
    (root / "benchmark" / "reference" / "pools_in_test.py").write_text(
        POOLS_REFERENCE)
    cfg["work"] = "benchmark.work_pools_in_test.work"
    cfg["reference"] = "benchmark.reference.pools_in_test"
    del cfg["kernels"]
    if kernels is not None:
        cfg["kernels"] = "benchmark.work_pools_in_test.kernels" \
            if kernels is True else kernels
    file = f"benchmark/configs/{cfg['name']}.json"
    (root / file).write_text(json.dumps(cfg))
    return {**bench,
            "configs": bench["configs"] + [{**base, "name": cfg["name"],
                                            "file": file}],
            "workloads": bench["workloads"] + [{
                "name": POOLS_CELL, "config": cfg["name"],
                "traffic": "unit-b1024", "chips": 1, "why": "test"}]}


@pytest.mark.parametrize("append", ["metric", "cell_and_metric",
                                    "pools_cell"])
def test_a_later_append_keeps_to_the_contract(tmp_path, append):
    """What a later PR adds, with no existing file or test edited: a
    metric with its file and the cell it is read in; or a configuration
    that states its own row share, its cell, and a metric of that cell;
    or a configuration whose model takes no `fanouts`, with its own
    table kernels' counts and a reference that seeds its own weights."""
    root = _later_tree(tmp_path)
    bench = json.loads(json.dumps(BENCH))
    cells = ["sagelstm2-papers100m-s32.unit-b1024"]
    if append == "cell_and_metric":
        bench = _add_cell(root, bench, row_share=8)
        cells = [LATER_CELL]
    elif append == "pools_cell":
        bench = _add_pools_cell(root, bench)
        cells = [POOLS_CELL]
    bench = _add_metric(root, bench, "later_ms", cells)
    keeps_to_the_contract(bench, root)
    # the tests that pin the list's head and the set-up four still hold
    assert bench["per_layer"][:len(BENCH["per_layer"])] == BENCH["per_layer"]
    import test_setup_readers

    test_setup_readers.assert_the_four_stand_together(bench)


@pytest.mark.parametrize("fault", [
    "file_without_entry", "entry_without_file", "unknown_cell",
    "empty_cells", "share_not_stated", "kernels_missing",
    "kernels_no_function"])
def test_an_append_that_breaks_the_contract_is_refused(tmp_path, fault):
    root = _later_tree(tmp_path)
    bench = json.loads(json.dumps(BENCH))
    if fault == "share_not_stated":
        bench = _add_cell(root, bench, row_share=None)
    elif fault == "kernels_missing":
        bench = _add_pools_cell(root, bench, kernels=None)
    elif fault == "kernels_no_function":
        bench = _add_pools_cell(
            root, bench, kernels="benchmark.work_pools_in_test.no_such")
    else:
        bench = _add_metric(
            root, bench, "later_ms",
            {"unknown_cell": ["no-such-cell.unit-b1"],
             "empty_cells": []}.get(
                fault, ["sagelstm2-papers100m-s32.unit-b1024"]),
            entry=fault != "file_without_entry",
            file=fault != "entry_without_file")
    with pytest.raises((AssertionError, FileNotFoundError, LookupError)):
        keeps_to_the_contract(bench, root)
