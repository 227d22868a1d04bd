"""CPU checks of what ISSUE 24 adds to the benchmark: the readers of the
program's spans and scopes (benchmark/scope_readers.py) on planes made up
by hand, the metadata reader on bytes made up by hand and on a recorded
trace, kernel_work.py's hand sums, the new metric files against the
contract, and the program's own side at the tiny size: the names in the
lowered steps and the spans in the trace of a whole run.
"""

import argparse
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import (  # noqa: E402
    cell, kernel_work, readers, reduce_trace, run, scope_readers, tables,
    xplane_metadata,
)
from benchmark.cell import load_config  # noqa: E402
from benchmark.traffic import load_traffic  # noqa: E402

TINY = str(Path(__file__).resolve().parent / "tiny")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SEED = 2147483699
NEW = ("idle_input_wait_pct", "idle_enqueue_pct", "idle_result_wait_pct",
       "idle_unattributed_pct", "feeder_produce_ms", "draw_ms", "gather_ms",
       "encode_ms", "update_ms", "cache_ms", "unscoped_pct",
       "gather_hbm_pct", "draw_hbm_pct")
MS = 1e6
STEP = "jit(train_loop)/while/body/closed_call/"
FWD = STEP + "jvp(M)/M.embed/"
BWD = STEP + "transpose(jvp(M))/M.embed/"


def _planes():
    """Two dispatches of two steps on one device, 200 ms. Busy 10-90,
    102-108 and 130-180 ms: idle 64 ms."""
    ops = [
        ("%while.1 = (s32[]) while(...)", 10, 80),    # encloses 12..90
        (FWD + "draw/hop1/jit(_take)/gather:", 12, 30),
        # under two names (the encoder module holds the gather): once
        (FWD + "encoder/gather/hop2/jit(_take)/gather:", 42, 8),
        (BWD + "encoder/enc/agg_1/reduce_sum:", 50, 20),
        (STEP + "guard/cond/branch_1_fun/update/mul:", 70, 5),
        # no scope of ours: `update` inside a primitive's name is none
        ("jit(train_loop)/while/body/dynamic_update_slice:", 75, 15),
        ("%copy.1 = s32[1,64] copy(...)", 102, 6),    # no op name at all
        (BWD + "encoder/cache_1/jit(_take)/scatter-add:", 130, 40),
        (FWD + "encoder/cache/jit(_where)/select_n:", 170, 10),
    ]
    train = [
        ("bench.dispatch", 0, 95),
        ("euler.input_wait", 0, 1),            # the first batch's
        ("euler.train_dispatch", 1, 93),
        ("euler.input_wait", 1, 6),
        ("euler.stack", 7, 2),
        ("euler.device_step", 9, 1.5),
        ("euler.result_wait", 10.5, 83),
        ("bench.dispatch", 110, 90),
        ("euler.input_wait", 110, 1),
        ("euler.train_dispatch", 111, 88.5),
        ("euler.input_wait", 111, 14),
        ("euler.stack", 125, 3),
        ("euler.device_step", 128, 3),
        ("euler.result_wait", 131, 68),
    ]
    feeder = [("euler.feeder_produce", 2, 1), ("euler.feeder_transform", 2.5, .5),
              ("euler.feeder_produce", 112, 3),
              ("euler.feeder_produce", 300, 50)]      # after the window
    scale = lambda evs: [(n, s * MS, d * MS) for n, s, d in evs]  # noqa: E731
    return {"device": {"/device:TPU:0": scale(ops)},
            "host": [scale(train), scale(feeder)]}


def _ctx(monkeypatch, planes, cfg="sage3-tiny", traffic="unit-b64"):
    monkeypatch.setattr(scope_readers, "load", lambda trace_dir: planes)
    return {"window": {"trace": "made-up", "spl": 2},
            "cfg": load_config(TINY, cfg),
            "traffic": load_traffic(TINY, traffic),
            "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}}


def test_idle_shares_by_hand_add_up_to_the_idle_share(monkeypatch):
    planes = _planes()
    ctx = _ctx(monkeypatch, planes)
    got = {k: getattr(scope_readers, f"idle_{k}_pct")(ctx)
           for k in ("input_wait", "enqueue", "result_wait", "unattributed")}
    # gaps 0-10, 90-102, 108-130, 180-200 ms of a 200 ms window
    assert got["input_wait"] == pytest.approx(100 * (7 + 15) / 200)
    assert got["enqueue"] == pytest.approx(100 * (2 + 1 + 3 + 2) / 200)
    assert got["result_wait"] == pytest.approx(100 * (3.5 + 19) / 200)
    assert got["unattributed"] == pytest.approx(
        100 * (8.5 + 2 + 1) / 200)
    # ... which is the accepted metric's number on the same events
    old = {"/device:TPU:0": {"XLA Ops": planes["device"]["/device:TPU:0"]},
           "/host:CPU": {"python3": planes["host"][0]}}
    idle = readers.device_idle_pct({"trace": reduce_trace.reduce(old)})
    assert idle == pytest.approx(32.0)
    assert sum(got.values()) == pytest.approx(idle)


def test_scope_times_by_hand_charge_every_operation_once(monkeypatch):
    ctx = _ctx(monkeypatch, _planes())
    steps = 2 * 2
    want = {"draw_ms": 30, "gather_ms": 8, "encode_ms": 20, "update_ms": 5,
            "cache_ms": 50}
    for name, ms in want.items():
        assert getattr(scope_readers, name)(ctx) == pytest.approx(
            ms / steps), name
    # the while's own 2 ms, the slice's 15 and the copy's 6, of 136 busy
    assert scope_readers.unscoped_pct(ctx) == pytest.approx(
        100 * 23 / 136)
    assert sum(want.values()) + 23 == 136
    # shares of the HBM peak: rows x stored bytes over the kernel's time
    work = kernel_work.sage(ctx["cfg"], 64, weighted=False)
    rows, row_bytes = work["gather"]
    assert scope_readers.gather_hbm_pct(ctx) == pytest.approx(
        100 * rows * row_bytes / (8e-3 / steps) / 1e6)
    rows, row_bytes = work["draw"]
    assert scope_readers.draw_hbm_pct(ctx) == pytest.approx(
        100 * rows * row_bytes / (30e-3 / steps) / 1e6)
    assert scope_readers.feeder_produce_ms(ctx) == pytest.approx(2.0)


@pytest.mark.parametrize("name,scope", [
    (FWD + "draw/hop3/jit(_take)/gather:", "draw"),
    (FWD + "encoder/gather/hop0/mul:", "gather"),
    (BWD + "encoder/enc/agg_0/nbr/dot_general:", "encode"),
    (BWD + "encoder/cache/cache_1/scatter-add:", "cache"),
    (STEP + "guard/reduce_and:", "update"),
    (STEP + "jvp(M)/loss/jit(log_softmax)/reduce_max:", "unscoped"),
    (STEP + "jvp(M)/labels/jit(_take)/gather:", "unscoped"),
    ("%fusion.1 = f32[8] fusion(f32[8] %p), kind=kLoop", "unscoped"),
])
def test_scope_of_an_op_name(name, scope):
    assert scope_readers.scope_of(name) == scope


def test_a_nameless_operation_takes_the_name_of_what_reads_it():
    loop = "jit(train_loop)/while/body/closed_call:"
    scatter = FWD + "encoder/cache/cache_1/scatter:"
    labels = STEP + "jvp(M)/labels/jit(_take)/gather:"
    names = {
        # hoisted out of the scatter's rule by the compiler
        "%broadcast.1 = u32[8,4] broadcast(u32[] %constant.9), "
        "dimensions={}": loop,
        "%fusion.2 = u32[8,4] fusion(u32[8,4] %broadcast.1, s32[2] "
        "%bitcast.7), kind=kCustom": scatter,
        # read by a tuple only, which is no recorded operation
        "%copy.3 = bf16[8,4] copy(bf16[8,4] %get-tuple-element.4)": "",
        # two steps from a name
        "%copy.5 = f32[8,3] copy(f32[8,3] %label_table.1)": "",
        "%fusion.6 = f32[2,3] fusion(f32[8,3] %copy.5)":
            "jit(train_loop)/while:",
        "%fusion.7 = f32[2] fusion(f32[2,3] %fusion.6, f32[2,3] %p)": labels,
        # names of its own are kept whoever reads it
        "%fusion.8 = f32[2] fusion(f32[2] %fusion.7)": FWD + "draw/hop1/x:",
    }
    got = scope_readers.inherit_names(names)
    texts = list(names)
    assert got[texts[0]] == scatter and got[texts[1]] == scatter
    assert got[texts[2]] == ""
    assert got[texts[3]] == labels and got[texts[4]] == labels
    assert got[texts[5]] == labels and got[texts[6]] == names[texts[6]]


def test_readers_find_nothing_where_nothing_is(monkeypatch):
    full = _planes()
    # a program without the spans (the parent commit): no idle shares
    bare = {"device": full["device"], "host": [[
        ev for ev in full["host"][0] if ev[0] == "bench.dispatch"]]}
    # ... and without the scopes: no kernel times, not a column of zeros
    bare["device"] = {"/device:TPU:0": [
        (re.sub(r"\b(draw|gather)/hop\d/", "", n), s, d)
        for n, s, d in full["device"]["/device:TPU:0"]]}
    ctx = _ctx(monkeypatch, bare)
    for name in NEW:
        assert getattr(scope_readers, name)(ctx) is None, name
    # the CPU's trace: spans, no device plane
    ctx = _ctx(monkeypatch, {"device": {}, "host": full["host"]})
    assert scope_readers.feeder_produce_ms(ctx) == pytest.approx(2.0)
    for name in set(NEW) - {"feeder_produce_ms"}:
        assert getattr(scope_readers, name)(ctx) is None, name
    # an untraced run
    ctx["window"]["trace"] = None
    assert scope_readers.feeder_produce_ms(ctx) is None


def test_kernel_work_matches_hand_sums():
    cfg = {"feature_dim": 8, "cap": 4, "feature_storage": "int8",
           "work": "benchmark.work.sage",
           "model": {"kwargs": {"dim": 2, "fanouts": [2, 3]}}}
    # hops of 5, 10, 30 rows
    assert kernel_work.for_config(cfg) is kernel_work.sage
    assert kernel_work.sage(cfg, 5, weighted=False) == {
        "gather": (45, 8), "draw": (15, 16)}
    assert kernel_work.sage(cfg, 5, weighted=True)["draw"] == (15, 32)
    cfg["work"] = "benchmark.work.scalablesage"
    cfg["model"]["kwargs"] = {"dim": 2, "fanout": 3, "num_layers": 2}
    assert kernel_work.for_config(cfg) is kernel_work.scalablesage
    assert kernel_work.scalablesage(cfg, 5, weighted=False) == {
        "gather": (20, 8), "draw": (5, 16)}
    # the two kernels' bytes are work.py's table term less the label rows
    from benchmark import work

    for fn, other in ((kernel_work.sage, work.sage),
                      (kernel_work.scalablesage, work.scalablesage)):
        full = load_config(TINY, "sage3-tiny" if fn is kernel_work.sage
                           else "scalablesage-tiny")
        k = fn(full, 64, weighted=True)
        moved = sum(rows * b for rows, b in k.values()) \
            + 64 * full["num_classes"] * 4
        assert moved == work._tables(
            full, k["draw"][0], k["gather"][0], 64, True)
        assert other(full, 64, True)["bytes"] > moved


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_event_metadata_stats_of_bytes_made_by_hand(tmp_path):
    stat_meta = b"".join(
        _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, n)))
        for i, n in ((1, b"tf_op"), (2, b"flops"), (3, b"hlo_category"),
                     (300, b"fusion")))
    event = _field(1, 7) + _field(2, b"%fusion.3 = f32[8] fusion()") \
        + _field(5, _field(1, 1) + _field(5, b"jit(f)/draw/hop1/gather:")) \
        + _field(5, _field(1, 2) + _field(3, 4096)) \
        + _field(5, _field(1, 3) + _field(7, 300)) \
        + _field(3, b"opaque") + _field(6, 9)
    plane = _field(1, 1) + _field(2, b"/device:TPU:0") \
        + _field(3, _field(2, b"XLA Ops") + _field(9, 123)) \
        + _field(4, _field(1, 7) + _field(2, event)) + stat_meta
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(_field(1, plane) + _field(1, _field(2, b"/host:CPU"))
                     + _field(4, b"a warning"))
    assert xplane_metadata.event_metadata_stats(str(path)) == {
        "/device:TPU:0": {"%fusion.3 = f32[8] fusion()": {
            "tf_op": "jit(f)/draw/hop1/gather:", "flops": 4096,
            "hlo_category": "fusion"}},
        "/host:CPU": {}}


# -- the program's side, at the tiny size -------------------------------------
def _tiny_program(config, traffic):
    cfg, mix = load_config(TINY, config), load_traffic(TINY, traffic)
    host = tables.make_tables(SEED, cfg["num_nodes"], cfg["feature_dim"],
                              cfg["cap"], cfg["num_classes"],
                              mix["edge_weights"])
    return cell.Program(cfg, mix, host, SEED, 1)


@pytest.mark.parametrize("config,traffic,scopes", [
    ("sage3-tiny", "unit-b64",
     ["draw/hop1", "draw/hop3", "gather/hop0", "gather/hop3", "update",
      "guard", "labels", "loss", "encoder"]),
    ("sage3-tiny", "weighted-b64", ["draw/hop1", "draw/hop3", "gather/hop0"]),
    ("scalablesage-tiny", "unit-b64",
     ["draw/hop1", "gather/hop0", "gather/hop1", "update", "cache",
      "encoder"]),
])
def test_the_lowered_step_names_its_kernels(config, traffic, scopes):
    import jax

    prog = _tiny_program(config, traffic)
    try:
        batch = next(prog.feed)
        est = prog.est
        est.train(iter([batch]), max_steps=0)
        text = jax.jit(est._make_one_step()).lower(
            est.state, {**batch, **est.static_batch}).as_text(
            debug_info=True)
    finally:
        prog.free()
    names = set(re.findall(r'loc\("([^"]+)"', text))
    by_scope = {}
    for n in names:
        by_scope.setdefault(scope_readers.scope_of(n), []).append(n)
    for scope in scopes:
        assert any(re.search(rf"\b{scope}\b", n) for n in names), scope
    # the backward pass keeps the forward's scope inside transpose(jvp())
    assert any("transpose(jvp(" in n for n in by_scope["encode"])
    assert {"draw", "gather", "update", "encode"} <= set(by_scope)
    # the hop-0 gather sits in the encoder module of sage3: charged once
    assert all("draw/" not in n for n in by_scope["gather"])


def test_a_traced_run_holds_the_programs_spans(tmp_path):
    """The whole run of a tiny cell, traced: the spans are events of the
    host plane inside the runner's dispatch spans, on two threads, and
    the readers that need no device plane report."""
    bench = dict(BENCH)
    bench["workloads"] = [{"name": "scalablesage-tiny.unit-b64",
                           "config": "scalablesage-tiny",
                           "traffic": "unit-b64", "chips": 1, "why": "test"}]
    args = argparse.Namespace(workload="scalablesage-tiny.unit-b64",
                              seed=SEED, seconds=0.3, trace=1)
    trace_dir = str(tmp_path / "trace")
    r = run.run_cell(bench, args, cells_dir=TINY, require_tpu=False,
                     trace_dir=trace_dir)
    assert r["correct"] is True, r["compared"]
    assert r["metrics"]["feeder_produce_ms"]["value"] > 0
    assert not set(r["metrics"]) & (set(NEW) - {"feeder_produce_ms"})
    scope_readers.load.cache_clear()
    planes = scope_readers.load(trace_dir)
    assert planes["device"] == {}
    (train,) = [line for line in planes["host"]
                if any(n == "bench.dispatch" for n, _, _ in line)]
    dispatches = [(s, s + d) for n, s, d in train if n == "bench.dispatch"]
    assert len(dispatches) == 2
    for want in ("train_dispatch", "input_wait", "stack", "device_step",
                 "result_wait"):
        spans = [(s, s + d) for n, s, d in train if n == "euler." + want]
        assert len(spans) >= 2, want
        for s, e in spans:
            assert any(a <= s and e <= b for a, b in dispatches), want
    (feeder,) = [line for line in planes["host"] if line is not train]
    assert {n for n, _, _ in feeder} == {"euler.feeder_produce",
                                         "euler.feeder_transform"}
    # the metadata reader reads what was recorded, plane by plane
    found = xplane_metadata.event_metadata_stats(
        reduce_trace.find_xplane(trace_dir))
    assert "/host:CPU" in found


# -- the data ------------------------------------------------------------------
@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_keeps_to_the_contract(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    spec = json.loads((ROOT / "benchmark" / "metrics"
                       / (name + ".json")).read_text())
    assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$", name)
    assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", entry["unit"])
    assert set(spec) == {"name", "unit", "better", "source", "layer",
                         "moves", "reader"}
    assert spec["name"] == name
    for k in ("unit", "better", "source", "layer", "moves"):
        assert spec[k] == entry[k], k
    assert entry["moves"] == "train_nodes_per_s"
    assert entry["better"] == ("higher" if name.endswith("_hbm_pct")
                               else "lower")
    assert entry["source"] == ("device_trace" if name in NEW[5:]
                               else "program_span")
    # a layer the benchmark already named, letter for letter
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"][:6]}
    assert spec["reader"] == f"benchmark.scope_readers.{name}"
    assert callable(cell.resolve(spec["reader"]))
    cells = {w["name"] for w in BENCH["workloads"]}
    if name == "cache_ms":
        assert entry["workloads"] == [
            "scalablesage-papers100m-s32.unit-b32768"]
    else:
        assert "workloads" not in entry
    assert set(entry.get("workloads", cells)) <= cells
    # appended: what was there keeps its place
    assert [m["name"] for m in BENCH["per_layer"][:6]] == [
        "input_wait_ms", "dispatch_ms_max", "compiles_in_window",
        "step_mfu_pct", "step_hbm_pct", "device_idle_pct"]
