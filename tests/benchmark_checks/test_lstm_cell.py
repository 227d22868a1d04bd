"""CPU checks of what ISSUE 36 adds to the benchmark, at the tiny size
(tiny/configs/sagelstm2-tiny.json: 16-wide features, fanouts 3,4, the
LSTM at its published 128): a whole traced run of the GraphSAGE-LSTM
cell, the four planted controls of sagelstm2_controls.py against ONE
reference run, work_lstm.py's hand sums at a small size and at the real
one, the two readers of lstm_readers.py on a plane made up by hand, and
the names the lowered step gives its recurrences.
"""

import argparse
import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark import (  # noqa: E402
    check, kernel_work, lstm_readers, run, scope_readers, tables, work_lstm,
)
from benchmark.cell import load_config  # noqa: E402
from benchmark.reference import sagelstm2  # noqa: E402
from benchmark.traffic import RootSource, load_traffic  # noqa: E402
import sagelstm2_controls as controls  # noqa: E402
import test_benchmark_harness as harness  # noqa: E402

TINY = harness.TINY
SEED = harness.SEED
REAL = "sagelstm2-papers100m-s32.unit-b1024"
CELL = "sagelstm2-tiny.unit-b64"
TINY_CELL = {"name": CELL, "config": "sagelstm2-tiny", "traffic": "unit-b64",
             "chips": 1, "why": "test"}
NEW = ("lstm_ms", "lstm_roofline")
MS = 1e6
STEP = "jit(train_loop)/while/body/closed_call/"
FWD = STEP + "jvp(M)/M.embed/encoder/"
BWD = STEP + "transpose(jvp(M))/M.embed/encoder/"


def _entry(name: str) -> dict:
    """The cell's metric as BENCHMARK.json lists it."""
    (entry,) = [m for m in harness.BENCH["per_layer"] if m["name"] == name]
    return entry


def _bench():
    """BENCHMARK.json with the tiny cell in the place of the real one,
    in `workloads` and in the lists of the metrics that name it."""
    b = dict(harness.BENCH)
    b["workloads"] = [TINY_CELL]
    b["per_layer"] = [
        {**m, "workloads": [CELL]} if m["name"] in NEW else m
        for m in b["per_layer"]]
    return b


def test_the_benchmark_names_the_cell_and_its_two_metrics():
    cells = {w["name"]: w for w in harness.BENCH["workloads"]}
    real = cells[REAL]
    assert (real["config"], real["traffic"], real["chips"]) \
        == ("sagelstm2-papers100m-s32", "unit-b1024", 1)
    # the mix of cells 4-6: no traffic file is added
    assert cells["gat3-papers100m-s32.unit-b1024"]["traffic"] \
        == real["traffic"]
    from benchmark.cell import resolve

    layers = {m["layer"] for m in harness.BENCH["per_layer"]
              if m["name"] not in NEW}
    for name in NEW:
        entry = _entry(name)
        assert (entry["name"], entry["source"], entry["moves"]) \
            == (name, "device_trace", "train_nodes_per_s")
        assert entry["layer"] in layers and harness.UNIT.match(entry["unit"])
        # read in this cell alone; the file is the entry and its reader
        assert entry["workloads"] == [REAL]
        spec = json.loads((ROOT / "benchmark" / "metrics"
                           / (name + ".json")).read_text())
        assert {**spec, "workloads": [REAL]} == {
            **entry, "reader": f"benchmark.lstm_readers.{name}"}
        assert resolve(spec["reader"]) is getattr(lstm_readers, name)
    # a kernel's share of its roofline is named for the kernel
    assert _entry("lstm_roofline")["unit"] == "%"
    cfg = load_config(str(ROOT / "benchmark"), "sagelstm2-papers100m-s32")
    kw = cfg["model"]["kwargs"]
    assert (cfg["feature_dim"], cfg["num_classes"], cfg["lstm_hidden"],
            kw["encoder"], kw["aggregator"], kw["dim"], kw["fanouts"],
            kw["l2_head"]) \
        == (128, 172, 128, "sage", "lstm", 128, [10, 25], True)
    assert cfg["num_nodes"] == 111059956 // 32
    assert cfg["reference"] == "benchmark.reference.sagelstm2"
    assert kernel_work.for_config(cfg) is kernel_work.sage
    assert not set(cfg["limits"]) & set(cfg.get("not_compared", {}))


@pytest.fixture(scope="module")
def whole_run(tmp_path_factory):
    """One traced run of the tiny cell, and the names its single step
    gives its operations (lowered, not compiled, from the program the
    run itself built: `planted` hands it over before its first step)."""
    import jax

    names = set()

    def lowered_names(prog):
        est = prog.est
        batch = {"rows": [jax.numpy.arange(64, dtype=jax.numpy.int32)],
                 "sample_seed": jax.numpy.uint32(1)}
        text = jax.jit(est._make_one_step()).lower(
            est.state, {**batch, **est.static_batch}).as_text(
            debug_info=True)
        names.update(re.findall(r'loc\("([^"]+)"', text))

    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.2,
                              trace=1)
    result = run.run_cell(_bench(), args, cells_dir=TINY, require_tpu=False,
                          trace_dir=str(tmp_path_factory.mktemp("t")),
                          planted=lowered_names)
    return json.loads(json.dumps(result)), names


def test_the_tiny_cell_runs_whole_and_is_correct(whole_run):
    """Three single Adam steps and the scanned dispatch against
    reference/sagelstm2.py. Traced, so the new readers are asked: the
    CPU has no device plane, and they report nothing."""
    r, _ = whole_run
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["compared"]) >= {"loss1", "loss2", "loss3", "grad1",
                                  "dparam3", "scan_loss", "scan_dparam",
                                  "scan_mom2"}
    assert not set(r["metrics"]) & set(NEW)
    assert r["metrics"]["compiles_in_window"]["value"] == 0


def test_the_lowered_step_names_its_recurrences(whole_run):
    _, names = whole_run
    lstm = [n for n in names if lstm_readers.in_lstm(n)]
    for depth in (0, 1):
        assert any(f"encoder/enc/agg_{depth}/lstm" in n for n in lstm), depth
    assert any("transpose(jvp(" in n for n in lstm)
    assert any("while" in n for n in lstm)              # the scan's loop
    # every recurrence is the encoder's to encode_ms
    assert all(scope_readers.scope_of(n) == "encode" for n in lstm)
    # S and N are outside the recurrence
    assert any("agg_0/self" in n for n in names)
    assert not any("agg_0/self" in n or "agg_0/nbr" in n for n in lstm)


def test_a_program_that_reverses_the_slots_is_not_correct(tmp_path):
    """The order guard on the timed path itself: the program's recurrence
    handed each target's slots last to first, from the first step on."""
    import jax

    from euler_tpu.utils import aggregators

    kept = aggregators.lstm_over_slots

    def reversed_slots(nbr, mask, kernel, bias, axis):
        flip = lambda a: None if a is None else jax.numpy.flip(a, axis)  # noqa: E731
        parts = tuple(map(flip, nbr)) if isinstance(nbr, tuple) \
            else flip(nbr)
        return kept(parts, flip(mask), kernel, bias, axis)

    aggregators.lstm_over_slots = reversed_slots
    try:
        r = run.run_cell(_bench(), argparse.Namespace(
            workload=CELL, seed=SEED, seconds=0.2, trace=0),
            cells_dir=TINY, require_tpu=False,
            trace_dir=str(tmp_path / "t"))
    finally:
        aggregators.lstm_over_slots = kept
    assert r["correct"] is False
    over = {n for n, (v, lim) in r["compared"].items() if not v <= lim}
    assert {"loss1", "grad1", "scan_mom2"} <= over, r["compared"]


@pytest.fixture(scope="module")
def tiny_reference():
    """The tiny tables, seeded weights and roots, and the reference as it
    is over them: made once for every control judged against it."""
    cfg, traffic = load_config(TINY, "sagelstm2-tiny"), \
        load_traffic(TINY, "unit-b64")
    host = tables.make_tables(SEED, cfg["num_nodes"], cfg["feature_dim"],
                              cfg["cap"], cfg["num_classes"],
                              traffic["edge_weights"])
    weights = check.make_weights(cfg, SEED)
    src = RootSource(cfg["num_nodes"], 0, SEED)
    records = [(src.sample_node(64).astype(np.int32), i)
               for i in range(1 + 3 + cfg["steps_per_loop"])]
    tabs = check.place_tables(cfg, traffic, host)
    ref = check.run_reference(cfg, traffic, tabs, records, weights)
    return cfg, traffic, tabs, records, weights, ref


@pytest.mark.parametrize("name", list(controls.CONTROLS))
def test_each_planted_control_fails_the_tiny_limits(tiny_reference, name):
    """The reference with its gates and carries in bfloat16, without the
    forget bias, with each target's slots reversed, or with the last slot
    left out, in the program's place."""
    fault = controls.judge_control(name, *tiny_reference)
    assert fault["correct"] is False
    assert {"scan_mom2"} <= set(fault["over"]), fault
    # the control is planted in a copy: the configuration has none
    assert "planted" not in tiny_reference[0]


# -- the yardstick's arithmetic ---------------------------------------------
_HAND = {"feature_dim": 8, "num_classes": 3, "cap": 4, "lstm_hidden": 2,
         "feature_storage": "int8", "work": "benchmark.work_lstm.sage",
         "kernels": "benchmark.kernel_work.sage",
         "model": {"kwargs": {"dim": 2, "fanouts": [2, 3]}}}


def test_work_counts_match_hand_sums():
    # hops of 5, 10, 30 rows; H = 2 (gates 8), dim 2. Layer 0 (width 8)
    # on (5 <- 2 slots) and (10 <- 3 slots), its slots data; layer 1
    # (width 4) on (5 <- 2 slots of layer 0's hop 1)
    def pair(n, k, width, data):
        x = 2 * n * k * width * 8
        h = 2 * n * (k - 1) * 2 * 8
        return x + h + x + h + h + (0 if data else x)
    lstm = pair(5, 2, 8, True) + pair(10, 3, 8, True) + pair(5, 2, 4, False)
    dense = (2 * 5 * 8 * 2 * 2 + 2 * 5 * 2 * 2 * 3) \
        + (2 * 10 * 8 * 2 * 2 + 2 * 10 * 2 * 2 * 3) \
        + (2 * 5 * 4 * 2 * 3 + 2 * 5 * 2 * 2 * 3) + 2 * 5 * 4 * 3 * 3
    w = work_lstm.sage(_HAND, 5, weighted=False)
    assert w["lstm_flops"] == lstm
    assert w["flops"] == lstm + dense
    # slots read twice at their width (layer 0 int8, layer 1 float32), a
    # target's h written, layer 1's slot cotangent written, K and b read
    # with their gradient written
    lstm_b = 2 * (5 * 2 + 10 * 3) * 8 + 2 * 5 * 2 * 4 * 4 \
        + (5 + 10 + 5) * 2 * 4 + 5 * 2 * 4 * 4 \
        + ((8 + 2) * 8 + 8 + (4 + 2) * 8 + 8) * 4 * 2
    assert w["lstm_bytes"] == lstm_b
    acts = (5 + 5 + 10 + 10 + 5 + 5) * 2 * 4 * 2 + 5 * 3 * 4 * 2
    n_params = (10 * 8 + 8 + 8 * 2 + 2 * 2) + (6 * 8 + 8 + 4 * 2 + 2 * 2) \
        + 4 * 3 + 3
    moved = (5 + 10) * 4 * 4 + (5 + 10 + 30) * 8 + 5 * 3 * 4
    assert w["bytes"] == moved + acts + lstm_b + n_params * 4 * 4 * 2
    # the parameters counted are the reference's
    assert sum(int(np.prod(s)) for s in sagelstm2.param_shapes(
        _HAND).values()) == n_params


def test_work_counts_at_the_real_size():
    """ISSUE 36's figures: 266,240 slot-rows at layer 0, 10,240 at layer
    1. Its 69.8 G (layer 0) and 4.0 G (layer 1) forward count every
    slot's recurrent product; the floor leaves out a target's first (h_0
    is 0): 1.5 G and 0.14 G less."""
    cfg = load_config(str(ROOT / "benchmark"), "sagelstm2-papers100m-s32")
    w = work_lstm.sage(cfg, 1024, weighted=False)
    rows0, first0, rows1 = 266_240, 1024 + 10_240, 10_240
    fwd0 = 2 * rows0 * 256 * 512 - 2 * first0 * 128 * 512
    fwd1 = 2 * rows1 * 384 * 512 - 2 * 1024 * 128 * 512
    assert fwd0 == pytest.approx(68.3e9, rel=1e-3)
    assert fwd1 == pytest.approx(3.89e9, rel=1e-3)
    # backward: dK of both products, dh of the recurrent one, and dx of
    # layer 1's slots (layer 0's are data)
    bwd0 = fwd0 + (2 * rows0 - 2 * first0) * 128 * 512
    bwd1 = fwd1 + (2 * rows1 - 2 * 1024) * 128 * 512 \
        + 2 * rows1 * 256 * 512
    assert w["lstm_flops"] == fwd0 + fwd1 + bwd0 + bwd1
    assert w["lstm_flops"] == pytest.approx(181.7e9, rel=1e-3)
    assert w["flops"] == pytest.approx(184.1e9, rel=1e-3)
    # about 0.92 ms at 197 TFLOP/s: bound by its FLOPs, its floor of
    # bytes (0.11 GB) would take 0.13 ms at 819 GB/s
    assert w["lstm_bytes"] == pytest.approx(0.108e9, rel=0.01)
    assert kernel_work.sage(cfg, 1024, False)["gather"] \
        == (1024 + 10_240 + 256_000, 128)
    assert kernel_work.sage(cfg, 1024, False)["draw"][0] == 1024 + 10_240


def _planes():
    """Two dispatches of two steps on one device."""
    ops = [
        (FWD + "gather/hop2/jit(_take)/gather:", 10, 8),
        (FWD + "enc/agg_0/lstm/while:", 20, 30),          # encloses two
        (FWD + "enc/agg_0/lstm/while/body/dot_general:", 22, 10),
        (FWD + "enc/agg_0/lstm/while/body/logistic:", 34, 6),
        (FWD + "enc/agg_0/self/dot_general:", 50, 4),
        (BWD + "enc/agg_1/lstm/while/body/dot_general:", 54, 12),
        (FWD + "enc/agg_1/nbr/dot_general:", 66, 2),
        (STEP + "jvp(M)/loss/lstm/reduce_max:", 70, 5),   # not the encoder's
        (BWD + "enc/agg_0/lstm/transpose:", 130, 20),
    ]
    train = [("bench.dispatch", 0, 95), ("bench.dispatch", 110, 90)]
    scale = lambda evs: [(n, s * MS, d * MS) for n, s, d in evs]  # noqa: E731
    return {"device": {"/device:TPU:0": scale(ops)}, "host": [scale(train)]}


def _ctx(monkeypatch, planes, cfg):
    monkeypatch.setattr(scope_readers, "load", lambda trace_dir: planes)
    return {"window": {"trace": "made-up", "spl": 2}, "cfg": cfg,
            "traffic": load_traffic(TINY, "unit-b64"),
            "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}}


def test_the_two_readers_by_hand(monkeypatch):
    ctx = _ctx(monkeypatch, _planes(), _HAND)
    steps = 2 * 2
    lstm = 30 + 12 + 20        # the loop's whole span, its body inside it
    assert lstm_readers.lstm_ms(ctx) == pytest.approx(lstm / steps)
    # a part of encode_ms, which also holds S, N and the gather's rest
    assert scope_readers.encode_ms(ctx) == pytest.approx(
        (lstm + 4 + 2) / steps)
    w = work_lstm.sage(_HAND, 64, weighted=False)
    floor = max(w["lstm_flops"] / 1e9, w["lstm_bytes"] / 1e6)
    assert w["lstm_bytes"] / 1e6 > w["lstm_flops"] / 1e9   # bytes bound here
    assert lstm_readers.lstm_roofline(ctx) == pytest.approx(
        100 * floor / (lstm * 1e-3 / steps))


def test_the_readers_find_nothing_where_nothing_is(monkeypatch):
    full = _planes()
    bare = {"device": {"/device:TPU:0": [
        (n, s, d) for n, s, d in full["device"]["/device:TPU:0"]
        if "/lstm/" not in n or "loss" in n]}, "host": full["host"]}
    for planes in (bare, {"device": {}, "host": full["host"]}):
        ctx = _ctx(monkeypatch, planes, _HAND)
        for name in NEW:
            assert getattr(lstm_readers, name)(ctx) is None, name
    # a configuration whose work function counts no recurrence
    ctx = _ctx(monkeypatch, full, load_config(TINY, "sage3-tiny"))
    assert lstm_readers.lstm_ms(ctx) is not None
    assert lstm_readers.lstm_roofline(ctx) is None
    ctx["window"]["trace"] = None                     # an untraced run
    assert lstm_readers.lstm_ms(ctx) is None
