"""CPU checks of what ISSUE 26 adds to the benchmark, at the tiny size
(tiny/configs/gat3-tiny.json): a whole run of the graph-attention cell,
the two faults planted in its timed path from the first step on and
inside the scanned dispatch alone, the bfloat16 control, work_gat.py's
hand sums, the four readers of gat_readers.py on a plane made up by
hand, and the names the lowered step gives its attention kernels.
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
    cell, check, gat_readers, run, scope_readers, tables, work_gat,
)
from benchmark.cell import load_config  # noqa: E402
from benchmark.traffic import RootSource, load_traffic  # noqa: E402
import test_benchmark_harness as harness  # noqa: E402 - its planted faults

TINY = harness.TINY
SEED = harness.SEED
CELL = "gat3-tiny.unit-b64"
NEW = ("attn_ms", "proj_ms", "attn_hbm_pct", "proj_mfu_pct")
MS = 1e6
STEP = "jit(train_loop)/while/body/closed_call/"
FWD = STEP + "jvp(M)/M.embed/encoder/"
BWD = STEP + "transpose(jvp(M))/M.embed/encoder/"


def _bench():
    """BENCHMARK.json with the tiny cell in the place of the real one,
    in `workloads` and in the lists of the metrics that name it."""
    b = dict(harness.BENCH)
    b["workloads"] = [{"name": CELL, "config": "gat3-tiny",
                       "traffic": "unit-b64", "chips": 1, "why": "test"}]
    b["per_layer"] = [
        {**m, "workloads": [CELL]} if m["name"] in NEW else m
        for m in b["per_layer"]]
    return b


def _run(tmp, trace=0, planted=None):
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.3,
                              trace=trace)
    return run.run_cell(_bench(), args, cells_dir=TINY, require_tpu=False,
                        trace_dir=str(tmp / "trace"), planted=planted)


def test_the_tiny_cell_runs_whole_and_is_correct(tmp_path):
    """Three single Adam steps and the scanned dispatch against
    reference/gat3.py: losses, the first gradient, the parameters'
    change, Adam's second moment. Traced, so the new readers are asked:
    the CPU has no device plane, and they report nothing, not 0."""
    r = json.loads(json.dumps(_run(tmp_path, trace=1)))
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["compared"]) >= {"loss1", "loss2", "loss3", "grad1",
                                  "dparam3", "scan_loss", "scan_dparam",
                                  "scan_mom2", "scan_mom2_worst"}
    assert not set(r["metrics"]) & set(NEW)
    assert r["metrics"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("fault,caught_by", [
    (harness._half_batch, {"loss1", "grad1"}),
    (harness._in_scan_only(harness._scan_keeps_state),
     {"scan_dparam", "scan_mom2"})],
    ids=["half_batch", "state_unchanged_in_scan"])
def test_a_broken_timed_path_is_not_correct(fault, caught_by, tmp_path):
    """One fault planted in the program from its first step on, the
    other inside the scanned dispatch alone; the other two pairings are
    judged below, planted in the reference put in the program's place."""
    r = _run(tmp_path, planted=fault)
    assert r["correct"] is False
    over = {n for n, (v, lim) in r["compared"].items() if not v <= lim}
    assert caught_by <= over, r["compared"]


def test_the_control_and_the_faults_fail_the_tiny_limits():
    """The reference in bfloat16 (the control), with a state left
    unchanged from the first step on, and with half of each batch left
    out inside the scanned dispatch alone, each in the program's place."""
    cfg, traffic = load_config(TINY, "gat3-tiny"), \
        load_traffic(TINY, "unit-b64")
    host = tables.make_tables(SEED, cfg["num_nodes"], cfg["feature_dim"],
                              cfg["cap"], cfg["num_classes"],
                              traffic["edge_weights"])
    weights = check.make_weights(cfg, SEED)
    # seeded, not left at zero: the comparison runs on real attention
    for leaf in ("att_src", "att_dst"):
        assert np.abs(weights[f"encoder/enc/layer0/{leaf}/kernel"]).min() > 0
    src = RootSource(cfg["num_nodes"], 0, SEED)
    records = [(src.sample_node(64).astype(np.int32), i)
               for i in range(1 + 3 + cfg["steps_per_loop"])]
    tabs = check.place_tables(cfg, traffic, host)
    ref = check.run_reference(cfg, traffic, tabs, records, weights)
    for kw, caught_by in (
            ({"precision": "bfloat16"}, {"loss1", "grad1", "scan_loss"}),
            ({"frozen": True}, {"dparam3", "scan_dparam"}),
            ({"batch_share": 0.5, "fault_from": cell.CHECK_STEPS + 1},
             {"scan_loss", "scan_mom2"})):
        stand_in = check.run_reference(cfg, traffic, tabs, records,
                                       weights, **kw)
        ok, compared = check.judge(
            check.first_step_numbers(stand_in, ref), cfg["limits"],
            cfg.get("not_compared", ()))
        assert not ok, kw
        over = {n for n, (v, lim) in compared.items() if not v <= lim}
        assert caught_by <= over, (kw, compared)


# -- the yardstick's arithmetic ---------------------------------------------
_HAND = {"feature_dim": 8, "num_classes": 3, "cap": 4,
         "feature_storage": "int8", "work": "benchmark.work_gat.sage",
         "model": {"kwargs": {"dim": 2, "heads": 2, "fanouts": [2, 3]}}}


def test_work_counts_match_hand_sums():
    # hops of 5, 10, 30 rows. Layer 0 (8 -> 2 heads x 2, input is data:
    # x2) projects all three hops and skips two; layer 1 (4 -> 2 heads x
    # 3 classes, averaged: x3) projects hops 0, 1 and skips hop 0
    proj = 2 * (5 + 10 + 30) * 8 * 4 * 2 + 2 * (5 + 10) * 8 * 4 * 2 \
        + 2 * (5 + 10) * 4 * 6 * 3 + 2 * 5 * 4 * 3 * 3
    # a pair: scores 2 (sources + 2 targets) zw, sum 2 (sources +
    # targets) zw, forward and twice backward
    attn = 3 * ((2 * (10 + 2 * 5) + 2 * (10 + 5)) * 4
                + (2 * (30 + 2 * 10) + 2 * (30 + 10)) * 4
                + (2 * (10 + 2 * 5) + 2 * (10 + 5)) * 6)
    w = work_gat.sage(_HAND, 5, weighted=False)
    assert w["proj_flops"] == proj
    assert w["flops"] == proj + attn
    z = ((5 + 10 + 30) * 4 + (5 + 10) * 6) * 4 * 3
    skips = ((5 + 10) * 4 + 5 * 3) * 4 * 2
    n_params = (8 * 4 + 2 * 2 * 2 + 4 + 8 * 4 + 4) \
        + (4 * 6 + 2 * 3 * 2 + 3 + 4 * 3 + 3)
    moved = (5 + 10) * 4 * 4 + (5 + 10 + 30) * 8 * 1 + 5 * 3 * 4
    assert w["bytes"] == moved + z + skips + n_params * 4 * 4 * 2
    # each projected row of a pair read twice, its output written, and
    # the same again backward
    assert w["attn_bytes"] == 2 * 4 * (
        (2 * (10 + 5) * 4 + 5 * 4) + (2 * (30 + 10) * 4 + 10 * 4)
        + (2 * (10 + 5) * 6 + 5 * 3))
    assert work_gat.sage(_HAND, 5, weighted=True)["bytes"] - w["bytes"] \
        == (5 + 10) * 4 * 4
    # named `sage`: the table kernels' counts are the fanout model's
    from benchmark import kernel_work

    assert kernel_work.for_config(_HAND) is kernel_work.sage
    assert kernel_work.for_config(load_config(TINY, "gat3-tiny")) \
        is kernel_work.sage


def _planes():
    """Two dispatches of two steps on one device."""
    ops = [
        ("%while.1 = (s32[]) while(...)", 10, 80),    # encloses 12..90
        (FWD + "gather/hop3/jit(_take)/gather:", 12, 8),
        (FWD + "enc/layer0/proj/dot_general:", 20, 10),
        (FWD + "enc/layer0/attn/reduce_sum:", 30, 16),
        (FWD + "enc/layer0/skip/skip/dot_general:", 46, 4),
        (BWD + "enc/layer2/attn/att_src/mul:", 50, 20),
        (BWD + "enc/layer1/proj/transpose:", 70, 6),
        (FWD + "enc/layer1/transpose:", 76, 4),        # neither part
        # attention in a name outside the encoder module is not it
        (STEP + "jvp(M)/loss/attn/reduce_max:", 80, 5),
        (BWD + "enc/layer0/attn/exp:", 130, 40),
    ]
    train = [("bench.dispatch", 0, 95), ("bench.dispatch", 110, 90)]
    scale = lambda evs: [(n, s * MS, d * MS) for n, s, d in evs]  # noqa: E731
    return {"device": {"/device:TPU:0": scale(ops)}, "host": [scale(train)]}


def _ctx(monkeypatch, planes, cfg):
    monkeypatch.setattr(scope_readers, "load", lambda trace_dir: planes)
    return {"window": {"trace": "made-up", "spl": 2}, "cfg": cfg,
            "traffic": load_traffic(TINY, "unit-b64"),
            "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}}


def test_the_four_readers_by_hand(monkeypatch):
    ctx = _ctx(monkeypatch, _planes(), _HAND)
    steps = 2 * 2
    assert gat_readers.attn_ms(ctx) == pytest.approx((16 + 20 + 40) / steps)
    assert gat_readers.proj_ms(ctx) == pytest.approx((10 + 4 + 6) / steps)
    # parts of encode_ms, which also holds what is rooted in neither
    assert scope_readers.encode_ms(ctx) == pytest.approx(
        (76 + 20 + 4) / steps)
    w = work_gat.sage(_HAND, 64, weighted=False)
    assert gat_readers.attn_hbm_pct(ctx) == pytest.approx(
        100 * w["attn_bytes"] / (76e-3 / steps) / 1e6)
    assert gat_readers.proj_mfu_pct(ctx) == pytest.approx(
        100 * w["proj_flops"] / (20e-3 / steps) / 1e9)


def test_the_readers_find_nothing_where_nothing_is(monkeypatch):
    full = _planes()
    # a program without the attention scopes (the mean model, any commit
    # before the encoder): nothing, not a column of zeros
    bare = {"device": {"/device:TPU:0": [
        (re.sub(r"/(attn|proj|skip)\b", "", n), s, d)
        for n, s, d in full["device"]["/device:TPU:0"]]},
        "host": full["host"]}
    for planes in (bare, {"device": {}, "host": full["host"]}):
        ctx = _ctx(monkeypatch, planes, _HAND)
        for name in NEW:
            assert getattr(gat_readers, name)(ctx) is None, name
    # a configuration whose work function counts no attention
    ctx = _ctx(monkeypatch, full, load_config(TINY, "sage3-tiny"))
    assert gat_readers.attn_ms(ctx) is not None
    assert gat_readers.attn_hbm_pct(ctx) is None
    assert gat_readers.proj_mfu_pct(ctx) is None
    ctx["window"]["trace"] = None                     # an untraced run
    assert gat_readers.attn_ms(ctx) is None


@pytest.mark.parametrize("name,part", [
    (FWD + "enc/layer0/attn/exp:", "attn"),
    (BWD + "enc/layer2/attn/att_dst/mul:", "attn"),
    (FWD + "enc/layer1/proj/dot_general:", "proj"),
    (BWD + "enc/layer1/skip/skip/dot_general:", "proj"),
    (FWD + "enc/layer1/skip/elu:", "proj"),
    (FWD + "gather/hop2/mul:", "other"),
    (FWD + "enc/agg_0/nbr/dot_general:", "other"),
    (STEP + "jvp(M)/loss/attn/reduce_max:", "other"),
    ("%fusion.1 = f32[8] fusion(f32[8] %p), kind=kLoop", "other"),
])
def test_part_of_an_op_name(name, part):
    assert gat_readers.part_of(name) == part


def test_the_lowered_step_names_its_attention_kernels():
    import jax

    cfg, mix = load_config(TINY, "gat3-tiny"), load_traffic(TINY, "unit-b64")
    host = tables.make_tables(SEED, cfg["num_nodes"], cfg["feature_dim"],
                              cfg["cap"], cfg["num_classes"],
                              mix["edge_weights"])
    prog = cell.Program(cfg, mix, host, SEED, 1)
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
    parts = {}
    for n in names:
        parts.setdefault(gat_readers.part_of(n), []).append(n)
    for layer in range(3):
        for scope in ("proj", "attn", "skip"):
            assert any(f"encoder/enc/layer{layer}/{scope}" in n
                       for n in names), (layer, scope)
    for part in ("attn", "proj"):
        assert any("transpose(jvp(" in n for n in parts[part]), part
        # every part of the encoder is the encoder's to encode_ms
        assert all(scope_readers.scope_of(n) == "encode"
                   for n in parts[part]), part
    for scope in ("draw/hop3", "gather/hop3", "update", "labels", "loss"):
        assert any(re.search(rf"\b{scope}\b", n) for n in names), scope
    # the model has no output layer of its own
    assert not any(re.search(r"\bout\b", n) for n in names)
