"""CPU checks of what ISSUE 30 adds to the benchmark, at the tiny size
(tiny/configs/unimp3-tiny.json: two layers; the three-layer model
against the reference is tests/test_unimp_encoder.py's): a whole run of
the UniMP cell, the bfloat16 control and the faults planted in the
reference (the configuration's own among them: the label input switched
off; the faults planted under the estimator are test_gat_cell.py's, on
the same estimator, feeder and dispatch), work_unimp.py's hand sums, the
six readers of unimp_readers.py on a plane made up by hand, and the
names the lowered step gives its label input and its attention.
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
    cell, check, run, scope_readers, tables, unimp_label_off,
    unimp_readers, work_unimp,
)
from benchmark.cell import load_config  # noqa: E402
from benchmark.traffic import RootSource, load_traffic  # noqa: E402
import test_benchmark_harness as harness  # noqa: E402 - its planted faults

TINY = harness.TINY
SEED = harness.SEED
CELL = "unimp3-tiny.unit-b64"
TINY_CELL = {"name": CELL, "config": "unimp3-tiny", "traffic": "unit-b64",
             "chips": 1, "why": "test"}
NEW = ("labelin_ms", "labelin_hbm_pct", "dotattn_ms", "dotattn_hbm_pct",
       "qkv_ms", "qkv_mfu_pct")
MS = 1e6
STEP = "jit(train_loop)/while/body/closed_call/"
FWD = STEP + "jvp(M)/M.embed/encoder/"
BWD = STEP + "transpose(jvp(M))/M.embed/encoder/"


def _bench():
    """BENCHMARK.json with the tiny cell in the place of the real one,
    in `workloads` and in the lists of the metrics that name it."""
    b = dict(harness.BENCH)
    b["workloads"] = [TINY_CELL]
    b["per_layer"] = [
        {**m, "workloads": [CELL]} if m["name"] in NEW else m
        for m in b["per_layer"]]
    return b


def _run(tmp, trace=0, planted=None):
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.3,
                              trace=trace)
    return run.run_cell(_bench(), args, cells_dir=TINY, require_tpu=False,
                        trace_dir=str(tmp / "trace"), planted=planted)


def test_the_benchmark_names_the_cell_and_its_six_metrics():
    cells = {w["name"]: w for w in harness.BENCH["workloads"]}
    real = cells["unimp3-papers100m-s32.unit-b1024"]
    assert (real["config"], real["traffic"], real["chips"]) \
        == ("unimp3-papers100m-s32", "unit-b1024", 1)
    # gat3's exact mix: that cell is this one's control
    assert cells["gat3-papers100m-s32.unit-b1024"]["traffic"] \
        == real["traffic"]
    for m in harness.BENCH["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [real["name"]], m["name"]
            assert m["moves"] == "train_nodes_per_s"
    assert {m["name"] for m in harness.BENCH["per_layer"]} >= set(NEW)
    cfg = load_config(str(ROOT / "benchmark"), "unimp3-papers100m-s32")
    kw = cfg["model"]["kwargs"]
    assert (kw["encoder"], kw["heads"], kw["dim"], kw["label_rate"],
            kw["fanouts"]) == ("unimp", 4, 32, 0.625, [10, 10, 10])
    assert "remat" not in kw
    assert set(cfg["limits"]) >= {
        "loss1", "loss2", "loss3", "grad1", "dparam3", "scan_loss",
        "scan_dparam", "scan_mom2", "scan_mom2_worst"}


def test_the_tiny_cell_runs_whole_and_is_correct(tmp_path):
    """Three single Adam steps and the scanned dispatch against
    reference/unimp3.py: losses, the first gradient, the parameters'
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


@pytest.fixture(scope="module")
def tiny_reference():
    """The tiny tables, seeded weights and roots, and the reference as it
    is over them: made once for every stand-in judged against it."""
    cfg, traffic = load_config(TINY, "unimp3-tiny"), \
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


@pytest.mark.parametrize("kw, caught_by", [
    ({"precision": "bfloat16"}, {"grad1", "dparam3", "scan_loss"}),
    ({"batch_share": 0.5}, {"loss1", "grad1", "scan_mom2"}),
    ({"frozen": True, "fault_from": cell.CHECK_STEPS + 1},
     {"scan_dparam", "scan_mom2"}),
], ids=["control_bfloat16", "half_batch", "state_unchanged_in_scan"])
def test_the_control_and_the_faults_fail_the_tiny_limits(
        tiny_reference, kw, caught_by):
    """The reference in bfloat16 (the control), with half of each batch
    left out from the first step on, and with a state left unchanged
    inside the scanned dispatch alone, each in the program's place."""
    cfg, traffic, tabs, records, weights, ref = tiny_reference
    # seeded, not left at zero: the comparison runs with labels in the
    # input and a gate that is no constant
    for leaf in ("encoder/label_emb", "encoder/enc/layer0/beta"):
        assert np.abs(weights[leaf + "/kernel"]).min() > 0
    assert not weights["encoder/enc/layer0/norm/gain_offset"].any()
    stand_in = check.run_reference(cfg, traffic, tabs, records, weights,
                                   **kw)
    ok, compared = check.judge(
        check.first_step_numbers(stand_in, ref), cfg["limits"],
        cfg.get("not_compared", ()))
    assert not ok, kw
    over = {n for n, (v, lim) in compared.items() if not v <= lim}
    assert caught_by <= over, (kw, compared)


def test_the_label_input_switched_off_fails_the_tiny_limits(tiny_reference):
    """The configuration's own fault, as the chip reads it
    (unimp_label_off.label_input_off): the label embedding then has no
    gradient and does not move, and the losses are other losses."""
    fault = unimp_label_off.label_input_off(*tiny_reference)
    assert fault["correct"] is False
    assert {"loss1", "grad1", "dparam3", "scan_dparam"} \
        <= set(fault["over"]), fault
    # the label embedding is the leaf that stands still
    assert fault["numbers"]["dparam3"] == pytest.approx(1.0)


# -- the yardstick's arithmetic ---------------------------------------------
_HAND = {"feature_dim": 8, "num_classes": 3, "cap": 4,
         "feature_storage": "int8", "work": "benchmark.work_unimp.sage",
         "model": {"kwargs": {"dim": 2, "heads": 2, "fanouts": [2, 3]}}}


def test_work_counts_match_hand_sums():
    # hops of 5, 10, 30 rows; labels enter hops 1 and 2 (40 rows).
    # Layer 0 (8 -> 2 heads x 2 = 4): targets hops 0, 1 (query and skip,
    # 4 + 4 wide), sources hops 1, 2 (key and value, 2 x 4); the roots'
    # rows are data (x2), every other input carries the label
    # embedding's gradient (x3). Layer 1 (4 -> 2 heads x 3 classes = 6,
    # averaged to 3): target hop 0 (6 + 3 wide), source hop 1 (2 x 6)
    qkv = 2 * 5 * 8 * (4 + 4) * 2 + 2 * 10 * 8 * (4 + 4) * 3 \
        + 2 * (10 + 30) * 8 * (2 * 4) * 3 \
        + 2 * 5 * 4 * (6 + 3) * 3 + 2 * 10 * 4 * (2 * 6) * 3
    # a pair: the scores q . k and the sum alpha . v over its sources,
    # forward and twice backward
    attn = 3 * 2 * 2 * (10 * 4 + 30 * 4 + 10 * 6)
    # the label embedding: forward and its own gradient (labels are data)
    label = 2 * 40 * 3 * 8 * 2
    w = work_unimp.sage(_HAND, 5, weighted=False)
    assert w["qkv_flops"] == qkv
    assert w["flops"] == qkv + attn + label
    assert w["labelin_bytes"] == 40 * 3 * 4
    # q, k, v rows written, read forward, read backward; skips written
    # and read
    act = ((5 + 10) * (4 * 3 + 4 * 2) + (10 + 30) * 2 * 4 * 3
           + 5 * (6 * 3 + 3 * 2) + 10 * 2 * 6 * 3) * 4
    n_params = 3 * 8 \
        + 3 * (8 * 4 + 4) + (8 * 4 + 4) + 3 * 4 + 2 * 4 \
        + 3 * (4 * 6 + 6) + (4 * 3 + 3) + 3 * 3
    moved = (5 + 10) * 4 * 4 + (5 + 10 + 30) * 8 * 1 + 5 * 3 * 4
    assert w["bytes"] == moved + 40 * 3 * 4 + act + n_params * 4 * 4 * 2
    # each key and each value row of a pair read, its queries read, its
    # output written, and the same again backward
    assert w["dotattn_bytes"] == 2 * 4 * (
        (2 * 10 * 4 + 5 * 4 + 5 * 4) + (2 * 30 * 4 + 10 * 4 + 10 * 4)
        + (2 * 10 * 6 + 5 * 6 + 5 * 3))
    assert work_unimp.sage(_HAND, 5, weighted=True)["bytes"] - w["bytes"] \
        == (5 + 10) * 4 * 4
    # named `sage`: the table kernels' counts are the fanout model's
    from benchmark import kernel_work

    assert kernel_work.for_config(_HAND) is kernel_work.sage
    assert kernel_work.for_config(load_config(TINY, "unimp3-tiny")) \
        is kernel_work.sage
    # the parameters counted are the reference's
    from benchmark.reference import unimp3

    cfg = load_config(str(ROOT / "benchmark"), "unimp3-papers100m-s32")
    shapes = unimp3.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 444_352


def _planes():
    """Two dispatches of two steps on one device."""
    ops = [
        ("%while.1 = (s32[]) while(...)", 10, 80),    # encloses 12..90
        (FWD + "gather/hop3/jit(_take)/gather:", 12, 8),
        (FWD + "encoder._with_labels/labelin/hop3/jit(_take)/gather:",
         20, 10),
        (FWD + "encoder._with_labels/labelin/hop3/reduce_or:", 30, 2),
        (FWD + "enc/layer0/qkv/key/dot_general:", 32, 8),
        (FWD + "enc/layer0/attn/reduce_sum:", 40, 6),
        (FWD + "enc/layer0/gate/norm/rsqrt:", 46, 4),
        (BWD + "enc/layer2/attn/mul:", 50, 20),
        (BWD + "enc/layer1/qkv/value/transpose:", 70, 6),
        (FWD + "enc/layer1/transpose:", 76, 4),        # none of the parts
        # a name outside the encoder module is not the encoder's
        (STEP + "jvp(M)/loss/attn/reduce_max:", 80, 5),
        (BWD + "encoder._with_labels/labelin/hop2/label_emb/dot_general:",
         130, 40),
    ]
    train = [("bench.dispatch", 0, 95), ("bench.dispatch", 110, 90)]
    scale = lambda evs: [(n, s * MS, d * MS) for n, s, d in evs]  # noqa: E731
    return {"device": {"/device:TPU:0": scale(ops)}, "host": [scale(train)]}


def _ctx(monkeypatch, planes, cfg):
    monkeypatch.setattr(scope_readers, "load", lambda trace_dir: planes)
    return {"window": {"trace": "made-up", "spl": 2}, "cfg": cfg,
            "traffic": load_traffic(TINY, "unit-b64"),
            "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}}


def test_the_six_readers_by_hand(monkeypatch):
    ctx = _ctx(monkeypatch, _planes(), _HAND)
    steps = 2 * 2
    assert unimp_readers.labelin_ms(ctx) == pytest.approx(
        (10 + 2 + 40) / steps)
    assert unimp_readers.dotattn_ms(ctx) == pytest.approx((6 + 20) / steps)
    assert unimp_readers.qkv_ms(ctx) == pytest.approx((8 + 4 + 6) / steps)
    # parts of encode_ms, which also holds what is rooted in none; the
    # label rows' gather is the encoder's, not the feature gather's
    assert scope_readers.encode_ms(ctx) == pytest.approx(
        (52 + 26 + 18 + 4) / steps)
    assert scope_readers.gather_ms(ctx) == pytest.approx(8 / steps)
    w = work_unimp.sage(_HAND, 64, weighted=False)
    assert unimp_readers.labelin_hbm_pct(ctx) == pytest.approx(
        100 * w["labelin_bytes"] / (52e-3 / steps) / 1e6)
    assert unimp_readers.dotattn_hbm_pct(ctx) == pytest.approx(
        100 * w["dotattn_bytes"] / (26e-3 / steps) / 1e6)
    assert unimp_readers.qkv_mfu_pct(ctx) == pytest.approx(
        100 * w["qkv_flops"] / (18e-3 / steps) / 1e9)


def test_the_readers_find_nothing_where_nothing_is(monkeypatch):
    full = _planes()
    # a program without these scopes (the mean model, the GAT, any commit
    # before the encoder): nothing, not a column of zeros
    bare = {"device": {"/device:TPU:0": [
        (re.sub(r"(encoder\._with_labels/labelin/hop\d/|/(qkv|gate)\b)",
                "", n), s, d)
        for n, s, d in full["device"]["/device:TPU:0"]]},
        "host": full["host"]}
    for planes in (bare, {"device": {}, "host": full["host"]}):
        ctx = _ctx(monkeypatch, planes, _HAND)
        for name in NEW:
            assert getattr(unimp_readers, name)(ctx) is None, name
    # a configuration whose work function counts none of the three
    ctx = _ctx(monkeypatch, full, load_config(TINY, "gat3-tiny"))
    assert unimp_readers.dotattn_ms(ctx) is not None
    for name in ("labelin_hbm_pct", "dotattn_hbm_pct", "qkv_mfu_pct"):
        assert getattr(unimp_readers, name)(ctx) is None, name
    ctx["window"]["trace"] = None                     # an untraced run
    assert unimp_readers.labelin_ms(ctx) is None


@pytest.mark.parametrize("name,part", [
    (FWD + "encoder._with_labels/labelin/hop1/jit(_take)/gather:",
     "labelin"),
    (BWD + "encoder._with_labels/labelin/hop3/label_emb/dot_general:",
     "labelin"),
    (FWD + "labelin/hop2/eq:", "labelin"),
    (FWD + "enc/layer0/attn/exp:", "dotattn"),
    (BWD + "enc/layer2/attn/dot_general:", "dotattn"),
    (FWD + "enc/layer1/qkv/query/dot_general:", "qkv"),
    (BWD + "enc/layer1/gate/beta/dot_general:", "qkv"),
    (FWD + "enc/layer0/gate/norm/rsqrt:", "qkv"),
    (FWD + "gather/hop2/mul:", "other"),
    (FWD + "enc/layer1/proj/dot_general:", "other"),
    (STEP + "jvp(M)/labels/labelin/hop1/gather:", "other"),
    ("%fusion.1 = f32[8] fusion(f32[8] %p), kind=kLoop", "other"),
])
def test_part_of_an_op_name(name, part):
    assert unimp_readers.part_of(name) == part


def test_the_lowered_step_names_its_label_input_and_its_attention():
    import jax

    cfg, mix = load_config(TINY, "unimp3-tiny"), \
        load_traffic(TINY, "unit-b64")
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
        parts.setdefault(unimp_readers.part_of(n), []).append(n)
    for layer in range(2):
        for scope in ("qkv", "attn", "gate"):
            assert any(re.search(rf"\bencoder/enc/layer{layer}/{scope}\b", n)
                       for n in names), (layer, scope)
    for hop in (1, 2):
        assert any(re.search(rf"\bencoder/.*labelin/hop{hop}\b", n)
                   for n in names), hop
    assert not any("labelin/hop0" in n for n in names)   # the roots: none
    for part in ("labelin", "dotattn", "qkv"):
        assert any("transpose(jvp(" in n for n in parts[part]), part
        # every part of the encoder is the encoder's to encode_ms: the
        # label rows' gather is not the feature gather's, nor a draw's
        assert all(scope_readers.scope_of(n) == "encode"
                   for n in parts[part]), part
    for scope in ("draw/hop2", "gather/hop2", "update", "labels", "loss"):
        assert any(re.search(rf"\b{scope}\b", n) for n in names), scope
    # the model has no output layer of its own
    assert not any(re.search(r"\bout\b", n) for n in names)
