"""CPU checks of what ISSUE 32 adds to the benchmark, at the tiny size
(tiny/configs/gat2-tiny.json: 256-wide features, 2 heads, a norm a layer
and the MLP head): a whole run of the MAG240M GAT cell, the
configuration's own fault planted in the program (the norm's statistics
taken pair by pair), the bfloat16 control and the faults planted in the
reference, all against ONE reference run, work_mag.py's hand sums, the
five readers of mag_readers.py on a plane made up by hand, and the names
the lowered step gives its norms and its head.
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
    cell, check, gat2bn_norm_by_pair, gat_readers, kernel_work, mag_readers,
    mag_reference, run, scope_readers, tables, work_mag,
)
from benchmark.cell import load_config  # noqa: E402
from benchmark.reference import gat2bn  # noqa: E402
from benchmark.traffic import RootSource, load_traffic  # noqa: E402
import test_benchmark_harness as harness  # noqa: E402 - its planted faults

TINY = harness.TINY
SEED = harness.SEED
REAL = "gat2-mag240m-s32.unit-b1024"
CELL = "gat2-tiny.unit-b64"
TINY_CELL = {"name": CELL, "config": "gat2-tiny", "traffic": "unit-b64",
             "chips": 1, "why": "test"}
NEW = ("wideattn_ms", "wideattn_hbm_pct", "wideproj_ms",
       "wideproj_mfu_pct", "norm_ms")
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
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.2,
                              trace=trace)
    return run.run_cell(_bench(), args, cells_dir=TINY, require_tpu=False,
                        trace_dir=str(tmp / "trace"), planted=planted)


def test_the_benchmark_names_the_cell_and_its_five_metrics():
    cells = {w["name"]: w for w in harness.BENCH["workloads"]}
    real = cells[REAL]
    assert (real["config"], real["traffic"], real["chips"]) \
        == ("gat2-mag240m-s32", "unit-b1024", 1)
    # gat3's exact mix: that cell has all of it absent
    assert cells["gat3-papers100m-s32.unit-b1024"]["traffic"] \
        == real["traffic"]
    for m in harness.BENCH["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [REAL], m["name"]
            assert (m["moves"], m["source"]) \
                == ("train_nodes_per_s", "device_trace")
    assert {m["name"] for m in harness.BENCH["per_layer"]} >= set(NEW)
    cfg = load_config(str(ROOT / "benchmark"), "gat2-mag240m-s32")
    kw = cfg["model"]["kwargs"]
    assert (cfg["feature_dim"], cfg["num_classes"], kw["encoder"],
            kw["heads"], kw["dim"], kw["fanouts"], kw["norm"],
            kw["head_dim"]) \
        == (768, 153, "gat", 4, 256, [25, 15], "batch", 1024)
    assert cfg["num_nodes"] == 121751666 // 32 and "remat" not in kw
    assert set(cfg["limits"]) >= {
        "loss1", "loss3", "grad1", "dparam3", "state3", "scan_loss",
        "scan_dparam", "scan_mom2", "scan_state"}
    assert kernel_work.for_config(cfg) is kernel_work.sage
    # 4.9 M parameters, as the issue reckons them
    shapes = gat2bn.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 4_890_777


def test_a_program_without_the_fields_fails_before_anything_is_placed():
    """`mag_reference.param_shapes` is the first thing of the
    configuration the harness resolves (`check.make_weights`, before
    `cell.Program`): a model class that lacks `norm` or `head_dim` (any
    commit before this one) raises there what `cell.Program` would
    raise after quantising and placing an 11.7 GB table."""
    cfg = load_config(str(ROOT / "benchmark"), "gat2-mag240m-s32")
    assert cfg["reference"] == "benchmark.mag_reference"
    assert check.make_weights(cfg, SEED).keys() \
        == gat2bn.param_shapes(cfg).keys()
    assert mag_reference.loss is gat2bn.loss
    assert mag_reference.init_extra is gat2bn.init_extra
    older = {**cfg, "model": {**cfg["model"], "kwargs": {
        **cfg["model"]["kwargs"], "a_field_no_model_has": 1}}}
    with pytest.raises(TypeError, match="a_field_no_model_has"):
        check.make_weights(older, SEED)


def test_the_rebound_chunk_leaves_the_reference_quantisation_its_bytes():
    """`mag_reference.param_shapes` sets the default chunk of the
    reference's quantisation to 16 MB of float32 at 768 columns (the
    file chunks by rows; mag_reference.py says why): the table and the
    scale are the same bytes at any chunking."""
    from benchmark.reference import common

    check.make_weights(load_config(TINY, "gat2-tiny"), SEED)
    assert common.quantize_int8.__defaults__ == (mag_reference.CHUNK_ROWS,)
    assert mag_reference.CHUNK_ROWS == 5461
    f = np.random.default_rng(2).standard_normal(
        (3 * mag_reference.CHUNK_ROWS + 5, 8), dtype=np.float32)
    f[:, 2] = 0
    q, scale = common.quantize_int8(f)                 # 4 chunks
    q1, scale1 = common.quantize_int8(f, chunk_rows=262_144)    # one
    assert q.tobytes() == q1.tobytes()
    assert scale.tobytes() == scale1.tobytes()


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

    result = _run(tmp_path_factory.mktemp("whole"), trace=1,
                  planted=lowered_names)
    return json.loads(json.dumps(result)), names


def test_the_tiny_cell_runs_whole_and_is_correct(whole_run):
    """Three single Adam steps and the scanned dispatch against
    reference/gat2bn.py: losses, the first gradient, the parameters'
    change, Adam's second moment, and the running statistics after the
    single steps and after the dispatch. Traced, so the new readers are
    asked: the CPU has no device plane, and they report nothing."""
    r, _ = whole_run
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["compared"]) >= {"loss1", "loss2", "loss3", "grad1",
                                  "dparam3", "state3", "scan_loss",
                                  "scan_dparam", "scan_mom2", "scan_state"}
    assert not set(r["metrics"]) & set(NEW)
    assert r["metrics"]["compiles_in_window"]["value"] == 0


def _norm_by_pair(prog):
    """The configuration's own fault, planted in the program: the norm
    called once a pair (shared parameters, the running statistics moved
    once a call), where the layer calls it once over all it wrote."""
    from euler_tpu.utils import encoders

    whole = encoders.HopBatchNorm.__call__

    def by_pair(self, xs, masks):
        return [whole(self, [x], [m])[0] for x, m in zip(xs, masks)]

    encoders.HopBatchNorm.__call__ = by_pair


def test_statistics_taken_pair_by_pair_are_not_correct(tmp_path):
    from euler_tpu.utils import encoders

    whole = encoders.HopBatchNorm.__call__
    try:
        r = _run(tmp_path, planted=_norm_by_pair)
    finally:
        encoders.HopBatchNorm.__call__ = whole
    assert r["correct"] is False
    over = {n for n, (v, lim) in r["compared"].items() if not v <= lim}
    assert {"loss1", "grad1", "state3"} <= over, r["compared"]


@pytest.fixture(scope="module")
def tiny_reference():
    """The tiny tables, seeded weights and roots, and the reference as it
    is over them: made once for every stand-in judged against it."""
    cfg, traffic = load_config(TINY, "gat2-tiny"), \
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
    ({"precision": "bfloat16"}, {"loss1", "grad1", "scan_loss"}),
    ({"frozen": True, "fault_from": cell.CHECK_STEPS + 1},
     {"scan_dparam", "scan_mom2", "scan_state"}),
], ids=["control_bfloat16", "state_unchanged_in_scan"])
def test_the_control_and_the_faults_fail_the_tiny_limits(
        tiny_reference, kw, caught_by):
    """The reference in bfloat16 (the control) and with a state left
    unchanged inside the scanned dispatch alone, each in the program's
    place (half a batch left out is test_gat_cell.py's, on the same
    estimator, feeder and dispatch)."""
    cfg, traffic, tabs, records, weights, ref = tiny_reference
    # seeded, not left at zero: the comparison runs on real attention;
    # the norms' gains are offsets from one, so zero is a norm
    assert np.abs(weights["encoder/enc/layer0/att_src/kernel"]).min() > 0
    assert not weights["encoder/enc/head/norm/gain_offset"].any()
    stand_in = check.run_reference(cfg, traffic, tabs, records, weights,
                                   **kw)
    ok, compared = check.judge(
        check.first_step_numbers(stand_in, ref), cfg["limits"],
        cfg.get("not_compared", ()))
    assert not ok, kw
    over = {n for n, (v, lim) in compared.items() if not v <= lim}
    assert caught_by <= over, (kw, compared)


def test_the_reference_with_pair_by_pair_statistics_fails_the_tiny_limits(
        tiny_reference):
    """The fault as the chip reads it (gat2bn_norm_by_pair.norm_by_pair):
    the reference with its `by_pair` switch, in the program's place."""
    fault = gat2bn_norm_by_pair.norm_by_pair(*tiny_reference)
    assert fault["correct"] is False
    assert {"loss1", "grad1", "state3", "scan_state"} <= set(fault["over"]), \
        fault


# -- the yardstick's arithmetic ---------------------------------------------
_HAND = {"feature_dim": 8, "num_classes": 3, "cap": 4,
         "feature_storage": "int8", "work": "benchmark.work_mag.sage",
         "model": {"kwargs": {"dim": 2, "heads": 2, "fanouts": [2, 3],
                              "head_dim": 6}}}


def test_work_counts_match_hand_sums():
    # hops of 5, 10, 30 rows; both layers 2 heads x 2 = 4 wide. Layer 0
    # (8 -> 4, input is data: x2) projects all three hops and skips two;
    # layer 1 (4 -> 4: x3) projects hops 0, 1 and skips hop 0; the head
    # is 4 -> 6 -> 3 on the 5 roots (x3)
    proj = 2 * (5 + 10 + 30) * 8 * 4 * 2 + 2 * (5 + 10) * 8 * 4 * 2 \
        + 2 * (5 + 10) * 4 * 4 * 3 + 2 * 5 * 4 * 4 * 3 \
        + 2 * 5 * 4 * 6 * 3 + 2 * 5 * 6 * 3 * 3
    attn = 3 * ((2 * (10 + 2 * 5) + 2 * (10 + 5)) * 4
                + (2 * (30 + 2 * 10) + 2 * (30 + 10)) * 4
                + (2 * (10 + 2 * 5) + 2 * (10 + 5)) * 4)
    w = work_mag.sage(_HAND, 5, weighted=False)
    assert w["proj_flops"] == proj
    assert w["flops"] == proj + attn
    z = ((5 + 10 + 30) * 4 + (5 + 10) * 4) * 4 * 3
    skips = ((5 + 10) * 4 + 5 * 4) * 4 * 2
    head = (5 * 6 + 5 * 3) * 4 * 2
    # a norm's rows: read and written forward, three passes backward
    norm = ((5 + 10) * 4 + 5 * 4 + 5 * 6) * 4 * 5
    n_params = (8 * 4 + 2 * 2 * 2 + 4 + 8 * 4 + 4 + 2 * 4) \
        + (4 * 4 + 2 * 2 * 2 + 4 + 4 * 4 + 4 + 2 * 4) \
        + (4 * 6 + 6 + 2 * 6 + 6 * 3 + 3)
    moved = (5 + 10) * 4 * 4 + (5 + 10 + 30) * 8 * 1 + 5 * 3 * 4
    assert w["norm_bytes"] == norm
    assert w["bytes"] == moved + z + skips + head + norm \
        + n_params * 4 * 4 * 2
    assert w["attn_bytes"] == 2 * 4 * (
        (2 * (10 + 5) * 4 + 5 * 4) + (2 * (30 + 10) * 4 + 10 * 4)
        + (2 * (10 + 5) * 4 + 5 * 4))
    # the parameters counted are the reference's
    cfg = load_config(TINY, "gat2-tiny")
    assert sum(int(np.prod(s)) for s in gat2bn.param_shapes(
        {**_HAND, "model": _HAND["model"]}).values()) == n_params
    # named `sage`: the table kernels' counts are the fanout model's,
    # and a feature row counts its 256 (tiny) or 768 stored bytes
    assert kernel_work.for_config(cfg) is kernel_work.sage
    assert kernel_work.sage(cfg, 64, False)["gather"] \
        == (64 + 192 + 384, 256)
    # the real size: 1.6 TFLOP a step, 1.3 of them layer 0's projection
    real = work_mag.sage(load_config(str(ROOT / "benchmark"),
                                     "gat2-mag240m-s32"), 1024, False)
    assert real["proj_flops"] == pytest.approx(1.557e12, rel=1e-3)
    assert kernel_work.sage(load_config(str(ROOT / "benchmark"),
                                        "gat2-mag240m-s32"), 1024,
                            False)["gather"] == (410_624, 768)


def _planes():
    """Two dispatches of two steps on one device."""
    ops = [
        ("%while.1 = (s32[]) while(...)", 10, 80),    # encloses 12..90
        (FWD + "gather/hop2/jit(_take)/gather:", 12, 8),
        (FWD + "enc/layer0/proj/dot_general:", 20, 10),
        (FWD + "enc/layer0/attn/reduce_sum:", 30, 16),
        (FWD + "enc/layer0/skip/skip/dot_general:", 46, 4),
        (FWD + "enc/layer0/norm/rsqrt:", 50, 6),
        (BWD + "enc/layer1/norm/mul:", 56, 4),
        (FWD + "enc/head/fc/dot_general:", 60, 5),
        (BWD + "enc/head/norm/reduce_sum:", 65, 3),
        (BWD + "enc/layer1/attn/att_src/mul:", 68, 8),
        (FWD + "enc/layer1/transpose:", 76, 4),        # no part
        # a norm in a name outside the encoder module is not it
        (STEP + "jvp(M)/loss/norm/reduce_max:", 80, 5),
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


def test_the_five_readers_by_hand(monkeypatch):
    ctx = _ctx(monkeypatch, _planes(), _HAND)
    steps = 2 * 2
    assert mag_readers.wideattn_ms(ctx) == pytest.approx(
        (16 + 8 + 40) / steps)
    assert mag_readers.wideproj_ms(ctx) == pytest.approx(
        (10 + 4 + 5) / steps)
    # the head's norm is the norm's, not the head's
    assert mag_readers.norm_ms(ctx) == pytest.approx((6 + 4 + 3) / steps)
    # parts of encode_ms, which also holds what is rooted in none
    assert scope_readers.encode_ms(ctx) == pytest.approx(
        (64 + 19 + 13 + 4) / steps)
    # wideattn_ms IS attn_ms, under a second name
    assert gat_readers.attn_ms(ctx) == mag_readers.wideattn_ms(ctx)
    w = work_mag.sage(_HAND, 64, weighted=False)
    assert mag_readers.wideattn_hbm_pct(ctx) == pytest.approx(
        100 * w["attn_bytes"] / (64e-3 / steps) / 1e6)
    assert mag_readers.wideproj_mfu_pct(ctx) == pytest.approx(
        100 * w["proj_flops"] / (19e-3 / steps) / 1e9)


def test_the_readers_find_nothing_where_nothing_is(monkeypatch):
    full = _planes()
    # a program without a norm scope (gat3, any commit before this
    # encoder): nothing, not a column of zeros
    bare = {"device": {"/device:TPU:0": [
        (n, s, d) for n, s, d in full["device"]["/device:TPU:0"]
        if "/norm/" not in n or "loss" in n]}, "host": full["host"]}
    for planes in (bare, {"device": {}, "host": full["host"]}):
        ctx = _ctx(monkeypatch, planes, _HAND)
        for name in NEW:
            assert getattr(mag_readers, name)(ctx) is None, name
    # a configuration whose work function counts neither share
    ctx = _ctx(monkeypatch, full, load_config(TINY, "sage3-tiny"))
    assert mag_readers.norm_ms(ctx) is not None
    assert mag_readers.wideattn_hbm_pct(ctx) is None
    assert mag_readers.wideproj_mfu_pct(ctx) is None
    ctx["window"]["trace"] = None                     # an untraced run
    assert mag_readers.norm_ms(ctx) is None


@pytest.mark.parametrize("name,part", [
    (FWD + "enc/layer0/attn/exp:", "wideattn"),
    (BWD + "enc/layer1/attn/att_dst/mul:", "wideattn"),
    (FWD + "enc/layer1/proj/dot_general:", "wideproj"),
    (BWD + "enc/layer1/skip/skip/dot_general:", "wideproj"),
    (FWD + "enc/head/fc/dot_general:", "wideproj"),
    (BWD + "enc/head/out/transpose:", "wideproj"),
    (FWD + "enc/head/max:", "wideproj"),
    (FWD + "enc/layer0/norm/rsqrt:", "norm"),
    (FWD + "enc/layer0/norm/elu:", "norm"),
    (BWD + "enc/head/norm/mul:", "norm"),
    (FWD + "gather/hop2/mul:", "other"),
    (STEP + "jvp(M)/loss/norm/reduce_max:", "other"),
    ("%fusion.1 = f32[8] fusion(f32[8] %p), kind=kLoop", "other"),
])
def test_part_of_an_op_name(name, part):
    assert mag_readers.part_of(name) == part


def test_the_lowered_step_names_its_norms_and_its_head(whole_run):
    _, names = whole_run
    parts = {}
    for n in names:
        parts.setdefault(mag_readers.part_of(n), []).append(n)
    for scope in ("layer0/proj", "layer0/attn", "layer0/skip",
                  "layer0/norm", "layer1/norm", "head/fc", "head/norm",
                  "head/out"):
        assert any(f"encoder/enc/{scope}" in n for n in names), scope
    for part in ("wideattn", "wideproj", "norm"):
        assert any("transpose(jvp(" in n for n in parts[part]), part
        # every part of the encoder is the encoder's to encode_ms
        assert all(scope_readers.scope_of(n) == "encode"
                   for n in parts[part]), part
    for scope in ("draw/hop2", "gather/hop2", "update", "guard", "labels",
                  "loss"):
        assert any(re.search(rf"\b{scope}\b", n) for n in names), scope
    # the model has no output layer of its own: the head's is the last
    assert not any(re.search(r"\bM/out\b|\)/out\b", n) for n in names)
