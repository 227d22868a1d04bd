"""CPU checks of what ISSUE 34 adds to the benchmark: the four readers of
set-up (benchmark/setup_readers.py) on snapshots made up by hand, on the
snapshot of a program that lacks the families (the parent commit), and on
the registry of a tiny program driven the way run.py drives a cell; the
four metric files against their entries.
"""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import cell, setup_readers, tables  # noqa: E402
from benchmark.cell import load_config, resolve  # noqa: E402
from benchmark.traffic import load_traffic  # noqa: E402

TINY = str(Path(__file__).resolve().parent / "tiny")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ("setup_place_s", "setup_train_s", "setup_compile_s",
       "setup_cache_misses")


def _hist(total_ms, count=1):
    return {"count": count, "sum": total_ms, "buckets": [["+Inf", count]]}


def _ctx(before, after=None):
    return {"window": {"obs_before": before,
                       "obs_after": after if after is not None else before}}


def _snapshot():
    return {
        "placement_ms": {"type": "histogram", "help": "", "values": {
            "table=features,stage=place_features": _hist(9000.0),
            "table=features,stage=quantize": _hist(5000.0),
            "table=features,stage=transfer": _hist(3500.0),
            "table=labels,stage=transfer": _hist(400.0),
            "table=neighbors,stage=place_neighbors": _hist(2500.0),
            "table=nbr,stage=store_rows": _hist(1200.0),
            "table=nbr,stage=transfer": _hist(100.0)}},
        "estimator_train_call_ms": {
            "type": "histogram", "help": "", "values": {
                "estimator=estimator0": _hist(7250.0, count=5),
                "estimator=estimator1": _hist(750.0, count=1)}},
        "estimator_compile_ms": {"type": "histogram", "help": "", "values": {
            "fn=init,stage=trace": _hist(300.0, 40),
            "fn=init,stage=cache_fetch": _hist(200.0, 9),
            "fn=train_step,stage=lower": _hist(250.0),
            "fn=train_loop,stage=compile": _hist(1000.0),
            "fn=other,stage=compile": _hist(250.0, 3)}},
        "estimator_compiles_total": {
            "type": "counter", "help": "", "values": {
                "fn=init,cache=hit": 9.0, "fn=train_loop,cache=miss": 1.0,
                "fn=other,cache=miss": 2.0, "fn=other,cache=hit": 1.0}},
        "estimator_input_wait_ms": {"type": "histogram", "help": "",
                                    "values": {"estimator=estimator0":
                                               _hist(12.0, 8)}},
    }


def test_the_readers_sum_the_start_of_the_window_by_hand():
    # what the window adds (obs_after) is not set-up's: it is not read
    later = _snapshot()
    later["estimator_train_call_ms"]["values"]["estimator=estimator0"] = \
        _hist(99999.0, 30)
    ctx = _ctx(_snapshot(), later)
    # the two parents only: their stages are inside them
    assert setup_readers.setup_place_s(ctx) == pytest.approx(9.0 + 2.5)
    assert setup_readers.setup_train_s(ctx) == pytest.approx(7.25 + 0.75)
    assert setup_readers.setup_compile_s(ctx) == pytest.approx(
        0.3 + 0.2 + 0.25 + 1.0 + 0.25)
    assert setup_readers.setup_cache_misses(ctx) == 3.0


def test_a_warm_run_reads_no_miss_as_zero_not_as_nothing():
    warm = _snapshot()
    warm["estimator_compiles_total"]["values"] = {"fn=init,cache=hit": 9.0}
    assert setup_readers.setup_cache_misses(_ctx(warm)) == 0.0
    del warm["estimator_compiles_total"]     # every program was warm in
    assert setup_readers.setup_cache_misses(_ctx(warm)) == 0.0   # memory


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_family_reads_none(name):
    """The parent commit: the registry holds what PR 33's program wrote,
    the reader finds nothing and does not raise; the line leaves it out."""
    parent = {k: v for k, v in _snapshot().items()
              if k == "estimator_input_wait_ms"}
    assert getattr(setup_readers, name)(_ctx(parent)) is None
    assert getattr(setup_readers, name)(_ctx({})) is None


@pytest.mark.parametrize("name", NEW)
def test_the_metric_files_are_the_entries(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    spec = json.loads((ROOT / "benchmark" / "metrics"
                       / (name + ".json")).read_text())
    assert {k: v for k, v in spec.items() if k != "reader"} == entry
    assert resolve(spec["reader"]) is getattr(setup_readers, name)
    assert entry["moves"] == "setup_s" and "workloads" not in entry
    assert entry["better"] == "lower"
    assert_the_four_stand_together(BENCH)


def assert_the_four_stand_together(bench: dict) -> None:
    """The four were appended as one block, in this order, each in a
    layer an entry before them names: a rule that later appends keep."""
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == list(NEW)
    layers = {m["layer"] for m in bench["per_layer"][:at]}
    for m in bench["per_layer"][at:at + len(NEW)]:
        assert m["layer"] in layers, m["name"]


def test_the_four_stand_together_after_an_append():
    appended = {"name": "later_ms", "unit": "ms", "better": "lower",
                "source": "device_trace",
                "layer": BENCH["per_layer"][0]["layer"],
                "moves": "train_nodes_per_s",
                "workloads": [BENCH["workloads"][0]["name"]]}
    assert_the_four_stand_together(
        {**BENCH, "per_layer": BENCH["per_layer"] + [appended]})
    moved = [m for m in BENCH["per_layer"] if m["name"] != NEW[2]] \
        + [m for m in BENCH["per_layer"] if m["name"] == NEW[2]]
    with pytest.raises(AssertionError):
        assert_the_four_stand_together({**BENCH, "per_layer": moved})


def test_the_readers_on_a_tiny_programs_own_registry():
    """The harness's own sequence at the tiny size: what the four read at
    the window's start against the clock around the same calls."""
    import time

    from euler_tpu import obs

    cfg = load_config(TINY, "sage3-tiny")
    traffic = load_traffic(TINY, "unit-b64")
    host = tables.make_tables(11, cfg["num_nodes"], cfg["feature_dim"],
                              cfg["cap"], cfg["num_classes"],
                              traffic["edge_weights"])
    zero = obs.snapshot()
    t0 = time.perf_counter()
    prog = cell.Program(cfg, traffic, host, 11, 1)
    try:
        t1 = time.perf_counter()
        prog.est.train(iter([next(prog.feed)]), max_steps=0)
        prog.first_steps()
        t2 = time.perf_counter()
        start = obs.snapshot_delta(zero, obs.snapshot())
    finally:
        prog.close()
    ctx = _ctx(start)
    place, train, compiles, misses = (
        getattr(setup_readers, n)(ctx) for n in NEW)
    assert 0 < place <= t1 - t0
    # five calls: state init, three single steps, the scanned dispatch
    hist = start["estimator_train_call_ms"]["values"]
    assert sum(h["count"] for h in hist.values()) == 5
    assert 0.5 * (t2 - t1) <= train <= t2 - t1
    # the compile path is most of a first call at this size, and a part
    # of it: nothing is counted twice
    assert 0.3 * train <= compiles <= t2 - t0
    # init, train_step, train_loop at the least; fetched, not missed,
    # where an earlier test of this process left jax a warm cache
    built = sum(start["estimator_compiles_total"]["values"].values())
    assert built >= 3 and 0 <= misses <= built
