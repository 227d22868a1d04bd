"""Set-up accounts for itself (PR 34): the span tree of placement and of
the first est.train calls, the compile path's events booked to the jitted
function that paid them, a planted recompile, and obs.disable().

ONE tiny program is built and driven once, in the module's fixture, the
way benchmark/run.py drives a cell (from_arrays twice, the state-init
call, two single steps, two scanned dispatches), then a batch of another
shape, then one more with tracing off; every test reads what that left.
The registry is the process's, so everything is a difference of two
snapshots.
"""

import sys
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from euler_tpu import obs  # noqa: E402
from euler_tpu.obs import first_calls  # noqa: E402

TINY = str(ROOT / "tests" / "benchmark_checks" / "tiny")
ROWS = 120_000     # tables large enough that a stage outweighs its span
FNS = ("init", "train_step", "train_loop")


def _compiles(snap):
    """{fn: executables built or fetched} of a snapshot (or a delta)."""
    out = {}
    for key, v in snap.get("estimator_compiles_total",
                           {}).get("values", {}).items():
        fn = dict(p.split("=") for p in key.split(","))["fn"]
        out[fn] = out.get(fn, 0) + v
    return out


def _compile_ms(snap, fn):
    """{stage: self ms} booked to `fn` in a snapshot (or a delta)."""
    out = {}
    for key, h in snap.get("estimator_compile_ms",
                           {}).get("values", {}).items():
        labels = dict(p.split("=") for p in key.split(","))
        if labels["fn"] == fn and h["count"]:
            out[labels["stage"]] = h["sum"]
    return out


@pytest.fixture(scope="module")
def run():
    from benchmark import cell, tables
    from benchmark.cell import load_config
    from benchmark.traffic import load_traffic

    cfg = dict(load_config(TINY, "sage3-tiny"), num_nodes=ROWS)
    traffic = load_traffic(TINY, "unit-b64")
    host = tables.make_tables(7, ROWS, cfg["feature_dim"], cfg["cap"],
                              cfg["num_classes"], traffic["edge_weights"])
    obs.enable()
    obs.clear_trace()
    snaps = [obs.snapshot()]
    prog = cell.Program(cfg, traffic, host, 7, 1)
    try:
        est, spl = prog.est, prog.spl
        est.train(iter([next(prog.feed)]), max_steps=0)     # state init
        est.train(prog.feed, max_steps=1)
        est.train(prog.feed, max_steps=2)
        est.train(prog.feed, max_steps=2 + spl)
        snaps.append(obs.snapshot())                        # 1: first calls
        est.train(prog.feed, max_steps=3 + spl)
        est.train(prog.feed, max_steps=3 + 2 * spl)
        snaps.append(obs.snapshot())                        # 2: second calls
        batch = next(prog.feed)

        def fewer_roots(n):
            return iter([{"rows": [batch["rows"][0][:n]],
                          "sample_seed": batch["sample_seed"]}])

        est.train(fewer_roots(32), max_steps=4 + 2 * spl)
        snaps.append(obs.snapshot())                        # 3: planted
        spans = obs.default_tracer().spans()
        obs.disable()
        try:
            est.train(fewer_roots(16), max_steps=5 + 2 * spl)
        finally:
            obs.enable()
        snaps.append(obs.snapshot())                        # 4: disabled
        yield {"spans": [s for s in spans
                         if s.tid == threading.get_ident()],
               "spans_after_disabled": len(obs.default_tracer()),
               "spans_before_disabled": len(spans),
               "snaps": snaps, "name": est._obs_name, "spl": spl,
               "cap": cfg["cap"], "dim": cfg["feature_dim"],
               "classes": cfg["num_classes"]}
    finally:
        prog.close()


def _kids(spans, parent, named=True):
    """The parent's children in time order; `first_call` spans are detail
    under a leaf, not phases, and are left out unless asked for."""
    return sorted((s for s in spans if s.parent_id == parent.span_id
                   and (not named or s.name != "first_call")),
                  key=lambda s: s.ts_us)


def _assert_back_to_back(parent, kids):
    """The children follow each other inside the parent and leave under
    5 % of it under none of them."""
    assert kids, parent.name
    end = parent.ts_us
    for k in kids:
        assert k.ts_us >= end - 1, (parent.name, k.name)   # 1 us of clock
        end = k.ts_us + k.dur_us
    assert end <= parent.ts_us + parent.dur_us + 1
    covered = sum(k.dur_us for k in kids)
    assert covered >= 0.95 * parent.dur_us, (
        parent.name, parent.attrs, covered, parent.dur_us,
        [(k.name, k.dur_us) for k in kids])


def test_placement_is_two_parents_with_their_stages_back_to_back(run):
    spans = run["spans"]
    (feats,) = [s for s in spans if s.name == "place_features"]
    (nbrs,) = [s for s in spans if s.name == "place_neighbors"]
    assert feats.parent_id == 0 and nbrs.parent_id == 0
    assert feats.ts_us + feats.dur_us <= nbrs.ts_us
    # rows, row bytes as stored, shard_rows
    assert feats.attrs == {
        "table": "features", "rows": ROWS + 1, "shard_rows": False,
        "row_bytes": run["dim"], "label_row_bytes": 4 * run["classes"]}
    assert nbrs.attrs == {"table": "neighbors", "rows": ROWS + 1,
                          "shard_rows": False, "row_bytes": 4 * run["cap"]}
    kids = _kids(spans, feats)
    assert [(k.name, k.attrs["table"]) for k in kids] == [
        ("quantize", "features"), ("transfer", "features"),
        ("transfer", "scale"), ("cast", "labels"), ("transfer", "labels")]
    assert kids[0].attrs == {"table": "features", "rows": ROWS + 1,
                             "dim": run["dim"]}      # PR 32's, kept
    _assert_back_to_back(feats, kids)
    kids = _kids(spans, nbrs)
    assert [(k.name, k.attrs["table"]) for k in kids] == [
        ("detect_uniform_rows", "neighbors"), ("cast", "neighbors"),
        ("store_rows", "nbr"), ("transfer", "nbr"),
        ("store_rows", "cum"), ("transfer", "cum")]
    _assert_back_to_back(nbrs, kids)


def test_each_stage_is_one_observation_of_placement_ms(run):
    delta = obs.snapshot_delta(run["snaps"][0], run["snaps"][1])
    hist = {k: v for k, v in delta["placement_ms"]["values"].items()
            if v["count"]}
    assert set(hist) == {
        "table=features,stage=place_features",
        "table=features,stage=quantize", "table=features,stage=transfer",
        "table=scale,stage=transfer", "table=labels,stage=cast",
        "table=labels,stage=transfer",
        "table=neighbors,stage=place_neighbors",
        "table=neighbors,stage=detect_uniform_rows",
        "table=neighbors,stage=cast", "table=nbr,stage=store_rows",
        "table=nbr,stage=transfer", "table=cum,stage=store_rows",
        "table=cum,stage=transfer"}
    assert all(v["count"] == 1 for v in hist.values())
    # the histogram and the span time the same interval
    spans = {(s.name, s.attrs.get("table")): s for s in run["spans"]}
    for key, v in hist.items():
        table, stage = (p.split("=")[1] for p in key.split(","))
        span_ms = spans[stage, table].dur_us / 1e3
        assert span_ms <= v["sum"] <= span_ms + 1.0, key
    assert delta["quantize_chunks_total"]["values"][""] >= 2


def test_every_train_call_is_one_parent_with_its_phases_back_to_back(run):
    spans, spl = run["spans"], run["spl"]
    calls = [s for s in spans if s.name == "train"]
    assert [c.attrs["max_steps"] for c in calls] == [
        0, 1, 2, 2 + spl, 3 + spl, 3 + 2 * spl, 4 + 2 * spl]
    assert all(c.parent_id == 0 and c.attrs["estimator"] == run["name"]
               for c in calls)
    names = [[k.name for k in _kids(spans, c)] for c in calls]
    assert names[0] == ["input_wait", "init_state", "restore_checkpoint",
                        "build_fn", "train_finish"]
    # a call that finds batches the last call on its iterator read ahead
    # has no first batch to wait for (PR 35)
    assert all(n in (["input_wait", "train_dispatch", "train_finish"],
                     ["train_dispatch", "train_finish"])
               for n in names[1:])
    assert names[1] == names[3] == names[-1] == [
        "input_wait", "train_dispatch", "train_finish"]
    (init,) = [s for s in spans if s.name == "init_state"]
    assert [k.name for k in _kids(spans, init)] == [
        "model_init", "create_state"]        # no mesh: nothing to commit
    dispatches = [_kids(spans, c)[-2] for c in calls[1:]]
    assert all(d.name == "train_dispatch" for d in dispatches)
    phases = [[k.name for k in _kids(spans, d)] for d in dispatches]
    # the single steps read nothing ahead; a scanned window on the feed
    # (an iterator: the caller's) reads the next one's batches while the
    # device works, if it is not done already: a matter of timing at this
    # size. The second window still waits and stacks: the single step
    # before it took the first of the batches read ahead
    assert phases[0] == phases[1] == phases[3] == phases[5] == [
        "device_step", "result_wait"]
    assert [n for n in phases[2] if n != "read_ahead"] == [
        "input_wait", "build_fn", "stack", "device_step", "result_wait"]
    assert [n for n in phases[4] if n != "read_ahead"] == [
        "input_wait", "stack", "device_step", "result_wait"]
    assert all(p[-2:] == ["read_ahead", "result_wait"]
               for p in phases if "read_ahead" in p)
    # every parent long enough for its spans' own cost not to count
    parents = [s for s in calls + dispatches + [init] if s.dur_us > 20e3]
    assert len(parents) >= 7     # init, first step, first dispatch, planted
    for parent in parents:
        _assert_back_to_back(parent, _kids(spans, parent))


def test_train_call_histogram_observes_each_call_whole(run):
    key = f"estimator={run['name']}"
    delta = obs.snapshot_delta(run["snaps"][0], run["snaps"][3])
    hist = delta["estimator_train_call_ms"]["values"][key]
    calls = [s for s in run["spans"] if s.name == "train"]
    assert hist["count"] == len(calls) == 7
    span_ms = sum(c.dur_us for c in calls) / 1e3
    assert span_ms <= hist["sum"] <= span_ms + 7.0


def test_first_calls_are_booked_to_the_function_that_paid(run):
    first = obs.snapshot_delta(run["snaps"][0], run["snaps"][1])
    built = _compiles(first)
    assert built["train_step"] == 1 and built["train_loop"] == 1
    assert built["init"] >= 1        # model.init, and the optimizer's zeros
    for fn in FNS:
        stages = _compile_ms(first, fn)
        assert {"trace", "lower", "compile"} <= set(stages), (fn, stages)
        assert all(ms > 0 for ms in stages.values())
    # self times: a function's stages add up to no more than the span
    # that paid them, and to most of it (nothing is counted twice, little
    # is left out)
    spans = run["spans"]
    steps = [s for s in spans if s.name == "device_step"]
    (model_init,) = [s for s in spans if s.name == "model_init"]
    for fn, payer in (("train_step", steps[0]), ("train_loop", steps[2]),
                      ("init", model_init)):
        kids = _kids(spans, payer, named=False)
        assert kids and all(k.name == "first_call" and k.attrs["fn"] == fn
                            for k in kids), fn
        if fn != "init":             # init also pays in create_state
            total_ms = sum(_compile_ms(first, fn).values())
            assert 0.5 * payer.dur_us / 1e3 <= total_ms \
                <= payer.dur_us / 1e3, (fn, total_ms, payer.dur_us)
    # a second call of each finds its executable: nothing is booked
    second = obs.snapshot_delta(run["snaps"][1], run["snaps"][2])
    assert not any(_compiles(second).get(fn) for fn in FNS)
    assert not any(_compile_ms(second, fn) for fn in FNS)
    later = [s for s in spans if s.name == "first_call"
             and s.ts_us > steps[3].ts_us and s.ts_us < steps[5].ts_us]
    assert not [s for s in later if s.attrs["fn"] in FNS]


def test_a_planted_recompile_is_booked_to_the_step_that_took_it(run):
    planted = obs.snapshot_delta(run["snaps"][2], run["snaps"][3])
    built = _compiles(planted)
    assert built.get("train_step") == 1
    assert not built.get("train_loop") and not built.get("init")
    assert {"trace", "lower", "compile"} <= set(
        _compile_ms(planted, "train_step"))
    spans = run["spans"]
    step = [s for s in spans if s.name == "device_step"][-1]
    kids = _kids(spans, step, named=False)
    assert {k.attrs["stage"] for k in kids} >= {"trace", "lower", "compile"}
    assert all(k.attrs["fn"] == "train_step" for k in kids)
    assert all(k.ts_us >= step.ts_us - 1e3 and k.ts_us + k.dur_us
               <= step.ts_us + step.dur_us + 1e3 for k in kids)


def test_disabled_tracing_still_counts_and_records_no_span(run):
    assert run["spans_after_disabled"] == run["spans_before_disabled"]
    quiet = obs.snapshot_delta(run["snaps"][3], run["snaps"][4])
    assert _compiles(quiet).get("train_step") == 1
    assert "compile" in _compile_ms(quiet, "train_step")
    key = f"estimator={run['name']}"
    assert quiet["estimator_train_call_ms"]["values"][key]["count"] == 1


def test_nested_stages_are_booked_as_self_time(monkeypatch):
    """A fetch inside the interval jax calls backend_compile, and an
    inner trace inside an outer one: each interval's own part is booked
    once, and the hit is the enclosing executable's."""
    clock = [100.0]
    monkeypatch.setattr(first_calls.time, "monotonic", lambda: clock[0])
    before = obs.snapshot()

    def fire(event, secs, at):
        clock[0] = at
        first_calls.on_duration(event, secs)

    trace, lower, compile_, fetch = first_calls.STAGES
    done = []

    def worker():
        with first_calls.calling("probe"):
            fire(trace, 0.25, 100.50)       # an inner function, 100.25..
            fire(trace, 1.0, 101.0)         # the outer trace, 100.0..101.0
            fire(lower, 0.5, 101.5)
            first_calls.on_event("/jax/compilation_cache/cache_hits")
            fire(fetch, 0.75, 102.5)        # 101.75..102.5
            fire(compile_, 1.0, 102.6)      # 101.6..102.6: holds the fetch
            fire(compile_, 2.0, 105.0)      # no hit event: compiled
        fire(lower, 0.125, 106.0)           # outside: fn="other"
        done.append(True)

    t = threading.Thread(target=worker)     # a fresh thread: no history
    t.start()
    t.join(30)
    assert done
    delta = obs.snapshot_delta(before, obs.snapshot())
    assert _compile_ms(delta, "probe") == pytest.approx(
        {"trace": 250.0 + 750.0, "lower": 500.0, "cache_fetch": 750.0,
         "compile": 250.0 + 2000.0})
    assert _compile_ms(delta, "other") == pytest.approx({"lower": 125.0})
    values = delta["estimator_compiles_total"]["values"]
    assert values["fn=probe,cache=hit"] == 1
    assert values["fn=probe,cache=miss"] == 1


def test_calling_nests_and_restores():
    assert getattr(first_calls._tls, "fn", first_calls.OTHER) == "other"
    with first_calls.calling("a"):
        with first_calls.calling("b"):
            assert first_calls._tls.fn == "b"
        assert first_calls._tls.fn == "a"
    assert first_calls._tls.fn == "other"


def test_a_recorded_span_is_a_child_of_the_open_one_and_ends_now():
    tracer = obs.Tracer()
    with tracer.span("payer") as payer:
        tracer.record("first_call", 0.004, fn="f", stage="trace")
    tracer.record("first_call", 0.002, fn="g", stage="lower")
    child, root = [s for s in tracer.spans() if s.name == "first_call"]
    assert child.parent_id == payer.span_id
    assert child.trace_id == payer.trace_id
    assert child.dur_us == pytest.approx(4000.0)
    assert child.ts_us + child.dur_us <= payer.ts_us + payer.dur_us + 50
    assert root.parent_id == 0 and root.trace_id not in (0, payer.trace_id)
    args = [e["args"] for e in tracer.chrome_trace()["traceEvents"]
            if e["name"] == "first_call"]
    assert {a["fn"] for a in args} == {"f", "g"}


def test_traced_paths_is_one_family_for_every_trace_time_branch():
    family = obs.counter("traced_paths_total", "", ("path", "detail"))
    before = family.labels(path="probe", detail="3").value
    obs.traced_path("probe", 3)
    obs.traced_path("probe", 3, n=2)
    assert family.labels(path="probe", detail="3").value == before + 3
    import euler_tpu

    src = Path(euler_tpu.__file__).parent
    stale = [str(p) for p in src.rglob("*.py")
             if "_traces_total" in p.read_text()]
    assert not stale, stale
