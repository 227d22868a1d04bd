"""What ISSUE 32 adds to the program, on the CPU at small sizes:
`utils/encoders.HopBatchNorm` (one set of statistics over every hop a
layer writes), `GATLayer(batch_norm=True)`, the hidden last layer and
the MLP head of `DeviceSampledGraphSage(encoder="gat", norm="batch",
head_dim=W)` against the benchmark's plain reference
(benchmark/reference/gat2bn.py, which imports nothing of euler_tpu) on
seeded weights at 256-wide features, `batch_stats` through the
estimator (single step, scanned dispatch, a skipped step, checkpoint,
evaluate), and the chunked `feature_store.quantize_int8`. One tiny table
set, one model and one estimator a module.
"""

import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import cell, tables  # noqa: E402
from benchmark.cell import flatten, load_config, unflatten  # noqa: E402
from benchmark.reference import common, gat2bn  # noqa: E402
from benchmark.traffic import load_traffic  # noqa: E402
from euler_tpu import obs  # noqa: E402
from euler_tpu.models import DeviceSampledGraphSage  # noqa: E402
from euler_tpu.parallel import feature_store  # noqa: E402
from euler_tpu.parallel.device_sampler import store_rows  # noqa: E402
from euler_tpu.utils.encoders import HopBatchNorm  # noqa: E402

TINY = str(ROOT / "tests" / "benchmark_checks" / "tiny")
STATS = "batch_stats"


# -- the norm alone ---------------------------------------------------------
def _hops(seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((5, 4)).astype(np.float32)),
            jnp.asarray(3.0 + 2.0 * rng.standard_normal((12, 4))
                        .astype(np.float32))]


def _apply_norm(xs, masks, stats=None):
    norm = HopBatchNorm()
    variables = norm.init(jax.random.key(0), xs, masks)
    if stats is not None:
        variables = {**variables, STATS: stats}
    return norm.apply(variables, xs, masks, mutable=[STATS]), variables


def test_the_statistics_are_those_of_the_concatenated_targets():
    xs = _hops()
    (ys, new), variables = _apply_norm(xs, [None, None])
    both = np.concatenate([np.asarray(x) for x in xs])
    mu, var = both.mean(axis=0), both.var(axis=0)
    want = (both - mu) / np.sqrt(var + 1e-5)
    np.testing.assert_allclose(np.concatenate(ys), want, rtol=1e-5,
                               atol=1e-6)
    # not those of each pair: the two hops keep their distance
    by_pair = np.concatenate([
        (np.asarray(x) - np.asarray(x).mean(0))
        / np.sqrt(np.asarray(x).var(0) + 1e-5) for x in xs])
    assert np.abs(np.concatenate(ys) - by_pair).max() > 0.5
    # init leaves the running statistics alone (mean 0, variance 1); a
    # train step moves them by PyTorch's rule, the variance unbiased
    assert not np.asarray(variables[STATS]["mean"]).any()
    assert (np.asarray(variables[STATS]["var"]) == 1).all()
    n = both.shape[0]
    np.testing.assert_allclose(new[STATS]["mean"], 0.1 * mu, rtol=1e-5)
    np.testing.assert_allclose(new[STATS]["var"],
                               0.9 + 0.1 * var * n / (n - 1), rtol=1e-5)


def test_a_masked_row_is_left_out_of_the_statistics():
    xs = _hops()
    keep = np.ones(12, bool)
    keep[[2, 7]] = False
    (ys, new), _ = _apply_norm(xs, [None, jnp.asarray(keep)])
    loud = [xs[0], xs[1].at[jnp.asarray([2, 7])].set(1e6)]
    (ys2, new2), _ = _apply_norm(loud, [None, jnp.asarray(keep)])
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(new2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ys[0]), np.asarray(ys2[0]))
    np.testing.assert_array_equal(np.asarray(ys[1])[keep],
                                  np.asarray(ys2[1])[keep])
    real = np.concatenate([np.asarray(xs[0]), np.asarray(xs[1])[keep]])
    np.testing.assert_allclose(new[STATS]["mean"], 0.1 * real.mean(0),
                               rtol=1e-5)
    # unmasked, the same rows do count
    (_, new3), _ = _apply_norm(xs, [None, None])
    assert np.abs(np.asarray(new3[STATS]["mean"])
                  - np.asarray(new[STATS]["mean"])).max() > 1e-3


def test_evaluation_reads_the_running_statistics_and_leaves_them():
    xs = _hops()
    stats = {"mean": jnp.full((4,), 0.5), "var": jnp.full((4,), 4.0)}
    norm = HopBatchNorm()
    variables = {**norm.init(jax.random.key(0), xs, [None, None]),
                 STATS: stats}
    ys = norm.apply(variables, xs, [None, None])       # nothing mutable
    for x, y in zip(xs, ys):
        np.testing.assert_allclose(
            y, (np.asarray(x) - 0.5) / np.sqrt(4.0 + 1e-5), rtol=1e-5,
            atol=1e-6)


# -- the model against the reference -----------------------------------------
D, CLASSES, FANOUTS, HEADS, DIM, HEAD_DIM = 256, 5, (3, 2), 2, 4, 6


def _hand_tables(n=300, cap=6, seed=0):
    rng = np.random.default_rng(seed)
    nbr = rng.integers(0, n, (n + 1, cap)).astype(np.int32)
    deg = rng.integers(1, cap + 1, n + 1)
    deg[:8] = 0                                  # nodes without neighbours
    nbr[np.arange(cap)[None] >= deg[:, None]] = n
    nbr[n] = n
    cum = np.cumsum((nbr != n).astype(np.float32), axis=1)
    feat = rng.standard_normal((n + 1, D)).astype(np.float32)
    feat[n] = 0
    cls = rng.integers(0, CLASSES, n + 1).astype(np.int32)
    return nbr, cum, feat, cls


def _seeded(shapes, seed=7):
    """The harness's seeding, the zero leaves then filled too, so that a
    bias or a gain the encoder forgot would show."""
    flat = common.lecun_normal(np.random.default_rng(seed), shapes)
    rng = np.random.default_rng(seed + 1)
    return {p: v if p.endswith("/kernel") else
            rng.standard_normal(v.shape).astype(np.float32) * 0.1
            for p, v in flat.items()}


@pytest.fixture(scope="module")
def model_case():
    nbr, cum, feat, cls = _hand_tables()
    q = np.clip(np.rint(feat * 20), -127, 127).astype(np.int8)
    scale = np.full((D,), 0.05, np.float32)
    model = DeviceSampledGraphSage(
        encoder="gat", heads=HEADS, dim=DIM, fanouts=FANOUTS, norm="batch",
        head_dim=HEAD_DIM, num_classes=CLASSES, multilabel=False,
        uniform_sampling=True)
    roots = jnp.arange(0, 32, dtype=jnp.int32)       # 0..7 have no slots
    batch = {"rows": [roots], "sample_seed": jnp.uint32(9),
             "nbr_table": jnp.asarray(store_rows(nbr, "nbr")),
             "cum_table": jnp.asarray(store_rows(cum, "cum")),
             "feature_table": jnp.asarray(q),
             "feature_scale": jnp.asarray(scale),
             "label_table": jnp.asarray(np.eye(CLASSES,
                                               dtype=np.float32)[cls])}
    cfg = {"feature_dim": D, "num_classes": CLASSES,
           "model": {"kwargs": {"dim": DIM, "heads": HEADS,
                                "fanouts": list(FANOUTS),
                                "head_dim": HEAD_DIM}}}
    tabs = {"nbr": jnp.asarray(nbr), "cum": jnp.asarray(cum[:1]),
            "q": jnp.asarray(q), "scale": jnp.asarray(scale),
            "cls": jnp.asarray(cls)}
    return model, batch, cfg, tabs, roots


def test_the_model_matches_the_reference_over_three_steps(model_case):
    """Loss, every gradient and the running statistics of three steps,
    pad slots and pad rows among the targets, against gat2bn.loss on the
    same tables. Plain gradient steps: a bias in front of a BatchNorm
    has no gradient but rounding's, which Adam would turn into steps of
    +-lr that the running MEAN then shows (the benchmark's `state3`)."""
    model, batch, cfg, tabs, roots = model_case
    flat = _seeded(gat2bn.param_shapes(cfg))
    init = jax.eval_shape(model.init, jax.random.key(0), batch)
    assert {p: v.shape for p, v in flatten(init["params"]).items()} \
        == {p: v.shape for p, v in flat.items()}
    extra = gat2bn.init_extra(cfg, 0)
    assert {p: v.shape for p, v in flatten(
        {STATS: init[STATS]}).items()} \
        == {p: v.shape for p, v in extra.items()}

    @jax.jit
    def ref_step(p, extra, seed):
        return jax.value_and_grad(
            lambda p: gat2bn.loss(p, extra, tabs, roots, seed, cfg, True,
                                  jnp.float32), has_aux=True)(p)

    @jax.jit
    def prog_step(p, stats, seed):
        def loss(p):
            out, new = model.apply(
                {"params": unflatten(p), **unflatten(stats)},
                {**batch, "sample_seed": seed}, mutable=[STATS])
            return out.loss, (flatten(new), out.embedding)
        return jax.value_and_grad(loss, has_aux=True)(p)

    p_ref = p_prog = {k: jnp.asarray(v) for k, v in flat.items()}
    s_ref = s_prog = extra                            # zeros on both sides
    for step in range(3):
        seed = jnp.uint32(9 + step)
        (want, s_ref), g_ref = ref_step(p_ref, s_ref, seed)
        (loss, (s_prog, emb)), g_prog = prog_step(p_prog, s_prog, seed)
        np.testing.assert_allclose(loss, want, rtol=1e-5)
        assert emb.shape == (32, CLASSES)        # the head's logits
        for path, g in g_ref.items():
            np.testing.assert_allclose(g_prog[path], g, rtol=1e-3,
                                       atol=1e-7, err_msg=path)
            assert float(jnp.abs(g).max()) > 0, path
        for path, s in s_ref.items():
            np.testing.assert_allclose(s_prog[path], s, rtol=1e-4,
                                       atol=1e-7, err_msg=path)
        p_ref = {k: v - 0.05 * g_ref[k] for k, v in p_ref.items()}
        p_prog = {k: v - 0.05 * g_prog[k] for k, v in p_prog.items()}
    assert float(jnp.abs(s_ref[f"{gat2bn.STATS}/head/norm/var"]).min()) > 0


def test_the_fields_are_validated_before_the_draw(model_case):
    _, batch, _, _, _ = model_case
    kw = dict(fanouts=FANOUTS, num_classes=CLASSES, multilabel=False)
    for bad, match in ((dict(encoder="gat", norm="layer"), "norm must be"),
                       (dict(encoder="gat", head_dim=-1), "head_dim >= 0"),
                       (dict(encoder="sage", norm="batch"), "'gat''s"),
                       (dict(encoder="unimp", head_dim=8), "'gat''s")):
        with pytest.raises(ValueError, match=match):
            jax.eval_shape(DeviceSampledGraphSage(**kw, **bad).init,
                           jax.random.key(0), batch)
    # each field alone: normalised hidden layers with the class layer
    # last, or the head with no norm anywhere
    normed = jax.eval_shape(
        DeviceSampledGraphSage(encoder="gat", heads=2, dim=4, norm="batch",
                               **kw).init, jax.random.key(0), batch)
    enc = normed["params"]["encoder"]["enc"]
    assert "norm" in enc["layer0"] and "norm" not in enc["layer1"]
    assert "head" not in enc and set(normed[STATS]["encoder"]["enc"]) \
        == {"layer0"}
    headed = jax.eval_shape(
        DeviceSampledGraphSage(encoder="gat", heads=2, dim=4, head_dim=6,
                               **kw).init, jax.random.key(0), batch)
    enc = headed["params"]["encoder"]["enc"]
    assert set(enc["head"]) == {"fc", "out"} and STATS not in headed
    assert enc["layer1"]["proj"]["kernel"].shape == (8, 8)


# sha256 of the lowered value-and-grad programs at the shapes below,
# recorded at the parent commit (a9e8140) with the jax this container
# has: `norm` and `head_dim` left alone must leave the accepted
# configurations' programs as they are, text for text
_PARENT_PROGRAMS = {
    "gat": ("ff6c345419d77e3e", "523983cb2ce190cd"),
    "unimp": ("8382e918699a7e20", "439e497b47df4207"),
}


@pytest.mark.parametrize("encoder", sorted(_PARENT_PROGRAMS))
def test_the_accepted_attention_models_lower_to_the_parents_programs(encoder):
    host = tables.make_tables(5, 500, 16, 8, 7, {"kind": "unit"})
    from euler_tpu.parallel import DeviceFeatureStore, DeviceNeighborTable

    store = DeviceFeatureStore.from_arrays(
        host["feat"].astype(np.dtype(jnp.bfloat16)),
        tables.one_hot_labels(host["cls"], 7), quantize="int8",
        scale_dtype=jnp.bfloat16)
    samp = DeviceNeighborTable.from_arrays(host["nbr"], host["cum"])
    batch = {"rows": [jnp.arange(32, dtype=jnp.int32)],
             "sample_seed": jnp.uint32(3),
             "feature_table": store.features,
             "feature_scale": store.feature_scale,
             "label_table": store.labels, "nbr_table": samp.neighbors,
             "cum_table": samp.cum_weights}
    model = DeviceSampledGraphSage(
        dim=8, heads=2, fanouts=(3, 2), encoder=encoder, num_classes=7,
        multilabel=False, uniform_sampling=True)
    params = jax.eval_shape(model.init, jax.random.key(0), batch)["params"]
    tree = str(jax.tree_util.tree_map(
        lambda a: (a.shape, str(a.dtype)), params))

    def loss(p, b):
        return model.apply({"params": p}, b).loss

    text = jax.jit(jax.value_and_grad(loss)).lower(params, batch).as_text()
    got = tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                for t in (tree, text))
    assert got == _PARENT_PROGRAMS[encoder], (got, jax.__version__)


# -- batch_stats through the estimator ---------------------------------------
@pytest.fixture(scope="module")
def program():
    """The tiny cell's estimator over its feeder, weights seeded and the
    running statistics zeroed as the harness does, one single step and
    one scanned dispatch made."""
    cfg, mix = load_config(TINY, "gat2-tiny"), load_traffic(TINY, "unit-b64")
    host = tables.make_tables(11, cfg["num_nodes"], cfg["feature_dim"],
                              cfg["cap"], cfg["num_classes"],
                              mix["edge_weights"])
    prog = cell.Program(cfg, mix, host, 11, 1)
    from benchmark import check

    prog.install_weights(check.make_weights(cfg, 11))
    yield prog
    prog.free()


def _stats(est):
    return {p: np.asarray(v) for p, v in
            flatten(jax.device_get(est.state.extra_vars)).items()}


def test_train_moves_the_running_statistics_single_and_scanned(program):
    est, spl = program.est, program.spl
    start = _stats(est)
    assert set(start) == set(gat2bn.init_extra(program.cfg, 0))
    assert not any(v.any() for v in start.values())
    assert est.train(program.feed, max_steps=1)["global_step"] == 1
    one = _stats(est)
    assert all(np.abs(v).max() > 0 for v in one.values())
    assert est.train(program.feed, max_steps=1 + spl)["global_step"] \
        == 1 + spl
    many = _stats(est)
    # the variance is a moving average from 0: it grows towards the
    # batch's, by 1 - 0.9^steps of it
    for path, v in many.items():
        assert np.isfinite(v).all(), path
        if path.endswith("/var"):
            assert (v > one[path]).all(), path
    assert int(est.state.skipped_steps) == 0


def test_a_skipped_step_leaves_batch_stats_as_it_was(program):
    """Every label NaN: the guard skips the step, single or scanned, and
    the running statistics (not a cache: they go through the guard's
    lax.cond) keep their bits."""
    est, spl = program.est, program.spl
    before, step0 = _stats(est), int(est.state.step)
    skipped0 = int(est.state.skipped_steps)
    labels = est.static_batch["label_table"]
    est.static_batch["label_table"] = jnp.full_like(labels, jnp.nan)
    try:
        est.train(program.feed, max_steps=step0 + 1)
        est.train(program.feed, max_steps=step0 + 1 + spl)
    finally:
        est.static_batch["label_table"] = labels
    assert int(est.state.skipped_steps) - skipped0 == 1 + spl
    after = _stats(est)
    for path, v in before.items():
        assert v.tobytes() == after[path].tobytes(), path
    # and a sound step after it moves them again
    est.train(program.feed, max_steps=step0 + 2 + spl)
    assert any(v.tobytes() != before[p].tobytes()
               for p, v in _stats(est).items())


def test_evaluate_reads_the_running_statistics(program):
    est = program.est
    rows = np.arange(64, dtype=np.int32)

    def batches():
        yield {"rows": [rows], "sample_seed": np.uint32(5)}

    first = est.evaluate(batches, steps=1)
    again = est.evaluate(batches, steps=1)
    assert first == again and np.isfinite(first["loss"])
    kept = est.state
    try:
        moved = jax.tree_util.tree_map(lambda a: a * 4.0 + 1.0,
                                       kept.extra_vars)
        est.state = kept.replace(extra_vars=moved)
        other = est.evaluate(batches, steps=1)
    finally:
        est.state = kept
    assert abs(other["loss"] - first["loss"]) > 1e-4
    # evaluation wrote nothing
    assert est.evaluate(batches, steps=1) == first


def test_save_and_restore_round_trip_the_running_statistics(program,
                                                            tmp_path):
    est = program.est
    est.model_dir, est._ckpt_mgr = str(tmp_path), None
    try:
        before, step = _stats(est), int(est.state.step)
        est.save_checkpoint(step)
        est.finalize_checkpoints()
        est.state = est.state.replace(extra_vars=jax.tree_util.tree_map(
            jnp.zeros_like, est.state.extra_vars))
        assert est.restore_checkpoint() == step
        after = _stats(est)
    finally:
        est.model_dir, est._ckpt_mgr = None, None
    assert set(after) == set(before)
    for path, v in before.items():
        assert v.tobytes() == after[path].tobytes(), path


def test_one_count_a_norm_a_trace():
    counter = obs.counter("traced_paths_total", "", ("path", "detail")).labels(
        path="batch_norm", detail="")
    before = counter.value
    xs = _hops()
    norm = HopBatchNorm()
    variables = norm.init(jax.random.key(0), xs, [None, None])
    fn = jax.jit(lambda v, xs: norm.apply(v, xs, [None, None],
                                          mutable=[STATS]))
    fn(variables, xs)
    fn(variables, xs)                   # cached: no new trace, no count
    assert counter.value - before == 2   # init, the jit


# -- the chunked quantisation ------------------------------------------------
def _one_pass(feats):
    """`quantize_int8` as it was before it worked in chunks."""
    scale = np.abs(feats).max(axis=0).astype(np.float32) / 127.0
    scale[scale == 0] = 1.0
    q = np.clip(np.rint(feats.astype(np.float32, copy=False) / scale),
                -127, 127)
    return q.astype(np.int8), scale


@pytest.mark.parametrize("columns", [1, 128, 200, 768])
def test_chunked_quantisation_is_the_one_pass_result(columns, monkeypatch):
    rows = 3000
    f = np.random.default_rng(columns).standard_normal(
        (rows, columns), dtype=np.float32)
    if columns > 1:
        f[:, 1] = 0                      # an all-zero column: scale 1
    chunks = obs.counter("quantize_chunks_total", "")
    # a chunk boundary inside the table, the last chunk a short one
    monkeypatch.setattr(feature_store, "_QUANT_CHUNK_ELEMS",
                        700 * columns + 1)
    for handed in (f, f.astype(np.dtype(jnp.bfloat16)),
                   f.astype(np.float64)):
        before = chunks.labels().value
        q, scale = feature_store.quantize_int8(handed)
        assert chunks.labels().value - before == 2 * 5
        want_q, want_scale = _one_pass(np.asarray(handed, np.float32))
        assert q.dtype == np.int8 and scale.dtype == np.float32
        assert q.tobytes() == want_q.tobytes()
        assert scale.tobytes() == want_scale.tobytes()
    # whole, in one chunk: the same bytes
    monkeypatch.undo()
    assert feature_store.quantize_int8(f)[0].tobytes() \
        == _one_pass(f)[0].tobytes()
