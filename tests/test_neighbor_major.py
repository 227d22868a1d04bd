"""The mean encoders read their hops NEIGHBOUR-MAJOR where the draw ran
on the device (PR 31): one algorithm whose slot axis the caller states.
The neighbour-major encoder on `neighbor_major_rows`-ordered hops is the
target-major one on the draw's own order — value, every parameter's
gradient, the parameter tree — and the device-sampled model's loss is
the plain reference's (benchmark/reference/sage3.py, target-major, which
imports nothing of euler_tpu). Float32 on the CPU, small shapes."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.cell import unflatten  # noqa: E402
from benchmark.reference import common, sage3  # noqa: E402
from euler_tpu import obs  # noqa: E402
from euler_tpu.models import DeviceSampledGraphSage  # noqa: E402
from euler_tpu.models.graphsage import (  # noqa: E402
    DeviceSampledUnsupervisedSage, _GatherEncode,
)
from euler_tpu.parallel.device_sampler import store_rows  # noqa: E402
from euler_tpu.parallel.mesh import make_mesh  # noqa: E402
from euler_tpu.parallel.placement import put_row_sharded  # noqa: E402
from euler_tpu.utils import encoders as E  # noqa: E402
from test_gat_encoder import _leaf, _tables  # noqa: E402

N, CAP, D, CLASSES = 300, 6, 12, 5
FANOUTS = (15, 10, 5)        # the sage3 cells' widths: no multiple of 8


def _filled(shapes, seed):
    """Every leaf of a tree of shapes filled, biases too, so that one an
    encoder missed would show."""
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda v: jnp.asarray(rng.standard_normal(v.shape)
                              .astype(np.float32) * 0.3), shapes)


def _reorders():
    """The trace-time count of `sage` fanouts put neighbour-major."""
    return obs.counter("traced_paths_total", "", ("path", "detail")).labels(
        path="neighbor_major_fanout", detail="sage").value


@pytest.fixture(scope="module")
def graph():
    """Hand-made tables (nodes 0..7 have no neighbours, so pad rows are
    among every hop), one draw of FANOUTS hops in the draw's own order,
    the same ids neighbour-major, and the features of both."""
    nbr, cum, feat, cls = _tables(N, CAP, D, CLASSES)
    rng = np.random.default_rng(1)
    ids = [np.array([0, 9, 200], np.int32)]      # root 0 has no neighbour
    for k in FANOUTS:
        slot = rng.integers(0, CAP, (ids[-1].shape[0], k))
        ids.append(nbr[ids[-1][:, None], slot].reshape(-1))
    major = [np.asarray(r) for r in E.neighbor_major_rows(
        [jnp.asarray(r) for r in ids], FANOUTS)]
    assert all((r == N).any() and (r != N).any() for r in ids[1:])
    return dict(nbr=nbr, cum=cum, feat=feat, cls=cls,
                target=[jnp.asarray(feat[r]) for r in ids],
                major=[jnp.asarray(feat[r]) for r in major])


def _encoder(kind, **kw):
    name, _, variant = kind.partition("-")
    if name == "gcn":
        return E.GCNEncoder(8, FANOUTS, **kw)
    if name == "genie":
        return E.GenieEncoder(8, FANOUTS, **kw)
    agg, _, how = variant.partition("-")
    return E.SageEncoder(8, FANOUTS, agg, concat=how != "sum", **kw)


@pytest.mark.parametrize("kind", [
    "sage-mean", "sage-mean-sum", "sage-meanpool", "sage-meanpool-sum",
    "sage-maxpool", "sage-maxpool-sum", "sage-gcn", "gcn", "genie"])
def test_neighbor_major_encoder_is_the_target_major_one(graph, kind):
    before, after = _encoder(kind), _encoder(kind, neighbor_major=True)
    shapes = jax.eval_shape(before.init, jax.random.key(1), graph["target"])
    # one tree: the same names and shapes
    assert shapes == jax.eval_shape(after.init, jax.random.key(1),
                                    graph["major"])
    params = _filled(shapes, 2)
    out = jax.eval_shape(before.apply, params, graph["target"])
    w = jnp.asarray(np.random.default_rng(3).standard_normal(out.shape)
                    .astype(np.float32))

    @jax.jit
    def both(p):
        return [jax.value_and_grad(
            lambda p: (enc.apply(p, layers) * w).sum())(p)
            for enc, layers in ((before, graph["target"]),
                                (after, graph["major"]))]

    (want, g_want), (got, g_got) = both(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat_want = jax.tree_util.tree_leaves_with_path(g_want)
    for (path, a), b in zip(flat_want, jax.tree_util.tree_leaves(g_got)):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
        assert float(jnp.abs(a).max()) > 0, path


def test_host_fed_order_read_as_neighbor_major_is_another_function(graph):
    """The order is the caller's to state: the same array under the
    other statement is another (wrong) aggregation, not an error."""
    layers = graph["target"][:2]
    enc = E.SageEncoder(8, FANOUTS[:1])
    params = enc.init(jax.random.key(1), layers)
    wrong = E.SageEncoder(8, FANOUTS[:1], neighbor_major=True).apply(
        params, layers)
    assert float(jnp.abs(enc.apply(params, layers) - wrong).max()) > 1e-3


def _batch(graph, place=jnp.asarray, **extra):
    q = np.clip(np.rint(graph["feat"] * 20), -127, 127).astype(np.int8)
    return q, {
        "rows": [jnp.arange(0, 32, dtype=jnp.int32)],   # 0..7: no slots
        "sample_seed": jnp.uint32(9),
        "nbr_table": place(store_rows(graph["nbr"], "nbr")),
        "cum_table": place(store_rows(graph["cum"], "cum")),
        "feature_table": place(q),
        "feature_scale": jnp.full((D,), 0.05, jnp.float32),
        "label_table": place(np.eye(CLASSES,
                                    dtype=np.float32)[graph["cls"]]),
        **extra}


@pytest.mark.parametrize("tables", ["replicated", "row_sharded"])
def test_the_model_matches_the_target_major_reference(graph, tables):
    """DeviceSampledGraphSage(encoder='sage') through its own draw,
    re-ordering and gather gives the loss and the gradients of sage3.loss,
    which views every hop [n, k, D] in the draw's order: what the model
    computed before it took the neighbour-major order."""
    fanouts = (5, 3)
    sharded = tables == "row_sharded"
    mesh = make_mesh(model_parallel=2) if sharded else None
    place = (lambda a: put_row_sharded(a, mesh)) if sharded else jnp.asarray
    q, batch = _batch(graph, place)
    # row-sharded tables keep the inverse-CDF draw; both sides draw alike
    model = DeviceSampledGraphSage(
        dim=4, fanouts=fanouts, num_classes=CLASSES, multilabel=False,
        uniform_sampling=not sharded, table_mesh=mesh)
    cfg = {"feature_dim": D, "num_classes": CLASSES,
           "model": {"kwargs": {"dim": 4, "fanouts": list(fanouts)}}}
    flat = common.lecun_normal(np.random.default_rng(7),
                               sage3.param_shapes(cfg))
    flat = {k: jnp.asarray(v) for k, v in flat.items()}
    nested = unflatten(flat)
    init = jax.eval_shape(model.init, jax.random.key(0), batch)["params"]
    assert jax.tree_util.tree_map(jnp.shape, init) \
        == jax.tree_util.tree_map(jnp.shape, nested)
    tabs = {"nbr": jnp.asarray(graph["nbr"]),
            "cum": jnp.asarray(graph["cum"] if sharded
                               else graph["cum"][:1]),
            "q": jnp.asarray(q), "scale": batch["feature_scale"],
            "cls": jnp.asarray(graph["cls"])}
    want, g_ref = jax.jit(jax.value_and_grad(
        lambda p: sage3.loss(p, {}, tabs, batch["rows"][0], jnp.uint32(9),
                             cfg, not sharded, jnp.float32)[0]))(flat)
    count = _reorders()
    step = jax.jit(jax.value_and_grad(
        lambda p: model.apply({"params": p}, batch).loss))
    if sharded:
        with mesh:
            loss, g_prog = step(nested)
    else:
        loss, g_prog = step(nested)
        step(nested)                    # cached: no new trace, no count
    assert _reorders() == count + 1     # one a traced program
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for path, g in g_ref.items():
        np.testing.assert_allclose(_leaf(g_prog, path), g, rtol=2e-4,
                                   atol=1e-7, err_msg=path)


def test_only_int32_ids_are_transposed(graph):
    """The re-ordering moves ids, never features: in the jaxpr of
    _GatherEncode's value-and-grad every transpose is of an int32 array,
    and every view of a gathered hop puts the slots first."""
    _, batch = _batch(graph)
    enc = _GatherEncode(8, FANOUTS, "mean", "sage")
    rows = [jnp.zeros((4 * int(np.prod(FANOUTS[:h])),), jnp.int32)
            for h in range(len(FANOUTS) + 1)]
    args = (batch["feature_table"], batch["feature_scale"], rows)
    params = jax.eval_shape(enc.init, jax.random.key(0), *args)

    def loss(p):
        return enc.apply(p, *args).sum()

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "transpose":
                found.append(eqn.invars[0].aval)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(jax.value_and_grad(loss))(params).jaxpr)
    moved = [a for a in found if a.ndim > 2]   # a matrix's .T is a dW's
    assert len(moved) == len(FANOUTS) - 1      # hop 1 is one axis: no move
    assert all(a.dtype == jnp.int32 for a in moved), moved


def test_the_unsupervised_model_draws_neighbor_major_too(graph):
    """DeviceSampledUnsupervisedSage takes the same order: its embedding
    is what its encoder gives target-major on the draw's own order."""
    from euler_tpu.models.graphsage import gather_feature_rows
    from euler_tpu.parallel.device_sampler import sample_fanout_rows

    fanouts = (5, 3)
    _, batch = _batch(
        graph, neg_rows=jnp.arange(N, dtype=jnp.int32),
        neg_cum=jnp.asarray(np.cumsum(np.ones((N,), np.float32))))
    model = DeviceSampledUnsupervisedSage(
        num_rows=N, dim=8, fanouts=fanouts, uniform_sampling=True)
    params = _filled(
        jax.eval_shape(model.init, jax.random.key(0), batch), 3)
    count = _reorders()

    @jax.jit
    def both(p):
        # the model's own draw (its key, the first of three)
        key = jax.random.split(jax.random.fold_in(
            jax.random.key(29), batch["sample_seed"]), 3)[0]
        rows = sample_fanout_rows(
            batch["nbr_table"], batch["cum_table"], batch["rows"][0],
            fanouts, key, uniform=True)
        return model.apply(p, batch).embedding, E.SageEncoder(
            8, fanouts, concat=False).apply(
                {"params": p["params"]["encoder"]},
                gather_feature_rows(batch, rows))

    got, want = both(params)
    assert _reorders() == count + 1
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
