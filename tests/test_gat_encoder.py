"""The graph-attention encoder (utils/encoders.GATLayer / GATEncoder,
DeviceSampledGraphSage(encoder="gat")) against the benchmark's plain
reference (benchmark/reference/gat3.py, which imports nothing of
euler_tpu) on seeded weights, float32 on the CPU."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.cell import unflatten  # noqa: E402
from benchmark.reference import common, gat3  # noqa: E402
from euler_tpu import obs  # noqa: E402
from euler_tpu.models import DeviceSampledGraphSage  # noqa: E402
from euler_tpu.parallel.device_sampler import store_rows  # noqa: E402
from euler_tpu.utils.encoders import (  # noqa: E402
    GATEncoder, GATLayer, neighbor_major_rows,
)

M, K, D, HEADS, C = 6, 3, 5, 4, 8
BASE = "encoder/enc/layer0"


def _seeded(shapes, seed=7):
    """The harness's own seeding: leaves named kernel N(0, 1/fan_in), the
    rest zero; the zero leaves are then filled too, so that a bias the
    encoder forgot would show."""
    flat = common.lecun_normal(np.random.default_rng(seed), shapes)
    rng = np.random.default_rng(seed + 1)
    return {p: v if p.endswith("/kernel") else
            rng.standard_normal(v.shape).astype(np.float32) * 0.1
            for p, v in flat.items()}


def _leaf(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return tree


def _layer_case(last):
    c = 3 if last else C
    d_out = c if last else HEADS * c
    flat = _seeded({
        BASE + "/proj/kernel": (D, HEADS * c),
        BASE + "/att_src/kernel": (c, HEADS),
        BASE + "/att_dst/kernel": (c, HEADS),
        BASE + "/bias": (d_out,),
        BASE + "/skip/kernel": (D, d_out), BASE + "/skip/bias": (d_out,)})
    rng = np.random.default_rng(3)
    x_t = rng.standard_normal((M, D)).astype(np.float32)
    x_s = rng.standard_normal((M, K, D)).astype(np.float32)
    valid = rng.random((M, K)) < 0.7
    valid[1] = False                     # every slot of node 1 is a pad
    valid[2] = True
    nested = unflatten({k[len(BASE) + 1:]: jnp.asarray(v)
                        for k, v in flat.items()})
    return flat, nested, x_t, x_s, valid, c


@pytest.mark.parametrize("last", [False, True],
                         ids=["heads_concatenated", "heads_averaged"])
def test_layer_matches_the_reference_forward_and_gradients(last):
    flat, nested, x_t, x_s, valid, c = _layer_case(last)
    layer = GATLayer(c, HEADS, concat=not last, name="layer0")
    # the encoder reads its slots neighbour-major: [k, M, D] flattened
    hidden = [jnp.asarray(x_t),
              jnp.asarray(x_s.transpose(1, 0, 2).reshape(K * M, D))]
    masks = [None, jnp.asarray(valid.T.reshape(-1))]

    def prog(p, xt, xs):
        return layer.apply({"params": p}, [xt, xs], masks)[0]

    def ref(p, xt, xs):
        return gat3.layer(p, BASE, xt, xs, jnp.asarray(valid), HEADS, last,
                          jnp.float32)

    flat_j = {k: jnp.asarray(v) for k, v in flat.items()}
    got = jax.jit(prog)(nested, *hidden)
    want = jax.jit(ref)(flat_j, jnp.asarray(x_t), jnp.asarray(x_s))
    assert got.shape == (M, c if last else HEADS * c)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert np.isfinite(np.asarray(got)).all()
    # gradients of a scalar of the output, by parameters and inputs
    w = jnp.asarray(np.random.default_rng(5).standard_normal(got.shape)
                    .astype(np.float32))
    g_prog = jax.jit(jax.grad(lambda p, a, b: (prog(p, a, b) * w).sum(),
                              argnums=(0, 1, 2)))(nested, *hidden)
    g_ref = jax.jit(jax.grad(lambda p, a, b: (ref(p, a, b) * w).sum(),
                             argnums=(0, 1, 2)))(
        flat_j, jnp.asarray(x_t), jnp.asarray(x_s))
    for path, g in g_ref[0].items():
        node = _leaf(g_prog[0], path[len(BASE) + 1:])
        assert np.isfinite(np.asarray(node)).all(), path
        np.testing.assert_allclose(node, g, rtol=2e-4, atol=2e-6,
                                   err_msg=path)
        assert float(jnp.abs(g).max()) > 0, path
    np.testing.assert_allclose(g_prog[1], g_ref[1], rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(
        g_prog[2].reshape(K, M, D).transpose(1, 0, 2), g_ref[2],
        rtol=2e-4, atol=2e-6)
    # a pad slot takes no weight and gives no gradient
    assert not np.asarray(g_ref[2])[~valid].any()
    assert not np.asarray(g_prog[2].reshape(K, M, D)
                          .transpose(1, 0, 2))[~valid].any()


def test_a_node_whose_slots_are_all_pads_attends_to_itself_only():
    flat, nested, x_t, x_s, valid, c = _layer_case(False)
    layer = GATLayer(c, HEADS, concat=True, name="layer0")
    xs = jnp.asarray(x_s.transpose(1, 0, 2).reshape(K * M, D))
    got = layer.apply({"params": nested}, [jnp.asarray(x_t), xs],
                      [None, jnp.asarray(valid.T.reshape(-1))])[0]
    # alpha_ii = 1: the row's own projection, its bias and its skip
    alone = jax.nn.elu(
        x_t[1] @ flat[BASE + "/proj/kernel"] + flat[BASE + "/bias"]
        + x_t[1] @ flat[BASE + "/skip/kernel"] + flat[BASE + "/skip/bias"])
    np.testing.assert_allclose(got[1], alone, rtol=2e-5, atol=2e-6)
    # whatever its pad slots hold, huge values included
    loud = xs.reshape(K, M, D).at[:, 1].set(1e30).reshape(K * M, D)
    again = layer.apply({"params": nested}, [jnp.asarray(x_t), loud],
                        [None, jnp.asarray(valid.T.reshape(-1))])[0]
    np.testing.assert_array_equal(np.asarray(again[1]), np.asarray(got[1]))
    # unmasked, the same slots do take weight
    unmasked = layer.apply({"params": nested}, [jnp.asarray(x_t), xs],
                           [None, None])[0]
    assert float(jnp.abs(unmasked[1] - got[1]).max()) > 1e-3


def test_neighbor_major_rows_keeps_every_hop_consistent():
    b, fanouts = 4, (3, 2, 5)
    rows = [np.arange(b, dtype=np.int32)]
    for k in fanouts:      # a child's id names its parent's and its slot
        rows.append((rows[-1][:, None] * 10 + np.arange(1, k + 1)[None])
                    .reshape(-1).astype(np.int32))
    got = [np.asarray(r) for r in neighbor_major_rows(
        [jnp.asarray(r) for r in rows], fanouts)]
    np.testing.assert_array_equal(got[0], rows[0])
    for hop, k in enumerate(fanouts):
        parents = got[hop]
        slots = got[hop + 1].reshape(k, parents.shape[0])
        for j in range(k):
            np.testing.assert_array_equal(slots[j], parents * 10 + j + 1)
        assert sorted(got[hop + 1]) == sorted(rows[hop + 1])


def _tables(n=300, cap=6, d=12, classes=5, seed=0):
    rng = np.random.default_rng(seed)
    nbr = rng.integers(0, n, (n + 1, cap)).astype(np.int32)
    deg = rng.integers(1, cap + 1, n + 1)
    deg[:8] = 0                                  # nodes without neighbours
    nbr[np.arange(cap)[None] >= deg[:, None]] = n
    nbr[n] = n
    cum = np.cumsum((nbr != n).astype(np.float32), axis=1)
    feat = rng.standard_normal((n + 1, d)).astype(np.float32)
    feat[n] = 0
    cls = rng.integers(0, classes, n + 1).astype(np.int32)
    return nbr, cum, feat, cls


def _stored(nbr, cum):
    """The batch's sampling tables in the form the program reads."""
    return {"nbr_table": jnp.asarray(store_rows(nbr, "nbr")),
            "cum_table": jnp.asarray(store_rows(cum, "cum"))}


def test_the_model_matches_the_reference_loss_with_pad_slots():
    """DeviceSampledGraphSage(encoder='gat') through its own draw and
    gather, roots without neighbours among them, against gat3.loss on
    the same tables (features float32 behind a unit int8 scale)."""
    n, classes, fanouts = 300, 5, (3, 2, 2)
    nbr, cum, feat, cls = _tables(n)
    q = np.clip(np.rint(feat * 20), -127, 127).astype(np.int8)
    scale = np.full((feat.shape[1],), 0.05, np.float32)
    model = DeviceSampledGraphSage(
        encoder="gat", heads=2, dim=4, fanouts=fanouts,
        num_classes=classes, multilabel=False, uniform_sampling=True)
    roots = jnp.arange(0, 32, dtype=jnp.int32)       # 0..7 have no slots
    batch = {"rows": [roots], "sample_seed": jnp.uint32(9),
             **_stored(nbr, cum),
             "feature_table": jnp.asarray(q),
             "feature_scale": jnp.asarray(scale),
             "label_table": jnp.asarray(np.eye(classes,
                                               dtype=np.float32)[cls])}
    cfg = {"feature_dim": feat.shape[1], "num_classes": classes,
           "model": {"kwargs": {"dim": 4, "heads": 2,
                                "fanouts": list(fanouts)}}}
    flat = _seeded(gat3.param_shapes(cfg))
    nested = unflatten({k: jnp.asarray(v) for k, v in flat.items()})
    init = jax.eval_shape(model.init, jax.random.key(0), batch)["params"]
    assert jax.tree_util.tree_map(jnp.shape, init) \
        == jax.tree_util.tree_map(jnp.shape, nested)
    tabs = {"nbr": jnp.asarray(nbr), "cum": jnp.asarray(cum[:1]),
            "q": jnp.asarray(q), "scale": jnp.asarray(scale),
            "cls": jnp.asarray(cls)}

    def ref_loss(p):
        return gat3.loss(p, {}, tabs, roots, jnp.uint32(9), cfg, True,
                         jnp.float32)[0]

    def prog_loss(p):
        out = model.apply({"params": p}, batch)
        return out.loss, out.embedding

    flat_j = {k: jnp.asarray(v) for k, v in flat.items()}
    (loss, emb), g_prog = jax.jit(jax.value_and_grad(
        prog_loss, has_aux=True))(nested)
    want, g_ref = jax.jit(jax.value_and_grad(ref_loss))(flat_j)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    assert emb.shape == (32, classes)            # the logits themselves
    for path, g in g_ref.items():
        np.testing.assert_allclose(_leaf(g_prog, path), g, rtol=5e-4,
                                   atol=1e-7, err_msg=path)


def test_the_logits_hook_leaves_the_mean_models_parameters_as_they_were():
    """SuperviseModel.logits defaults to the `out` layer: the tree of
    DeviceSampledGraphSage(encoder='sage') has the paths, shapes and
    init values of a model whose __call__ builds `out` inline, as the
    base class did before the hook."""
    import flax.linen as nn

    class Inline(DeviceSampledGraphSage):
        def logits(self, emb):
            return nn.Dense(self.num_classes, name="out")(emb)

    nbr, cum, feat, cls = _tables()
    batch = {"rows": [jnp.arange(16, dtype=jnp.int32)],
             "sample_seed": jnp.uint32(1),
             **_stored(nbr, cum),
             "feature_table": jnp.asarray(feat),
             "label_table": jnp.asarray(np.eye(5, dtype=np.float32)[cls])}
    kw = dict(dim=8, fanouts=(3, 2), num_classes=5, multilabel=False)
    hooked = jax.jit(DeviceSampledGraphSage(**kw).init)(
        jax.random.key(0), batch)
    inline = jax.jit(Inline(**kw).init)(jax.random.key(0), batch)
    paths = {"/".join(k.key for k in path): v.shape for path, v in
             jax.tree_util.tree_leaves_with_path(hooked["params"])}
    from benchmark.reference import sage3

    assert paths == sage3.param_shapes(
        {"feature_dim": feat.shape[1], "num_classes": 5,
         "model": {"kwargs": {"dim": 8, "fanouts": [3, 2]}}})
    for a, b in zip(jax.tree_util.tree_leaves(hooked),
                    jax.tree_util.tree_leaves(inline)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    gat = jax.eval_shape(
        DeviceSampledGraphSage(encoder="gat", heads=2, **kw).init,
        jax.random.key(0), batch)
    assert "out" not in gat["params"]


def test_one_count_a_layer_a_trace_and_the_encoders_named():
    counter = obs.counter("traced_paths_total", "", ("path", "detail"))

    def count():
        return {k: counter.labels(path="gat_attention", detail=k).value
                for k in ("layer0", "layer1")}

    enc = GATEncoder(4, (3, 2), heads=2, out_dim=5)
    layers = [jnp.ones((2, 6)), jnp.ones((6, 6)), jnp.ones((12, 6))]
    before = count()
    params = jax.jit(enc.init)(jax.random.key(0), layers)
    fn = jax.jit(lambda p, xs: enc.apply(p, xs))
    fn(params, layers)
    fn(params, layers)                  # cached: no new trace, no count
    after = count()
    assert {k: after[k] - before[k] for k in after} \
        == {"layer0": 2, "layer1": 2}    # init's trace and the jit's
    with pytest.raises(ValueError, match="'genie', 'gat' or 'unimp'"):
        nbr, cum, feat, cls = _tables()
        jax.eval_shape(
            DeviceSampledGraphSage(encoder="gta", fanouts=(2,)).init,
            jax.random.key(0),
            {"rows": [jnp.arange(4, dtype=jnp.int32)],
             "sample_seed": jnp.uint32(1),
             **_stored(nbr, cum),
             "feature_table": jnp.asarray(feat),
             "label_table": jnp.asarray(cls)})
