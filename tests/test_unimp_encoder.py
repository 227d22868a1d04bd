"""The UniMP encoder (utils/encoders.TransformerConvLayer / UniMPEncoder /
OffsetLayerNorm, DeviceSampledGraphSage(encoder="unimp") with the
labels in its input) against the benchmark's plain reference
(benchmark/reference/unimp3.py, which imports nothing of euler_tpu) and
against slot-by-slot loops, on seeded weights, float32 on the CPU."""

import sys
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark.cell import unflatten  # noqa: E402
from benchmark.reference import unimp3  # noqa: E402
from euler_tpu import obs  # noqa: E402
from euler_tpu.models import DeviceSampledGraphSage  # noqa: E402
from euler_tpu.models.graphsage import (  # noqa: E402
    FANOUT_ENCODERS, among_roots, label_visible,
)
from euler_tpu.parallel.device_sampler import store_rows  # noqa: E402
from euler_tpu.utils.encoders import (  # noqa: E402
    OffsetLayerNorm, TransformerConvLayer, UniMPEncoder, _NO_SELF, _slot_softmax,
)

# the harness's seeding with the zero leaves filled too, so that a bias
# or a gain the encoder forgot would show
from test_gat_encoder import _leaf, _seeded  # noqa: E402

M, K, D, HEADS, C = 6, 3, 5, 4, 8
BASE = "encoder/enc/layer0"


def _layer_case(last):
    c = 3 if last else C
    # a one-layer configuration's layer 0 is its last; a two-layer one's
    # is hidden (and carries the norm)
    cfg = {"feature_dim": D, "num_classes": c, "model": {"kwargs": {
        "dim": c, "heads": HEADS, "fanouts": [K] if last else [K, K]}}}
    shapes = {p: s for p, s in unimp3.param_shapes(cfg).items()
              if p.startswith(BASE)}
    assert (BASE + "/norm/bias" in shapes) == (not last)
    flat = _seeded(shapes)
    rng = np.random.default_rng(3)
    x_t = rng.standard_normal((M, D)).astype(np.float32)
    x_s = rng.standard_normal((M, K, D)).astype(np.float32)
    valid = rng.random((M, K)) < 0.7
    valid[1] = False                     # every slot of node 1 is a pad
    valid[2] = True
    nested = unflatten({k[len(BASE) + 1:]: jnp.asarray(v)
                        for k, v in flat.items()})
    return flat, nested, x_t, x_s, valid, c


def _by_slot(flat, x_t, x_s, valid, heads, last):
    """The layer's equations one target, one head and one slot at a time,
    in float64 numpy: no layout, no indicator products, no batching."""
    p = {k[len(BASE) + 1:]: np.asarray(v, np.float64) for k, v in flat.items()}
    c = p["query/kernel"].shape[1] // heads
    out = []
    for i in range(x_t.shape[0]):
        q = x_t[i] @ p["query/kernel"] + p["query/bias"]
        msg = []
        for h in range(heads):
            lanes = slice(h * c, (h + 1) * c)
            scores, values = [], []
            for j in range(x_s.shape[1]):
                if not valid[i, j]:
                    continue
                k_j = x_s[i, j] @ p["key/kernel"] + p["key/bias"]
                scores.append(q[lanes] @ k_j[lanes] / np.sqrt(c))
                values.append((x_s[i, j] @ p["value/kernel"]
                               + p["value/bias"])[lanes])
            if not scores:
                msg.append(np.zeros(c))
                continue
            w = np.exp(np.array(scores) - max(scores))
            msg.append((w / w.sum()) @ np.array(values))
        msg = np.mean(msg, axis=0) if last else np.concatenate(msg)
        r = x_t[i] @ p["skip/kernel"] + p["skip/bias"]
        gate = 1 / (1 + np.exp(-(np.concatenate([r, msg, r - msg])
                                 @ p["beta/kernel"][:, 0])))
        o = gate * r + (1 - gate) * msg
        if not last:
            o = (o - o.mean()) / np.sqrt(o.var() + 1e-5) \
                * (1 + p["norm/gain_offset"]) + p["norm/bias"]
            o = np.maximum(o, 0)
        out.append(o)
    return np.array(out)


@pytest.mark.parametrize("last", [False, True],
                         ids=["heads_concatenated", "heads_averaged"])
def test_layer_matches_a_slot_by_slot_loop_and_the_reference(last):
    flat, nested, x_t, x_s, valid, c = _layer_case(last)
    layer = TransformerConvLayer(c, HEADS, concat=not last, name="layer0")
    # the encoder reads its slots neighbour-major: [k, M, D] flattened
    hidden = [jnp.asarray(x_t),
              jnp.asarray(x_s.transpose(1, 0, 2).reshape(K * M, D))]
    masks = [None, jnp.asarray(valid.T.reshape(-1))]

    def prog(p, xt, xs):
        return layer.apply({"params": p}, [xt, xs], masks)[0]

    def ref(p, xt, xs):
        return unimp3.layer(p, BASE, xt, xs, jnp.asarray(valid), HEADS,
                            last, jnp.float32)

    flat_j = {k: jnp.asarray(v) for k, v in flat.items()}
    # the output, and the gradients of a scalar of it by parameters and
    # inputs
    w = jnp.asarray(np.random.default_rng(5).standard_normal(
        (M, c if last else HEADS * c)).astype(np.float32))

    def with_grads(fn):
        def scalar(p, a, b):
            out = fn(p, a, b)
            return (out * w).sum(), out
        return jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, got), g_prog = with_grads(prog)(nested, *hidden)
    (_, want), g_ref = with_grads(ref)(
        flat_j, jnp.asarray(x_t), jnp.asarray(x_s))
    assert got.shape == w.shape
    np.testing.assert_allclose(
        got, _by_slot(flat, x_t, x_s, valid, HEADS, last), rtol=2e-5,
        atol=2e-6)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    for path, g in g_ref[0].items():
        node = _leaf(g_prog[0], path[len(BASE) + 1:])
        assert np.isfinite(np.asarray(node)).all(), path
        np.testing.assert_allclose(node, g, rtol=2e-4, atol=2e-6,
                                   err_msg=path)
        # a key's bias moves every slot's score alike: no gradient
        assert (float(jnp.abs(g).max()) > 1e-6) \
            == (not path.endswith("key/bias")), path
    np.testing.assert_allclose(g_prog[1], g_ref[1], rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(
        g_prog[2].reshape(K, M, D).transpose(1, 0, 2), g_ref[2],
        rtol=2e-4, atol=2e-6)
    # a pad slot takes no weight and gives no gradient
    assert not np.asarray(g_ref[2])[~valid].any()
    assert not np.asarray(g_prog[2].reshape(K, M, D)
                          .transpose(1, 0, 2))[~valid].any()


def test_a_target_whose_slots_are_all_pads_aggregates_zero():
    flat, nested, x_t, x_s, valid, c = _layer_case(True)
    layer = TransformerConvLayer(c, HEADS, concat=False, name="layer0")
    xs = jnp.asarray(x_s.transpose(1, 0, 2).reshape(K * M, D))
    mask = jnp.asarray(valid.T.reshape(-1))
    apply = jax.jit(lambda p, s, m: layer.apply(
        {"params": p}, [jnp.asarray(x_t), s], [None, m])[0])
    got = apply(nested, xs, mask)
    # m = 0: o = beta r, beta = sigmoid(w_b . [r; 0; r])
    r = x_t[1] @ flat[BASE + "/skip/kernel"] + flat[BASE + "/skip/bias"]
    w_b = flat[BASE + "/beta/kernel"][:, 0]
    alone = r / (1 + np.exp(-(r @ w_b[:c] + r @ w_b[2 * c:])))
    np.testing.assert_allclose(got[1], alone, rtol=2e-5, atol=2e-6)
    # whatever its pad slots hold, huge values included, in both passes
    loud = xs.reshape(K, M, D).at[:, 1].set(1e30).reshape(K * M, D)
    again, grads = jax.jit(jax.value_and_grad(
        lambda p, s: apply(p, s, mask)[1].sum(), argnums=(0, 1)))(
        nested, loud)
    np.testing.assert_allclose(again, got[1].sum(), rtol=1e-6)
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))
    # unmasked, the same slots do take weight
    unmasked = apply(nested, xs, jnp.ones_like(mask))
    assert float(jnp.abs(unmasked[1] - got[1]).max()) > 1e-3


def test_the_slot_softmax_serves_both_attentions():
    """With the target's own term (GAT) an all-pad target gives it
    weight 1; with `_NO_SELF` in its place (UniMP) the term weighs 0
    beside any slot and every slot weight of an all-pad target is 0; the
    live targets' weights add up to 1 either way."""
    rng = np.random.default_rng(0)
    e = jnp.asarray(rng.standard_normal((2, K, M)).astype(np.float32))
    own = jnp.asarray(rng.standard_normal((2, M)).astype(np.float32))
    mask = np.ones((K, M), bool)
    mask[:, 1] = False
    mask[0, 2] = False
    a_self, a_nbr = _slot_softmax(e, jnp.asarray(mask), own)
    np.testing.assert_allclose(a_self + a_nbr.sum(axis=1), 1.0, rtol=1e-6)
    assert float(a_self[0, 1]) == 1.0 and not np.asarray(a_nbr)[:, :, 1].any()
    none, alone = _slot_softmax(e, jnp.asarray(mask),
                                jnp.full((2, M), _NO_SELF))
    assert not np.delete(np.asarray(none), 1, axis=1).any()
    total = np.asarray(alone.sum(axis=1))
    np.testing.assert_allclose(np.delete(total, 1, axis=1), 1.0, rtol=1e-6)
    assert not np.asarray(alone)[:, :, 1].any()
    assert not np.asarray(alone)[:, 0, 2].any()
    np.testing.assert_allclose(
        alone[:, :, 0], jax.nn.softmax(e[:, :, 0], axis=1), rtol=1e-6)


def test_the_offset_norm_is_layer_norm_at_init_and_after_an_adam_step():
    x = jnp.asarray(np.random.default_rng(1).standard_normal((9, 16))
                    .astype(np.float32)) * 3 + 1
    w = jnp.asarray(np.random.default_rng(2).standard_normal((9, 16))
                    .astype(np.float32))
    mine, flax_ln = OffsetLayerNorm(), nn.LayerNorm(epsilon=1e-5)
    p_mine = mine.init(jax.random.key(0), x)["params"]
    p_flax = flax_ln.init(jax.random.key(0), x)["params"]
    assert not np.asarray(p_mine["gain_offset"]).any()      # stored at 0
    tx = optax.adam(1e-3)

    def one_step(module, params):
        def scalar(p):
            return (module.apply({"params": p}, x) * w).sum()

        @jax.jit
        def step(params):
            grads = jax.grad(scalar)(params)
            updates, _ = tx.update(grads, tx.init(params), params)
            return grads, optax.apply_updates(params, updates)
        return step(params)

    np.testing.assert_allclose(mine.apply({"params": p_mine}, x),
                               flax_ln.apply({"params": p_flax}, x),
                               rtol=1e-5, atol=1e-6)
    (g_mine, new_mine), (g_flax, new_flax) = \
        one_step(mine, p_mine), one_step(flax_ln, p_flax)
    np.testing.assert_allclose(g_mine["gain_offset"], g_flax["scale"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(g_mine["bias"], g_flax["bias"], rtol=1e-6)
    np.testing.assert_allclose(1.0 + new_mine["gain_offset"],
                               new_flax["scale"], rtol=1e-6)
    np.testing.assert_allclose(mine.apply({"params": new_mine}, x),
                               flax_ln.apply({"params": new_flax}, x),
                               rtol=1e-5, atol=1e-6)


def test_label_visibility_is_a_rate_and_a_function_of_id_and_word():
    ids = jnp.arange(100_000, dtype=jnp.int32)
    words = [jax.random.bits(jax.random.key(s), (), jnp.uint32)
             for s in (1, 2)]
    seen = [np.asarray(jax.jit(label_visible, static_argnums=2)(
        ids, w, 0.625)) for w in words]
    for s in seen:
        assert abs(s.mean() - 0.625) < 0.005, s.mean()
    # two steps show different nodes, about rate**2 + (1 - rate)**2 alike
    assert abs((seen[0] == seen[1]).mean() - 0.53125) < 0.01
    # a node drawn twice shows the same thing, wherever it stands
    twice = np.asarray(label_visible(
        jnp.asarray([7, 99_999, 7, 5, 99_999], jnp.int32), words[0], 0.625))
    assert twice[0] == twice[2] == seen[0][7]
    assert twice[1] == twice[4] == seen[0][99_999]
    assert not np.asarray(label_visible(ids, words[0], 0.0)).any()
    assert np.asarray(label_visible(ids, words[0], 1.0)).all()
    # the reference's own rule, written from the same text
    np.testing.assert_array_equal(
        seen[0], np.asarray(unimp3.shown(ids, jnp.asarray([-1]), words[0],
                                         0.625, pad=-2)))
    roots = jnp.asarray([5, 17, 99_999], jnp.int32)
    hit = np.asarray(among_roots(ids, roots))
    assert hit.sum() == 3 and hit[[5, 17, 99_999]].all()


# -- the model: its own draw and gather, labels in the input ----------------
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
    cls = rng.integers(0, classes, n).astype(np.int32)
    return nbr, cum, feat, cls


def _one_hot(cls, classes):
    """[N + 1, classes] as the store holds labels: the pad row zero."""
    return np.concatenate([np.eye(classes, dtype=np.float32)[cls],
                           np.zeros((1, classes), np.float32)])


def _batch(nbr, cum, q, scale, label, roots, seed=9):
    return {"rows": [jnp.asarray(roots, jnp.int32)],
            "sample_seed": jnp.uint32(seed),
            "nbr_table": jnp.asarray(store_rows(nbr, "nbr")),
            "cum_table": jnp.asarray(store_rows(cum, "cum")),
            "feature_table": jnp.asarray(q),
            "feature_scale": None if scale is None else jnp.asarray(scale),
            "label_table": None if label is None else jnp.asarray(label)}


def test_the_model_matches_the_reference_loss_with_labels_and_pad_slots():
    """DeviceSampledGraphSage(encoder='unimp') through its own draw,
    gather, label rows and masks, roots without neighbours among them,
    against unimp3.loss on the same tables (features float32 behind a
    unit int8 scale, classes as integers)."""
    n, classes, fanouts = 300, 5, (3, 2, 2)
    nbr, cum, feat, cls = _tables(n)
    q = np.clip(np.rint(feat * 20), -127, 127).astype(np.int8)
    scale = np.full((feat.shape[1],), 0.05, np.float32)
    model = DeviceSampledGraphSage(
        encoder="unimp", heads=2, dim=4, label_rate=0.625, fanouts=fanouts,
        num_classes=classes, multilabel=False, uniform_sampling=True)
    roots = jnp.arange(0, 32, dtype=jnp.int32)       # 0..7 have no slots
    batch = _batch(nbr, cum, q, scale, _one_hot(cls, classes), roots)
    cfg = {"feature_dim": feat.shape[1], "num_classes": classes,
           "model": {"kwargs": {"dim": 4, "heads": 2, "label_rate": 0.625,
                                "fanouts": list(fanouts)}}}
    flat = _seeded(unimp3.param_shapes(cfg))
    nested = unflatten({k: jnp.asarray(v) for k, v in flat.items()})
    init = jax.eval_shape(model.init, jax.random.key(0), batch)["params"]
    assert jax.tree_util.tree_map(jnp.shape, init) \
        == jax.tree_util.tree_map(jnp.shape, nested)
    assert "out" not in init           # the last layer maps to the classes
    tabs = {"nbr": jnp.asarray(nbr), "cum": jnp.asarray(cum[:1]),
            "q": jnp.asarray(q), "scale": jnp.asarray(scale),
            "cls": jnp.asarray(cls)}

    def ref_loss(p):
        return unimp3.loss(p, {}, tabs, roots, jnp.uint32(9), cfg, True,
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


def _two_cycles():
    """Four nodes in two 2-cycles (0 <-> 1, 2 <-> 3), one neighbour
    each: with fanouts (1, 1) root 0's hops are node 1 and then node 0
    ITSELF, root 2's are node 3 and node 2 itself."""
    n, cap = 4, 2
    nbr = np.full((n + 1, cap), n, np.int32)
    nbr[np.arange(n), 0] = [1, 0, 3, 2]
    cum = np.cumsum((nbr != n).astype(np.float32), axis=1)
    feat = np.random.default_rng(0).standard_normal((n + 1, 6)) \
        .astype(np.float32)
    feat[n] = 0
    return nbr, cum, feat


def test_a_roots_own_label_never_reaches_its_logits():
    """The leak guard: each root is drawn again as its own 2-hop
    neighbour, and every label is shown (rate 1). Changing the roots'
    label rows leaves the logits bit for bit (the loss reads them, the
    model's input never); changing a neighbour's label moves them."""
    classes = 3
    nbr, cum, feat = _two_cycles()
    model = DeviceSampledGraphSage(
        encoder="unimp", heads=2, dim=4, label_rate=1.0, fanouts=(1, 1),
        num_classes=classes, multilabel=False, uniform_sampling=True)
    roots = np.array([0, 2], np.int32)
    batch = _batch(nbr, cum, feat, None, None, roots)
    del batch["feature_scale"], batch["label_table"]   # float32 features
    apply = jax.jit(lambda p, label: model.apply(
        {"params": p}, {**batch, "label_table": label}).embedding)

    def logits(params, cls):
        return np.asarray(apply(params, jnp.asarray(_one_hot(cls, classes))))

    cls = np.array([0, 1, 2, 0], np.int32)
    cfg = {"feature_dim": feat.shape[1], "num_classes": classes,
           "model": {"kwargs": {"dim": 4, "heads": 2, "fanouts": [1, 1]}}}
    params = unflatten({k: jnp.asarray(v) for k, v in
                        _seeded(unimp3.param_shapes(cfg)).items()})
    base = logits(params, cls)
    for root_classes in ((1, 0), (2, 1), (1, 1)):
        other = cls.copy()
        other[roots] = root_classes
        assert logits(params, other).tobytes() == base.tobytes()
    for neighbour in (1, 3):                     # no roots: shown
        moved = cls.copy()
        moved[neighbour] = (cls[neighbour] + 1) % classes
        assert np.abs(logits(params, moved) - base).max() > 1e-4
    # without the guard the same change does reach the logits: node 0,
    # drawn as its own 2-hop neighbour by a step whose roots it is not
    # among, is a neighbour like any other
    alone = jax.jit(lambda p, label: model.apply(
        {"params": p}, {**batch, "rows": [jnp.asarray([1, 3], jnp.int32)],
                        "label_table": label}).embedding)
    other = cls.copy()
    other[roots] = (1, 0)
    seen = [np.asarray(alone(params, jnp.asarray(_one_hot(c, classes))))
            for c in (cls, other)]
    assert np.abs(seen[1] - seen[0]).max() > 1e-4


def test_one_count_a_layer_and_a_hop_a_trace_and_the_encoders_named():
    paths = obs.counter("traced_paths_total", "", ("path", "detail"))

    def count():
        return ({k: paths.labels(path="unimp_attention", detail=k).value
                 for k in ("layer0", "layer1")},
                {k: paths.labels(path="label_input", detail=k).value
                 for k in ("1", "2")})

    enc = UniMPEncoder(4, (3, 2), heads=2, out_dim=5)
    xs = [jnp.ones((2, 6)), jnp.ones((6, 6)), jnp.ones((12, 6))]
    before = count()
    params = jax.jit(enc.init)(jax.random.key(0), xs)
    fn = jax.jit(lambda p, xs: enc.apply(p, xs))
    fn(params, xs)
    fn(params, xs)                      # cached: no new trace, no count
    after = count()
    assert {k: after[0][k] - before[0][k] for k in after[0]} \
        == {"layer0": 2, "layer1": 2}    # init's trace and the jit's
    assert after[1] == before[1]         # the encoder alone reads no label
    nbr, cum, feat, cls = _tables()
    batch = _batch(nbr, cum, feat, np.ones(12, np.float32),
                   _one_hot(cls, 5), np.arange(4))
    model = DeviceSampledGraphSage(encoder="unimp", heads=2, dim=4,
                                   fanouts=(2, 2), num_classes=5,
                                   multilabel=False)
    jax.eval_shape(model.init, jax.random.key(0), batch)
    assert {k: v - after[1][k] for k, v in count()[1].items()} \
        == {"1": 1, "2": 1}
    with pytest.raises(ValueError, match=r"label_rate .*\[0, 1\]; got 1.5"):
        jax.eval_shape(model.clone(label_rate=1.5).init, jax.random.key(0),
                       batch)
    assert FANOUT_ENCODERS[-2:] == ("gat", "unimp")
    with pytest.raises(ValueError, match="'gat' or 'unimp', got 'unmip'"):
        # refused before the draw is traced: no table is read for it
        jax.eval_shape(
            DeviceSampledGraphSage(encoder="unmip", fanouts=(2,)).init,
            jax.random.key(0), {"rows": [jnp.arange(4, dtype=jnp.int32)],
                                "sample_seed": jnp.uint32(1)})
