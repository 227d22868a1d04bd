"""The GAT pair's attention with its backward as one Pallas kernel
(euler_tpu/ops/gat_pair.py), run in the TPU interpreter: its value and
every gradient against the XLA path (utils/encoders._attend under
autodiff) to float32 rounding and against a float64 numpy loop, and
where `GATLayer` takes it."""

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from euler_tpu import obs
from euler_tpu.ops import gat_pair
from euler_tpu.utils import aggregators
from euler_tpu.utils.encoders import GATLayer, _attend, _block_diagonal

HEADS = 2
ROWS = 256              # two blocks of 128 targets
NAMES = ("z_t", "z_s", "t_src", "t_dst", "att_src")


def _case(slots, width, concat=True, seed=0):
    """Inputs of one pair, the output's weights, and a mask whose target
    1 has pads only and target 2 none."""
    rng = np.random.default_rng(seed)
    lanes = HEADS * width
    ins = (rng.standard_normal((ROWS, lanes)),
           rng.standard_normal((slots, ROWS, lanes)),
           rng.standard_normal((HEADS, ROWS)),
           rng.standard_normal((HEADS, ROWS)),
           rng.standard_normal((width, HEADS)) * 0.1)
    mask = rng.random((slots, ROWS)) < 0.7
    mask[:, 1] = False
    mask[:, 2] = True
    w = rng.standard_normal((ROWS, lanes if concat else width))
    return [np.asarray(x, np.float32) for x in ins], mask, w.astype(
        np.float32)


def _xla(z_t, z_s, t_src, t_dst, att_src, mask, concat):
    """The pair as GATLayer's XLA path writes it."""
    slots, rows, lanes = z_s.shape
    src = jnp.einsum("fg,nf->gn", _block_diagonal(att_src),
                     z_s.reshape(slots * rows, lanes))
    e_self = jax.nn.leaky_relu(t_src + t_dst, 0.2)
    e_nbr = jax.nn.leaky_relu(src.reshape(HEADS, slots, rows)
                              + t_dst[:, None], 0.2)
    return _attend(z_t, z_s, e_self, e_nbr, mask, concat)


def _value_and_grads(pair, ins, mask, w, concat):
    """-> (the pair's output, the gradients of sum(output * w))."""
    def run(*xs):
        y, pull = jax.vjp(lambda *xs: pair(*xs, jnp.asarray(mask), concat),
                          *xs)
        return y, pull(jnp.asarray(w))

    return jax.jit(run)(*map(jnp.asarray, ins))


def _numpy64(ins, mask, w, concat):
    """The pair and its gradients in float64, target by target, the
    softmax's backward through its Jacobian diag(a) - a a^T."""
    z_t, z_s, t_src, t_dst, att = (x.astype(np.float64) for x in ins)
    slots, rows, lanes = z_s.shape
    c = lanes // HEADS
    leaky = lambda x: np.where(x >= 0, x, 0.2 * x)           # noqa: E731
    dleaky = lambda x: np.where(x >= 0, 1.0, 0.2)            # noqa: E731
    y = np.zeros((rows, lanes))
    grads = [np.zeros_like(x) for x in (z_t, z_s, t_src, t_dst, att)]
    for h in range(HEADS):
        lane = slice(h * c, (h + 1) * c)
        pre = np.concatenate([(t_src[h] + t_dst[h])[:, None],
                              (z_s[:, :, lane] @ att[:, h]).T
                              + t_dst[h][:, None]], axis=1)   # [M, 1 + k]
        live = np.concatenate([np.ones((rows, 1), bool), mask.T], axis=1)
        e = np.where(live, leaky(pre), -np.inf)
        p = np.exp(e - e.max(axis=1, keepdims=True))
        a = p / p.sum(axis=1, keepdims=True)
        rows_in = np.concatenate([z_t[None, :, lane], z_s[:, :, lane]])
        y[:, lane] = np.einsum("mj,jmc->mc", a, rows_in)
        g = w[:, lane] if concat else w / HEADS
        da = np.einsum("mc,jmc->mj", g, rows_in)
        jac = a[:, :, None] * np.eye(1 + slots) - a[:, :, None] * a[:, None]
        dpre = np.einsum("mij,mj->mi", jac, da) * dleaky(pre)
        grads[0][:, lane] = a[:, :1] * g
        grads[1][:, :, lane] = (a.T[1:, :, None] * g
                                + dpre.T[1:, :, None] * att[:, h])
        grads[2][h] = dpre[:, 0]
        grads[3][h] = dpre.sum(axis=1)
        grads[4][:, h] = np.einsum("jm,jmc->c", dpre.T[1:], z_s[:, :, lane])
    if not concat:
        y = y.reshape(rows, HEADS, c).mean(axis=1)
    return (y * w).sum(), grads


def _close(got, want, rtol, err_msg=""):
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=rtol,
                               atol=rtol * np.abs(want).max(),
                               err_msg=err_msg)


@pytest.fixture
def blocks_of_128(monkeypatch):
    monkeypatch.setattr(gat_pair, "ROW_BLOCKS", (128,))


@pytest.mark.parametrize("width", [128, 256])
@pytest.mark.parametrize("slots", [10, 15, 25])
def test_the_kernel_is_the_xla_path(blocks_of_128, slots, width):
    """Value and every gradient, over two row blocks with pad slots,
    against the XLA path under autodiff, to float32 rounding; a pad slot
    takes no weight and gets no cotangent, a target of pads only
    attends to itself."""
    ins, mask, w = _case(slots, width)
    assert gat_pair.row_block(slots, ROWS, HEADS * width, HEADS,
                              HEADS * width) == 128
    want = _value_and_grads(_xla, ins, mask, w, True)
    with pltpu.force_tpu_interpret_mode():
        got = _value_and_grads(gat_pair.attend, ins, mask, w, True)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for name, g, gw in zip(NAMES, got[1], want[1]):
        assert np.isfinite(np.asarray(g)).all(), name
        _close(g, gw, 1e-5, name)
    assert not np.asarray(got[1][1])[~mask].any()
    np.testing.assert_array_equal(np.asarray(got[0][1]), ins[0][1])


@pytest.mark.parametrize("concat", [True, False],
                         ids=["heads_concatenated", "heads_averaged"])
def test_the_kernel_is_the_float64_loop(blocks_of_128, concat):
    ins, mask, w = _case(10, 128, concat, seed=3)
    want_loss, want = _numpy64(ins, mask, w, concat)
    with pltpu.force_tpu_interpret_mode():
        y, got = _value_and_grads(gat_pair.attend, ins, mask, w, concat)
    _close((np.asarray(y, np.float64) * w).sum(), want_loss, 1e-5)
    for name, g, gw in zip(NAMES, got, want):
        _close(g, gw, 1e-4, name)


def _layer_case(width, concat, rows=128, k=2):
    """A GATLayer over three hops, neighbour-major: pairs of `rows` and
    `rows * k` targets, k slots each, some of them pads."""
    rng = np.random.default_rng(5)
    d = 16
    hidden = [jnp.asarray(rng.standard_normal((rows * k ** i, d)),
                          jnp.float32) for i in range(3)]
    masks = [None] + [jnp.asarray(rng.random(rows * k ** i) < 0.8)
                      for i in (1, 2)]
    layer = GATLayer(width, HEADS, concat=concat, name="layer0")
    params = jax.eval_shape(layer.init, jax.random.key(0), hidden,
                            masks)["params"]
    # every leaf drawn, so that a bias or vector left out would show
    leaves, tree = jax.tree_util.tree_flatten(params)
    params = jax.tree_util.tree_unflatten(tree, [
        jnp.asarray(rng.standard_normal(x.shape) * 0.2, jnp.float32)
        for x in leaves])
    return layer, params, hidden, masks


@pytest.mark.parametrize("on_tpu, width, concat, taken", [
    (True, 128, True, 2),
    (True, 128, False, 2),
    (False, 128, True, 0),        # off the TPU
    (True, 172, False, 0),        # cell 4's last layer: C = 172
    (True, 8, True, 0),
], ids=["tpu", "tpu_averaged", "cpu", "c172", "c8"])
def test_the_layer_takes_the_kernel_where_it_tiles(monkeypatch, on_tpu,
                                                   width, concat, taken):
    """Counted one a pair it engages in, at trace time; where it does,
    the layer's output and its gradients by parameters and hops are the
    XLA path's to float32 rounding."""
    layer, params, hidden, masks = _layer_case(width, concat)

    def loss(p, hs):
        return sum((y * y).sum() for y in layer.apply({"params": p}, hs,
                                                      masks))

    def step():        # a new function each time: jit's cache keys on it
        return jax.value_and_grad(loss, argnums=(0, 1))

    paths = obs.counter("traced_paths_total", "", ("path", "detail"))
    details = [f"{r}x2x{HEADS * width}" for r in (128, 256)]
    before = [paths.labels(path="gat_attention_kernel", detail=d).value
              for d in details]
    monkeypatch.setattr(aggregators, "_on_tpu", lambda: on_tpu)
    jax.make_jaxpr(step())(params, hidden)
    counts = [paths.labels(path="gat_attention_kernel", detail=d).value - n
              for d, n in zip(details, before)]
    assert sum(counts) == taken and counts[0] == counts[1], counts
    if not taken:
        return
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(step())(params, hidden)
    monkeypatch.setattr(aggregators, "_on_tpu", lambda: False)
    want = jax.jit(step())(params, hidden)
    _close(got[0], want[0], 1e-6)
    for (path, g), gw in zip(
            flax.traverse_util.flatten_dict(got[1][0]).items(),
            flax.traverse_util.flatten_dict(want[1][0]).values()):
        _close(g, gw, 1e-5, "/".join(path))
    for g, gw in zip(got[1][1], want[1][1]):
        _close(g, gw, 1e-5)
