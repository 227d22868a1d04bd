"""UniMP's pair attention as one Pallas kernel a pass
(euler_tpu/ops/dot_pair.py), run in the TPU interpreter: its value and
its gradients against the XLA path (`TransformerConvLayer`'s
expressions under autodiff, with the one bfloat16 pass XLA's default
precision makes of their two indicator products on the TPU made
explicit, since the CPU makes none) and against a float64 numpy loop
rounded at the same points, and where `TransformerConvLayer` takes it."""

import flax
import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from euler_tpu import obs
from euler_tpu.ops import dot_pair
from euler_tpu.utils import aggregators, encoders

ROWS = 256              # two blocks of 128 targets
NAMES = ("q", "k_s", "v_s")


@jax.custom_vjp
def _one_pass(x):
    """x as one bfloat16 pass of the TPU's matrix unit takes it, and the
    cotangent as the pass of the transposed product takes it."""
    return x.astype(jnp.bfloat16).astype(x.dtype)


_one_pass.defvjp(lambda x: (_one_pass(x), None),
                 lambda _, g: (_one_pass(g),))


def _xla(q, k_s, v_s, mask, heads, rounded=True):
    """The pair as `encoders._dot_attend` writes it; `rounded`, each
    product with the indicator takes its operand (q * k_s, the spread
    weights) and its cotangent (the scores', g * v_s) at bfloat16, as
    on the TPU."""
    r = _one_pass if rounded else (lambda x: x)
    k, m, lanes = k_s.shape
    c = lanes // heads
    head_of = encoders._block_diagonal(jnp.ones((c, heads), jnp.float32))
    e = jnp.einsum("fg,kmf->gkm", head_of, r(q[None] * k_s)) / np.sqrt(c)
    _, alpha = encoders._slot_softmax(
        e, None if mask is None else mask.reshape(k, m),
        jnp.full((heads, m), encoders._NO_SELF, e.dtype))
    return (r(jnp.einsum("gkm,fg->kmf", alpha, head_of)) * v_s).sum(axis=0)


def _case(slots, lanes, seed=0):
    """q, keys, values, the output's weights, and a mask whose target 1
    has pads only and target 2 none; target 3's slots 0 and 1 hold the
    same row (a slot drawn twice)."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((ROWS, lanes))
    k_s, v_s = (rng.standard_normal((slots, ROWS, lanes)) for _ in "kv")
    k_s[1, 3], v_s[1, 3] = k_s[0, 3], v_s[0, 3]
    mask = rng.random((slots, ROWS)) < 0.7
    mask[:, 1] = False
    mask[:, 2] = True
    mask[:2, 3] = True
    w = rng.standard_normal((ROWS, lanes))
    return [np.asarray(x, np.float32) for x in (q, k_s, v_s)], mask, \
        w.astype(np.float32)


def _value_and_grads(pair, ins, mask, w, heads):
    """-> (the pair's output, the gradients of sum(output * w))."""
    def run(*xs):
        y, pull = jax.vjp(lambda *xs: pair(
            *xs, None if mask is None else jnp.asarray(mask), heads), *xs)
        return y, pull(jnp.asarray(w))

    return jax.jit(run)(*map(jnp.asarray, ins))


def _bf(x):
    return x.astype(ml_dtypes.bfloat16).astype(np.float64)


def _numpy64(ins, mask, w, heads):
    """The pair and its gradients in float64, target by target, rounded
    to bfloat16 where the chip's XLA path rounds (q * k before the head
    sum, the weights that take the values, g * v, the scores'
    cotangent); the softmax's backward through its Jacobian."""
    q, k_s, v_s = (x.astype(np.float64) for x in ins)
    slots, rows, lanes = k_s.shape
    c = lanes // heads
    y = np.zeros((rows, lanes))
    dq, dk, dv = np.zeros_like(q), np.zeros_like(k_s), np.zeros_like(v_s)
    for h in range(heads):
        lane = slice(h * c, (h + 1) * c)
        qk = _bf((q[None, :, lane] * k_s[:, :, lane]).astype(np.float32))
        e = np.where(mask.T, qk.sum(axis=2).T / np.sqrt(c), -np.inf)
        top = np.maximum(e.max(axis=1, keepdims=True), encoders._NO_SELF)
        p = np.exp(e - top)
        a = p / (p.sum(axis=1, keepdims=True) + np.exp(encoders._NO_SELF
                                                       - top))   # [M, k]
        ab = _bf(a.astype(np.float32))
        y[:, lane] = np.einsum("mj,jmc->mc", ab, v_s[:, :, lane])
        g = w[:, lane].astype(np.float64)
        da = _bf((g[None] * v_s[:, :, lane]).astype(np.float32)).sum(
            axis=2).T
        jac = a[:, :, None] * np.eye(slots) - a[:, :, None] * a[:, None]
        de = _bf((np.einsum("mij,mj->mi", jac, da) / np.sqrt(c)).astype(
            np.float32))
        dv[:, :, lane] = ab.T[:, :, None] * g
        dk[:, :, lane] = de.T[:, :, None] * q[None, :, lane]
        dq[:, lane] = np.einsum("mj,jmc->mc", de, k_s[:, :, lane])
    return (y * w).sum(), (dq, dk, dv)


def _close(got, want, rtol, err_msg=""):
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=rtol,
                               atol=rtol * np.abs(want).max(),
                               err_msg=err_msg)


@pytest.fixture
def blocks_of_128(monkeypatch):
    monkeypatch.setattr(dot_pair, "ROW_BLOCKS", (128,))


@pytest.mark.parametrize("slots, heads, lanes, masked", [
    (10, 4, 128, True),     # cell 5's pairs: 4 heads of 32
    (3, 2, 256, False),     # a head a lane tile, no pads
    (5, 8, 256, True),      # heads of 32 over two tiles
], ids=["cell5", "c128_unmasked", "c32_wide"])
def test_the_kernels_are_the_xla_path(blocks_of_128, slots, heads, lanes,
                                      masked):
    """Value and gradients, over two row blocks with pad slots, against
    the XLA path under autodiff rounded as on the TPU, to float32
    rounding (on the CPU the value's last bits are not even the path's
    own: its jitted and its eager forms differ by an ulp); a pad slot
    takes no weight and gets no cotangent, a target of pads only
    aggregates zero with finite gradients."""
    ins, mask, w = _case(slots, lanes)
    mask = mask if masked else None
    assert dot_pair.row_block(slots, ROWS, lanes, heads) == 128
    # the reference's copy of the expressions is the layer's fallback
    args = [jnp.asarray(x) for x in ins] + [
        None if mask is None else jnp.asarray(mask)]
    head_of = encoders._block_diagonal(
        jnp.ones((lanes // heads, heads), jnp.float32))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(_xla, static_argnums=(4, 5))(*args, heads, False)),
        np.asarray(jax.jit(encoders._dot_attend)(*args, head_of)))
    want = _value_and_grads(_xla, ins, mask, w, heads)
    with pltpu.force_tpu_interpret_mode():
        got = _value_and_grads(dot_pair.attend, ins, mask, w, heads)
    _close(got[0], want[0], 1e-6)
    for name, g, gw in zip(NAMES, got[1], want[1]):
        assert np.isfinite(np.asarray(g)).all(), name
        _close(g, gw, 1e-5, name)
    if mask is not None:
        for g in got[1][1:]:
            assert not np.asarray(g)[~mask].any()
        assert not np.asarray(got[0][1]).any()
        assert not np.asarray(got[1][0][1]).any()


def test_the_kernels_are_the_float64_loop(blocks_of_128):
    ins, mask, w = _case(4, 128, seed=3)
    want_loss, want = _numpy64(ins, mask, w, 4)
    with pltpu.force_tpu_interpret_mode():
        y, got = _value_and_grads(dot_pair.attend, ins, mask, w, 4)
    _close((np.asarray(y, np.float64) * w).sum(), want_loss, 1e-5)
    for name, g, gw in zip(NAMES, got, want):
        _close(g, gw, 1e-4, name)


@pytest.mark.parametrize("slots, rows, lanes, heads, block", [
    (10, 1024, 128, 4, 512),
    (10, 1280, 128, 4, 256),
    (10, 1024, 688, 4, None),      # H*C no multiple of 128
    (10, 1000, 128, 4, None),      # no block divides the targets
    (200, 1024, 128, 4, None),     # no block fits VMEM
], ids=["fits", "smaller_block", "lanes688", "rows1000", "too_many_slots"])
def test_row_block_tiles_or_leaves_it_to_xla(slots, rows, lanes, heads,
                                             block):
    assert dot_pair.row_block(slots, rows, lanes, heads) == block
    if block is not None:
        assert dot_pair.vmem_bytes(block, slots, lanes) \
            <= dot_pair.VMEM_BUDGET < dot_pair.VMEM_LIMIT


def _layer_case(width, concat, rows=128, k=2):
    """A TransformerConvLayer over three hops, neighbour-major: pairs of
    `rows` and `rows * k` targets, k slots each, some of them pads."""
    rng = np.random.default_rng(5)
    d = 16
    hidden = [jnp.asarray(rng.standard_normal((rows * k ** i, d)),
                          jnp.float32) for i in range(3)]
    masks = [None] + [jnp.asarray(rng.random(rows * k ** i) < 0.8)
                      for i in (1, 2)]
    layer = encoders.TransformerConvLayer(width, 4, concat=concat,
                                          name="layer0")
    params = jax.eval_shape(layer.init, jax.random.key(0), hidden,
                            masks)["params"]
    # every leaf drawn, so that a bias or vector left out would show
    leaves, tree = jax.tree_util.tree_flatten(params)
    params = jax.tree_util.tree_unflatten(tree, [
        jnp.asarray(rng.standard_normal(x.shape) * 0.2, jnp.float32)
        for x in leaves])
    return layer, params, hidden, masks


@pytest.mark.parametrize("on_tpu, width, concat, taken", [
    (True, 32, True, 2),          # cell 5's layers 0-1: 4 heads of 32
    (True, 32, False, 2),
    (False, 32, True, 0),         # off the TPU
    (True, 172, False, 0),        # cell 5's last layer: 688 lanes
], ids=["tpu", "tpu_averaged", "cpu", "lanes688"])
def test_the_layer_takes_the_kernels_where_they_tile(monkeypatch, on_tpu,
                                                     width, concat, taken):
    """Counted one a pair it engages in, at trace time; where it does,
    the layer's output and its gradients by parameters and hops are the
    XLA path's, rounded as on the TPU, to float32 rounding."""
    layer, params, hidden, masks = _layer_case(width, concat)

    def loss(p, hs):
        return sum((y * y).sum() for y in layer.apply({"params": p}, hs,
                                                      masks))

    def step():        # a new function each time: jit's cache keys on it
        return jax.value_and_grad(loss, argnums=(0, 1))

    paths = obs.counter("traced_paths_total", "", ("path", "detail"))
    details = [f"{r}x2x{4 * width}" for r in (128, 256)]
    before = [paths.labels(path="unimp_attention_kernel", detail=d).value
              for d in details]
    monkeypatch.setattr(aggregators, "_on_tpu", lambda: on_tpu)
    jax.make_jaxpr(step())(params, hidden)
    counts = [paths.labels(path="unimp_attention_kernel", detail=d).value
              - n for d, n in zip(details, before)]
    assert sum(counts) == taken and counts[0] == counts[1], counts
    if not taken:
        return
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(step())(params, hidden)
    monkeypatch.setattr(aggregators, "_on_tpu", lambda: False)
    monkeypatch.setattr(encoders, "_dot_attend", lambda q, k_s, v_s, mask,
                        head_of: _xla(q, k_s, v_s, mask, head_of.shape[1]))
    want = jax.jit(step())(params, hidden)
    _close(got[0], want[0], 1e-6)
    for (path, g), gw in zip(
            flax.traverse_util.flatten_dict(got[1][0]).items(),
            flax.traverse_util.flatten_dict(want[1][0]).values()):
        _close(g, gw, 1e-5, "/".join(path))
    for g, gw in zip(got[1][1], want[1][1]):
        _close(g, gw, 1e-5)
