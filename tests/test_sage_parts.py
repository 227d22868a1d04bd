"""The deepest hop a depth of `SageEncoder` writes stays in its lane parts
(PR 33): it is only ever the next depth's `nbr`, and
mean_k(concat(a, b)) = concat(mean_k a, mean_k b), sum for sum and lane
for lane. `SageEncoder` against an in-test encoder that concatenates
every hop, as the encoder did before: value, every parameter's gradient
and every input's gradient EQUAL BIT FOR BIT, one parameter tree.
Float32 on the CPU, small shapes."""

import sys
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from euler_tpu import obs  # noqa: E402
from euler_tpu.utils import aggregators as A  # noqa: E402
from euler_tpu.utils import encoders as E  # noqa: E402
from test_neighbor_major import FANOUTS, _filled, graph  # noqa: E402,F401

DIM = 8


class ConcatEveryHop(nn.Module):
    """SageEncoder as it was: every hop of every depth a whole array."""

    fanouts: tuple
    aggregator: str
    concat: bool
    neighbor_major: bool

    @nn.compact
    def __call__(self, layers):
        hidden = list(layers)
        for depth in range(len(self.fanouts)):
            agg = A.get_aggregator(self.aggregator)(
                dim=DIM, concat=self.concat, name=f"agg_{depth}")
            hidden = [
                agg(x, *E._hop_neighbors(child, x, self.neighbor_major))
                for x, child in zip(hidden[:-1], hidden[1:])]
        return hidden[0]


def _parts(aggregator):
    """The trace-time count of depths reduced part by part."""
    return obs.counter("traced_paths_total", "", ("path", "detail")).labels(
        path="sage_hop_parts", detail=aggregator).value


def _value_and_grads(enc, params, layers):
    """One traced program: a weighted sum of the encoder's output, its
    gradient in every parameter and in every hop's features."""
    w = jnp.asarray(np.random.default_rng(3).standard_normal(
        (layers[0].shape[0], 2 * DIM)).astype(np.float32))

    def loss(p, xs):
        out = enc.apply(p, xs)            # [B, DIM] or [B, 2 * DIM]
        return (out * w[:, :out.shape[1]]).sum()

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, layers)


def _assert_same_bits(got, want):
    flat = jax.tree_util.tree_leaves_with_path(want)
    assert len(flat) == len(jax.tree_util.tree_leaves(got))
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(
            np.asarray(b), np.asarray(a),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("neighbor_major", [False, True],
                         ids=["target_major", "neighbor_major"])
@pytest.mark.parametrize("concat", [True, False], ids=["concat", "sum"])
@pytest.mark.parametrize("aggregator", ["mean", "meanpool", "maxpool", "gcn"])
def test_parts_are_the_concat_bit_for_bit(graph, aggregator, concat,
                                          neighbor_major):
    """Fanouts 15,10,5 with pad rows in every hop: two depths have a
    deepest hop to keep apart."""
    layers = graph["major" if neighbor_major else "target"]
    new = E.SageEncoder(DIM, FANOUTS, aggregator, concat=concat,
                        neighbor_major=neighbor_major)
    old = ConcatEveryHop(FANOUTS, aggregator, concat, neighbor_major)
    shapes = jax.eval_shape(new.init, jax.random.key(1), layers)
    assert shapes == jax.eval_shape(old.init, jax.random.key(1), layers)
    params = _filled(shapes, 2)
    count = _parts(aggregator)
    (got, g_got) = _value_and_grads(new, params, layers)
    # `mean` alone reduces a tuple, where there is one (concat) to reduce
    assert _parts(aggregator) - count == (
        2 if aggregator == "mean" and concat else 0)
    (want, g_want) = _value_and_grads(old, params, layers)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _assert_same_bits(g_got, g_want)
    for g in jax.tree_util.tree_leaves(g_want[0]):
        assert float(jnp.abs(g).max()) > 0


@pytest.mark.parametrize("aggregator", ["mean", "maxpool"])
def test_one_fanout_has_no_hop_to_keep_apart(graph, aggregator):
    layers = graph["major"][:2]
    new = E.SageEncoder(DIM, FANOUTS[:1], aggregator, neighbor_major=True)
    old = ConcatEveryHop(FANOUTS[:1], aggregator, True, True)
    params = _filled(jax.eval_shape(new.init, jax.random.key(1), layers), 2)
    count = _parts(aggregator)
    got = _value_and_grads(new, params, layers)
    assert _parts(aggregator) == count
    _assert_same_bits(got, _value_and_grads(old, params, layers))


def test_the_counter_counts_traced_programs_not_calls(graph):
    enc = E.SageEncoder(DIM, FANOUTS, "mean", neighbor_major=True)
    params = _filled(
        jax.eval_shape(enc.init, jax.random.key(1), graph["major"]), 2)
    fn = jax.jit(enc.apply)
    count = _parts("mean")
    fn(params, graph["major"])
    fn(params, graph["major"])            # cached: no new trace, no count
    assert _parts("mean") == count + 2    # two depths, one program


def test_wider_fanouts_at_evaluation_than_the_parameters_were_made_with():
    """k comes from the shapes, part by part as for a whole array."""
    rng = np.random.default_rng(5)

    def layers(fanouts, roots=3):
        sizes = [roots * int(np.prod(fanouts[:h]))
                 for h in range(len(fanouts) + 1)]
        return [jnp.asarray(rng.standard_normal((n, 12)).astype(np.float32))
                for n in sizes]

    new = E.SageEncoder(DIM, (3, 2, 2))
    old = ConcatEveryHop((3, 2, 2), "mean", True, False)
    params = _filled(
        jax.eval_shape(new.init, jax.random.key(1), layers((3, 2, 2))), 2)
    wide = layers((7, 5, 4))
    _assert_same_bits(_value_and_grads(new, params, wide),
                      _value_and_grads(old, params, wide))


@pytest.mark.parametrize("neighbor_major", [False, True],
                         ids=["target_major", "neighbor_major"])
def test_gcn_encoder_is_unchanged_in_value(graph, neighbor_major):
    """GCNEncoder makes no parts: `mean_with_self` on whole arrays, as
    written out here."""
    layers = graph["major" if neighbor_major else "target"]
    enc = E.GCNEncoder(DIM, FANOUTS, neighbor_major=neighbor_major)
    params = _filled(jax.eval_shape(enc.init, jax.random.key(1), layers), 2)
    count = _parts("gcn")
    got = jax.jit(enc.apply)(params, layers)
    assert _parts("gcn") == count

    @jax.jit
    def want(p, hidden):
        for depth in range(len(FANOUTS)):
            w = p["params"][f"w_{depth}"]["kernel"]
            nxt = []
            for x, child in zip(hidden[:-1], hidden[1:]):
                k = child.shape[0] // x.shape[0]
                nbr = (child.reshape(k, x.shape[0], -1).sum(0)
                       if neighbor_major
                       else child.reshape(x.shape[0], k, -1).sum(1))
                h = ((x + nbr) / (k + 1)) @ w
                nxt.append(h if depth == len(FANOUTS) - 1 else nn.relu(h))
            hidden = nxt
        return hidden[0]

    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want(params, layers)))


@pytest.mark.parametrize("axis", [0, 1])
def test_mean_with_self_sums_a_tuple_part_by_part(axis):
    """The one path GCNAggregator and GCNEncoder share takes parts too,
    lane for lane what it gives the whole array."""
    rng = np.random.default_rng(7)
    shape = (5, 4, 6) if axis == 0 else (4, 5, 6)
    a, b = (jnp.asarray(rng.standard_normal(shape).astype(np.float32))
            for _ in range(2))
    x = jnp.asarray(rng.standard_normal((4, 12)).astype(np.float32))
    count = _parts("gcn")
    got = jax.jit(lambda x, a, b: A.mean_with_self(x, (a, b), axis))(x, a, b)
    assert _parts("gcn") == count + 1
    want = jax.jit(lambda x, a, b: A.mean_with_self(
        x, jnp.concatenate([a, b], -1), axis))(x, a, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_layer_that_is_no_whole_fanout_is_refused_part_by_part():
    parent = jnp.zeros((4, 3))
    with pytest.raises(AssertionError, match="not a whole fanout"):
        E._hop_neighbors((jnp.zeros((10, 2)), jnp.zeros((10, 2))), parent)
    views, axis = E._hop_neighbors(
        (jnp.zeros((12, 2)), jnp.zeros((12, 5))), parent, True)
    assert axis == 0 and [v.shape for v in views] == [(3, 4, 2), (3, 4, 5)]
