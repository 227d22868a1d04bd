"""Shape + sanity tests for the conv zoo (mirrors reference
convolution/conv_test.py shape tests, SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from euler_tpu import convolution as C

N, E, D_IN, D_OUT = 12, 40, 6, 8


@pytest.fixture(scope="module")
def graph_data():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(N, D_IN)), dtype=jnp.float32)
    src = jnp.asarray(rng.integers(0, N, E), dtype=jnp.int32)
    dst = jnp.asarray(rng.integers(0, N, E), dtype=jnp.int32)
    edge_index = jnp.stack([src, dst])
    return x, edge_index


SIMPLE_LAYERS = [
    C.GCNConv(out_dim=D_OUT),
    C.SAGEConv(out_dim=D_OUT),
    C.SAGEConv(out_dim=D_OUT, normalize=True),
    C.GATConv(out_dim=D_OUT, heads=2, concat=False),
    C.AGNNConv(),
    C.APPNPConv(k_hop=3),
    C.ARMAConv(out_dim=D_OUT, num_stacks=2, num_layers=2),
    C.GINConv(out_dim=D_OUT, train_eps=True),
    C.GraphConv(out_dim=D_OUT, aggr="mean"),
    C.GatedGraphConv(out_dim=D_OUT, num_layers=2),
    C.SGCNConv(out_dim=D_OUT, k_hop=2),
    C.TAGConv(out_dim=D_OUT, k_hop=2),
    C.Conv(out_dim=D_OUT, aggr="max"),
]


# ids that are the same in every run: the type's name and the layer's
# place in SIMPLE_LAYERS
@pytest.mark.parametrize(
    "layer", SIMPLE_LAYERS,
    ids=[f"{type(l).__name__}-{i}" for i, l in enumerate(SIMPLE_LAYERS)])
def test_layer_shapes(graph_data, layer):
    x, edge_index = graph_data
    params = layer.init(jax.random.key(0), x, edge_index)
    out = layer.apply(params, x, edge_index)
    expected_dim = {
        "AGNNConv": D_IN,
        "APPNPConv": D_IN,
    }.get(type(layer).__name__, D_OUT)
    assert out.shape == (N, expected_dim)
    assert jnp.all(jnp.isfinite(out))


def test_gat_concat_heads(graph_data):
    x, edge_index = graph_data
    layer = C.GATConv(out_dim=D_OUT, heads=3, concat=True)
    params = layer.init(jax.random.key(0), x, edge_index)
    out = layer.apply(params, x, edge_index)
    assert out.shape == (N, 3 * D_OUT)


def test_relation_conv(graph_data):
    x, edge_index = graph_data
    etype = jnp.asarray(np.random.default_rng(1).integers(0, 3, E), jnp.int32)
    layer = C.RelationConv(out_dim=D_OUT, num_relations=3)
    params = layer.init(jax.random.key(0), x, edge_index, etype)
    out = layer.apply(params, x, edge_index, etype)
    assert out.shape == (N, D_OUT)


def test_dna_conv(graph_data):
    x, edge_index = graph_data
    hist = jnp.stack([x, x * 2, x * 3], axis=1)  # [N, T=3, D]
    layer = C.DNAConv(out_dim=D_IN, heads=2)
    params = layer.init(jax.random.key(0), hist, edge_index)
    out = layer.apply(params, hist, edge_index)
    assert out.shape == (N, D_IN)


def test_bipartite_block(graph_data):
    """Sampled-fanout block: distinct src/tgt node sets."""
    x, _ = graph_data
    n_tgt = 5
    rng = np.random.default_rng(2)
    src = jnp.asarray(rng.integers(0, N, E), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n_tgt, E), jnp.int32)
    ei = jnp.stack([src, dst])
    x_tgt = x[:n_tgt]
    for layer in [C.SAGEConv(out_dim=D_OUT), C.GCNConv(out_dim=D_OUT),
                  C.GINConv(out_dim=D_OUT), C.GATConv(out_dim=D_OUT)]:
        params = layer.init(jax.random.key(0), (x, x_tgt), ei, n_tgt)
        out = layer.apply(params, (x, x_tgt), ei, n_tgt)
        assert out.shape[0] == n_tgt


def test_gcn_trains(graph_data):
    """One gradient step decreases a toy loss (autodiff through segment ops)."""
    import optax

    x, edge_index = graph_data
    layer = C.GCNConv(out_dim=2)
    params = layer.init(jax.random.key(0), x, edge_index)
    target = jnp.ones((N, 2))

    def loss_fn(p):
        return jnp.mean((layer.apply(p, x, edge_index) - target) ** 2)

    opt = optax.adam(0.05)
    state = opt.init(params)
    l0 = loss_fn(params)
    for _ in range(10):
        g = jax.grad(loss_fn)(params)
        updates, state = opt.update(g, state)
        params = optax.apply_updates(params, updates)
    assert loss_fn(params) < l0


def test_jit_compatible(graph_data):
    x, edge_index = graph_data
    layer = C.SAGEConv(out_dim=D_OUT)
    params = layer.init(jax.random.key(0), x, edge_index)
    f = jax.jit(lambda p, xx, ei: layer.apply(p, xx, ei))
    out = f(params, x, edge_index)
    assert out.shape == (N, D_OUT)
