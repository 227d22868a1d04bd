"""Test configuration: force an 8-device virtual CPU mesh.

Every test runs on the CPU backend (backend selection happens at the
first device query, which hasn't run yet at conftest import). What the
chip's compiler accepts is checked by tests/test_chip_compile.py against
a described topology; what runs on the chip is chip_smoke.py.
"""

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def ring_graph():
    """10-node, 2-type ring graph with dense + sparse features (the canned
    in-proc test graph — role of the reference's mock_api.cc EulerGraph)."""
    from euler_tpu.graph import GraphBuilder, seed

    seed(1234)
    b = GraphBuilder()
    b.set_num_types(2, 2)
    b.set_feature(0, 0, 4, "f_dense")
    b.set_feature(1, 1, 0, "f_sparse")
    b.set_feature(0, 0, 2, "e_dense", edge=True)
    ids = np.arange(1, 11, dtype=np.uint64)
    b.add_nodes(ids, types=np.array([0, 1] * 5), weights=np.arange(1, 11, dtype=np.float32))
    src = np.concatenate([ids, ids])
    dst = np.concatenate([np.roll(ids, -1), np.roll(ids, -2)])
    et = np.array([0] * 10 + [1] * 10)
    w = np.arange(1, 21, dtype=np.float32)
    b.add_edges(src, dst, types=et, weights=w)
    b.set_node_dense(ids, 0, np.arange(40, dtype=np.float32).reshape(10, 4))
    b.set_node_sparse(ids, 1, np.arange(11, dtype=np.uint64) * 2,
                      np.arange(20, dtype=np.uint64))
    b.set_edge_dense(src, dst, et, 0,
                     np.stack([w, -w], axis=1).astype(np.float32))
    return b.finalize()
