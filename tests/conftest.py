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


class NumberedSource:
    """Batches numbered in the order they are pulled (`n` is the batch's
    number, also the model's metric), each made from its number alone.
    `pulled` counts them; `delay_s` slows every pull; `fail_at` raises
    OSError once at that pull; `stop_at` ends the stream there."""

    def __init__(self, delay_s=0.0, fail_at=None, stop_at=None):
        self.pulled = 0
        self.delay_s = delay_s
        self.fail_at = fail_at
        self.stop_at = stop_at

    @staticmethod
    def batch(n):
        r = np.random.default_rng(n)
        return {"x": r.standard_normal((8, 4)).astype(np.float32),
                "y": r.standard_normal((8, 1)).astype(np.float32),
                "n": np.full((1,), n, np.float32)}

    def __call__(self):
        """A stateless stream in the estimator's sense: a recreated
        iterator goes on where the numbering stands."""
        while self.stop_at is None or self.pulled < self.stop_at:
            if self.delay_s:
                import time

                time.sleep(self.delay_s)
            if self.fail_at == self.pulled:
                self.fail_at = None
                raise OSError("planted input failure")
            self.pulled += 1
            yield self.batch(self.pulled - 1)


@pytest.fixture
def slow_step_estimator():
    """make(spin, **params) -> a BaseEstimator over a one-Dense regression
    whose every device step first runs `spin` small matrix products that
    change nothing (~40 us each: on the CPU a window of a tiny model would
    otherwise be done before the host has read anything ahead; a host
    callback that sleeps would make the dispatch synchronous), its state
    made, so every estimator starts from the same parameters."""
    import flax.linen as nn
    import jax.numpy as jnp

    from euler_tpu.estimator import BaseEstimator
    from euler_tpu.mp_utils.base import ModelOutput

    mix = jnp.asarray(np.random.default_rng(0).standard_normal(
        (128, 128)).astype(np.float32) / 12)

    class SlowStep(nn.Module):
        spin: int

        @nn.compact
        def __call__(self, batch):
            x = batch["x"]
            rows, cols = x.shape
            a = jnp.zeros_like(mix).at[:rows, :cols].set(x)
            a = jax.lax.fori_loop(0, self.spin,
                                  lambda _, a: jnp.tanh(a @ mix), a)
            x = x + 0.0 * a[:rows, :cols]
            loss = jnp.mean((nn.Dense(1)(x) - batch["y"]) ** 2)
            return ModelOutput(x, loss, "n", batch["n"][0])

    def make(spin=0, **params):
        est = BaseEstimator(SlowStep(spin), {
            "learning_rate": 0.05, "log_steps": 1 << 30,
            "checkpoint_steps": 0, "input_backoff_s": 0.001, **params})
        est._init_state({k: jnp.asarray(v)
                         for k, v in NumberedSource.batch(0).items()})
        return est

    return make


@pytest.fixture
def numbered_source():
    return NumberedSource
