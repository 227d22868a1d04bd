"""Encoders + models: shape/sanity on tiny dims (kept small: every init
is an XLA compile on 1 CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from euler_tpu.utils import encoders as E

B, K1, K2, D = 4, 3, 2, 6
FANOUTS = (K1, K2)


@pytest.fixture(scope="module")
def fanout_layers():
    rng = np.random.default_rng(0)
    sizes = [B, B * K1, B * K1 * K2]
    return [jnp.asarray(rng.normal(size=(s, D)), jnp.float32) for s in sizes]


def test_sage_encoder(fanout_layers):
    enc = E.SageEncoder(dim=8, fanouts=FANOUTS)
    params = enc.init(jax.random.key(0), fanout_layers)
    out = enc.apply(params, fanout_layers)
    assert out.shape == (B, 16)  # concat=True → 2*dim


def test_gcn_encoder(fanout_layers):
    enc = E.GCNEncoder(dim=8, fanouts=FANOUTS)
    params = enc.init(jax.random.key(0), fanout_layers)
    assert enc.apply(params, fanout_layers).shape == (B, 8)


def test_genie_encoder(fanout_layers):
    enc = E.GenieEncoder(dim=8, fanouts=FANOUTS)
    params = enc.init(jax.random.key(0), fanout_layers)
    assert enc.apply(params, fanout_layers).shape == (B, 8)


def test_shallow_encoder():
    enc = E.ShallowEncoder(dim=8, max_id=50, use_feature=True)
    ids = jnp.array([1, 2, 3])
    feats = jnp.ones((3, 5))
    params = enc.init(jax.random.key(0), ids, feats)
    assert enc.apply(params, ids, feats).shape == (3, 16)


def test_scalable_sage_cache_updates():
    enc = E.ScalableSageEncoder(dim=8, num_layers=2, max_id=20)
    ids = jnp.array([1, 2, 3])
    x = jnp.ones((3, 8))
    nbr_ids = jnp.array([[4, 5], [6, 7], [8, 9]])
    nbr_x = jnp.ones((3, 2, 8))
    variables = enc.init(jax.random.key(0), ids, x, nbr_ids, nbr_x)
    out, updated = enc.apply(variables, ids, x, nbr_ids, nbr_x,
                             mutable=["cache"])
    assert out.shape == (3, 8)
    cache = jax.tree_util.tree_leaves(updated["cache"])[0]
    assert float(jnp.abs(cache[1:4]).sum()) > 0  # batch rows were written


def test_layer_encoder():
    m = [4, 6, 8]
    layers = [jnp.ones((mi, D)) for mi in m]
    adjs = [jnp.ones((m[i], m[i + 1])) / m[i + 1] for i in range(2)]
    enc = E.LayerEncoder(dim=8)
    params = enc.init(jax.random.key(0), layers, adjs)
    assert enc.apply(params, layers, adjs).shape == (4, 8)


def test_kg_models_train():
    import optax

    from euler_tpu.models import DistMult, TransD, TransE

    rng = np.random.default_rng(0)
    batch = {
        "h": jnp.asarray(rng.integers(0, 20, 8), jnp.int32),
        "r": jnp.asarray(rng.integers(0, 4, 8), jnp.int32),
        "t": jnp.asarray(rng.integers(0, 20, 8), jnp.int32),
        "neg_t": jnp.asarray(rng.integers(0, 20, (8, 5)), jnp.int32),
    }
    for cls in (TransE, TransD, DistMult):
        model = cls(num_entities=20, num_relations=4, dim=8)
        params = model.init(jax.random.key(0), batch)
        out = model.apply(params, batch)
        assert out.loss.shape == ()
        assert 0.0 <= float(out.metric) <= 1.0


def test_deepwalk_model():
    from euler_tpu.models import DeepWalk

    batch = {
        "src": jnp.array([1, 2], jnp.int32),
        "pos": jnp.array([3, 4], jnp.int32),
        "negs": jnp.array([[5, 6], [7, 8]], jnp.int32),
    }
    model = DeepWalk(max_id=10, dim=8)
    params = model.init(jax.random.key(0), batch)
    out = model.apply(params, batch)
    assert out.embedding.shape == (2, 8)


# --- the activation cache's fused write-then-read (PR 25) -----------------

N_ROWS, CB, CK, CD = 4096, 8, 3, 16
ENCODERS = {"sage": E.ScalableSageEncoder, "gcn": E.ScalableGCNEncoder}


def _cache_batch():
    """8 roots, 3 neighbours each; five of the 24 neighbours ARE roots of
    the step (planted), one of them read twice."""
    rng = np.random.default_rng(7)
    ids = jnp.asarray(rng.choice(np.arange(1, N_ROWS - 1), CB, replace=False),
                      jnp.int32)
    nbr = rng.integers(1, N_ROWS - 1, (CB, CK))
    for (i, j), root in {(0, 0): 3, (1, 2): 3, (2, 1): 0, (5, 0): 6,
                         (7, 2): 7}.items():
        nbr[i, j] = int(ids[root])
    x = jnp.asarray(rng.normal(size=(CB, CD)), jnp.float32)
    nbr_x = jnp.asarray(rng.normal(size=(CB, CK, CD)), jnp.float32)
    return ids, x, jnp.asarray(nbr, jnp.int32), nbr_x


def _old_caches(num_layers, dtype):
    """Non-zero old rows (every third row stays never-written: zeros)."""
    rng = np.random.default_rng(11)
    out = {}
    for layer in range(1, num_layers):
        h = rng.normal(size=(N_ROWS, CD)) * (np.arange(N_ROWS) % 3 > 0)[:, None]
        out[f"cache_{layer}"] = {"h": jnp.asarray(h, dtype)}
    return out


def _plain_write_then_read(kind, params, caches, ids, x, nbr_ids, nbr_x,
                           num_layers, decay, cut=False):
    """The encoders' mathematics with jnp alone, the cache written by
    .at[].set and read by take (as benchmark/reference/scalablesage.py has
    it). cut=True stops the gradient at the read: a different model."""
    b, k = nbr_ids.shape
    caches = {name: c["h"] for name, c in caches.items()}
    h, nbr_h = x, nbr_x
    for layer in range(num_layers):
        w = params[f"w_{layer}"]
        if kind == "sage":
            h = jnp.concatenate([h, nbr_h.mean(axis=1)], -1) @ w["kernel"] \
                + w["bias"]
        else:
            h = jnp.concatenate([h[:, None], nbr_h], 1).mean(axis=1) \
                @ w["kernel"]
        if layer < num_layers - 1:
            h = jax.nn.relu(h)
            name = f"cache_{layer + 1}"
            old = jnp.take(caches[name], ids, axis=0).astype(jnp.float32)
            seen = jnp.any(old != 0, axis=-1, keepdims=True)
            upd = jnp.where(seen, decay * old + (1 - decay) * h, h)
            caches[name] = caches[name].at[ids].set(
                upd.astype(caches[name].dtype))
            nbr_h = jnp.take(caches[name], nbr_ids.ravel(), axis=0)
            if cut:
                nbr_h = jax.lax.stop_gradient(nbr_h)
            nbr_h = nbr_h.astype(jnp.float32).reshape(b, k, -1)
    return h, {name: {"h": c} for name, c in caches.items()}


def _cache_losses(kind, dtype, num_layers):
    ids, x, nbr_ids, nbr_x = _cache_batch()
    enc = ENCODERS[kind](dim=CD, num_layers=num_layers, max_id=N_ROWS - 1,
                         cache_dtype=dtype)
    params = enc.init(jax.random.key(0), ids, x, nbr_ids, nbr_x)["params"]
    caches = _old_caches(num_layers, dtype)
    target = jnp.asarray(np.random.default_rng(3).normal(size=(CB, CD)),
                         jnp.float32)

    def fused(p, c):
        out, new = enc.apply({"params": p, "cache": c}, ids, x, nbr_ids,
                             nbr_x, mutable=["cache"])
        return ((out - target) ** 2).sum(), (out, new["cache"])

    def plain(p, c, cut=False):
        out, new = _plain_write_then_read(kind, p, c, ids, x, nbr_ids, nbr_x,
                                          num_layers, enc.store_decay, cut)
        return ((out - target) ** 2).sum(), (out, new)

    return fused, plain, params, caches


def _flat(tree):
    return jnp.concatenate([a.ravel()
                            for a in jax.tree_util.tree_leaves(tree)])


@pytest.mark.parametrize("num_layers", [2, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["sage", "gcn"])
def test_act_cache_gradient_is_the_plain_write_then_read(kind, dtype,
                                                         num_layers):
    fused, plain, params, caches = _cache_losses(kind, dtype, num_layers)
    g, (out, new) = jax.grad(fused, has_aux=True)(params, caches)
    g_ref, (out_ref, new_ref) = jax.grad(plain, has_aux=True)(params, caches)
    g_cut, _ = jax.grad(lambda p, c: plain(p, c, cut=True),
                        has_aux=True)(params, caches)
    np.testing.assert_allclose(out, out_ref, rtol=1e-6, atol=1e-6)
    for name in caches:   # the stored rows, rounding and all
        np.testing.assert_array_equal(
            np.asarray(new[name]["h"], np.float32),
            np.asarray(new_ref[name]["h"], np.float32))
    through_the_write = float(jnp.linalg.norm(_flat(g_ref) - _flat(g_cut)))
    assert through_the_write > 0.1    # the planted neighbours carry gradient
    # float32: the same sums, at most in another order. bfloat16: the
    # read's cotangent is rounded to the stored rows' dtype before it is
    # summed, here and in the plain pair alike: only that term may differ
    tol = 1e-6 if dtype == jnp.float32 else 2 ** -7 * through_the_write
    for a, r in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, r, rtol=1e-5, atol=tol)
    # and a read wrapped in stop_gradient fails this test
    assert float(jnp.abs(_flat(g) - _flat(g_cut)).max()) > 0.05


@pytest.mark.parametrize("wrap", [0, N_ROWS], ids=["plain", "modulo"])
def test_act_cache_duplicate_ids_one_winner(wrap):
    """Rows 5 and 9 are written twice (the second 5 and the 9s arrive as
    id + N_ROWS where wrap is set: bucketize_ids folds them). The stored
    row is one of the duplicates' values, the same one call after call,
    and that write alone takes the cotangent of the reads of its row."""
    cache_mod = E._ScalableCache(N_ROWS - 1, CD, decay=0.0)
    ids = jnp.asarray([5, 7, 5 + wrap, 9 + wrap, 9, 11], jnp.int32)
    nbr = jnp.asarray([5, 9 + wrap, 5 + wrap, 7, 100, 9], jnp.int32)[:, None]
    fresh = jnp.asarray(
        np.random.default_rng(5).normal(size=(6, CD)), jnp.float32)
    weight = jnp.arange(1.0, 7.0)[:, None, None]
    zeros = {"cache": {"h": jnp.zeros((N_ROWS, CD), jnp.float32)}}

    def read(fresh):
        out, new = cache_mod.apply(zeros, ids, fresh, nbr, mutable=["cache"])
        return (out * weight).sum(), (out, new["cache"]["h"])

    run = jax.jit(jax.grad(read, has_aux=True))
    g, (out, new) = run(fresh)
    g2, (out2, new2) = run(fresh)
    np.testing.assert_array_equal(new, new2)
    np.testing.assert_array_equal(g, g2)
    for row, writers, read_weight in ((5, (0, 2), 1.0 + 3.0),
                                      (9, (3, 4), 2.0 + 6.0)):
        winner = [w for w in writers
                  if np.array_equal(new[row], fresh[w])]
        assert len(winner) == 1, (row, winner)
        loser = [w for w in writers if w != winner[0]][0]
        np.testing.assert_allclose(g[winner[0]], read_weight)
        np.testing.assert_array_equal(g[loser], 0.0)
    np.testing.assert_array_equal(out[0, 0], new[5])   # reads see the winner
    np.testing.assert_array_equal(out[4, 0], 0.0)      # row 100: not written
    np.testing.assert_allclose(g[1], 4.0)              # row 7, read once
    np.testing.assert_array_equal(g[5], 0.0)           # row 11, never read


def _table_shaped(jaxpr, shape, found):
    """Every (primitive, dtype) whose result has the table's shape, in
    the jaxpr and in every jaxpr nested in its equations."""
    for eqn in jaxpr.eqns:
        for out in eqn.outvars:
            if getattr(out.aval, "shape", None) == shape:
                found.append((eqn.primitive.name, str(out.aval.dtype)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _table_shaped(sub, shape, found)
    return found


@pytest.mark.parametrize("kind", ["sage", "gcn"])
def test_act_cache_gradient_builds_nothing_table_sized(kind):
    """What keeps the table-sized work from coming back where there is no
    chip: differentiating the write-then-read makes ONE value of the
    cache's shape a cache layer (the write), none of them a mask, an id
    table or a scattered cotangent (the parent made 15)."""
    from euler_tpu import obs

    name = ENCODERS[kind].__name__
    counter = obs.counter("act_cache_fused_traces_total",
                          labelnames=("encoder",)).labels(encoder=name)
    for num_layers in (2, 3):
        fused, _, params, caches = _cache_losses(kind, jnp.bfloat16,
                                                 num_layers)
        before = counter.value
        jaxpr = jax.make_jaxpr(jax.grad(fused, has_aux=True))(params, caches)
        # one trace of the program: one count a cache layer
        assert counter.value == before + num_layers - 1
        found = _table_shaped(jaxpr.jaxpr, (N_ROWS, CD), [])
        assert len(found) <= 2 * (num_layers - 1), found
        assert ("scatter", "bfloat16") in found
        for primitive, dtype in found:
            assert primitive not in ("select_n", "eq", "scatter-add"), found
            assert dtype != "uint32", found


@pytest.mark.parametrize("hit_share", [0.0, 0.05, 1.0],
                         ids=["no_hit", "few_hits", "all_hit"])
def test_act_cache_backward_sums_every_chunk(monkeypatch, hit_share):
    """_sum_by_write over several loop turns (chunk 16, 200 reads: the
    last chunk is ragged) is the plain segment sum, whatever share of
    the reads saw a write."""
    monkeypatch.setattr(E, "_GRAD_CHUNK", 16)
    rng = np.random.default_rng(13)
    n_writes, n_reads = 32, 200
    src = np.where(rng.random(n_reads) < hit_share,
                   rng.integers(0, n_writes, n_reads), n_writes)
    g = jnp.asarray(rng.normal(size=(n_reads, CD)), jnp.float32)
    src = jnp.asarray(src, jnp.int32)
    got = jax.jit(E._sum_by_write, static_argnums=2)(g, src, n_writes)
    want = jax.ops.segment_sum(g, src, num_segments=n_writes)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
