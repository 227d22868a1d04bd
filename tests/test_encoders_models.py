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
    counter = obs.counter(
        "traced_paths_total", labelnames=("path", "detail")).labels(
            path="act_cache_fused", detail=name)
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


# --- the non-finite guard rolls a cache back by rows (PR 27) ---------------

def _guard_net(kind, dtype, num_layers):
    import flax.linen as nn

    from euler_tpu.mp_utils.base import ModelOutput

    class CacheNet(nn.Module):
        @nn.compact
        def __call__(self, batch):
            emb = ENCODERS[kind](
                dim=CD, num_layers=num_layers, max_id=N_ROWS - 1,
                cache_dtype=dtype, name="encoder")(
                    batch["ids"], batch["x"], batch["nbr_ids"],
                    batch["nbr_x"])
            # sqrt(0 * s) is 0 with an infinite slope: root_scale 0 gives
            # a finite loss under a NaN gradient, 1 a plain smooth term
            loss = ((emb - batch["labels"]) ** 2).mean() + 1e-3 * jnp.sqrt(
                batch["root_scale"] * (emb ** 2).sum())
            return ModelOutput(emb, loss, "mse", loss)

    return CacheNet()


def _guard_batch(seed=7, poison=None):
    """_cache_batch's roots and neighbours (five neighbours ARE roots),
    made harder: root 3 twice, root 6 once more as id + N_ROWS (the
    modulo wrap). poison: 'labels' (NaN loss), 'x' (NaN rows written into
    the cache), 'grad' (finite loss, NaN gradient)."""
    ids, x, nbr_ids, nbr_x = _cache_batch()
    rng = np.random.default_rng(seed)
    ids = ids.at[4].set(ids[3]).at[1].set(ids[6] + N_ROWS)
    batch = {"ids": np.asarray(ids), "nbr_ids": np.asarray(nbr_ids),
             "x": rng.normal(size=x.shape).astype(np.float32),
             "nbr_x": rng.normal(size=nbr_x.shape).astype(np.float32),
             "labels": rng.normal(size=(CB, CD)).astype(np.float32),
             "root_scale": np.float32(1.0)}
    if poison == "grad":
        batch["root_scale"] = np.float32(0.0)
    elif poison is not None:
        batch[poison] = np.full_like(batch[poison], np.nan)
    return batch


def _guard_estimator(kind, dtype, num_layers, model_dir=None, warm=True,
                     **params):
    """A BaseEstimator over the cache encoder, its caches holding
    non-zero old rows; warm: one sound step behind it (so Adam's moments
    are not zeros either)."""
    from euler_tpu.estimator import BaseEstimator

    est = BaseEstimator(
        _guard_net(kind, dtype, num_layers),
        {"learning_rate": 0.01, "log_steps": 1 << 30,
         "checkpoint_steps": 0, **params}, model_dir=model_dir)
    est._init_state(jax.tree_util.tree_map(jnp.asarray, _guard_batch()))
    est.state = est.state.replace(
        extra_vars={"cache": {"encoder": _old_caches(num_layers, dtype)}})
    est._train_step = est._build_train_step()
    if warm:
        est.state, loss, _ = est._train_step(
            est.state, jax.tree_util.tree_map(jnp.asarray, _guard_batch(1)))
        assert np.isfinite(float(loss))
    return est


def _parent_one_step(est):
    """_make_one_step as it was before PR 27 (every mutable collection
    through the guard's lax.cond): the plain reference."""
    mutable_keys = list(est.state.extra_vars or {})
    dropout_key = jax.random.key(int(est.params_cfg.get("seed", 0)) + 1)

    def one_step(state, batch):
        rngs = {"dropout": jax.random.fold_in(dropout_key, state.step)}

        def loss_fn(p):
            variables = {"params": p, **(state.extra_vars or {})}
            if mutable_keys:
                out, new_vars = state.apply_fn(
                    variables, batch, mutable=mutable_keys, rngs=rngs)
            else:
                out = state.apply_fn(variables, batch, rngs=rngs)
                new_vars = {}
            return out.loss, (out, new_vars)

        (loss, (out, new_vars)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)

        def apply_update(_):
            with jax.named_scope("update"):
                s2 = state.apply_gradients(grads=grads)
            if new_vars:
                s2 = s2.replace(extra_vars=dict(new_vars))
            return s2

        def skip_update(_):
            return state.replace(step=state.step + 1,
                                 skipped_steps=state.skipped_steps + 1)

        with jax.named_scope("guard"):
            ok = jnp.isfinite(loss)
            for g in jax.tree_util.tree_leaves(grads):
                ok &= jnp.all(jnp.isfinite(g))
            state = jax.lax.cond(ok, apply_update, skip_update, None)
        return state, loss, out.metric

    return one_step


def _bytes_of(tree):
    return [np.asarray(leaf).tobytes()
            for leaf in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("num_layers", [2, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["sage", "gcn"])
def test_guard_skipped_step_leaves_every_byte(kind, dtype, num_layers):
    """A poisoned batch (duplicate and modulo-wrapped roots, a root that
    is also a neighbour, old rows non-zero) leaves every cache table,
    the parameters and the optimizer state byte for byte; step and
    skipped_steps advance by one; the next sound step trains."""
    est = _guard_estimator(kind, dtype, num_layers)
    written = np.asarray(_guard_batch()["ids"]) % N_ROWS
    for table in jax.tree_util.tree_leaves(est.state.extra_vars):
        assert np.asarray(table, np.float32)[written].any()
    for n, poison in enumerate(["labels", "x", "grad"]):
        before = est.state
        kept = _bytes_of((before.params, before.opt_state,
                          before.extra_vars))
        step, skipped = int(before.step), int(before.skipped_steps)
        est.state, loss, _ = est._train_step(
            est.state, jax.tree_util.tree_map(jnp.asarray,
                                              _guard_batch(2 + n, poison)))
        assert np.isfinite(float(loss)) == (poison == "grad")
        assert list(est.state.extra_vars) == ["cache"]
        assert _bytes_of((est.state.params, est.state.opt_state,
                          est.state.extra_vars)) == kept, poison
        assert int(est.state.step) == step + 1
        assert int(est.state.skipped_steps) == skipped + 1
        est.state, loss, _ = est._train_step(
            est.state, jax.tree_util.tree_map(jnp.asarray,
                                              _guard_batch(5 + n)))
        assert np.isfinite(float(loss))
        assert int(est.state.skipped_steps) == skipped + 1
        assert _bytes_of(est.state.extra_vars) != kept[-(num_layers - 1):]


@pytest.mark.parametrize("num_layers", [2, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["sage", "gcn"])
def test_guard_rollback_inside_scanned_dispatch(kind, dtype, num_layers):
    """steps_per_loop 4, the bad batches (NaN rows written into the
    cache; a NaN gradient) in the middle: the dispatch ends in the state
    that the lax.cond form of the guard reaches from the same start."""
    est = _guard_estimator(kind, dtype, num_layers, warm=False,
                           steps_per_loop=4)
    start = jax.tree_util.tree_map(jnp.copy, est.state)
    batches = [_guard_batch(10), _guard_batch(11, "x"),
               _guard_batch(12, "grad"), _guard_batch(13)]
    res = est.train(iter(batches), max_steps=int(start.step) + 4)
    assert res["skipped_steps"] == 2
    plain = jax.jit(_parent_one_step(est))
    want = start
    for b in batches:
        want, _, _ = plain(want, jax.tree_util.tree_map(jnp.asarray, b))
    assert int(want.skipped_steps) == 2 and int(est.state.step) == int(
        want.step)
    for got, ref in zip(
            jax.tree_util.tree_leaves((est.state.params, est.state.opt_state,
                                       est.state.extra_vars)),
            jax.tree_util.tree_leaves((want.params, want.opt_state,
                                       want.extra_vars))):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        assert np.isfinite(got).all()
        # the same arithmetic in two programs: a last bit of the stored
        # dtype at most
        tol = 1e-6 if dtype == jnp.float32 or got.shape != (N_ROWS, CD) \
            else 2 ** -8
        np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)


def _conds(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _conds(sub, found)
    return found


@pytest.mark.parametrize("kind", ["sage", "gcn"])
def test_guard_cond_never_holds_the_cache(kind, tmp_path):
    """What keeps the two whole-table copies from coming back where there
    is no chip: no conditional of the scanned step takes or returns a
    value of the cache's shape (both tables alive at a lax.cond cost a
    copy before the scatter and one after), the undo record is no state,
    and the trace is counted once a cache layer."""
    from euler_tpu import obs

    num_layers = 3
    est = _guard_estimator(kind, jnp.bfloat16, num_layers,
                           model_dir=str(tmp_path), steps_per_loop=2)
    counter = obs.counter(
        "traced_paths_total", labelnames=("path", "detail")).labels(
            path="guard_row_rollback", detail="cache")
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), _guard_batch(1), _guard_batch(2))
    before = counter.value
    jaxpr = jax.make_jaxpr(est._build_train_loop())(est.state, stacked, {})
    assert counter.value == before + num_layers - 1
    conds = _conds(jaxpr.jaxpr, [])
    n_state = len(jax.tree_util.tree_leaves(
        (est.state.params, est.state.opt_state)))
    assert any(len(c.outvars) >= n_state for c in conds)   # the guard's
    for c in conds:
        for v in list(c.invars) + list(c.outvars):
            assert getattr(v.aval, "shape", None) != (N_ROWS, CD), c
    # ... while the plain reference's guard does hold it
    held = [v for c in _conds(jax.make_jaxpr(_parent_one_step(est))(
        est.state, jax.tree_util.tree_map(jnp.asarray, _guard_batch(1))
    ).jaxpr, []) for v in c.outvars if v.aval.shape == (N_ROWS, CD)]
    assert len(held) == num_layers - 1
    # the record is an output of the apply alone: not in the state after
    # a dispatch, a save or a restore
    est.train(iter([_guard_batch(4), _guard_batch(5, "labels")]),
              max_steps=int(est.state.step) + 2)
    assert list(est.state.extra_vars) == ["cache"]
    est.save_checkpoint(int(est.state.step))
    est.finalize_checkpoints()
    kept = _bytes_of(est.state.extra_vars)
    est.state = est.state.replace(extra_vars=jax.tree_util.tree_map(
        jnp.zeros_like, est.state.extra_vars))
    assert est.restore_checkpoint() == int(est.state.step)
    assert list(est.state.extra_vars) == ["cache"]
    assert _bytes_of(est.state.extra_vars) == kept


def test_guard_without_a_mutable_collection_traces_as_before():
    """A model with no mutable collection (every benchmark cell but the
    cache model's): one_step is the program it was, equation for
    equation."""
    import flax.linen as nn

    from euler_tpu import obs
    from euler_tpu.estimator import BaseEstimator
    from euler_tpu.mp_utils.base import ModelOutput

    class Net(nn.Module):
        @nn.compact
        def __call__(self, batch):
            emb = nn.Dense(CD)(nn.Dropout(0.1, deterministic=False)(
                batch["x"]))
            loss = ((emb - batch["labels"]) ** 2).mean()
            return ModelOutput(emb, loss, "mse", loss)

    est = BaseEstimator(Net(), {"learning_rate": 0.01})
    batch = {k: jnp.asarray(v) for k, v in _guard_batch().items()
             if k in ("x", "labels")}
    est._init_state(batch)
    assert est.state.extra_vars == {}
    counter = obs.counter(
        "traced_paths_total", labelnames=("path", "detail")).labels(
            path="guard_row_rollback", detail="cache")
    before = counter.value
    now = jax.make_jaxpr(est._make_one_step())(est.state, batch)
    was = jax.make_jaxpr(_parent_one_step(est))(est.state, batch)
    assert str(now) == str(was)
    assert counter.value == before
