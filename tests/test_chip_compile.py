"""What the chip's compiler accepts, checked without the chip.

The TPU compiler is installed here and compiles for a DESCRIBED v5e:2x2
topology (guide on-chip-measurement §2, rehearsal 3): the trainer's real
scanned step at chip_smoke.py's phase-A widths, the act-cache step, the
row-sharded step on a 2x2 mesh (tier-1 at 4,096 roots a step over the
whole-size tables, `slow` at the whole batch), both table exchanges and
the Pallas kernel. A compile that passes is not a chip run — chip_smoke.py is.

Everything that touches the topology lives in fixtures/tests of THIS
file (never at import, in skipif/parametrize or conftest): only one
process may load the TPU library, and it keeps it until exit.
"""

import os
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402 — imports nothing of jax at import time

HBM_BYTES = 16 * 2 ** 30  # one v5e chip
SPL = 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such compiles are written to the persistent cache but cannot be
    # read back without a chip — keep it off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))


def _canon():
    return dict(chip_smoke.CANON)


def _with_sharding(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _abstract_tables(c, rows, table_sharding, small_sharding):
    """ShapeDtypeStructs of the int8 feature / label / nbr / cum tables
    as the stores place them (rows = N + 1 + any row-shard padding; the
    nbr and cum rows in their stored form, device_sampler.store_rows:
    int8 [rows, 4 * cap])."""
    def sds(shape, dt, sh):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    store = SimpleNamespace(
        features=sds((rows, c["feat_dim"]), jnp.int8, table_sharding),
        feature_scale=sds((c["feat_dim"],), jnp.bfloat16, small_sharding),
        labels=sds((rows, c["num_classes"]), jnp.float32, table_sharding))
    sampler = SimpleNamespace(tables={
        "nbr_table": sds((rows, 4 * c["cap"]), jnp.int8, table_sharding),
        "cum_table": sds((rows, 4 * c["cap"]), jnp.int8, table_sharding)})
    return store, sampler


def _compile_scanned_step(est, small_sharding):
    """Lower + compile the estimator's jitted model.init and its REAL
    scanned train loop (BaseEstimator._build_train_loop, the program
    _run_looped dispatches) from abstract shapes placed on described
    devices."""
    from euler_tpu.estimator.base_estimator import TrainState, _merged

    b = est.batch_size
    one = {"rows": [jax.ShapeDtypeStruct((b,), jnp.int32)],
           "sample_seed": jax.ShapeDtypeStruct((), jnp.uint32)}

    def make_state(batch):
        variables = est.model.init(jax.random.key(0), batch)
        params = variables.pop("params")
        return TrainState.create(
            apply_fn=est.model.apply, params=params, tx=est.tx,
            extra_vars=dict(variables),
            skipped_steps=jnp.zeros((), jnp.int32))

    est.state = jax.eval_shape(make_state, _merged(one, est.static_batch))
    # the program _init_state dispatches before the first step
    key = jax.eval_shape(lambda: jax.random.key(0))
    jax.jit(est.model.init).lower(
        _with_sharding(key, small_sharding),
        _merged(_with_sharding(one, small_sharding),
                est.static_batch)).compile()
    state = _with_sharding(est.state, small_sharding)
    stacked = _with_sharding(
        {"rows": [jax.ShapeDtypeStruct((SPL, b), jnp.int32)],
         "sample_seed": jax.ShapeDtypeStruct((SPL,), jnp.uint32)},
        small_sharding)
    return est._build_train_loop().lower(
        state, stacked, est.static_batch).compile()


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("uniform", [True, False],
                         ids=["uniform", "inverse_cdf"])
def test_canonical_scanned_step_compiles_and_fits(one_chip, uniform):
    """(a) steps_per_loop=32 + adam over DeviceSampledGraphSage at the
    phase-A widths, both draw variants; tables counted, fits one chip."""
    c = _canon()
    store, sampler = _abstract_tables(c, c["n_nodes"] + 1, one_chip,
                                      one_chip)
    est = chip_smoke.build_estimator(
        None, store, sampler, dim=c["dim"], fanouts=c["fanouts"],
        num_classes=c["num_classes"], batch=c["batch"],
        steps_per_loop=SPL, uniform=uniform)
    compiled = _compile_scanned_step(est, one_chip)
    m = compiled.memory_analysis()
    # the uniform draw never reads the cum table, and jit drops an
    # unused argument from the executable
    table_bytes = (c["n_nodes"] + 1) * (
        c["feat_dim"] + 4 * c["num_classes"]
        + (4 if uniform else 8) * c["cap"])
    assert m.argument_size_in_bytes >= table_bytes  # tables are counted
    assert _device_bytes(compiled) < HBM_BYTES, m
    # the stored form holds the bytes the [rows, cap] words held: the
    # window's arguments are the parent's (my compiles of ab8870a, PR 29)
    parent = 730_573_824 if uniform else 1_044_179_968
    assert abs(m.argument_size_in_bytes - parent) < 1 << 20, m
    # no [rows, cap] table of words is left in the window (as an argument
    # it had the nodes on the lanes, {0,1}: a row in cap/8 tiles, 36 ns
    # a gathered row on the chip); the stored tables come in row-major
    # and the draw's gathers read their rows whole, 4 * cap bytes wide
    rows, cap = c["n_nodes"] + 1, c["cap"]
    text = compiled.as_text()
    assert not re.search(rf"[sf]32\[{rows},{cap}\]", text)
    stored = [line for line in text.splitlines()
              if " parameter(" in line and f"s8[{rows},{4 * cap}]" in line]
    assert stored and all(f"s8[{rows},{4 * cap}]{{1,0" in p
                          for p in stored), stored
    draws = [line for line in text.splitlines()
             if " gather(" in line and "draw/hop" in line]
    assert len(draws) == (1 if uniform else 2) * len(c["fanouts"]), draws
    assert all(f"slice_sizes={{1,{4 * cap}}}" in g
               and re.match(rf"\s*\S+ = s8\[\d+,{4 * cap}\]\{{1,0", g)
               for g in draws), draws


def test_act_cache_scanned_step_compiles_and_fits(one_chip):
    """(b) DeviceSampledScalableSage (bench --act_cache) at the same
    widths: the [N+1, dim] bf16 activation cache rides the train state."""
    from euler_tpu.estimator import NodeEstimator
    from euler_tpu.models import DeviceSampledScalableSage

    c = _canon()
    store, sampler = _abstract_tables(c, c["n_nodes"] + 1, one_chip,
                                      one_chip)
    model = DeviceSampledScalableSage(
        num_classes=c["num_classes"], multilabel=False, dim=c["dim"],
        fanout=c["fanouts"][0], num_layers=len(c["fanouts"]),
        max_id=c["n_nodes"], cache_dtype=jnp.bfloat16,
        uniform_sampling=True)
    est = NodeEstimator(
        model,
        dict(batch_size=c["batch"], learning_rate=0.01, optimizer="adam",
             label_dim=c["num_classes"], steps_per_loop=SPL),
        None, None, label_fid="label", label_dim=c["num_classes"],
        feature_store=store, device_sampler=sampler)
    compiled = _compile_scanned_step(est, one_chip)
    assert list(est.state.extra_vars) == ["cache"]
    assert _device_bytes(compiled) < HBM_BYTES, compiled.memory_analysis()
    # the guard rolls the cache back by rows (PR 27): with the table in
    # its lax.cond the compiler copied it before the write's scatter and
    # copied the written one back, and kept 1,309,784,576 bytes of
    # temporaries (my compile of the parent, PR 27; 550,852,608 now)
    table = f"bf16[{c['n_nodes'] + 1},{c['dim']}]"
    copies = [line for line in compiled.as_text().splitlines()
              if re.match(rf"\s*(ROOT )?\S+ = {re.escape(table)}\S* copy\(",
                          line)]
    assert not copies, copies
    table_bytes = (c["n_nodes"] + 1) * c["dim"] * 2
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 1_309_784_576 - table_bytes // 2, compiled.memory_analysis()


# The compile's time grows with the batch (my runs, PR 28, this sandbox
# idle: 7 s at 1,024 roots, 14 s at 4,096, 107 s at CANON's 32,768), and
# none of it is waiting. What is asserted is a matter of the TABLES' rows
# and widths, which stay CANON's in both cases: tier-1 compiles the same
# program at 4,096 roots a step, the whole-size compile is `slow`.
@pytest.mark.parametrize("batch", [
    pytest.param(None, id="canon_batch", marks=pytest.mark.slow),
    pytest.param(4096, id="batch_4096")])
def test_row_sharded_scanned_step_on_2x2_mesh(mesh, batch):
    """(c) tables row-sharded over 'model' on a 2x2 mesh of described
    devices: per-device argument bytes ~ 1/K of the tables, and the
    gathers/grad sync show up as collectives."""
    c = _canon()
    c["batch"] = batch or c["batch"]
    k = mesh.shape["model"]
    rows = -(-(c["n_nodes"] + 1) // k) * k  # put_row_sharded's padding
    repl = NamedSharding(mesh, P())
    store, sampler = _abstract_tables(
        c, rows, NamedSharding(mesh, P("model", None)), repl)
    est = chip_smoke.build_estimator(
        None, store, sampler, dim=c["dim"], fanouts=c["fanouts"],
        num_classes=c["num_classes"], batch=c["batch"],
        steps_per_loop=SPL, uniform=False, table_mesh=mesh)
    compiled = _compile_scanned_step(est, repl)
    m = compiled.memory_analysis()
    table_bytes = rows * (c["feat_dim"] + 4 * c["num_classes"]
                          + 8 * c["cap"])
    assert table_bytes / k <= m.argument_size_in_bytes \
        <= 1.1 * table_bytes / k, (m.argument_size_in_bytes, table_bytes)
    assert _device_bytes(compiled) < HBM_BYTES, m
    assert "all-reduce" in compiled.as_text()


@pytest.mark.parametrize("which", ["ring_lookup", "allgather_lookup"])
def test_table_exchanges_compile_on_mesh(mesh, which):
    """(d) the K-step ppermute ring and the all-gather + reduce-scatter
    exchange over the int8 feature table's row shards."""
    from euler_tpu.parallel import ring_exchange

    c = _canon()
    k = mesh.shape["model"]
    rows = -(-(c["n_nodes"] + 1) // k) * k
    table = jax.ShapeDtypeStruct(
        (rows, c["feat_dim"]), jnp.int8,
        sharding=NamedSharding(mesh, P("model", None)))
    ids = jax.ShapeDtypeStruct(
        (c["batch"],), jnp.int32, sharding=NamedSharding(mesh, P("model")))
    fn = getattr(ring_exchange, which)
    compiled = jax.jit(lambda t, i: fn(t, i, mesh, "model")).lower(
        table, ids).compile()
    text = compiled.as_text()
    if which == "ring_lookup":
        assert "collective-permute" in text
    else:
        assert "all-gather" in text
        assert "reduce-scatter" in text or "all-reduce" in text
    assert compiled.memory_analysis().argument_size_in_bytes \
        <= 1.1 * rows * c["feat_dim"] / k + 4 * c["batch"]


def test_pallas_gather_mean_compiles_at_accepted_shape(one_chip):
    """(e) the one Pallas kernel at a shape Mosaic accepts (f32, 128
    lanes) with the hop-2 row count of the canonical step."""
    from euler_tpu.ops.pallas_ops import _pallas_gather_mean

    c = _canon()
    n = c["batch"] * c["fanouts"][0]  # hop-1 nodes, each mean of fanouts[1]
    table = jax.ShapeDtypeStruct((c["n_nodes"] + 1, 128), jnp.float32,
                                 sharding=one_chip)
    rows = jax.ShapeDtypeStruct((n, c["fanouts"][1]), jnp.int32,
                                sharding=one_chip)
    for one_sem in (False, True):
        compiled = _pallas_gather_mean.lower(
            table, rows, tile_n=8, one_sem=one_sem).compile()
        assert "tpu_custom_call" in compiled.as_text()


def test_mean_encoder_views_its_hops_without_a_relayout(one_chip):
    """(f) _GatherEncode(encoder="sage") value-and-grad at the sage3
    cells' fanouts (15, 10, 5: no multiple of the 8-row tile) and 256
    roots: the ids go neighbour-major before the gather, so every view
    of a hop by its parents' slots is a bitcast. In the draw's own order
    the entry computation held six rank-3 reshapes (my compile of
    72bcd55, PR 31: `s8[38400,5,128]`, `f32[3840,10,512]`, twice
    `f32[256,15,512]`, ...), each a copy of the hop on the chip. And no
    instruction yields hop 2 at twice `dim` (PR 33)."""
    from euler_tpu import obs
    from euler_tpu.models.graphsage import _GatherEncode

    fanouts, roots = (15, 10, 5), 256

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    table, scale = sds((100_001, 128), jnp.int8), sds((128,), jnp.bfloat16)
    rows = [sds((roots * int(np.prod(fanouts[:h])),), jnp.int32)
            for h in range(len(fanouts) + 1)]
    enc = _GatherEncode(256, fanouts, "mean", "sage")
    params = _with_sharding(
        jax.eval_shape(enc.init, jax.random.key(0), table, scale, rows),
        one_chip)
    paths = obs.counter("traced_paths_total", "", ("path", "detail"))
    counter = paths.labels(path="neighbor_major_fanout", detail="sage")
    parts = paths.labels(path="sage_hop_parts", detail="mean")
    count, parts_count = counter.value, parts.value
    text = jax.jit(jax.value_and_grad(
        lambda p, t, s, r: enc.apply(p, t, s, r).sum())).lower(
            params, table, scale, rows).compile().as_text()
    assert counter.value == count + 1      # one a traced program
    assert parts.value == parts_count + 2  # hops 2 and 1, once each
    entry = text[text.index("\nENTRY "):]
    views = re.findall(
        r"= ((?:f32|s8)\[\d+,\d+,\d+\])\S* (reshape|copy|bitcast)\(", entry)
    copied = [v for v in views if v[1] != "bitcast"]
    assert not copied, copied
    # the views are there, slots first; a hidden hop's are of its lane
    # parts (PR 33)
    assert {("s8[5,38400,128]", "bitcast"), ("s8[10,3840,128]", "bitcast"),
            ("f32[10,3840,256]", "bitcast"),
            ("f32[15,256,256]", "bitcast")} <= set(views), views
    # hop 2 never exists at twice `dim`, forward or backward: layer 0's
    # pair is averaged over its slots half by half (at the parent
    # `pad_maximum_fusion` wrote f32[38400,512], `reduce` read it back
    # and `broadcast_in_dim` spread the cotangent f32[10,3840,512])
    whole = re.findall(r"= \(?[^=]*?(f32\[(?:38400|10,3840),512\])", entry)
    assert not whole, whole
