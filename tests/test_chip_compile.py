"""What the chip's compiler accepts, checked without the chip.

The TPU compiler is installed here and compiles for a DESCRIBED v5e:2x2
topology (guide on-chip-measurement §2, rehearsal 3): the trainer's real
scanned step at chip_smoke.py's phase-A widths, the act-cache step, the
GraphSAGE-LSTM step with its recurrence kernels, the GAT cells' steps
with their attention pairs' backward kernels, the UniMP cell's step with
its pairs' kernels, the row-sharded step on
a 2x2 mesh (tier-1 at 4,096 roots a step over the whole-size tables,
`slow` at the whole batch), both table exchanges and the gather-mean
Pallas kernel. A compile that passes is not a chip run — chip_smoke.py is.

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


def _abstract_state(est):
    """Set the estimator's TrainState to its shapes; -> one step's batch
    of roots, as shapes."""
    from euler_tpu.estimator.base_estimator import TrainState, _merged

    one = {"rows": [jax.ShapeDtypeStruct((est.batch_size,), jnp.int32)],
           "sample_seed": jax.ShapeDtypeStruct((), jnp.uint32)}

    def make_state(batch):
        variables = est.model.init(jax.random.key(0), batch)
        params = variables.pop("params")
        return TrainState.create(
            apply_fn=est.model.apply, params=params, tx=est.tx,
            extra_vars=dict(variables),
            skipped_steps=jnp.zeros((), jnp.int32))

    est.state = jax.eval_shape(make_state, _merged(one, est.static_batch))
    return one


def _compile_scanned_step(est, small_sharding):
    """Lower + compile the estimator's jitted model.init and its REAL
    scanned train loop (BaseEstimator._build_train_loop, the program
    _run_looped dispatches) from abstract shapes placed on described
    devices."""
    from euler_tpu.estimator.base_estimator import _merged

    b = est.batch_size
    one = _abstract_state(est)
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


def _compile_lstm_step(one_chip):
    """The scanned window of GraphSAGE-LSTM at the sagelstm2 cell's widths
    (the papers100M row share, fanouts 10,25, LSTM 128, 1,024 roots)."""
    from euler_tpu.estimator import NodeEstimator
    from euler_tpu.models import DeviceSampledGraphSage

    c = {"feat_dim": 128, "num_classes": 172, "cap": 32,
         "n_nodes": 111059956 // 32}
    store, sampler = _abstract_tables(c, c["n_nodes"] + 1, one_chip,
                                      one_chip)
    model = DeviceSampledGraphSage(
        aggregator="lstm", dim=128, fanouts=(10, 25), l2_head=True,
        num_classes=172, multilabel=False, uniform_sampling=True)
    est = NodeEstimator(
        model,
        dict(batch_size=1024, learning_rate=0.01, optimizer="adam",
             label_dim=172, steps_per_loop=SPL),
        None, None, label_fid="label", label_dim=172, feature_store=store,
        device_sampler=sampler)
    compiled = _compile_scanned_step(est, one_chip)
    assert _device_bytes(compiled) < HBM_BYTES, compiled.memory_analysis()
    return compiled.as_text()


def test_lstm_scanned_step_compiles_and_fits(one_chip):
    """(b') The GraphSAGE-LSTM window as a process off the TPU traces it:
    the recurrences' scans inside the scanned window fit one chip, and the
    gates' input product stays inside each scan step: no [25, 10240, 512]
    array of gates is written (hoisted, the 25-step scan read 10.58 ms
    against 6.11 on one TPU v5e)."""
    text = _compile_lstm_step(one_chip)
    assert "f32[25,10240,128]" in text          # the scan's own residuals
    assert "f32[25,10240,512]" not in text


def test_lstm_kernels_compile_in_the_step_as_the_encoders(one_chip,
                                                          monkeypatch):
    """(b'') The same window as a process on the chip traces it
    (`aggregators._on_tpu` says so): Mosaic accepts both recurrence
    kernels at the cell's shapes, the window fits, and each of the three
    recurrences is one forward and one backward custom call under its
    aggregator's `lstm` scope, which the benchmark's readers charge to
    `encode` and to the recurrence (`lstm_readers.in_lstm`). Layer 0's
    backward returns dK and db alone (its slots are gathered data), layer
    1's the cotangent of its two lane parts besides; no [K, B, 4H] is
    written."""
    from benchmark import lstm_readers, scope_readers
    from euler_tpu import obs
    from euler_tpu.utils import aggregators

    monkeypatch.setattr(aggregators, "_on_tpu", lambda: True)
    paths = obs.counter("traced_paths_total", "", ("path", "detail"))
    details = ("10240x25x128", "1024x10x128", "1024x10x256")
    counts = [paths.labels(path="lstm_kernel", detail=d).value
              for d in details]
    text = _compile_lstm_step(one_chip)
    taken = [paths.labels(path="lstm_kernel", detail=d).value - n
             for d, n in zip(details, counts)]
    assert taken[0] > 0 and taken == [taken[0]] * 3, taken
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    found = []
    for line in calls:
        name = re.search(r'op_name="([^"]+)"', line).group(1)
        assert scope_readers.scope_of(name) == "encode", name
        assert lstm_readers.in_lstm(name), name
        agg = re.search(r"encoder/enc/(agg_\d)/lstm/lstm_(fwd|bwd)/", name)
        outputs = line.split(" = ")[1].split(" custom-call(")[0]
        found.append((agg.group(1), agg.group(2),
                      len(re.findall(r"f32\[", outputs))))
    assert sorted(found) == [("agg_0", "bwd", 2)] * 2 + [
        ("agg_0", "fwd", 3)] * 2 + [("agg_1", "bwd", 4), ("agg_1", "fwd", 3)]
    assert "f32[25,10240,512]" not in text


@pytest.mark.parametrize("rows, width, dtype, data, block", [
    (10240, 128, jnp.bfloat16, True, 2048),     # cell 7's layer 0
    (1024, 256, jnp.float32, False, 1024),      # its layer 1
    (10240, 768, jnp.float32, False, 1024),     # MAG240M's rows
    (10240, 768, jnp.bfloat16, True, 1024),
    (1024, 4096, jnp.float32, False, None),     # no block fits
], ids=["cell7_layer0", "cell7_layer1", "mag240m_f32", "mag240m_bf16",
        "too_wide"])
def test_lstm_kernels_fit_vmem_or_leave_it_to_the_scan(
        one_chip, monkeypatch, rows, width, dtype, data, block):
    """(b''') The recurrence's value and gradients at 25 slots with pad
    slots, as a process on the chip traces it: where `row_block` finds a
    block within the VMEM budget, Mosaic accepts both kernels at it (with
    a mask, cell 7's layer-0 backward at 2,048 rows takes more than the
    16 MiB Mosaic allows a v5e kernel by default); where no block fits,
    the lax.scan runs, so a model the scan ran still compiles."""
    from euler_tpu import obs
    from euler_tpu.ops import slot_lstm
    from euler_tpu.utils import aggregators

    monkeypatch.setattr(aggregators, "_on_tpu", lambda: True)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    hid, slots = 128, 25
    nbr = sds((slots, rows, width), dtype)
    mask = sds((slots, rows, 1), jnp.bool_)
    kernel = sds((width + hid, 4 * hid), jnp.float32)
    bias = sds((4 * hid,), jnp.float32)
    assert slot_lstm.row_block((nbr,), hid, True) == block
    if block:
        assert slot_lstm.vmem_bytes(block, (nbr,), hid, True) \
            <= slot_lstm.VMEM_BUDGET < slot_lstm.VMEM_LIMIT

    def loss(kernel, bias, nbr, mask):
        return aggregators.lstm_over_slots(nbr, mask, kernel, bias, 0).sum()

    paths = obs.counter("traced_paths_total", "", ("path", "detail"))
    taken = paths.labels(path="lstm_kernel", detail=f"{rows}x{slots}x{width}")
    count = taken.value
    grad = jax.grad(loss, argnums=(0, 1) if data else (0, 1, 2))
    text = jax.jit(grad).lower(kernel, bias, nbr, mask).compile().as_text()
    assert taken.value == count + (block is not None)
    assert ("tpu_custom_call" in text) == (block is not None)


def _gat_estimator(one_chip, config):
    """A GAT cell's configuration (its model, widths and row share) in a
    NodeEstimator of 1,024 roots over tables placed on the described chip,
    its state as shapes; -> (the estimator, one step's roots)."""
    import json

    from euler_tpu.estimator import NodeEstimator
    from euler_tpu.models import DeviceSampledGraphSage

    cfg = json.loads((Path(__file__).resolve().parents[1] / "benchmark"
                      / "configs" / f"{config}.json").read_text())
    c = {"feat_dim": cfg["feature_dim"], "num_classes": cfg["num_classes"],
         "cap": cfg["cap"], "n_nodes": cfg["num_nodes"]}
    store, sampler = _abstract_tables(c, c["n_nodes"] + 1, one_chip,
                                      one_chip)
    model = DeviceSampledGraphSage(**cfg["model"]["kwargs"],
                                   uniform_sampling=True)
    est = NodeEstimator(
        model,
        dict(batch_size=1024, learning_rate=0.001, optimizer="adam",
             label_dim=c["num_classes"], steps_per_loop=SPL),
        None, None, label_fid="label", label_dim=c["num_classes"],
        feature_store=store, device_sampler=sampler)
    return est, _abstract_state(est)


@pytest.mark.parametrize("config, reader, kernels", [
    ("gat3-papers100m-s32", "gat_readers",
     {"1024x10x512": 2, "10240x10x512": 2, "102400x10x512": 1}),
    ("gat2-mag240m-s32", "mag_readers",
     {"1024x25x1024": 2, "25600x15x1024": 1}),
], ids=["cell4", "cell6"])
def test_gat_pair_kernels_lower_in_the_cells_backward(one_chip, monkeypatch,
                                                      config, reader,
                                                      kernels):
    """(e) A GAT cell's step as a process on the chip traces it
    (`aggregators._on_tpu` says so): each (target hop, source hop) pair
    whose head width is a multiple of 128 is counted once and is one
    Pallas call in the backward, under its layer's `attn` scope, which
    the benchmark's readers charge to `encode` and to the attention
    (`attn_ms` in cell 4, `wideattn_ms` in cell 6); cell 4's last layer
    (C = 172) keeps the XLA path."""
    import importlib

    from benchmark import scope_readers
    from euler_tpu import obs
    from euler_tpu.estimator.base_estimator import _merged
    from euler_tpu.utils import aggregators

    part_of = importlib.import_module(f"benchmark.{reader}").part_of
    monkeypatch.setattr(aggregators, "_on_tpu", lambda: True)
    paths = obs.counter("traced_paths_total", "", ("path", "detail"))

    def taken():
        return {d: paths.labels(path="gat_attention_kernel", detail=d).value
                for d in kernels}

    est, one = _gat_estimator(one_chip, config)
    before = taken()
    text = jax.jit(est._make_one_step()).lower(
        _with_sharding(est.state, one_chip),
        _merged(_with_sharding(one, one_chip), est.static_batch)).as_text(
        debug_info=True)
    assert {d: n - before[d] for d, n in taken().items()} == kernels
    where = dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', text, re.M))
    names = [where[re.findall(r"loc\((#loc\d+)\)", line)[-1]]
             for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(names) == sum(kernels.values()), names
    for name in names:
        assert re.search(r"transpose\(jvp\(.*/encoder/enc/layer[01]/attn/"
                         r"gat_pair_bwd/", name), name
        assert scope_readers.scope_of(name) == "encode", name
        assert part_of(name) in ("attn", "wideattn"), name


def test_unimp_pair_kernels_lower_in_the_cells_step(one_chip, monkeypatch):
    """(e'') Cell 5's step as a process on the chip traces it: each of
    its five (target hop, source hop) pairs of 128 projected lanes is
    counted once and is one Pallas call a pass, under its layer's `attn`
    scope, which `benchmark/unimp_readers` charges to `dotattn`; the last
    layer's 688 lanes keep the XLA path."""
    from benchmark import scope_readers, unimp_readers
    from euler_tpu import obs
    from euler_tpu.estimator.base_estimator import _merged
    from euler_tpu.utils import aggregators

    kernels = {"1024x10x128": 2, "10240x10x128": 2, "102400x10x128": 1}
    monkeypatch.setattr(aggregators, "_on_tpu", lambda: True)
    paths = obs.counter("traced_paths_total", "", ("path", "detail"))

    def taken():
        return {d: paths.labels(path="unimp_attention_kernel",
                                detail=d).value for d in kernels}

    est, one = _gat_estimator(one_chip, "unimp3-papers100m-s32")
    before = taken()
    text = jax.jit(est._make_one_step()).lower(
        _with_sharding(est.state, one_chip),
        _merged(_with_sharding(one, one_chip), est.static_batch)).as_text(
        debug_info=True)
    assert {d: n - before[d] for d, n in taken().items()} == kernels
    where = dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', text, re.M))

    def loc(line):
        return where[re.findall(r"loc\((#loc\d+)\)", line)[-1]]

    # a pass's call is one function of one shape, called from each pair
    # of that shape: the compiled kernel's op name is the call site's
    # followed by the kernel's own
    kernel_of, fn = {}, None
    for line in text.splitlines():
        fn = (re.findall(r"func\.func (?:private |public )?@(\w+)", line)
              or [fn])[0]
        if "tpu_custom_call" in line:
            kernel_of[fn] = loc(line)
    names = [f"{loc(line)}/{kernel_of[callee]}"
             for line in text.splitlines()
             for callee in re.findall(r"\bcall @(\w+)\(", line)
             if callee in kernel_of]
    assert sorted(re.search(r"dot_pair_(fwd|bwd)/", n).group(1)
                  for n in names) == ["bwd"] * 5 + ["fwd"] * 5, names
    for name in names:
        assert re.search(r"/encoder/enc/layer[01]/attn/jit\(_call\)/"
                         r"dot_pair_(fwd|bwd)/pallas_call$", name), name
        assert scope_readers.scope_of(name) == "encode", name
        assert unimp_readers.part_of(name) == "dotattn", name


@pytest.mark.parametrize("slots, rows, width, concat, block", [
    (10, 102400, 128, True, 512),    # cell 4's layer 0, hop 3 into hop 2
    (10, 10240, 128, True, 512),     # its hop 2 into hop 1
    (15, 25600, 256, True, 256),     # cell 6's layer 0, hop 2 into hop 1
    (25, 1024, 256, True, 128),      # cell 6's roots
    (10, 1024, 172, False, None),    # cell 4's last layer: C = 172
    (60, 1024, 256, True, None),     # no block fits
], ids=["cell4_hop3", "cell4_hop2", "cell6_hop2", "cell6_roots",
        "cell4_last", "too_many_slots"])
def test_gat_pair_kernel_fits_vmem_or_leaves_it_to_xla(one_chip, slots, rows,
                                                       width, concat, block):
    """(e') The pair's value and gradients at a cell's shapes, 4 heads:
    where `row_block` finds a block, its `vmem_bytes` (the block's
    float32 slots and their cotangent, each double-buffered) is within
    the budget and Mosaic accepts the backward at it, one call; where
    none is found the layer keeps the XLA path."""
    from euler_tpu.ops import gat_pair

    heads = 4
    lanes, out = heads * width, heads * width if concat else width
    assert gat_pair.row_block(slots, rows, lanes, heads, out) == block
    if block is None:
        return
    assert gat_pair.vmem_bytes(block, slots, lanes, heads, out) \
        <= gat_pair.VMEM_BUDGET < gat_pair.VMEM_LIMIT

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def loss(z_t, z_s, t_src, t_dst, att, mask, w):
        return (gat_pair.attend(z_t, z_s, t_src, t_dst, att, mask, concat)
                * w).sum()

    text = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        sds((rows, lanes)), sds((slots, rows, lanes)), sds((heads, rows)),
        sds((heads, rows)), sds((width, heads)), sds((slots, rows), jnp.bool_),
        sds((rows, out))).compile().as_text()
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("rows, lanes, block", [
    (102400, 128, 512),     # cell 5's layer 0, hop 3 into hop 2
    (1024, 688, None),      # its last layer: 4 heads of 172
], ids=["cell5_hop3", "cell5_last"])
def test_unimp_pair_kernels_fit_vmem_or_leave_it_to_xla(one_chip, rows,
                                                         lanes, block):
    """(e''') UniMP's pair, value and gradients at cell 5's shapes, 10
    slots and 4 heads: where `row_block` finds a block, its `vmem_bytes`
    is within the budget and Mosaic accepts both kernels at it; where
    none is found the layer keeps the XLA path."""
    from euler_tpu.ops import dot_pair

    slots, heads = 10, 4
    assert dot_pair.row_block(slots, rows, lanes, heads) == block
    if block is None:
        return
    assert dot_pair.vmem_bytes(block, slots, lanes) \
        <= dot_pair.VMEM_BUDGET < dot_pair.VMEM_LIMIT

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def loss(q, k_s, v_s, mask, w):
        return (dot_pair.attend(q, k_s, v_s, mask, heads) * w).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=range(3))).lower(
        sds((rows, lanes)), sds((slots, rows, lanes)),
        sds((slots, rows, lanes)), sds((slots, rows), jnp.bool_),
        sds((rows, lanes))).compile().as_text()
    assert text.count("tpu_custom_call") == 2


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
