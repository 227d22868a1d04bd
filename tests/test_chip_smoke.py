"""chip_smoke.py: refuses to pass without the TPU, and its phases — plain
functions that take their sizes — run tiny on the CPU mesh."""

import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

TINY = dict(n_nodes=600, feat_dim=12, cap=8, num_classes=4, dim=16,
            fanouts=(3, 2), batch=32, steps_per_loop=4)


@pytest.fixture(scope="module")
def watch():
    import chip_smoke

    return chip_smoke.CompileWatch()


@pytest.mark.parametrize("args", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_chip_smoke_fails_without_a_tpu(args):
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=300, cwd=str(REPO),
        env={"PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert '"phase"' not in proc.stdout  # no phase ran, no result printed


def test_phase_a_tiny(watch, capsys):
    import chip_smoke

    dev = jax.devices()[0]
    out = chip_smoke.phase_a(seed=0, windows=2, device=dev, watch=watch,
                             **TINY)
    assert out["steps"] == (TINY["steps_per_loop"] + 2) \
        + 2 * TINY["steps_per_loop"]
    assert np.isfinite(out["losses"]).all() and len(out["losses"]) == 3
    assert out["param_max_abs_change"] > 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert all('"device_kind"' in ln for ln in lines)
    assert any('"A.setup"' in ln and '"int8"' in ln for ln in lines)


def test_phase_b_tiny(watch):
    """engine -> tables -> train -> export_bundle -> InferenceServer ->
    ServingClient, with the served rows checked against the bundle."""
    import chip_smoke

    res = chip_smoke.phase_b(
        seed=0, n_nodes=400, avg_degree=6, feat_dim=12, cap=8,
        num_classes=4, dim=16, fanouts=(3, 2), batch=32, steps=4,
        n_queries=8, device=jax.devices()[0], watch=watch)
    assert res["global_step"] == 4 and np.isfinite(res["loss"])


def test_phase_c_two_devices():
    """The agreement check itself, between two CPU devices: identical
    rows and (here) identical losses."""
    import chip_smoke

    d = jax.devices()
    out = chip_smoke.phase_c(seed=0, device=d[1], cpu_device=d[0],
                             n_nodes=200, batch=16)
    for variant in ("inverse_cdf", "uniform"):
        assert out[variant]["sampled_rows_identical"]
        assert out[variant]["loss_chip"] == out[variant]["loss_cpu"]


def test_multichip_phase_on_four_virtual_devices(watch, capsys):
    """The --chips 4 phase on 4 of conftest's virtual devices: every
    table row-sharded over all four devices, sgd params and losses equal
    to the replicated run's within the stated bounds, exchanges exact."""
    import json

    import chip_smoke

    out = chip_smoke.phase_multichip(
        seed=0, devices=jax.devices()[:4], windows=1, watch=watch, **TINY)
    assert np.isfinite(out["losses"]).all()
    text = capsys.readouterr().out
    setup = next(ln for ln in text.splitlines() if "X.sharded.setup" in ln)
    # 602 padded rows -> 301 on each of 4 distinct devices, all 4 tables
    assert setup.count('"rows_per_device": 301') == 4
    assert setup.count('"devices": 4') == 4
    assert "partitioned_store.ring" in text and "allgather_lookup" in text
    cmp_ = json.loads(next(ln for ln in text.splitlines()
                           if "X.compare" in ln))
    np.testing.assert_allclose(cmp_["sgd_losses_sharded"],
                               cmp_["sgd_losses_replicated"], rtol=1e-5)
    assert cmp_["sgd_param_max_abs_diff"] <= 1e-6


def test_multichip_phase_catches_a_wrong_gather(watch, monkeypatch):
    """A sharded run whose tables differ from the replicated run's in a
    few percent of feature rows must fail the sgd comparison (the
    averaged-loss bound alone let that through)."""
    import chip_smoke

    real, calls = chip_smoke.place_tables, []

    def place(t, *, mesh=None, shard_rows=False):
        if shard_rows:  # 3 % of rows read some other node's features
            t = dict(t, feat=t["feat"].copy())
            bad = np.arange(0, len(t["feat"]) - 1, 33)
            t["feat"][bad] = t["feat"][bad[::-1]]
        calls.append(shard_rows)
        return real(t, mesh=mesh, shard_rows=shard_rows)

    monkeypatch.setattr(chip_smoke, "place_tables", place)
    with pytest.raises(AssertionError, match="sgd steps|Not equal"):
        chip_smoke.phase_multichip(
            seed=0, devices=jax.devices()[:4], windows=1, watch=watch,
            **TINY)
    assert calls == [True, False]


def test_placement_checks_catch_a_misplaced_table():
    """The smoke's own checks: a table left on the first device, or not
    row-sharded, must fail them (virtual devices hide this otherwise)."""
    import chip_smoke
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    d = jax.devices()[:4]
    mesh = Mesh(np.asarray(d).reshape(2, 2), ("data", "model"))
    x = np.zeros((8, 4), np.float32)
    good = jax.device_put(x, NamedSharding(mesh, P("model", None)))
    assert chip_smoke.check_row_sharded("t", good, mesh)[
        "rows_per_device"] == 4
    with pytest.raises(AssertionError, match="holds"):
        chip_smoke.check_row_sharded(
            "t", jax.device_put(x, NamedSharding(mesh, P())), mesh)
    with pytest.raises(AssertionError, match="shards on"):
        chip_smoke.check_row_sharded("t", jax.device_put(x, d[0]), mesh)
    with pytest.raises(AssertionError, match="expected"):
        chip_smoke.check_placed({"t": jax.device_put(x, d[1])}, [d[0]],
                                "table ")


def test_gather_mean_use_pallas_never_falls_back():
    """use_pallas=True raises off the TPU, and the kernel entry rejects
    up front the shapes/dtypes Mosaic refuses — it never returns the XLA
    result under the kernel's name."""
    from euler_tpu.ops.pallas_ops import _pallas_gather_mean, gather_mean

    rows = jnp.zeros((16, 5), jnp.int32)
    table = jnp.zeros((64, 128), jnp.float32)
    with pytest.raises(RuntimeError, match="needs the TPU backend"):
        gather_mean(table, rows, use_pallas=True)
    for bad_table, bad_rows, why in (
            (table.astype(jnp.bfloat16), rows, "not float32"),
            (table.astype(jnp.int8), rows, "not float32"),
            (jnp.zeros((64, 100), jnp.float32), rows, "multiple of 128"),
            (table, jnp.zeros((12, 5), jnp.int32), "row tile")):
        with pytest.raises(ValueError, match=why):
            _pallas_gather_mean(bad_table, bad_rows, interpret=True)
