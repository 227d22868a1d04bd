"""bench.py contract tests: the driver depends on exactly one JSON line
per invocation, in every mode — including the walk modes added in r3."""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np

import pytest

REPO = Path(__file__).resolve().parents[1]


def _run(extra):
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--smoke"] + extra,
        capture_output=True, text=True, timeout=420, cwd=str(REPO),
        env={"PATH": "/usr/bin:/bin:/usr/local/bin", "HOME": "/tmp",
             "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1, proc.stdout[-1500:]
    return json.loads(lines[0])


def test_bench_smoke_graphsage_device_and_host():
    dev = _run([])
    assert dev["metric"] == "graphsage_train_edges_per_sec_per_chip"
    # int8 feature table is the default config since the round-4 A/B
    assert dev["detail"]["feat_table_dtype"] == "int8"
    assert dev["value"] > 0
    assert dev["detail"]["sampler"] == "device"
    # the smoke graph is unweighted → the uniform path auto-enables and
    # the artifact says which draw actually ran
    assert dev["detail"]["sampler_variant"] == "uniform"
    assert 0.0 <= dev["detail"]["edge_keep_frac"] <= 1.0
    host = _run(["--host_sampler"])
    assert host["detail"]["sampler"] == "host"
    assert host["detail"]["sampler_variant"] == "host"
    assert host["value"] > 0


def test_bench_smoke_walk_modes():
    dev = _run(["--walk"])
    assert dev["metric"] == "deepwalk_train_pairs_per_sec_per_chip"
    assert dev["detail"]["sampler"] == "device"
    assert dev["value"] > 0
    host = _run(["--walk", "--host_sampler"])
    assert host["detail"]["sampler"] == "host"
    assert host["value"] > 0


def test_bench_smoke_perf_lever_flags():
    """The perf-lever flags (fused sampling table, int8 features) keep
    the one-JSON-line contract and record their provenance in detail."""
    fused = _run(["--fused_sampler"])
    assert fused["detail"]["sampler"] == "device_fused"
    assert fused["value"] > 0
    # int8 is the DEFAULT since the round-4 on-TPU A/B (the default-on
    # leg is asserted on the dev run in the first test); the off-switch
    # must restore the bf16 table for A/B re-runs
    off = _run(["--no-int8_features"])
    assert off["detail"]["feat_table_dtype"] != "int8"
    assert off["value"] > 0


def test_bench_smoke_alias_sampler():
    """--alias_sampler: the round-6 O(1) alias-draw leg keeps the
    one-JSON-line contract, records its variant in detail, and refuses
    contradictory lever combinations (a silently-dropped flag would
    mislabel the window's A/B artifacts)."""
    out = _run(["--alias_sampler"])
    assert out["detail"]["sampler"] == "device"
    assert out["detail"]["sampler_variant"] == "alias"
    assert out["detail"]["alias_sampler"] is True
    assert out["detail"]["uniform_path"] is False
    assert out["value"] > 0
    for flags in (["--alias_sampler", "--fused_sampler"],
                  ["--alias_sampler", "--host_sampler"],
                  ["--alias_sampler", "--uniform_path"],
                  ["--uniform_path", "--fused_sampler"],
                  ["--uniform_path", "--host_sampler"],
                  ["--uniform_path", "--layerwise"]):
        proc = subprocess.run(
            [sys.executable, str(REPO / "bench.py"), "--smoke"] + flags,
            capture_output=True, text=True, timeout=420, cwd=str(REPO),
            env={"PATH": "/usr/bin:/bin:/usr/local/bin", "HOME": "/tmp",
                 "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 2, (flags, proc.stderr[-800:])


def test_bench_argparser_defaults_contract():
    """Tools (infer_knn_products) derive their config from
    build_argparser(); the tuned round-4 defaults must live there."""
    sys.path.insert(0, str(REPO))
    import bench

    d = bench.build_argparser().parse_args([])
    assert d.int8_features is True      # round-4 on-TPU A/B winner
    assert d.fused_sampler is False     # measured regression — not flipped
    assert d.alias_sampler is False     # round-6 candidate — A/B leg only
    assert d.cap == 32 and d.steps_per_loop == 0
    # resolved TPU default: 32 since the round-5 on-chip A/B (28.81M vs
    # 28.27M at 16); the flag default stays 0 so the canonical-refresh
    # gate (not args.steps_per_loop) still recognizes default runs
    assert bench.TPU_STEPS_PER_LOOP == 32


def test_bench_smoke_layerwise_mode():
    out = _run(["--layerwise"])
    assert out["metric"] == "layerwise_train_pool_nodes_per_sec_per_chip"
    assert out["detail"]["sampler"] == "device"
    # layerwise's pool draw has no uniform lever: the artifact must say
    # the inverse-CDF draw ran, even on a unit-weight table
    assert out["detail"]["sampler_variant"] == "inverse_cdf"
    assert out["value"] > 0


def test_degree_sort_tables_is_isomorphic():
    """_degree_sort_tables is a pure relabeling: each node keeps its
    neighbor multiset (through the row permutation), weights, features,
    and labels; hubs land in the lowest rows; pad row survives."""
    sys.path.insert(0, str(REPO))
    from bench import _degree_sort_tables

    rng = np.random.default_rng(0)
    n, C = 50, 4
    nbr = rng.integers(0, n, (n + 1, C)).astype(np.int32)
    # variable degrees: pad out slots with the pad row id n
    deg = rng.integers(0, C + 1, n)
    for i in range(n):
        nbr[i, deg[i]:] = n
    nbr[-1] = n
    w = rng.random((n + 1, C), dtype=np.float32)
    w[nbr == n] = 0.0
    cum = np.cumsum(w, axis=1, dtype=np.float32)
    feat = rng.random((n + 1, 3), dtype=np.float32)
    label = rng.random((n + 1, 2), dtype=np.float32)
    nbr2, cum2, feat2, label2 = _degree_sort_tables(nbr, cum, feat, label)

    # recover the permutation from the feature rows (unique with p=1)
    order = []
    for r in range(n):
        hits = np.where((feat == feat2[r]).all(axis=1))[0]
        assert len(hits) == 1
        order.append(int(hits[0]))
    inv = {old: new for new, old in enumerate(order)}
    inv[n] = n
    # hub-first: degrees non-increasing over new rows
    deg2 = (nbr2[:n] != n).sum(axis=1)
    assert (np.diff(deg2) <= 0).all()
    for r in range(n):
        old = order[r]
        assert sorted(inv[x] for x in nbr[old]) == sorted(nbr2[r].tolist())
        np.testing.assert_allclose(cum2[r], cum[old])
        np.testing.assert_allclose(label2[r], label[old])
    assert (nbr2[-1] == n).all()
