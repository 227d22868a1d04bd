"""euler_tpu.platform: in-process backend init, no fallback, one place
that sets the compile cache directory.

init_platform configures jax before the first device query, so each case
runs in a child process (CPU-only children: they never need a chip)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _child(code: str, env_extra=None, drop=()):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "EULER_TPU_PLATFORM",
                        *drop)}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-c", f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
         + code],
        capture_output=True, text=True, timeout=120, env=env, cwd=str(REPO))


def test_init_platform_cpu_gives_n_devices():
    proc = _child(
        "from euler_tpu.platform import init_platform\n"
        "import jax\n"
        "name = init_platform('cpu', 4)\n"
        "print(name, len(jax.devices()), jax.devices()[0].platform)\n"
        "assert init_platform('cpu', 4) == 'cpu'  # idempotent\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == ["cpu", "4", "cpu"]


def test_init_platform_tpu_raises_without_a_tpu():
    """No chip here: 'tpu' must raise — never hand back a CPU backend."""
    proc = _child(
        "from euler_tpu.platform import init_platform\n"
        "try:\n"
        "    got = init_platform('tpu')\n"
        "except RuntimeError as e:\n"
        "    print('RAISED', str(e)[:200])\n"
        "else:\n"
        "    print('RETURNED', got)\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.startswith("RAISED"), proc.stdout
    assert "RETURNED" not in proc.stdout


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir_placement(tmp_path, env_set):
    """JAX_COMPILATION_CACHE_DIR set: the code sets nothing (jax reads
    the variable itself). Unset: one fixed in-checkout path, the same
    from two processes."""
    code = (
        "import json, jax\n"
        "from euler_tpu import platform as P\n"
        "calls = []\n"
        "orig = jax.config.update\n"
        "def spy(name, val):\n"
        "    calls.append(name)\n"
        "    return orig(name, val)\n"
        "jax.config.update = spy\n"
        "P.init_platform('cpu')\n"
        "print(json.dumps({'set_by_code': 'jax_compilation_cache_dir' in calls,\n"
        "                  'jax_dir': jax.config.jax_compilation_cache_dir,\n"
        "                  'reported': P.compile_cache_dir()}))\n")
    want = str(tmp_path / "cc") if env_set else str(REPO / ".jax_cache")
    extra = {"JAX_COMPILATION_CACHE_DIR": want} if env_set else {}
    outs = []
    for _ in range(1 if env_set else 2):
        proc = _child(code, extra)
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    for out in outs:
        assert out["set_by_code"] is (not env_set)
        assert out["jax_dir"] == want
        assert out["reported"] == want


def test_only_platform_sets_a_cache_dir():
    """Exactly one place in the code sets a compile cache directory."""
    hits = []
    # the program's own sources (ignored build/scratch dirs of a checkout
    # may hold copies of the tree)
    paths = list(REPO.glob("*.py"))
    for top in ("euler_tpu", "examples", "tools"):
        paths += (REPO / top).rglob("*.py")
    for path in sorted(paths):
        text = path.read_text(errors="ignore")
        if "jax_compilation_cache_dir" in text \
                or "JAX_COMPILATION_CACHE_DIR\"] =" in text \
                or "set_cache_dir" in text:
            hits.append(str(path.relative_to(REPO)))
    assert hits == ["euler_tpu/platform.py"], hits
