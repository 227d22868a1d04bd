"""Smoke-run every example script for a few steps on CPU.

Role of the reference's per-example READMEs + CI gap called out in round-1
review: each examples/*/run_*.py must at least import, build its dataset,
train a few steps, and evaluate without crashing. Runs in a subprocess so
each script exercises its real CLI entry (platform bootstrap included).
"""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
SCRIPTS = sorted(REPO.glob("examples/*/run_*.py"))

# Per-script extra flags to keep smoke runs small/fast. Every script
# accepts --dataset/--max_steps/--eval_steps (examples/common.py,
# examples/graph_common.py).
EXTRA = {
    "run_deepwalk.py": ["--walk_len", "2", "--batch_size", "16"],
    "run_line.py": ["--batch_size", "16"],
    "run_transx.py": ["--batch_size", "16"],
    "run_distmult.py": ["--batch_size", "16"],
    "run_rgcn.py": ["--batch_size", "16"],
    "run_dna.py": ["--batch_size", "32"],
    "run_lgcn.py": ["--batch_size", "32"],
}


# Non-default mode variants that a plain run never enters (the
# unsupervised graphsage path once rotted unnoticed for exactly this
# reason).
VARIANTS = [
    ("graphsage/run_graphsage.py",
     ["--mode", "unsupervised", "--batch_size", "16"]),
    ("graphsage/run_graphsage.py", ["--device_sampler"]),
    ("graphsage/run_graphsage.py",
     ["--mode", "unsupervised", "--device_sampler", "--batch_size", "16"]),
    ("graphsage/run_graphsage.py",
     ["--mode", "unsupervised", "--device_sampler", "--int8_features",
      "--batch_size", "16"]),
    ("solution/run_solution.py", ["--mode", "unsupervise"]),
    ("deepwalk/run_deepwalk.py",
     ["--device_sampler", "--batch_size", "16", "--walk_len", "2"]),
    ("deepwalk/run_deepwalk.py",
     ["--device_sampler", "--batch_size", "16", "--walk_len", "3",
      "--p", "0.5", "--q", "2.0"]),  # node2vec-biased device walk
    ("line/run_line.py",
     ["--device_sampler", "--batch_size", "16", "--order", "1"]),
    ("fastgcn/run_fastgcn.py",
     ["--device_sampler", "--batch_size", "16",
      "--layer_sizes", "8,8"]),  # device-resident layerwise pools
    ("geniepath/run_geniepath.py",
     ["--device_sampler", "--batch_size", "16",
      "--fanouts", "4,3"]),  # genie encoder over device fanouts
    ("graphsage/run_graphsage.py",
     ["--device_sampler", "--act_cache", "--batch_size", "16",
      "--fanouts", "4,3"]),  # in-jit historical-activation cache
    ("scalable_sage/run_scalable_sage.py",
     ["--device_sampler", "--batch_size", "16"]),
    ("scalable_sage/run_scalable_sage.py",
     ["--device_sampler", "--encoder", "gcn", "--batch_size", "16"]),
]


def _smoke(script, tmp_path, extra, checkpoints=True):
    cmd = [sys.executable, str(script), "--max_steps", "3",
           "--eval_steps", "2"] + extra
    if checkpoints:
        cmd += ["--model_dir", str(tmp_path / "model")]
    proc = subprocess.run(
        cmd, cwd=str(REPO), capture_output=True, text=True, timeout=600,
        env={"PATH": "/usr/bin:/bin:/usr/local/bin",
             "HOME": "/tmp",
             "JAX_PLATFORMS": "cpu",
             "EULER_TPU_PLATFORM": "cpu"},
    )
    assert proc.returncode == 0, (
        f"{script} rc={proc.returncode}\nstdout:\n{proc.stdout[-3000:]}\n"
        f"stderr:\n{proc.stderr[-3000:]}")


# Tier-1 keeps ONE smoke per input-path subsystem (~7 subprocess runs);
# the full ~40-script matrix rides the `slow` marker — it was the
# single largest tier-1 cost (~400s of a ~727s sweep on this
# container) while almost every script exercises the same estimator /
# dataset / platform plumbing. Run `-m slow` (or no marker filter)
# before touching examples/common.py or an encoder signature.
TIER1_SCRIPTS = {
    "run_gcn.py",        # host-fed supervised fanout (the default path)
    "run_graphsage.py",  # flagship model, host feeder
    "run_deepwalk.py",   # walk family input path
}
TIER1_VARIANTS = {
    "graphsage:--device_sampler",               # device fanout path
    "deepwalk:--device_sampler --batch_size 16 --walk_len 2",  # device walk
    "fastgcn:--device_sampler --batch_size 16 --layer_sizes 8,8",  # layerwise
    "graphsage:--device_sampler --act_cache --batch_size 16 "
    "--fanouts 4,3",                            # historical-activation cache
}


def _script_params():
    for s in SCRIPTS:
        ident = s.name[len("run_"):-len(".py")]
        marks = () if s.name in TIER1_SCRIPTS else (pytest.mark.slow,)
        yield pytest.param(s, id=ident, marks=marks)


def _variant_params():
    for rel, extra in VARIANTS:
        ident = f"{rel.split('/')[0]}:{' '.join(extra)}"
        marks = () if ident in TIER1_VARIANTS else (pytest.mark.slow,)
        yield pytest.param(rel, extra, id=ident, marks=marks)


@pytest.mark.parametrize("script", list(_script_params()))
def test_example_smoke(script, tmp_path):
    _smoke(script, tmp_path, EXTRA.get(script.name, []))


# A variant differs from its script's plain run in the input path it
# enters, not in how it checkpoints: the plain runs above write and
# restore through --model_dir, the variants train and evaluate without
# one (a third of such a run was the import of orbax.checkpoint).
@pytest.mark.parametrize("rel,extra", list(_variant_params()))
def test_example_mode_variants(rel, extra, tmp_path):
    _smoke(REPO / "examples" / rel, tmp_path, extra, checkpoints=False)
