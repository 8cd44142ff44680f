"""End-to-end elastic training over real processes (CPU, world size 2):
kill a rank mid-epoch → supervisor restarts the generation → workers
resume from the newest checkpoint with exact data order → the final model
matches an uninterrupted same-seed run. Plus the preemption variant
(SIGTERM → save → exit 75 → restart NOT charged).

Each case spawns 2 jax.distributed processes per generation, so these are
marked slow and stay out of tier-1; the same machinery is covered fast and
single-process in test_supervisor.py / test_resumable.py.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from tests.helpers import assert_same_model

pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "mnist_distributed.py"

# 64 synthetic samples / (bs 4 x 2 ranks) = 8 steps per epoch, 16 total
COMMON = [
    "--elastic", "-g", "2", "--epochs", "2", "--batch-size", "4",
    "--image-size", "28", "--synthetic-n", "64", "--limit-steps", "8",
    "--dtype", "fp32", "--plan", "plain", "--log-every", "1000",
    "--ckpt-every", "2",
]
TOTAL_STEPS = 16


def run_elastic(ckpt_dir, fault_plan=None, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["TPU_SANDBOX_BACKOFF"] = "0.1"
    env["TPU_SANDBOX_TERM_TIMEOUT"] = "10"
    if fault_plan is not None:
        env["TPU_SANDBOX_FAULT_PLAN"] = json.dumps(fault_plan)
    cmd = [sys.executable, str(SCRIPT), *COMMON, "--ckpt-dir", str(ckpt_dir)]
    return subprocess.run(
        cmd, env=env, cwd=REPO, capture_output=True, text=True,
        timeout=timeout,
    )


def final_params(ckpt_dir):
    f = Path(ckpt_dir) / f"step-{TOTAL_STEPS:08d}.npz"
    assert f.exists(), f"missing final checkpoint {f}"
    with np.load(f, allow_pickle=False) as z:
        return {k: z[k].copy() for k in z.files if k.startswith("leaf:")}


def test_kill_rank_midepoch_restart_resume_loss_parity(tmp_path):
    ref_dir = tmp_path / "ref"
    r = run_elastic(ref_dir)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "1 generation(s)" in r.stdout

    # rank 1 is SIGKILLed right after optimizer step 5 (mid-epoch 1)
    crash_dir = tmp_path / "crash"
    r = run_elastic(
        crash_dir, fault_plan=[{"rank": 1, "step": 5, "action": "kill"}]
    )
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout
    assert "gen1:failure" in out and "gen2:ok" in out, out
    assert "1 restart(s) charged" in out, out
    # kill hit step 5; the last committed checkpoint is step 4 — generation
    # 2 must resume exactly there, not start over
    assert "resumed from step 4" in out, out

    assert_same_model(final_params(ref_dir), final_params(crash_dir))


def test_sigterm_preemption_saves_and_is_not_charged(tmp_path):
    ref_dir = tmp_path / "ref"
    r = run_elastic(ref_dir)
    assert r.returncode == 0, r.stdout + r.stderr

    pre_dir = tmp_path / "preempt"
    r = run_elastic(
        pre_dir, fault_plan=[{"rank": 0, "step": 5, "action": "sigterm"}]
    )
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout
    assert "gen1:preemption" in out and "gen2:ok" in out, out
    assert "0 restart(s) charged" in out, out
    assert "1 preemption(s)" in out, out
    # the preempted generation saved at the signal boundary (step 5, an odd
    # step ckpt_every=2 alone would never have written) and generation 2
    # resumed from exactly there
    assert "resumed from step 5" in out, out

    assert_same_model(final_params(ref_dir), final_params(pre_dir))
