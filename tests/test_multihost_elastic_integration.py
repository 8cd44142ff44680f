"""End-to-end cross-host elastic training (CPU, 2 simulated hosts x 1 rank):
the AgentLauncher plays cluster scheduler, per-host agents elect a leader
over the KV store, and the three failure modes the architecture exists for
each recover to bitwise parity with an unfaulted same-seed run:

- leader death  (kill_agent on rank 0's agent) — the job survives losing
  the very process driving it; the restart is charged exactly once
- host death    (kill_agent on a follower's agent) — respawned agent
  reports its lost ranks instead of waiting out a heartbeat timeout
- partition     (partition_host) — ranks keep running but their agent goes
  silent; only agent-level heartbeats can see it, leadership moves to a
  live host (term 2), and the healed host is deposed + torn down before
  the next generation starts

Real subprocesses + jax.distributed per generation: slow-marked, out of
tier-1. The control-plane mechanics are covered fast in test_host_agent.py
and test_election.py.
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
    "--elastic", "--agents", "2", "-g", "2", "--epochs", "2",
    "--batch-size", "4", "--image-size", "28", "--synthetic-n", "64",
    "--limit-steps", "8", "--dtype", "fp32", "--plan", "plain",
    "--log-every", "1000", "--ckpt-every", "2",
]
TOTAL_STEPS = 16


def run_agents(ckpt_dir, fault_plan=None, timeout=600, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["TPU_SANDBOX_BACKOFF"] = "0.1"
    env["TPU_SANDBOX_TERM_TIMEOUT"] = "10"
    env["TPU_SANDBOX_LEASE_TTL"] = "2"
    env["TPU_SANDBOX_AGENT_TIMEOUT"] = "4"
    env.update(extra_env or {})
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


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """One unfaulted run shared by every parity assertion below."""
    ref_dir = tmp_path_factory.mktemp("mh") / "ref"
    r = run_agents(ref_dir)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "1 generation(s)" in r.stdout
    assert "elected leader (term 1)" in r.stdout
    return final_params(ref_dir)


def test_leader_death_fails_over_and_resumes(reference, tmp_path):
    """Rank 0's agent — the leader — is SIGKILLed at step 5. pdeathsig
    takes its rank down too. Whoever leads next (the respawned agent
    re-acquiring its still-live lease, or agent 1 stealing at term 2)
    reconstructs the generation state from the store, charges exactly one
    restart, and gen 2 resumes from the last checkpoint."""
    d = tmp_path / "leader_death"
    r = run_agents(
        d, fault_plan=[{"rank": 0, "step": 5, "action": "kill_agent"}]
    )
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout
    assert "fault: kill_agent" in out, out
    assert "respawning [1/" in out, out                 # scheduler replaced it
    assert "agent restarted; local ranks lost" in out, out
    assert "1 restart(s) charged" in out, out           # charged exactly once
    assert "resumed from step 4" in out, out            # ckpt_every=2, kill at 5
    assert "2 generation(s)" in out, out
    assert_same_model(reference, final_params(d))


def test_host_death_charged_once(reference, tmp_path):
    """A follower host dies (agent + its rank). The leader keeps the
    lease, the launcher replaces the host, and the replacement reports its
    lost ranks immediately instead of letting the rank heartbeat timeout
    (60s default) expire."""
    d = tmp_path / "host_death"
    r = run_agents(
        d, fault_plan=[{"rank": 1, "step": 5, "action": "kill_agent"}]
    )
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout
    assert "agent restarted; local ranks lost" in out, out
    assert "1 restart(s) charged" in out, out
    assert "0 preemption(s)" in out, out
    assert out.count("elected leader") >= 1, out
    assert_same_model(reference, final_params(d))


def test_partition_detected_within_heartbeat_timeout(reference, tmp_path):
    """Rank 0's agent goes silent toward the store for 8s while its rank
    keeps training — the failure only agent-level heartbeats can see.
    Agent 1 must steal the lease (term 2), flag the silent host with a
    bounded stamp age, and gate the relaunch until the healed host has
    acked the teardown (no zombie ranks in gen 2)."""
    d = tmp_path / "partition"
    r = run_agents(
        d,
        fault_plan=[{"rank": 0, "step": 5, "action": "partition_host",
                     "target": "8"}],
    )
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout
    assert "fault: partition_host" in out, out
    assert "elected leader (term 2)" in out, out        # true failover
    assert "silent for >4.0s" in out, out
    # detection latency is bounded: the frozen stamp's age at detection
    # must sit between the timeout and the partition duration
    age = float(out.split("stamp ages {0: ")[1].split("}")[0])
    assert 4.0 <= age <= 8.0, out
    assert "partition healed; rejoining the control plane" in out, out
    assert "deposed" in out, out                        # stale leader fenced
    assert "1 restart(s) charged" in out, out
    assert "2 generation(s)" in out, out
    assert_same_model(reference, final_params(d))
