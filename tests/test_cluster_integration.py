"""Two-tenant cluster mode end-to-end (CPU, real training jobs): a
ClusterScheduler pool runs two mnist jobs side by side and one of them is
killed every way runtime/faults.py knows how to kill a tenant, while the
other must not notice:

- agent death  (kill_agent on the noisy job's leader host) — the noisy
  job restarts a generation, charged once, inside its own namespace
- preemption   (sigterm to a noisy rank) — checkpoint-through-preemption
  inside the job, uncharged
- partition    (partition_host) — the noisy job's leadership moves hosts

In every variant the quiet job's final checkpoint must be bitwise equal
to a solo run of the same seed, and its budget counters untouched — the
fault-isolation contract of the multi-tenant scheduler.

The second half is the priority-preemption acceptance path: a
high-priority job lands on a full pool, the low-priority training job is
SIGTERMed by the scheduler, checkpoints through the preemption vote, exits
uncharged, and later resumes to bitwise parity with an uninterrupted run.

Real subprocesses + jax.distributed per generation: slow-marked, out of
tier-1. The scheduler control plane is covered fast in test_scheduler.py.
"""

import json
import os
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from tests.helpers import assert_same_model
from tpu_sandbox.runtime.scheduler import (
    ClusterScheduler,
    JobSpec,
    job_events,
    k_state,
    k_verdict,
)

pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "mnist_distributed.py"

# 64 synthetic samples / (bs 4 x 2 ranks) = 8 steps per epoch, 16 total
CFG = [
    "-g", "2", "--epochs", "2", "--batch-size", "4", "--image-size", "28",
    "--synthetic-n", "64", "--limit-steps", "8", "--dtype", "fp32",
    "--plan", "plain", "--log-every", "1000", "--ckpt-every", "2",
]
TOTAL_STEPS = 16

# a short filler job for the high-priority arrival (2 steps and done;
# world 2 because the gloo-backed CPU collectives need a real process
# group — single-rank elastic worlds are not a supported topology)
CFG_QUICK = [
    "-g", "2", "--epochs", "1", "--batch-size", "4", "--image-size", "28",
    "--synthetic-n", "16", "--limit-steps", "2", "--dtype", "fp32",
    "--plan", "plain", "--log-every", "1000",
]

KNOBS = {
    "PYTHONPATH": str(REPO) + os.pathsep + os.environ.get("PYTHONPATH", ""),
    "JAX_PLATFORMS": "cpu",
    "TPU_SANDBOX_BACKOFF": "0.1",
    "TPU_SANDBOX_TERM_TIMEOUT": "10",
    "TPU_SANDBOX_LEASE_TTL": "2",
    "TPU_SANDBOX_AGENT_TIMEOUT": "4",
}


def training_job(job_id, ckpt_dir, *, hosts=1, world=2, priority=0,
                 cfg=CFG, fault_plan=None):
    """A real elastic mnist job as a scheduler tenant — the exact argv
    shape mnist_distributed.run_cluster_pool submits for itself."""
    argv = [sys.executable, str(SCRIPT), "--elastic",
            "--agents", str(hosts), "--agent-id", "{agent_id}",
            "--kv-port", "{kv_port}", "--job-id", "{job_id}",
            "--max-restarts", "4", *cfg]
    if ckpt_dir is not None:
        argv += ["--ckpt-dir", str(ckpt_dir)]
    env = {}
    if fault_plan is not None:
        env["TPU_SANDBOX_FAULT_PLAN"] = json.dumps(fault_plan)
    return JobSpec(job_id=job_id, hosts=hosts, world_size=world,
                   agent_argv=argv, priority=priority,
                   admission_timeout=600.0, env=env)


def final_params(ckpt_dir):
    f = Path(ckpt_dir) / f"step-{TOTAL_STEPS:08d}.npz"
    assert f.exists(), f"missing final checkpoint {f}"
    with np.load(f, allow_pickle=False) as z:
        return {k: z[k].copy() for k in z.files if k.startswith("leaf:")}


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """One solo, unfaulted, un-scheduled run of the shared config — the
    parity target for both the quiet tenant and the resumed victim (the
    cluster path must not perturb the math of either)."""
    ref_dir = tmp_path_factory.mktemp("cluster") / "ref"
    import subprocess
    env = {**os.environ, **KNOBS}
    r = subprocess.run(
        [sys.executable, str(SCRIPT), "--elastic", "--agents", "1", *CFG,
         "--ckpt-dir", str(ref_dir)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    return final_params(ref_dir)


# -- the two-job fault matrix ----------------------------------------------


@pytest.mark.parametrize("fault_name,fault_plan", [
    ("agent_death",
     [{"rank": 0, "step": 5, "action": "kill_agent"}]),
    ("preemption",
     [{"rank": 0, "step": 5, "action": "sigterm"}]),
    ("partition",
     [{"rank": 0, "step": 5, "action": "partition_host", "target": "8"}]),
])
def test_faulted_neighbor_never_touches_quiet_job(
        reference, tmp_path, fault_name, fault_plan):
    """Jobs 'noisy' (2 hosts) and 'quiet' (1 host) share a 3-slot pool.
    The fault fires only inside noisy's namespace; noisy recovers through
    its own elastic machinery and quiet must come out bitwise identical
    to the solo reference with zero charges."""
    noisy_dir = tmp_path / "noisy"
    quiet_dir = tmp_path / "quiet"
    with ClusterScheduler(3, poll=0.05, extra_env=KNOBS,
                          verbose=False) as sched:
        sched.submit(training_job("noisy", noisy_dir, hosts=2,
                                  fault_plan=fault_plan))
        sched.submit(training_job("quiet", quiet_dir, hosts=1))
        states = sched.serve(timeout=900)
        assert states == {"noisy": "done", "quiet": "done"}, states

        # the isolation contract: quiet's budgets are untouched and the
        # scheduler never so much as sent it a signal
        verdict = json.loads(sched.kv.get(k_verdict("quiet")))
        assert verdict["ok"], verdict
        assert verdict["restarts"] == 0, verdict
        assert verdict["preemptions"] == 0, verdict
        assert "preempt_sent" not in job_events(sched.kv, "quiet")

        # the fault really fired — noisy paid for it, in its own ledger
        noisy = json.loads(sched.kv.get(k_verdict("noisy")))
        assert noisy["ok"], noisy
        if fault_name == "preemption":
            assert noisy["preemptions"] >= 1, noisy
            assert noisy["restarts"] == 0, noisy
        else:
            assert noisy["restarts"] >= 1, noisy

        # both namespaces swept: nothing leaks into the next tenant
        assert sched.kv.keys("job/noisy/") == []
        assert sched.kv.keys("job/quiet/") == []

    assert_same_model(reference, final_params(quiet_dir))


# -- priority preemption to bitwise parity ---------------------------------


def test_preempted_job_resumes_to_bitwise_parity(reference, tmp_path):
    """Low-priority training fills the pool; a high-priority job arrives
    mid-epoch. The scheduler SIGTERMs the gang, the ranks checkpoint
    through the preemption vote and exit uncharged, the high-priority job
    runs, and the victim resumes to the same final parameters as a run
    nobody ever interrupted."""
    low_dir = tmp_path / "low"
    with ClusterScheduler(1, poll=0.05, extra_env=KNOBS,
                          verbose=False) as sched:
        sched.submit(training_job("low", low_dir, hosts=1, priority=0))
        # outrank it only once it is demonstrably mid-training: running,
        # with at least one checkpoint on disk to resume from
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            sched._tick()
            state = (sched.kv.try_get(k_state("low")) or b"").decode()
            if state == "running" and list(low_dir.glob("step-*.npz")):
                break
            time.sleep(0.1)
        else:
            pytest.fail("low-priority job never reached a checkpoint")
        sched.submit(training_job("high", None, hosts=1, world=2,
                                  priority=5, cfg=CFG_QUICK))
        states = sched.serve(timeout=900)
        assert states == {"low": "done", "high": "done"}, states

        # the acceptance receipts, in causal order on the scheduler clock
        ev = job_events(sched.kv, "low")
        assert ev["admitted"] <= ev["preempt_sent"] \
            <= ev["preempted"] <= ev["readmitted"]
        assert job_events(sched.kv, "high")["admitted"] \
            >= ev["preempt_sent"]

        # preemption was free: the victim's verdict charges no restarts
        verdict = json.loads(sched.kv.get(k_verdict("low")))
        assert verdict["ok"], verdict
        assert verdict["restarts"] == 0, verdict
        assert verdict["preemptions"] >= 1, verdict

    assert_same_model(reference, final_params(low_dir))
