"""End-to-end serving gang (CPU, 2 replicas under real HostAgents): kill
one replica's agent mid-load and lose nothing.

The launcher plays autoscaler: AgentLauncher owns the KV store and spawns
2 HostAgent processes, each running one replica rank
(``python -m tpu_sandbox.serve.replica``). The test is the producer — it
enqueues the whole request load up front, waits for the gang to get
partway through, then SIGKILLs agent 1 via the fault mailbox. That
exercises every loss path at once:

- agent 1 dies uncleanly; pdeathsig takes its replica down with claimed
  requests in flight (leases expire, nobody says goodbye);
- the launcher replaces the agent; the replacement reports its lost
  ranks, the leader tears the generation down;
- the surviving replica drains on SIGTERM (requeues its in-flight work,
  exits preempted), and generation 2 relaunches both replicas;
- gen-2 scavenge requeues the killed replica's orphaned claims.

Zero loss means: every request has a result, and every result is
token-identical to the unfaulted greedy reference (greedy argmax over
bitwise-deterministic decode steps — see serve/decode.py — makes replay
exact, so "identical to a run with no fault" is a literal equality).

Real subprocesses + four cold jax compiles (2 replicas x 2 generations):
slow-marked, out of tier-1. The replica protocol runs fast and in-process
in test_serve.py.
"""

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parent.parent

N_REQUESTS = 80
MAX_CTX = 32

# Must mirror replica._build_engine's defaults (param_seed included) so the
# in-test reference uses bitwise-identical params and geometry.
SERVE_CFG = {
    "cache": {"num_blocks": 24, "block_size": 4, "max_blocks_per_seq": 8},
    "max_batch": 3,
    "buckets": [8, 16],
    "param_seed": 0,
    "lease_ttl": 1.0,
    "timeout": 240.0,
}


def _agent_main(argv):
    """One host agent whose single rank is a serve replica (the process
    the AgentLauncher spawns when this file is run as a script)."""
    import argparse

    from tpu_sandbox.runtime.host_agent import AgentConfig, HostAgent

    p = argparse.ArgumentParser()
    p.add_argument("--agent-id", type=int, required=True)
    p.add_argument("--agents", type=int, required=True)
    p.add_argument("--kv-port", type=int, required=True)
    p.add_argument("--config", required=True)
    args = p.parse_args(argv)

    cfg = AgentConfig(
        agent_id=args.agent_id, num_agents=args.agents,
        world_size=args.agents, kv_port=args.kv_port,
        lease_ttl=2.0, agent_timeout=4.0, term_timeout=10.0,
        backoff=0.1,
    )

    def rank_cmd(gen, rank, coord_port):
        return [sys.executable, "-m", "tpu_sandbox.serve.replica",
                "--config", args.config,
                "--tag", f"replica-r{rank}-g{gen}"]

    return HostAgent(cfg, rank_cmd).run()


def _requests(rng, n):
    out = []
    for i in range(n):
        prompt = [int(t) for t in
                  rng.integers(1, 64, size=int(rng.integers(4, 13)))]
        out.append((f"r{i}", prompt, int(rng.integers(8, 21))))
    return out


def _greedy_reference(reqs):
    """Unfaulted outputs via the padded one-shot forward — one compiled
    shape, bitwise-identical logits to the replicas' decode path."""
    import jax
    import jax.numpy as jnp

    from tpu_sandbox.models.transformer import (TransformerConfig,
                                                TransformerLM)

    mcfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                             n_layers=2, d_ff=64, max_len=128,
                             dtype=jnp.float32)
    model = TransformerLM(mcfg)
    params = model.init(jax.random.key(SERVE_CFG["param_seed"]),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    fwd = jax.jit(lambda t: model.apply({"params": params}, t))
    want = {}
    for rid, prompt, max_new in reqs:
        toks = list(prompt)
        out = []
        for _ in range(max_new):
            padded = np.zeros((1, MAX_CTX), np.int32)
            padded[0, :len(toks)] = toks
            t = int(np.asarray(fwd(jnp.asarray(padded)))[0, len(toks) - 1]
                    .argmax())
            out.append(t)
            toks.append(t)
        want[rid] = out
    return want


def test_replica_gang_survives_agent_kill_with_zero_loss(tmp_path):
    from tpu_sandbox.runtime.faults import agent_cmd_key
    from tpu_sandbox.runtime.host_agent import K_JOB_DONE, AgentLauncher
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.serve import replica as R

    rng = np.random.default_rng(0)
    reqs = _requests(rng, N_REQUESTS)

    server = KVServer()
    kv = KVClient(port=server.port)
    cfg_json = json.dumps(SERVE_CFG)

    def agent_cmd(aid, kv_port):
        return [sys.executable, str(Path(__file__).resolve()),
                "--serve-agent", "--agent-id", str(aid),
                "--agents", "2", "--kv-port", str(kv_port),
                "--config", cfg_json]

    trace_dir = tmp_path / "trace"
    launcher = AgentLauncher(
        2, agent_cmd, kv_server=server,
        extra_env={
            "JAX_PLATFORMS": "cpu",
            # conftest flips this in the test process; the replicas must
            # draw params from the same threefry stream or the reference
            # and the gang disagree from token 0
            "JAX_THREEFRY_PARTITIONABLE": "1",
            # flight recorder on in every agent/replica process: the
            # postmortem below reconstructs the incident from these logs
            "TPU_SANDBOX_TRACE_DIR": str(trace_dir),
            "PYTHONPATH": str(REPO) + os.pathsep
            + os.environ.get("PYTHONPATH", ""),
        })
    rc = []
    thread = threading.Thread(target=lambda: rc.append(launcher.run()),
                              name="serve-launcher")
    try:
        # load first, gang second: the queue is durable, replicas find it
        for rid, prompt, max_new in reqs:
            R.submit_request(kv, rid, prompt, max_new)
        R.announce_total(kv, N_REQUESTS)

        thread.start()

        # wait for the gang to be demonstrably mid-load: some results
        # published, most of the work still outstanding
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if len(kv.keys("serve/result/")) >= 3:
                break
            time.sleep(0.02)
        n_at_kill = len(kv.keys("serve/result/"))
        assert 0 < n_at_kill < N_REQUESTS, \
            f"no mid-load window: {n_at_kill}/{N_REQUESTS} at kill time"
        kv.set(agent_cmd_key(1), json.dumps({"action": "kill_agent"}))

        while launcher.respawns == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert launcher.respawns >= 1, "agent 1 was never replaced"

        thread.join(timeout=420)
        assert not thread.is_alive(), "launcher never saw a job verdict"
        assert rc and rc[0] == 0, f"job verdict not ok: rc={rc}"

        # zero loss: every request answered, every answer bitwise equal to
        # the unfaulted reference
        assert R.results_done(kv)
        want = _greedy_reference(reqs)
        for rid, _, _ in reqs:
            got = json.loads(kv.get(R.k_result(rid)))
            assert got["tokens"] == want[rid], rid
        # and the recovery actually ran through the requeue machinery:
        # drain and/or scavenge append fresh queue entries past the
        # producer's original N
        tail = int(kv.get(R.K_TAIL))
        assert tail > N_REQUESTS, \
            f"no requeues observed (tail {tail} == {N_REQUESTS})"

        # postmortem receipt: tracecat over the durable recorder logs
        # reconstructs the incident in causal order — the fault firing,
        # the dead claimant's lease expiring, the scavenger's requeue.
        # Instants are flushed before the SIGKILL executes, so the kill
        # record survives the process that wrote it.
        def tracecat(*args):
            proc = subprocess.run(
                [sys.executable, str(REPO / "tools" / "tracecat.py"),
                 str(trace_dir), *args],
                capture_output=True, text=True, timeout=120)
            assert proc.returncode == 0, proc.stderr
            return proc.stdout
        timeline = tracecat("--last", "600s")
        i_kill = timeline.index("fault:kill_agent")
        i_expire = timeline.index("lease:expired")
        i_requeue = timeline.index("scavenge:requeue")
        assert i_kill < i_expire < i_requeue, timeline
        # the exact incident-response invocation works too: the window is
        # measured back from the LAST record, so it always has content
        assert tracecat("--last", "10s").strip()
    finally:
        if thread.is_alive():
            # unwedge the launcher so teardown can't hang the suite
            kv.set(K_JOB_DONE, json.dumps(
                {"ok": False, "reason": "test teardown"}))
            thread.join(timeout=60)
        kv.close()
        server.stop()


def test_overload_plus_agent_kill_yields_exactly_one_verdict_each(tmp_path):
    """Chaos + SLO accounting: an overloaded gang (deadline'd cohorts
    queued behind cold compiles) loses an agent mid-load, and still every
    submitted request terminates with EXACTLY one verdict — an ok result
    or an explicit SHED — with no ok published materially past its
    deadline and no corruption in anything that did complete.

    Cohorts: A has no deadline (must all complete, bitwise-reference);
    B's deadline leaves room to finish unless the kill/relaunch eats it
    (either verdict is legal); C's deadline is tighter than the first
    cold compile, so C guarantees the shed path runs under chaos."""
    from tpu_sandbox.runtime.faults import agent_cmd_key
    from tpu_sandbox.runtime.host_agent import K_JOB_DONE, AgentLauncher
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.serve import replica as R

    rng = np.random.default_rng(1)
    reqs = _requests(rng, 60)
    cohort = {rid: ("A", "B", "C")[i % 3] for i, (rid, _, _) in
              enumerate(reqs)}

    server = KVServer()
    kv = KVClient(port=server.port)
    cfg_json = json.dumps(SERVE_CFG)

    def agent_cmd(aid, kv_port):
        return [sys.executable, str(Path(__file__).resolve()),
                "--serve-agent", "--agent-id", str(aid),
                "--agents", "2", "--kv-port", str(kv_port),
                "--config", cfg_json]

    launcher = AgentLauncher(
        2, agent_cmd, kv_server=server,
        extra_env={
            "JAX_PLATFORMS": "cpu",
            "JAX_THREEFRY_PARTITIONABLE": "1",
            "PYTHONPATH": str(REPO) + os.pathsep
            + os.environ.get("PYTHONPATH", ""),
        })
    rc = []
    thread = threading.Thread(target=lambda: rc.append(launcher.run()),
                              name="chaos-launcher")
    try:
        t0 = time.time()
        deadlines = {}
        for rid, prompt, max_new in reqs:
            dl = {"A": None, "B": t0 + 25.0, "C": t0 + 2.5}[cohort[rid]]
            deadlines[rid] = dl
            R.submit_request(kv, rid, prompt, max_new, deadline_unix=dl)
        R.announce_total(kv, len(reqs))

        thread.start()

        # monitor the verdict stream: first-seen wall time per rid, and
        # the kill once the gang is demonstrably mid-load
        first_seen = {}
        killed = False
        deadline = time.monotonic() + 420
        while time.monotonic() < deadline:
            for key in kv.keys("serve/result/"):
                rid = key[len("serve/result/"):]
                first_seen.setdefault(rid, time.time())
            if not killed and len(first_seen) >= 3:
                kv.set(agent_cmd_key(1),
                       json.dumps({"action": "kill_agent"}))
                n_at_kill = len(first_seen)
                killed = True
            if len(first_seen) >= len(reqs):
                break
            time.sleep(0.05)
        assert killed and n_at_kill < len(reqs), "no mid-load kill window"
        thread.join(timeout=120)
        assert not thread.is_alive(), "launcher never saw a job verdict"
        assert launcher.respawns >= 1, "agent 1 was never replaced"

        # exactly one terminal verdict per request, nothing extra
        results = {}
        for key in kv.keys("serve/result/"):
            rid = key[len("serve/result/"):]
            results[rid] = json.loads(kv.get(key))
        assert set(results) == {rid for rid, _, _ in reqs}
        ok = {r for r, v in results.items() if v["verdict"] == "ok"}
        shed = {r for r, v in results.items() if v["verdict"] == "SHED"}
        assert ok | shed == set(results) and not (ok & shed)
        # the undeadlined cohort can never legally shed; the
        # tighter-than-one-compile cohort guarantees sheds happened
        assert {r for r in shed if cohort[r] == "A"} == set()
        assert shed, "overload produced no sheds — not an overload"
        for r in shed:
            assert results[r]["reason"], results[r]
        # no ok verdict materially past its deadline (engine-clock
        # lateness becomes a SHED in _retire; the slack covers publish
        # tick + monitor poll latency only)
        for r in ok:
            if deadlines[r] is not None and r in first_seen:
                assert first_seen[r] <= deadlines[r] + 2.0, \
                    (r, first_seen[r] - deadlines[r])
        # everything that did complete is bitwise-identical to the
        # unfaulted greedy reference — chaos may shed, never corrupt
        want = _greedy_reference([q for q in reqs if q[0] in ok])
        for r in ok:
            assert results[r]["tokens"] == want[r], r
        # and the kill really exercised the requeue machinery
        assert int(kv.get(R.K_TAIL)) > len(reqs)
    finally:
        if thread.is_alive():
            kv.set(K_JOB_DONE, json.dumps(
                {"ok": False, "reason": "test teardown"}))
            thread.join(timeout=60)
        kv.close()
        server.stop()


if __name__ == "__main__":
    if "--serve-agent" in sys.argv:
        argv = [a for a in sys.argv[1:] if a != "--serve-agent"]
        sys.exit(_agent_main(argv))
    sys.exit(2)
