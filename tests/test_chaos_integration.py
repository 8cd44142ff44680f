"""Full chaos fault matrix, slow tier (module auto-marked slow).

Three seeded campaigns drawn by :func:`build_schedule` over every fault
family the in-process harness can execute (gateway kill, replica shed
storm, replica stall), against a 3-gateway / 3-replica stub fleet. Each
must end with zero lost requests and a clean claim audit, and after the
wreckage a prefix probe checks failover didn't degrade the door to
blind load balancing.
"""

import threading
import time

import pytest

from tests.helpers import StubStep, pumping
from tpu_sandbox.gateway.client import GatewayClient
from tpu_sandbox.gateway.fleet import FleetSpec
from tpu_sandbox.gateway.server import Gateway
from tpu_sandbox.models.transformer import TransformerConfig
from tpu_sandbox.obs import workload
from tpu_sandbox.runtime.chaos import (ChaosCampaign, build_schedule,
                                       check_alert_claims, prefix_probe)
from tpu_sandbox.serve.cache import CacheConfig, chain_digest
from tpu_sandbox.serve.engine import ContinuousEngine, ServeConfig

MCFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                         d_ff=64, max_len=128)
CCFG = CacheConfig(num_blocks=32, block_size=4, max_blocks_per_seq=8)
BLOCK = CCFG.block_size


def _worker(kv, tag):
    from tpu_sandbox.serve.replica import ReplicaWorker

    cfg = ServeConfig(model=MCFG, cache=CCFG, max_batch=2, buckets=(8, 16))
    eng = ContinuousEngine(None, cfg, step=StubStep(), clock=time.monotonic)
    return ReplicaWorker(kv, eng, tag=tag, lease_ttl=1.0, load_interval=0.02)


def _run_matrix_campaign(seed):
    """One seeded campaign over the full in-process fault matrix."""
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer

    server = KVServer()
    kv = KVClient(port=server.port)
    clones = []

    def clone():
        c = kv.clone()
        clones.append(c)
        return c

    trace = workload.synthesize(seed, 16, duration_s=0.8,
                                prompt_tokens=(4, 10),
                                decode_tokens=(2, 4))
    # gw2 is never a kill candidate, so the client always has a door
    schedule = build_schedule(seed, duration_s=0.8, targets={
        "kill_gateway": ["gw0", "gw1"],
        "shed_storm": ["w0", "w1", "w2"],
        "stall_replica": ["w0:0.3", "w1:0.3", "w2:0.3"],
    }, n_faults=5)
    fleets = [FleetSpec(block_size=BLOCK)]
    gws = {
        gid: Gateway(kv, fleets, gateway_id=gid, hb_ttl=0.5,
                     refresh_min_s=0.005).start()
        for gid in ("gw0", "gw1", "gw2")
    }

    def kill_gateway(gid):
        if not gws[gid].killed:  # a seed may draw the same target twice
            gws[gid].kill()

    workers = [_worker(clone(), f"w{i}") for i in range(3)]
    client = None
    try:
        with pumping(*workers):
            client = GatewayClient(
                endpoints=[("127.0.0.1", gws[g].port)
                           for g in ("gw0", "gw1", "gw2")],
                backoff_base=0.01)
            campaign = ChaosCampaign(
                clone(), trace, client.submit, seed=seed,
                schedule=schedule,
                hooks={"kill_gateway": kill_gateway},
                block_size=BLOCK, verdict_timeout=120.0)
            res = campaign.run()
            alert_failures = check_alert_claims(kv)
            routed = _probe_after(kv, client, campaign, trace, seed)
    finally:
        if client is not None:
            client.close()
        for g in gws.values():
            g.close()
        for c in clones:
            c.close()
        kv.close()
        server.stop()
    return res, alert_failures, routed


def _probe_after(kv, client, campaign, trace, seed, timeout=30.0):
    """Wait until some survivor advertises the chain's first block, then
    ask a surviving gateway to route one more request on that chain."""
    from tpu_sandbox.serve.replica import read_load_reports

    row = dict(workload.replay_order(trace)[0])
    row["prompt_tokens"] = max(int(row["prompt_tokens"]), BLOCK)
    prompt = campaign.prompt_for(row)
    head = chain_digest(prompt[:BLOCK], BLOCK)[0]
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        reports = read_load_reports(kv)
        if any(head in r.get("prefix_digest", ())
               for r in reports.values()):
            break
        time.sleep(0.02)
    else:
        raise AssertionError(f"no replica ever advertised block {head}")
    rid = f"probe-{seed}"
    routed = prefix_probe(client, prompt, rid)
    assert client.result(rid, timeout=60.0)["verdict"] == "ok"
    return routed


@pytest.mark.parametrize("seed", [101, 202, 303])
def test_matrix_campaign_zero_loss(seed):
    res, alert_failures, routed = _run_matrix_campaign(seed)
    assert res.ok, res.failures
    assert res.lost == []
    assert res.submitted == 16 and len(res.verdicts) == 16
    assert all(v["verdict"] == "ok" and v["tokens"]
               for v in res.verdicts.values())
    assert len(res.fired) == 5
    assert alert_failures == []
    assert routed, "prefix routing never engaged after the campaign"


def test_distinct_seeds_draw_distinct_campaigns():
    targets = {"kill_gateway": ["gw0", "gw1"],
               "shed_storm": ["w0", "w1", "w2"],
               "stall_replica": ["w0:0.3", "w1:0.3", "w2:0.3"]}
    drawn = [tuple(build_schedule(s, duration_s=0.8, targets=targets,
                                  n_faults=5))
             for s in (101, 202, 303)]
    assert len(set(drawn)) == 3


# -- agent-plane arm: kill_agent / partition_host against real HostAgents --
#
# The matrix campaigns above drive the serve fault mailbox and gateway
# kills; the agent actions (kill_agent, partition_host) were only ever
# exercised by the training-side fault matrix. This arm closes that gap:
# replicas run as rank SUBPROCESSES under real HostAgents (themselves
# subprocesses under AgentLauncher, so a kill_agent SIGKILL is a real
# process death and pdeathsig really takes the replica with it), and the
# campaign composes both agent actions mid-workload. A killed agent is
# respawned by the launcher, reports its lost ranks, and the leader
# bounces the whole gang to the next generation — the serve plane must
# ride through the bounce (leases lapse, peers scavenge, the queue
# drains) with zero lost requests. A partitioned agent goes silent on
# the control plane while its local replica keeps serving: the data
# plane must not notice.

_REPLICA_RANK = """
import os, sys, time
sys.path.insert(0, {root!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
from tpu_sandbox.models.transformer import TransformerConfig
from tpu_sandbox.runtime.kvstore import KVClient
from tpu_sandbox.serve.cache import CacheConfig
from tpu_sandbox.serve.engine import ContinuousEngine, ServeConfig
from tpu_sandbox.serve.replica import ReplicaWorker


class Stub:
    def __init__(self, buckets=(8, 16), vocab=64):
        self.buckets = tuple(buckets)
        self.vocab = vocab
        self.prefill = dict.fromkeys(self.buckets, self._prefill)

    def pick_bucket(self, plen):
        for b in self.buckets:
            if plen <= b:
                return b
        raise ValueError("prompt exceeds buckets")

    def _prefill(self, params, k, v, toks, dest, last):
        toks = np.asarray(toks)
        logits = np.zeros((self.vocab,), np.float32)
        logits[(int(toks[0, int(last)]) + 1) % self.vocab] = 1.0
        return logits, k, v

    def decode(self, params, k, v, tokens, lengths, tables):
        tokens = np.asarray(tokens)
        logits = np.zeros((tokens.shape[0], self.vocab), np.float32)
        for i in range(tokens.shape[0]):
            logits[i, (int(tokens[i, 0]) + 1) % self.vocab] = 1.0
        return logits, k, v


rank = int(sys.argv[1])
kv = KVClient(port=int(os.environ["TPU_SANDBOX_KV_PORT"]))
mcfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                         d_ff=64, max_len=128)
ccfg = CacheConfig(num_blocks=32, block_size=4, max_blocks_per_seq=8)
cfg = ServeConfig(model=mcfg, cache=ccfg, max_batch=2, buckets=(8, 16))
eng = ContinuousEngine(None, cfg, step=Stub(), clock=time.monotonic)
w = ReplicaWorker(kv, eng, tag="h%d" % rank, lease_ttl=1.0,
                  load_interval=0.02)
while kv.try_get("chaos/fleet_stop") is None:
    w.tick()
    time.sleep(0.001)
kv.close()
sys.exit(0)
"""

_AGENT_MAIN = """
import sys
sys.path.insert(0, {root!r})
from tpu_sandbox.runtime.host_agent import AgentConfig, HostAgent

aid, port, replica = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
cfg = AgentConfig(
    agent_id=aid, num_agents={n}, world_size={n}, kv_port=port,
    heartbeat_interval=0.1, agent_timeout=3.0, grace=30.0, lease_ttl=0.8,
    poll=0.02, term_timeout=5.0, ack_timeout=10.0, agent_wait=60.0,
    max_restarts=8, backoff=0.1, backoff_max=0.5)


def rank_cmd(gen, rank, coord_port):
    return [sys.executable, replica, str(rank)]


sys.exit(HostAgent(cfg, rank_cmd).run())
"""

N_AGENTS = 3


@pytest.mark.parametrize("seed", [404])
def test_agent_campaign_kill_and_partition_zero_loss(tmp_path, seed):
    import json
    import os
    import sys

    from tpu_sandbox.runtime.faults import agent_cmd_key
    from tpu_sandbox.runtime.host_agent import (AgentLauncher, K_JOB_DONE,
                                                K_RESTARTS)
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.serve.replica import read_load_reports

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    replica = tmp_path / "replica_rank.py"
    replica.write_text(_REPLICA_RANK.format(root=root))
    agent = tmp_path / "host_agent_main.py"
    agent.write_text(_AGENT_MAIN.format(root=root, n=N_AGENTS))

    server = KVServer()
    kv = KVClient(port=server.port)
    clones = []

    def clone():
        c = kv.clone()
        clones.append(c)
        return c

    launcher = AgentLauncher(
        N_AGENTS,
        lambda aid, port: [sys.executable, str(agent), str(aid), str(port),
                           str(replica)],
        kv_server=server, poll=0.05, drain_timeout=30.0,
        extra_env={"JAX_PLATFORMS": "cpu"}, verbose=True,
    )
    outcome = {}
    lt = threading.Thread(
        target=lambda: outcome.setdefault("code", launcher.run()),
        name="agent-launcher", daemon=True)
    lt.start()

    trace = workload.synthesize(seed, 12, duration_s=1.0,
                                prompt_tokens=(4, 10), decode_tokens=(2, 4))
    # agent 0 carries the election bias and rank 0's coordinator duty;
    # keeping it out of the pools keeps the control plane warm (same
    # shape as gw2 never being a kill candidate above). Both remaining
    # agents are fair game for both actions.
    schedule = build_schedule(seed, duration_s=1.0, targets={
        "kill_agent": ["1", "2"],
        "partition_host": ["1:1.2", "2:1.2"],
    }, n_faults=3)

    def kill_agent(target):
        kv.set(agent_cmd_key(int(target)),
               json.dumps({"action": "kill_agent", "arg": None}))

    def partition_host(target):
        aid, _, dur = target.partition(":")
        kv.set(agent_cmd_key(int(aid)),
               json.dumps({"action": "partition_host", "arg": float(dur)}))

    gws = {}
    client = None
    try:
        # wait for generation 1's replicas to report for duty before
        # opening the door (fresh interpreters pay the jax import)
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            if len(read_load_reports(kv)) >= N_AGENTS:
                break
            assert lt.is_alive(), "launcher died before the fleet was up"
            time.sleep(0.05)
        else:
            raise AssertionError("replicas never reported for duty")

        gws = {
            gid: Gateway(kv, [FleetSpec(block_size=BLOCK)], gateway_id=gid,
                         hb_ttl=0.5, refresh_min_s=0.005).start()
            for gid in ("gw0", "gw1")
        }
        client = GatewayClient(
            endpoints=[("127.0.0.1", g.port) for g in gws.values()],
            backoff_base=0.01)
        campaign = ChaosCampaign(
            clone(), trace, client.submit, seed=seed, schedule=schedule,
            hooks={"kill_agent": kill_agent,
                   "partition_host": partition_host},
            block_size=BLOCK, verdict_timeout=240.0)
        res = campaign.run()
        alert_failures = check_alert_claims(kv)

        # retire the fleet: ranks exit 0, agents converge on an ok verdict
        kv.set("chaos/fleet_stop", b"1")
        lt.join(timeout=120.0)
        assert not lt.is_alive(), "launcher never reached a verdict"
    finally:
        if client is not None:
            client.close()
        for g in gws.values():
            g.close()
        if lt.is_alive():  # belt and braces: unblock the join on failure
            kv.set("chaos/fleet_stop", b"1")
        verdict_raw = kv.try_get(K_JOB_DONE)
        restarts = int(kv.try_get(K_RESTARTS) or 0)
        for c in clones:
            c.close()
        kv.close()
        server.stop()

    assert res.ok, res.failures
    assert res.lost == []
    assert res.submitted == 12 and len(res.verdicts) == 12
    assert all(v["verdict"] == "ok" and v["tokens"]
               for v in res.verdicts.values())
    assert len(res.fired) == 3
    assert alert_failures == []
    assert outcome.get("code") == 0
    verdict = json.loads(verdict_raw)
    assert verdict["ok"], verdict
    fired = {f["action"] for f in res.fired}
    assert fired <= {"kill_agent", "partition_host"}
    if "kill_agent" in fired:
        # every SIGKILLed agent came back through the launcher, and the
        # leader charged the gang bounce to the restart budget
        assert launcher.respawns >= 1
        assert restarts >= 1
