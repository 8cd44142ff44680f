"""Fast cross-host elastic tests: HostAgents as in-process threads over one
KVServer, ranks as tiny ``python -c`` subprocesses. Covers the control
plane end to end (election → launch → report → resolve → relaunch →
verdict) without the jax-importing workers of the slow
test_multihost_elastic_integration module."""

import json
import sys
import threading
import time

import pytest

from tpu_sandbox.runtime.faults import (
    Fault,
    FaultInjector,
    FaultPlan,
    agent_cmd_key,
)
from tpu_sandbox.runtime.host_agent import (
    AgentConfig,
    AgentLauncher,
    HostAgent,
    K_GENERATION,
    K_JOB_DONE,
    K_PREEMPTIONS,
    K_RESTARTS,
    assign_ranks,
    ranks_for_agent,
)
from tpu_sandbox.runtime.kvstore import KVClient, KVServer
from tpu_sandbox.runtime.supervisor import PREEMPTED_EXIT_CODE, RankGroup

PY = sys.executable


# -- pure helpers ----------------------------------------------------------

def test_ranks_for_agent_contiguous_blocks():
    assert ranks_for_agent(0, 2, 4) == [0, 1]
    assert ranks_for_agent(1, 2, 4) == [2, 3]
    assert ranks_for_agent(2, 3, 3) == [2]


def test_assign_ranks_heterogeneous():
    # uneven worlds split into balanced contiguous blocks, extras first
    assert assign_ranks(3, 2) == [[0, 1], [2]]
    assert assign_ranks(7, 3) == [[0, 1, 2], [3, 4], [5, 6]]
    assert assign_ranks(4, 4) == [[0], [1], [2], [3]]
    # every world size covers exactly ranks 0..world-1, in order
    for world in range(1, 12):
        for agents in range(1, world + 1):
            flat = [r for b in assign_ranks(world, agents) for r in b]
            assert flat == list(range(world))
    # an over-provisioned gang is an admission-time error, never idle hosts
    with pytest.raises(ValueError, match="at least one rank"):
        assign_ranks(2, 3)
    with pytest.raises(ValueError, match="num_agents"):
        assign_ranks(4, 0)


# -- RankGroup -------------------------------------------------------------

def test_rank_group_spawn_poll_teardown():
    g = RankGroup(term_timeout=5.0)
    g.spawn([[PY, "-c", "import sys; sys.exit(3)"],
             [PY, "-c", "import sys; sys.exit(0)"]], None)
    assert len(g) == 2
    deadline = time.monotonic() + 10
    while g.running and time.monotonic() < deadline:
        time.sleep(0.02)
    assert g.poll() == [3, 0]
    assert g.teardown() == [3, 0]  # idempotent on dead groups


def test_rank_group_refuses_overlapping_spawn():
    g = RankGroup(term_timeout=5.0)
    g.spawn([[PY, "-c", "import time; time.sleep(60)"]], None)
    with pytest.raises(RuntimeError, match="previous group"):
        g.spawn([[PY, "-c", "pass"]], None)
    codes = g.teardown()
    assert codes[0] is not None  # SIGTERM'd, not still running
    g.spawn([[PY, "-c", "pass"]], None)  # after teardown: allowed
    g.teardown()


# -- fault routing to the agent mailbox ------------------------------------

def test_agent_fault_posts_to_mailbox():
    with KVServer() as srv:
        kv = KVClient(port=srv.port)
        plan = FaultPlan([Fault(rank=1, step=2, action="kill_agent")])
        inj = FaultInjector(plan, rank=1, kv=kv, agent_id=7)
        assert inj.maybe_fire(step=1) == []
        fired = inj.maybe_fire(step=2)
        assert [f.action for f in fired] == ["kill_agent"]
        cmd = json.loads(kv.get(agent_cmd_key(7)))
        assert cmd == {"action": "kill_agent", "arg": None}
        # claimed globally: a relaunched rank replaying step 2 won't re-fire
        inj2 = FaultInjector(plan, rank=1, kv=kv, agent_id=7)
        assert inj2.maybe_fire(step=2) == []
        kv.close()


def test_agent_fault_without_agent_context_fails_loud():
    plan = FaultPlan([Fault(rank=0, step=1, action="partition_host",
                            target="2.5")])
    inj = FaultInjector(plan, rank=0, kv=None, agent_id=None)
    with pytest.raises(RuntimeError, match="agent-mode"):
        inj.maybe_fire(step=1)


def test_partition_duration_validated():
    with pytest.raises(ValueError, match="duration"):
        Fault(rank=0, step=1, action="partition_host", target="soon")


# -- the agent/leader state machine (threads + subprocess ranks) -----------

def _cfg(aid, *, num_agents=2, world=2, port=0, **kw):
    kw.setdefault("heartbeat_interval", 0.1)
    kw.setdefault("agent_timeout", 3.0)
    kw.setdefault("grace", 20.0)
    kw.setdefault("lease_ttl", 0.8)
    kw.setdefault("poll", 0.02)
    kw.setdefault("term_timeout", 5.0)
    kw.setdefault("ack_timeout", 10.0)
    kw.setdefault("agent_wait", 20.0)
    kw.setdefault("backoff", 0.05)
    return AgentConfig(agent_id=aid, num_agents=num_agents,
                       world_size=world, kv_port=port, **kw)


def _run_agents(srv, rank_cmd, *, num_agents=2, world=2, timeout=40.0,
                cfg_kw=None):
    """Run one HostAgent per simulated host in threads; return exit codes."""
    results = {}

    def one(aid):
        cfg = _cfg(aid, num_agents=num_agents, world=world, port=srv.port,
                   **(cfg_kw or {}))
        results[aid] = HostAgent(cfg, rank_cmd).run()

    threads = [threading.Thread(target=one, args=(a,))
               for a in range(num_agents)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout)
    assert not any(t.is_alive() for t in threads), "agents never terminated"
    return [results[a] for a in range(num_agents)]


def test_clean_generation_reaches_ok_verdict():
    with KVServer() as srv:
        codes = _run_agents(
            srv, lambda gen, rank, port: [PY, "-c", "import sys; sys.exit(0)"]
        )
        assert codes == [0, 0]
        kv = KVClient(port=srv.port)
        verdict = json.loads(kv.get(K_JOB_DONE))
        assert verdict["ok"] and verdict["generations"] == 1
        assert verdict["restarts"] == 0
        kv.close()


def test_failure_charges_one_restart_then_recovers():
    """Gen 1 has a crashing rank; the leader tears the world down, charges
    exactly one restart (across two agents racing to resolve), and gen 2
    completes."""
    def rank_cmd(gen, rank, port):
        code = 1 if (gen == 1 and rank == 1) else 0
        return [PY, "-c", f"import sys; sys.exit({code})"]

    with KVServer() as srv:
        codes = _run_agents(srv, rank_cmd)
        assert codes == [0, 0]
        kv = KVClient(port=srv.port)
        verdict = json.loads(kv.get(K_JOB_DONE))
        assert verdict["ok"]
        assert int(kv.get(K_RESTARTS)) == 1
        assert int(kv.get(K_GENERATION)) == 2
        assert int(kv.try_get(K_PREEMPTIONS) or 0) == 0
        kv.close()


def test_preemption_is_not_charged_as_restart():
    def rank_cmd(gen, rank, port):
        code = PREEMPTED_EXIT_CODE if (gen == 1 and rank == 0) else 0
        return [PY, "-c", f"import sys; sys.exit({code})"]

    with KVServer() as srv:
        codes = _run_agents(srv, rank_cmd)
        assert codes == [0, 0]
        kv = KVClient(port=srv.port)
        verdict = json.loads(kv.get(K_JOB_DONE))
        assert verdict["ok"]
        assert int(kv.get(K_PREEMPTIONS)) == 1
        assert int(kv.try_get(K_RESTARTS) or 0) == 0
        kv.close()


def test_restart_budget_exhaustion_fails_the_job():
    with KVServer() as srv:
        codes = _run_agents(
            srv,
            lambda gen, rank, port: [PY, "-c", "import sys; sys.exit(1)"],
            cfg_kw={"max_restarts": 1},
        )
        assert codes == [1, 1]
        kv = KVClient(port=srv.port)
        verdict = json.loads(kv.get(K_JOB_DONE))
        assert not verdict["ok"] and not verdict["preempted"]
        assert "budget" in verdict["reason"]
        assert int(kv.get(K_RESTARTS)) == 2  # gen1 charge + gen2 over-budget
        kv.close()


# -- AgentLauncher (the scheduler stand-in) --------------------------------

_FAKE_AGENT = """
import json, sys
sys.path.insert(0, {root!r})
from tpu_sandbox.runtime.kvstore import KVClient
kv = KVClient(port=int(sys.argv[1]))
incarnation = kv.add("test/incarnation", 1)
if incarnation == 1:
    sys.exit(9)  # first life dies before any verdict
kv.set("job/done", json.dumps(
    {{"ok": True, "reason": "fake agent finished", "summary": "",
      "restarts": 0, "preemptions": 0, "generations": 1}}))
kv.close()
sys.exit(0)
"""


def test_launcher_respawns_dead_agent_until_verdict(tmp_path):
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "fake_agent.py"
    script.write_text(_FAKE_AGENT.format(root=root))
    with KVServer() as srv:
        launcher = AgentLauncher(
            1, lambda aid, port: [PY, str(script), str(port)],
            kv_server=srv, poll=0.05, drain_timeout=10,
        )
        assert launcher.run() == 0
        assert launcher.respawns == 1


def test_launcher_respawn_limit_bounds_crash_loops(tmp_path):
    script = tmp_path / "dying_agent.py"
    script.write_text("import sys; sys.exit(9)\n")
    with KVServer() as srv:
        launcher = AgentLauncher(
            1, lambda aid, port: [PY, str(script), str(port)],
            kv_server=srv, respawn_limit=2, poll=0.05, drain_timeout=5,
        )
        assert launcher.run() == 1
        assert launcher.respawns == 3  # 2 allowed + the one over the limit
