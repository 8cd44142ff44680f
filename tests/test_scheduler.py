"""Cluster scheduler control plane, fast (fake agents, no jax): the
durable queue API, per-job KV namespacing, gang admission, priority
preemption, admission timeouts with namespace sweeps, and scheduler-death
adoption (satellite: random kill orders must leave the surviving job
undamaged and un-double-charged). The full two-job fault matrix with real
training runs slow in test_cluster_integration.py."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from tpu_sandbox.runtime.kvstore import (
    KVClient,
    KVServer,
    NamespacedKV,
    for_job,
    job_namespace,
)
from tpu_sandbox.runtime.scheduler import (
    ClusterScheduler,
    JobSpec,
    cancel_job,
    job_events,
    k_state,
    k_verdict,
    list_jobs,
    submit_job,
)

PY = sys.executable
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {"PYTHONPATH": ROOT}


# -- per-job namespacing (kvstore layer) -----------------------------------


def test_job_namespace_spelling():
    assert job_namespace("") == ""
    assert job_namespace("default") == ""  # bare-prefix default-job alias
    assert job_namespace("alpha") == "job/alpha/"
    for bad in ("a/b", "a b", "a\tb", "a\nb"):
        with pytest.raises(ValueError):
            job_namespace(bad)


def test_for_job_default_is_identity_and_jobs_are_isolated():
    with KVServer() as srv:
        kv = KVClient(port=srv.port)
        assert for_job(kv, "") is kv
        assert for_job(kv, "default") is kv
        a = for_job(kv, "a")
        b = for_job(kv, "b")
        assert isinstance(a, NamespacedKV)
        a.set("leader/term", b"3")
        b.set("leader/term", b"7")
        kv.set("leader/term", b"1")  # the default job's view
        # three elections, three stores-within-the-store
        assert a.get("leader/term") == b"3"
        assert b.get("leader/term") == b"7"
        assert kv.get("leader/term") == b"1"
        assert kv.get("job/a/leader/term") == b"3"  # where it really lives
        # keys() is namespace-relative; the sweep is namespace-bounded
        assert a.keys("leader/") == ["leader/term"]
        a.set("budget/restarts", b"1")
        assert a.delete_prefix("") == 2  # whole-job sweep, nobody else's
        assert kv.get("job/b/leader/term") == b"7"
        assert kv.get("leader/term") == b"1"
        # nesting two job prefixes is always a bug
        with pytest.raises(ValueError, match="nest"):
            for_job(a, "c")
        kv.close()


def test_namespaced_add_and_barrier():
    with KVServer() as srv:
        kv = KVClient(port=srv.port)
        a = for_job(kv, "a")
        assert a.add("budget/claim/1", 1) == 1
        assert a.add("budget/claim/1", 1) == 2
        assert kv.add("budget/claim/1", 1) == 1  # default job unaffected
        a.barrier(1, key="sync")  # single-member barrier completes
        kv.close()


# -- JobSpec validation ----------------------------------------------------


def test_job_spec_validation():
    ok = dict(hosts=1, world_size=1, agent_argv=["true"])
    JobSpec(job_id="fine", **ok)
    with pytest.raises(ValueError, match="real job id"):
        JobSpec(job_id="", **ok)
    with pytest.raises(ValueError, match="real job id"):
        JobSpec(job_id="default", **ok)
    with pytest.raises(ValueError):
        JobSpec(job_id="has/slash", **ok)
    with pytest.raises(ValueError, match="hosts"):
        JobSpec(job_id="j", hosts=0, world_size=1, agent_argv=["true"])
    # gang shape: every host must own at least one rank
    with pytest.raises(ValueError, match="at least one rank"):
        JobSpec(job_id="j", hosts=3, world_size=2, agent_argv=["true"])
    # template placeholders are validated at submit time, not spawn time
    with pytest.raises(ValueError, match="template"):
        JobSpec(job_id="j", hosts=1, world_size=1,
                agent_argv=["run", "--x", "{unknown_placeholder}"])
    spec = JobSpec(job_id="j", hosts=2, world_size=3,
                   agent_argv=["run", "{agent_id}", "{kv_port}", "{job_id}",
                               "{num_agents}", "{world_size}"])
    assert spec.format_argv(agent_id=1, kv_port=99) == \
        ["run", "1", "99", "j", "2", "3"]
    assert JobSpec.from_json(spec.to_json()) == spec


# -- durable queue API -----------------------------------------------------


def test_submit_list_cancel_roundtrip():
    with KVServer() as srv:
        kv = KVClient(port=srv.port)
        s1 = submit_job(kv, JobSpec(job_id="a", hosts=1, world_size=1,
                                    agent_argv=["true"], priority=2))
        s2 = submit_job(kv, JobSpec(job_id="b", hosts=2, world_size=2,
                                    agent_argv=["true"]))
        assert s2 == s1 + 1
        jobs = list_jobs(kv)
        assert [j["job_id"] for j in jobs] == ["a", "b"]
        assert jobs[0] == {"job_id": "a", "state": "queued", "seq": s1,
                           "priority": 2, "hosts": 1, "world_size": 1,
                           "tenant": "", "share": 1.0, "cogroup": ""}
        with pytest.raises(ValueError, match="already exists"):
            submit_job(kv, JobSpec(job_id="a", hosts=1, world_size=1,
                                   agent_argv=["true"]))
        assert "submitted" in job_events(kv, "a")
        cancel_job(kv, "a")
        assert kv.try_get("sched/jobs/a/cancel") == b"1"
        kv.close()


# -- fake agents -----------------------------------------------------------
#
# Each agent is a real subprocess speaking the job-namespaced protocol the
# scheduler watches: heartbeat under agent_hb/<id>, verdict to job/done.
# Mirrors test_host_agent's _FAKE_AGENT idiom, one level up the stack.

_AGENT = """
import importlib.util, json, os, signal, sys, time
# load kvstore.py directly: the package __init__ drags in jax, which is
# ~0.5s of startup tax on each of the ~16 agents this suite spawns
_spec = importlib.util.spec_from_file_location(
    "_kv", os.path.join({root!r}, "tpu_sandbox", "runtime", "kvstore.py"))
_kv = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_kv)
KVClient, for_job = _kv.KVClient, _kv.for_job
aid = int(sys.argv[1]); port = int(sys.argv[2]); job = sys.argv[3]
mode = sys.argv[4]; arg = float(sys.argv[5]) if len(sys.argv) > 5 else 0.0
kv = for_job(KVClient(port=port), job)
stop = []
signal.signal(signal.SIGTERM, lambda s, f: stop.append(1))
# published only after the handler is in place: tests that wait on this
# key may then SIGTERM us without racing the default (kill) disposition
kv.set(f"test/ran/{{aid}}", str(os.getpid()))

def beat():
    kv.set_ttl(f"agent_hb/{{aid}}", repr(time.time()), 5.0)

def done(ok, preempted=False):
    if aid == 0:
        kv.set("job/done", json.dumps(
            {{"ok": ok, "preempted": preempted, "reason": "fake agent",
              "summary": "", "restarts": int(kv.try_get("budget/restarts")
                                             or 0),
              "preemptions": 0, "generations": 1}}))

if mode == "work":        # heartbeat for `arg` seconds, then succeed
    t0 = time.monotonic()
    while time.monotonic() - t0 < arg and not stop:
        beat(); time.sleep(0.03)
    if stop:
        done(False, preempted=True); sys.exit(75)
    done(True); time.sleep(0.1); sys.exit(0)
elif mode == "mortal":      # first life runs long; respawned lives crash
    lives = kv.add(f"test/lives/{{aid}}", 1)
    if lives >= 2:
        sys.exit(9)
    t0 = time.monotonic()
    while time.monotonic() - t0 < 60 and not stop:
        beat(); time.sleep(0.03)
    sys.exit(75 if stop else 0)
elif mode == "preemptible":
    # first life: run until SIGTERM, checkpoint-through-preemption;
    # second life: note the resume and finish clean, uncharged.
    # lives are PER AGENT: a gang's ranks must not count each other
    lives = kv.add(f"test/lives/{{aid}}", 1)
    if lives >= 2:
        kv.set("test/resumed", b"1")
        done(True); time.sleep(0.1); sys.exit(0)
    t0 = time.monotonic()
    while time.monotonic() - t0 < 60 and not stop:
        beat(); time.sleep(0.03)
    done(False, preempted=True)
    sys.exit(75)
"""


def _agent_argv(script, mode, arg=0.0):
    return [PY, str(script), "{agent_id}", "{kv_port}", "{job_id}",
            mode, str(arg)]


@pytest.fixture()
def agent_script(tmp_path):
    script = tmp_path / "fake_sched_agent.py"
    script.write_text(_AGENT.format(root=ROOT))
    return script


# -- gang admission --------------------------------------------------------


def test_gang_is_all_or_nothing(agent_script):
    """Pool of 3, two 2-host jobs: the second must not launch ANY agent
    (not even for the one free slot) until the first gang's slots free."""
    with ClusterScheduler(3, poll=0.02, extra_env=ENV,
                          verbose=False) as sched:
        sched.submit(JobSpec(job_id="first", hosts=2, world_size=3,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.5)))
        sched.submit(JobSpec(job_id="second", hosts=2, world_size=2,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.1)))
        saw_partial = []
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            states = {j["job_id"]: j["state"] for j in list_jobs(sched.kv)}
            second_agents = sched.kv.keys("job/second/test/ran/")
            if states.get("first") == "running" \
                    and states.get("second") == "queued" \
                    and second_agents:
                saw_partial.append(second_agents)
            if states.get("second") != "queued":
                break
            sched._tick()
            time.sleep(0.02)
        states = sched.serve(timeout=60)
        assert saw_partial == [], "gang launched while still queued"
        assert states == {"first": "done", "second": "done"}, states
        # both gangs eventually ran with their FULL host set
        ev = job_events(sched.kv, "second")
        assert ev["admitted"] >= ev["submitted"]


def test_heterogeneous_world_sizes_share_the_pool(agent_script):
    """3 ranks on 2 hosts next to 1 rank on 1 host: world % hosts != 0 is
    admissible (the launch record carries the rank table — unit-proven in
    test_host_agent.test_assign_ranks_heterogeneous)."""
    with ClusterScheduler(3, poll=0.02, extra_env=ENV,
                          verbose=False) as sched:
        sched.submit(JobSpec(job_id="train", hosts=2, world_size=3,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.3)))
        sched.submit(JobSpec(job_id="bench", hosts=1, world_size=1,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.3)))
        states = sched.serve(timeout=60)
        assert states == {"train": "done", "bench": "done"}, states
        # both gangs' namespaces were swept on completion
        assert sched.kv.keys("job/train/") == []
        assert sched.kv.keys("job/bench/") == []


# -- MPMD co-gangs: cogroup all-or-nothing admission ------------------------


def test_cogroup_admitted_all_or_nothing(agent_script):
    """Pool 3, a 2-host occupant running: a 2-member cogroup (1 host each)
    must NOT take the single free slot piecemeal — stage 1 without stage 0
    would just block on the transport. Both members admit together once
    the occupant drains."""
    with ClusterScheduler(3, poll=0.02, extra_env=ENV,
                          verbose=False) as sched:
        sched.submit(JobSpec(job_id="occupant", hosts=2, world_size=2,
                             priority=5,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.6)))
        assert _tick_until(sched, lambda: (
            sched.kv.try_get(k_state("occupant")) == b"running"))
        for s in (0, 1):
            sched.submit(JobSpec(job_id=f"stage{s}", hosts=1, world_size=1,
                                 priority=5, cogroup="pipe0",
                                 agent_argv=_agent_argv(agent_script, "work",
                                                        0.2)))
        # 1 slot free, group needs 2: neither member may launch — a bare
        # 1-host head WOULD fit, so any launch here is the cogroup bug
        for _ in range(10):
            sched._tick()
            time.sleep(0.02)
        assert sched.kv.try_get(k_state("stage0")) == b"queued"
        assert sched.kv.try_get(k_state("stage1")) == b"queued"
        assert sched.kv.keys("job/stage0/test/ran/") == []
        assert sched.kv.keys("job/stage1/test/ran/") == []
        states = sched.serve(timeout=60)
        assert states == {"occupant": "done", "stage0": "done",
                          "stage1": "done"}, states
        # co-admission: both members admitted in the same scheduling tick
        a0 = job_events(sched.kv, "stage0")["admitted"]
        a1 = job_events(sched.kv, "stage1")["admitted"]
        assert abs(a0 - a1) < 0.5, (a0, a1)


def test_cogroup_preempts_room_for_whole_group(agent_script):
    """A high-priority co-gang must carve out its TOTAL host need: the
    1-host head alone would fit beside the low-priority occupant, but
    victims are picked for the group's sum (2), so the occupant is
    preempted and both stages run."""
    with ClusterScheduler(2, poll=0.02, extra_env=ENV,
                          verbose=False) as sched:
        sched.submit(JobSpec(
            job_id="occupant", hosts=2, world_size=2, priority=0,
            agent_argv=_agent_argv(agent_script, "preemptible")))
        assert _tick_until(sched, lambda: (
            sched.kv.try_get(k_state("occupant")) == b"running"
            # BOTH agents' handlers in place: a SIGTERM that beats one of
            # them kills it outright and the preemption never completes
            and len(sched.kv.keys("job/occupant/test/ran/")) == 2))
        for s in (0, 1):
            sched.submit(JobSpec(job_id=f"stage{s}", hosts=1, world_size=1,
                                 priority=5, cogroup="pipe0",
                                 agent_argv=_agent_argv(agent_script, "work",
                                                        0.2)))
        states = sched.serve(timeout=120)
        assert states == {"occupant": "done", "stage0": "done",
                          "stage1": "done"}, states
        ev = job_events(sched.kv, "occupant")
        assert "preempt_sent" in ev and "readmitted" in ev
        # both stages were up while the occupant waited its turn back
        assert job_events(sched.kv, "stage0")["admitted"] \
            >= ev["preempt_sent"]


def test_cogroup_never_backfills_its_own_members(agent_script):
    """Backfill must not slip ONE member of the head's own co-gang into a
    free slot while the group as a whole is blocked — that is exactly the
    piecemeal admission cogroups exist to prevent."""
    with ClusterScheduler(3, poll=0.02, extra_env=ENV,
                          verbose=False) as sched:
        sched.submit(JobSpec(job_id="occupant", hosts=2, world_size=2,
                             priority=5,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.6)))
        assert _tick_until(sched, lambda: (
            sched.kv.try_get(k_state("occupant")) == b"running"))
        # head of the queue: the blocked co-gang (needs 2, only 1 free);
        # a LOWER-priority member of the same gang sits behind it and
        # would pass the plain backfill fit test
        sched.submit(JobSpec(job_id="stage0", hosts=1, world_size=1,
                             priority=5, cogroup="pipe0",
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.2)))
        sched.submit(JobSpec(job_id="stage1", hosts=1, world_size=1,
                             priority=0, cogroup="pipe0",
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.2)))
        for _ in range(10):
            sched._tick()
            time.sleep(0.02)
        assert sched.kv.try_get(k_state("stage1")) == b"queued"
        assert "backfilled" not in job_events(sched.kv, "stage1")
        states = sched.serve(timeout=60)
        assert all(s == "done" for s in states.values()), states


# -- priority preemption ---------------------------------------------------


def test_priority_preemption_checkpoints_and_resumes(agent_script):
    """Full pool, high-priority arrival: the low-priority job is SIGTERMed,
    posts a preempted (uncharged) verdict, re-queues at its original seq,
    and resumes after the high-priority job drains."""
    with ClusterScheduler(1, poll=0.02, extra_env=ENV,
                          verbose=False) as sched:
        low_seq = sched.submit(
            JobSpec(job_id="low", hosts=1, world_size=1, priority=0,
                    agent_argv=_agent_argv(agent_script, "preemptible")))
        # wait until low is actually running before outranking it
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            sched._tick()
            state = (sched.kv.try_get(k_state("low")) or b"").decode()
            if state == "running" and sched.kv.keys("job/low/test/ran/"):
                break
            time.sleep(0.02)
        sched.submit(
            JobSpec(job_id="high", hosts=1, world_size=1, priority=5,
                    agent_argv=_agent_argv(agent_script, "work", 0.3)))
        states = sched.serve(timeout=120)
        assert states == {"low": "done", "high": "done"}, states
        # the victim kept its place in line (seq unchanged through requeue)
        jobs = {j["job_id"]: j for j in list_jobs(sched.kv)}
        assert jobs["low"]["seq"] == low_seq
        ev_low = job_events(sched.kv, "low")
        ev_high = job_events(sched.kv, "high")
        # the event stamps, in causal order on the scheduler's clock
        assert ev_low["admitted"] <= ev_low["preempt_sent"] \
            <= ev_low["preempted"] <= ev_low["readmitted"]
        assert ev_high["admitted"] >= ev_low["preempt_sent"]
        # preemption was free: the resumed verdict charges no restarts
        verdict = json.loads(sched.kv.get(k_verdict("low")))
        assert verdict["ok"] and verdict["restarts"] == 0


# -- backfill --------------------------------------------------------------


def _tick_until(sched, pred, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        sched._tick()
        if pred():
            return True
        time.sleep(0.02)
    return False


def test_backfill_runs_small_job_behind_blocked_head(agent_script):
    """Pool 3: an equal-priority 2-host head can't preempt the 2-host
    occupant and can't fit the 1 free slot — a strictly-lower-priority
    1-host job may start behind it (and everything still finishes)."""
    with ClusterScheduler(3, poll=0.02, extra_env=ENV,
                          verbose=False) as sched:
        sched.submit(JobSpec(job_id="occupant", hosts=2, world_size=2,
                             priority=5,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.6)))
        assert _tick_until(sched, lambda: (
            sched.kv.try_get(k_state("occupant")) == b"running"))
        sched.submit(JobSpec(job_id="head", hosts=2, world_size=2,
                             priority=5,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.2)))
        sched.submit(JobSpec(job_id="small", hosts=1, world_size=1,
                             priority=0,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.2)))
        assert _tick_until(sched, lambda: (
            sched.kv.try_get(k_state("small")) == b"running"))
        # the head is still blocked and queued — small jumped it, safely
        assert sched.kv.try_get(k_state("head")) == b"queued"
        assert "backfilled" in job_events(sched.kv, "small")
        states = sched.serve(timeout=60)
        assert states == {"occupant": "done", "head": "done",
                          "small": "done"}, states


@pytest.mark.slow  # ~4s of real agent work; tier-1 keeps the positive case
def test_backfill_never_admits_equal_priority(agent_script):
    """An equal-priority candidate could starve the head (the head can't
    preempt it back out), so it must wait in line even when it fits."""
    with ClusterScheduler(3, poll=0.02, extra_env=ENV,
                          verbose=False) as sched:
        sched.submit(JobSpec(job_id="occupant", hosts=2, world_size=2,
                             priority=5,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.6)))
        assert _tick_until(sched, lambda: (
            sched.kv.try_get(k_state("occupant")) == b"running"))
        sched.submit(JobSpec(job_id="head", hosts=2, world_size=2,
                             priority=5,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.2)))
        sched.submit(JobSpec(job_id="peer", hosts=1, world_size=1,
                             priority=5,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.2)))
        for _ in range(10):
            sched._tick()
            time.sleep(0.02)
        assert sched.kv.try_get(k_state("peer")) == b"queued"
        assert sched.kv.keys("job/peer/test/ran/") == []
        assert "backfilled" not in job_events(sched.kv, "peer")
        states = sched.serve(timeout=60)
        assert states == {"occupant": "done", "head": "done",
                          "peer": "done"}, states
        # FIFO held: the head went first once the occupant's slots freed
        assert job_events(sched.kv, "head")["admitted"] <= \
            job_events(sched.kv, "peer")["admitted"]


@pytest.mark.slow  # ~4s of real agent work; tier-1 keeps the positive case
def test_backfill_starvation_guard_near_head_deadline(agent_script):
    """Once the head has burned half its admission window, backfill stops
    — the remaining window is reserved for making room."""
    with ClusterScheduler(3, poll=0.02, extra_env=ENV,
                          verbose=False) as sched:
        sched.submit(JobSpec(job_id="occupant", hosts=2, world_size=2,
                             priority=5,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.6)))
        assert _tick_until(sched, lambda: (
            sched.kv.try_get(k_state("occupant")) == b"running"))
        sched.submit(JobSpec(job_id="head", hosts=2, world_size=2,
                             priority=5,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.2)))
        sched._tick()  # registers the head's admission deadline
        # simulate the head having consumed ~75% of its 120s window
        sched._queue_deadline["head"] = time.monotonic() + 30.0
        sched.submit(JobSpec(job_id="late", hosts=1, world_size=1,
                             priority=0,
                             agent_argv=_agent_argv(agent_script, "work",
                                                    0.2)))
        for _ in range(10):
            sched._tick()
            time.sleep(0.02)
        assert sched.kv.try_get(k_state("late")) == b"queued"
        assert "backfilled" not in job_events(sched.kv, "late")
        states = sched.serve(timeout=60)
        assert states == {"occupant": "done", "head": "done",
                          "late": "done"}, states


# -- admission deadline + sweep --------------------------------------------


def test_unsatisfiable_job_times_out_with_clean_namespace(agent_script):
    with ClusterScheduler(1, poll=0.02, extra_env=ENV,
                          verbose=False) as sched:
        sched.start()
        # leaked-looking state from a previous life of the same id: the
        # sweep must take it out with the timeout
        ghost = for_job(sched.kv, "huge")
        ghost.set("leader/term", b"9")
        ghost.set("budget/claim/3", b"2")
        sched.submit(JobSpec(job_id="huge", hosts=4, world_size=4,
                             agent_argv=_agent_argv(agent_script, "work"),
                             admission_timeout=0.3))
        states = sched.serve(timeout=30)
        assert states == {"huge": "timeout"}, states
        # THE namespace-sweep assertion: no leaked claims anywhere
        assert sched.kv.keys(job_namespace("huge")) == []
        assert "timeout" in job_events(sched.kv, "huge")


# -- weighted fair share ---------------------------------------------------


def test_weighted_fair_share_converges_to_tenant_weights(agent_script):
    """Two equal-priority tenants on a pool of 1, shares 2:1.  Jobs are
    submitted interleaved (so raw seq order favours neither) and all have
    the same duration; the admission order must track virtual time, i.e.
    at every decision point the normalised service |served_a/2 - served_b|
    stays within one job of balanced.  Plain FIFO would drift to 1.5.
    Jobs are short — the property is about admission ORDER, and vtime
    normalises by duration, so only equality of durations matters."""
    alpha = [f"a{i}" for i in range(6)]
    beta = [f"b{i}" for i in range(3)]
    with ClusterScheduler(1, poll=0.02, extra_env=ENV,
                          verbose=False) as sched:
        # interleave submissions: a0 b0 a1 b1 a2 b2 a3 a4 a5
        order = [j for pair in zip(alpha, beta) for j in pair] + alpha[3:]
        for jid in order:
            tenant = "alpha" if jid.startswith("a") else "beta"
            share = 2.0 if tenant == "alpha" else 1.0
            sched.submit(JobSpec(
                job_id=jid, hosts=1, world_size=1, tenant=tenant,
                share=share,
                agent_argv=_agent_argv(agent_script, "work", 0.2)))
        states = sched.serve(timeout=120)
        assert all(s == "done" for s in states.values()), states
        admitted = sorted(
            alpha + beta, key=lambda j: job_events(sched.kv, j)["admitted"])
        na = nb = 0
        for jid in admitted:
            if jid.startswith("a"):
                na += 1
            else:
                nb += 1
            assert abs(na / 2.0 - nb / 1.0) <= 1.0, \
                f"service drifted from 2:1 weights at {admitted}"
        # both tenants were charged virtual time, normalised by share:
        # 6 jobs at share 2 and 3 jobs at share 1 accrue about equally.
        va, vb = sched.tenant_vtime("alpha"), sched.tenant_vtime("beta")
        assert va > 0 and vb > 0
        assert 0.4 < va / vb < 2.5, (va, vb)


@pytest.mark.slow  # ~12s of subprocess scheduler work; tier-1 keeps the
# in-process convergence test above plus both death-adoption kill orders
def test_vtime_ledger_survives_scheduler_death(agent_script):
    """Satellite: kill the scheduler mid-run; the successor must restore
    the per-tenant virtual-time ledger from sched/vtime/<tenant> and keep
    the 2:1 weighted convergence across the whole admission sequence — a
    successor that reset the ledger would restart both tenants at zero
    service and owe alpha nothing for what it already consumed."""
    alpha = [f"a{i}" for i in range(6)]
    beta = [f"b{i}" for i in range(3)]
    with KVServer() as srv:
        kv = KVClient(port=srv.port)
        order = [j for pair in zip(alpha, beta) for j in pair] + alpha[3:]
        for jid in order:
            tenant = "alpha" if jid.startswith("a") else "beta"
            submit_job(kv, JobSpec(
                job_id=jid, hosts=1, world_size=1, tenant=tenant,
                share=2.0 if tenant == "alpha" else 1.0,
                agent_argv=_agent_argv(agent_script, "work", 0.4)))
        sched1 = _spawn_scheduler_proc(srv.port, pool=1)
        try:
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                done = [j for j in list_jobs(kv) if j["state"] == "done"]
                if len(done) >= 3:
                    break
                time.sleep(0.05)
            else:
                pytest.fail("first scheduler never finished 3 jobs")
            _reap(sched1)
            # the ledger the dead scheduler persisted job-by-job
            persisted = {t: float(kv.get(f"sched/vtime/{t}"))
                         for t in ("alpha", "beta")}
            assert persisted["alpha"] > 0 and persisted["beta"] > 0
            with ClusterScheduler(1, kv_port=srv.port, poll=0.02,
                                  adopt_timeout=2.0, extra_env=ENV,
                                  verbose=False) as s2:
                s2.start()
                # restored BEFORE any new charge, not recomputed from zero
                assert s2.tenant_vtime("alpha") == persisted["alpha"]
                assert s2.tenant_vtime("beta") == persisted["beta"]
                states = s2.serve(timeout=120)
            assert all(s == "done" for s in states.values()), states
            admitted = sorted(
                alpha + beta, key=lambda j: job_events(kv, j)["admitted"])
            na = nb = 0
            for jid in admitted:
                if jid.startswith("a"):
                    na += 1
                else:
                    nb += 1
                assert abs(na / 2.0 - nb / 1.0) <= 1.0, \
                    f"2:1 convergence broken across restart: {admitted}"
        finally:
            if sched1.poll() is None:
                _reap(sched1)
            kv.close()


# -- serve/train colocation (autoscaler drives the scheduler) --------------


def test_autoscaler_preempts_training_and_returns_slots(agent_script):
    """End-to-end colocation story against a live scheduler: a queue-depth
    spike makes the autoscaler grow the serve gang at high priority, which
    preempts the low-priority 2-host training gang (checkpoint-out via
    SIGTERM, uncharged requeue); once load subsides the gang shrinks
    newest-first and training resumes on the returned slots and finishes
    clean.  The whole episode must be reconstructable from job_events +
    autoscale_events alone.  (Bitwise resume parity is proven by
    test_priority_preemption_checkpoints_and_resumes and the checkpoint
    suite; replica drain zero-loss by the serve SLO/chaos tests — here the
    stub agents prove the slot choreography.)"""
    from tpu_sandbox.serve.autoscale import (AutoscaleConfig,
                                             ReplicaAutoscaler,
                                             autoscale_events)
    from tpu_sandbox.serve.replica import k_load

    with ClusterScheduler(2, poll=0.02, extra_env=ENV,
                          verbose=False) as sched:
        sched.submit(JobSpec(
            job_id="train", hosts=2, world_size=2, priority=0,
            tenant="train",
            agent_argv=_agent_argv(agent_script, "preemptible")))
        assert _tick_until(sched, lambda: (
            sched.kv.try_get(k_state("train")) == b"running"
            # both agents' SIGTERM handlers in place (see the cogroup test)
            and len(sched.kv.keys("job/train/test/ran/")) == 2))
        asc = ReplicaAutoscaler(
            sched.kv, _agent_argv(agent_script, "work", 60.0),
            cfg=AutoscaleConfig(min_replicas=0, max_replicas=2,
                                scale_up_depth=4.0, scale_down_depth=0.5,
                                hysteresis_ticks=1, cooldown_s=0.0,
                                priority=10))

        def report(depth):
            sched.kv.set_ttl(k_load("stub"),
                             json.dumps({"queue_depth": depth}), 60.0)

        # overload: the replica engines report deep queues
        report(9.0)
        up1 = asc.tick()
        assert up1 and up1["action"] == "scale_up" \
            and up1["reason"] == "queue_depth"
        rep1, rep2 = up1["job_id"], None
        # the 1-host serve job outranks the 2-host training gang: training
        # is SIGTERMed, checkpoints out, and requeues at its original seq
        assert _tick_until(sched, lambda: (
            sched.kv.keys(f"job/{rep1}/test/ran/")
            and sched.kv.try_get(k_state("train")) == b"queued"))
        up2 = asc.tick()
        assert up2 and up2["action"] == "scale_up" and up2["n_after"] == 2
        rep2 = up2["job_id"]
        # wait for the replica agents themselves (not just the admission
        # record) so the scale-down SIGTERM can't race their startup
        assert _tick_until(sched, lambda: (
            sched.kv.keys(f"job/{rep2}/test/ran/")))
        # training needs 2 hosts and 0 are free: it must stay queued, NOT
        # half-launch (gang admission is all-or-nothing)
        assert sched.kv.try_get(k_state("train")) == b"queued"

        # load subsides: shrink newest-first, handing slots back
        report(0.0)
        down1 = asc.tick()
        assert down1 and down1["action"] == "scale_down" \
            and down1["job_id"] == rep2
        assert _tick_until(sched, lambda: (
            sched.kv.try_get(k_state(rep2)) == b"cancelled"))
        # 1 free host is still not enough for the 2-host training gang
        assert sched.kv.try_get(k_state("train")) == b"queued"
        down2 = asc.tick()
        assert down2 and down2["action"] == "scale_down" \
            and down2["job_id"] == rep1

        states = sched.serve(timeout=120)
        assert states["train"] == "done", states
        assert states[rep1] == "cancelled" and states[rep2] == "cancelled"
        # the resumed verdict is the second stub life's, uncharged
        verdict = json.loads(sched.kv.get(k_verdict("train")))
        assert verdict["ok"] and verdict["restarts"] == 0
        # the timeline: preempted before the re-admission that finished it
        ev = job_events(sched.kv, "train")
        assert ev["admitted"] <= ev["preempt_sent"] <= ev["preempted"] \
            <= ev["readmitted"]
        # and the autoscaler's own event log tells the same story
        actions = [(e["action"], e["job_id"])
                   for e in autoscale_events(sched.kv)]
        assert actions == [("scale_up", rep1), ("scale_up", rep2),
                           ("scale_down", rep2), ("scale_down", rep1)]


# -- scheduler death / adoption (satellite: random kill orders) ------------


def _spawn_scheduler_proc(port, pool):
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from tpu_sandbox.runtime.scheduler import ClusterScheduler\n"
        "ClusterScheduler(%d, kv_port=%d, poll=0.02,\n"
        "                 verbose=False).serve(timeout=120)\n"
        % (ROOT, pool, port)
    )
    return subprocess.Popen([PY, "-c", code],
                            env={**os.environ, "PYTHONPATH": ROOT})


def _reap(proc):
    proc.kill()
    proc.wait(timeout=10)  # raises if SIGKILL has not taken it by then


@pytest.mark.parametrize("kill_order", [
    ("scheduler", "victim_agent"),
    ("victim_agent", "scheduler"),
])
def test_scheduler_death_leaves_survivor_unharmed(agent_script, kill_order):
    """Kill the scheduler process and one job's agent in both orders: the
    OTHER job must finish clean (no deadlock) with zero restarts charged
    (no double-charge), reaped by a successor scheduler that adopts what
    the dead one left running."""
    with KVServer() as srv:
        kv = KVClient(port=srv.port)
        submit_job(kv, JobSpec(
            job_id="victim", hosts=1, world_size=1,
            agent_argv=_agent_argv(agent_script, "mortal")))
        submit_job(kv, JobSpec(
            job_id="survivor", hosts=1, world_size=1,
            agent_argv=_agent_argv(agent_script, "work", 2.0)))
        sched1 = _spawn_scheduler_proc(srv.port, pool=2)
        try:
            # wait for both gangs to be up (agents registered their pids)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if kv.keys("job/victim/test/ran/") \
                        and kv.keys("job/survivor/test/ran/"):
                    break
                time.sleep(0.05)
            else:
                pytest.fail("jobs never started under the scheduler")
            victim_pid = int(kv.get("job/victim/test/ran/0"))
            for target in kill_order:
                if target == "scheduler":
                    _reap(sched1)
                else:
                    os.kill(victim_pid, signal.SIGKILL)
                time.sleep(0.1)
            # the survivor's agent is parented to the dead scheduler but
            # keeps running — its verdict lands without any scheduler
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if kv.try_get("job/survivor/job/done") is not None:
                    break
                time.sleep(0.05)
            else:
                pytest.fail("survivor deadlocked after the kills")
            # a successor adopts the wreckage: survivor reaped as done,
            # the victim's dead gang detected by silence and failed
            with ClusterScheduler(2, kv_port=srv.port, poll=0.02,
                                  adopt_timeout=1.0, verbose=False) as s2:
                states = s2.serve(timeout=120)
            assert states["survivor"] == "done", states
            assert states["victim"] == "failed", states
            verdict = json.loads(kv.get(k_verdict("survivor")))
            assert verdict["ok"] and verdict["restarts"] == 0
            # both namespaces swept; neither job can leak into a third
            assert kv.keys("job/survivor/") == []
            assert kv.keys("job/victim/") == []
        finally:
            if sched1.poll() is None:
                _reap(sched1)
            kv.close()
