"""SLO guardrail layer, fast and in-process (tier-1).

Everything here runs the real engine/replica/client/autoscaler code paths
with a *stub* decode step (next token = last token + 1 mod vocab) — no jax
compiles, so the whole file stays inside the tier-1 budget, the pattern
test_scheduler.py uses for the cluster layer. The real-model paths under
faults are the slow-marked test_serve_integration.py.
"""

import json
import time

import numpy as np
import pytest

from tests.helpers import StubStep, kv_pair  # noqa: F401 (kv_pair: fixture)
from tpu_sandbox.models.transformer import TransformerConfig
from tpu_sandbox.serve.cache import CacheConfig
from tpu_sandbox.serve.engine import ContinuousEngine, Request, ServeConfig

MCFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                         d_ff=64, max_len=128)
CCFG = CacheConfig(num_blocks=24, block_size=4, max_blocks_per_seq=8)


class _Clock:
    def __init__(self):
        self.t = 0.0

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


def _engine(clock=None, **over):
    cfg = ServeConfig(model=MCFG, cache=CCFG, max_batch=2, buckets=(8, 16),
                      **over)
    return ContinuousEngine(None, cfg, step=StubStep(),
                            clock=clock or _Clock())


def _req(rid, n=3, **kw):
    return Request(rid=rid, prompt=[1, 2, 3], max_new_tokens=n, **kw)


# -- engine guardrails --------------------------------------------------------


def test_stub_engine_serves_end_to_end():
    eng = _engine()
    eng.submit(_req("r0", n=4))
    eng.run_until_idle()
    # next-token stub: 3 -> 4 -> 5 -> 6 -> 7
    assert eng.results["r0"].tokens == [4, 5, 6, 7]
    assert not eng.shed


def test_bounded_queue_sheds_incoming_with_verdict():
    eng = _engine(max_waiting=2)
    assert eng.submit(_req("r0"))
    assert eng.submit(_req("r1"))
    assert not eng.submit(_req("r2"))
    assert eng.shed["r2"].reason == "queue_full"
    # shed is terminal and exclusive: never also queued
    assert [r.rid for r in eng.waiting] == ["r0", "r1"]
    eng.drain_to_requests()


def test_overload_sheds_oldest_past_deadline_first():
    clock = _Clock()
    eng = _engine(clock, max_waiting=2)
    eng.submit(_req("r0", deadline=1.0))
    eng.submit(_req("r1"))
    clock.advance(2.0)  # r0 is now past its deadline
    assert eng.submit(_req("r2"))  # takes the slot r0's shed frees
    assert eng.shed["r0"].reason == "deadline"
    assert [r.rid for r in eng.waiting] == ["r1", "r2"]
    eng.drain_to_requests()


def test_no_result_ever_lands_past_deadline():
    clock = _Clock()
    eng = _engine(clock)
    # expires while waiting: shed before admission
    eng.submit(_req("rw", deadline=1.0))
    clock.advance(2.0)
    eng.step()
    assert eng.shed["rw"].reason == "deadline" and "rw" not in eng.results
    # expires while active: shed mid-flight, blocks returned
    free0 = eng.cache.free_blocks
    eng.submit(_req("ra", n=20, deadline=5.0))
    eng.step()  # admit + prefill
    assert eng.active_requests == 1
    clock.advance(10.0)
    eng.step()
    assert eng.shed["ra"].reason == "deadline" and "ra" not in eng.results
    assert eng.active_requests == 0 and eng.cache.free_blocks == free0
    # finishes past deadline (deadline passes inside the final step):
    # verdict is SHED, not a late result
    eng.submit(_req("rf", n=1, deadline=clock.t + 0.5))
    clock.advance(0.4)

    real_pick = eng._pick_token

    def slow_pick(slot, row):
        clock.advance(1.0)  # the step outlives the deadline
        return real_pick(slot, row)

    eng._pick_token = slow_pick
    eng.step()
    assert eng.shed["rf"].reason == "deadline" and "rf" not in eng.results


def test_overload_gives_every_request_exactly_one_verdict():
    """A seeded burst at twice what the engine drains, through a bounded
    queue with per-request deadlines: whatever is shed (at the queue, on
    its deadline while waiting, mid-flight) or completed, each request
    ends as a result or a SHED verdict, never both, never neither."""
    rng = np.random.default_rng(6)
    clock = _Clock()
    eng = _engine(clock, max_waiting=4)
    n = 48
    for i in range(n):
        eng.submit(_req(f"r{i}", n=int(rng.integers(2, 9)),
                        arrival=clock.t, deadline=clock.t + 6.0))
        if i % 2:  # two arrivals a step: twice the service rate
            clock.advance(1.0)
            eng.step()
    for _ in range(10 * n):  # every request ends or is shed well inside
        if eng.idle:
            break
        clock.advance(1.0)
        eng.step()
    assert eng.idle
    assert set(eng.results) | set(eng.shed) == {f"r{i}" for i in range(n)}
    assert not set(eng.results) & set(eng.shed)  # completed + shed == n
    # the overload was real, and both guardrails took part
    assert {v.reason for v in eng.shed.values()} == {"queue_full", "deadline"}
    assert eng.results


def test_load_report_signals():
    clock = _Clock()
    eng = _engine(clock, max_waiting=8)
    for i in range(4):
        eng.submit(_req(f"r{i}", n=6))
    eng.step()
    clock.advance(3.0)
    rep = eng.load_report()
    assert rep["active"] == 2 and rep["queue_depth"] == 2
    assert 0.0 < rep["free_block_frac"] < 1.0
    assert rep["step_age"] == pytest.approx(3.0)
    eng.run_until_idle()


# -- replica verdicts, load reports, fault mailbox ---------------------------


def _worker(kv, **over):
    from tpu_sandbox.serve.replica import ReplicaWorker

    eng_over = {k: over.pop(k) for k in ("max_waiting",) if k in over}
    over.setdefault("lease_ttl", 1.0)
    return ReplicaWorker(kv, _engine(**eng_over), **over)


def test_replica_publishes_shed_verdicts_and_results(kv_pair):
    from tpu_sandbox.serve import replica as R

    _, kv, _ = kv_pair
    w = _worker(kv, tag="w0")
    R.submit_request(kv, "ok0", [1, 2, 3], 3)
    # already expired at claim time: must still terminate with a verdict
    R.submit_request(kv, "late0", [1, 2, 3], 3,
                     deadline_unix=time.time() - 5.0)
    R.announce_total(kv, 2)
    w.run(timeout=30.0)
    ok = json.loads(kv.get(R.k_result("ok0")))
    late = json.loads(kv.get(R.k_result("late0")))
    assert ok["verdict"] == "ok" and ok["tokens"] == [4, 5, 6]
    assert late["verdict"] == "SHED" and late["reason"] == "deadline"
    assert R.results_done(kv)
    assert w.stats.completed == 1 and w.stats.shed == 1


def test_verdict_is_claim_once(kv_pair):
    from tpu_sandbox.serve import replica as R

    _, kv, _ = kv_pair
    a, b = _worker(kv, tag="wa"), _worker(kv, tag="wb")
    # same rid executed by both (scavenged-duplicate shape): one verdict
    R.submit_request(kv, "dup", [1, 2, 3], 2)
    R.enqueue(kv, "dup")  # duplicate queue entry
    a._publish_verdict("dup", {"rid": "dup", "verdict": "SHED",
                               "reason": "test", "replica": "wa"})
    b._publish_verdict("dup", {"rid": "dup", "verdict": "ok",
                               "tokens": [4, 5], "replica": "wb"})
    got = json.loads(kv.get(R.k_result("dup")))
    assert got["verdict"] == "SHED" and got["replica"] == "wa"
    a.engine.drain_to_requests()
    b.engine.drain_to_requests()


def test_replica_load_report_published(kv_pair):
    from tpu_sandbox.serve import replica as R

    _, kv, _ = kv_pair
    w = _worker(kv, tag="w0", load_interval=0.01)
    R.submit_request(kv, "r0", [1, 2, 3], 2)
    R.announce_total(kv, 1)
    w.run(timeout=30.0)
    reports = R.read_load_reports(kv)
    assert "w0" in reports
    assert {"queue_depth", "active", "free_block_frac",
            "step_age"} <= set(reports["w0"])


def test_shed_storm_fault_sheds_local_queue(kv_pair):
    from tpu_sandbox.runtime.faults import serve_cmd_key
    from tpu_sandbox.serve import replica as R

    _, kv, _ = kv_pair
    w = _worker(kv, tag="w0")
    for i in range(4):
        R.submit_request(kv, f"r{i}", [1, 2, 3], 2)
    R.announce_total(kv, 4)
    w.tick()  # claims land: max_batch in slots, the rest waiting locally
    assert len(w.engine.waiting) >= 1
    kv.set(serve_cmd_key("w0"), json.dumps({"action": "shed_storm"}))
    w.run(timeout=30.0)
    verdicts = [json.loads(kv.get(R.k_result(f"r{i}")))["verdict"]
                for i in range(4)]
    # every request terminated; the storm shed whatever was queued locally
    # at fire time (claim_depth 4 > max_batch 2, so some were waiting)
    assert verdicts.count("SHED") >= 1
    assert set(verdicts) <= {"ok", "SHED"}
    assert R.results_done(kv)


# -- client: retry on shed, hedging ------------------------------------------


def test_client_retries_shed_then_succeeds(kv_pair):
    from tpu_sandbox.serve import replica as R
    from tpu_sandbox.serve.client import ServeClient

    _, kv, _ = kv_pair
    client = ServeClient(kv, deadline_s=30.0, max_retries=2)
    client.submit("r0", [1, 2, 3], 3)
    # one replica sheds it (storm verdict), a second serves the retry
    storm = _worker(kv, tag="storm")
    storm._publish_verdict("r0", {"rid": "r0", "verdict": "SHED",
                                  "reason": "fault:shed_storm",
                                  "replica": "storm"})
    w = _worker(kv, tag="w0")
    # serve the retried entry in the background of the client poll: run a
    # few worker ticks interleaved by polling with a short timeout first
    got = None
    for _ in range(200):
        try:
            got = client.result("r0", timeout=0.05)
            break
        except TimeoutError:
            w.tick()
    assert got is not None and got["verdict"] == "ok"
    assert got["tokens"] == [4, 5, 6]
    assert client.stats.retries == 1


def test_client_raises_retries_exhausted_after_budget(kv_pair):
    from tpu_sandbox.serve.client import RetriesExhausted, ServeClient

    _, kv, _ = kv_pair
    client = ServeClient(kv, max_retries=1)
    # deadline already burnt: every execution sheds
    client.submit("r0", [1, 2, 3], 3, deadline_s=-1.0)
    w = _worker(kv, tag="w0")
    err = None
    for _ in range(200):
        try:
            client.result("r0", timeout=0.05)
            raise AssertionError("terminal shed must raise, not return")
        except TimeoutError:
            w.tick()
        except RetriesExhausted as e:
            err = e
            break
    assert err is not None
    assert err.rid == "r0" and err.last_reason == "deadline"
    assert err.verdict["verdict"] == "SHED"
    # the per-attempt timeline: the original submit plus one retry, each
    # stamped with its shed reason once resolved
    assert len(err.attempts) == 2
    assert all("submitted_at" in a for a in err.attempts)
    assert [a["shed_reason"] for a in err.attempts] == ["deadline"] * 2
    assert client.stats.retries == 1 and client.stats.shed == 1


def test_client_hedges_lost_claim(kv_pair):
    from tpu_sandbox.serve import replica as R
    from tpu_sandbox.serve.client import ServeClient

    _, kv, _ = kv_pair
    client = ServeClient(kv, deadline_s=30.0, hedge_after=0.01)
    client.submit("r0", [1, 2, 3], 3)
    # entry 0 claimed by a replica that died before leasing: no lease, no
    # result, nobody will ever finish it. Scavenge is parked (interval far
    # out) so the hedge path, not the scavenger, must do the rescue.
    assert kv.add(R.k_claim(0)) == 1
    time.sleep(0.02)
    w = _worker(kv, tag="w1", lease_ttl=0.2, scavenge_interval=60.0)
    got = None
    for _ in range(200):
        try:
            got = client.result("r0", timeout=0.05)
            break
        except TimeoutError:
            w.tick()
    assert got is not None and got["verdict"] == "ok"
    assert got["tokens"] == [4, 5, 6]
    assert client.stats.hedges == 1


# -- autoscaler ---------------------------------------------------------------


ARGV = ["python", "-m", "tpu_sandbox.serve.replica", "--config", "{job_id}"]


def _reports(kv, depths, ttl=10.0):
    from tpu_sandbox.serve.replica import k_load

    for tag, depth in depths.items():
        kv.set_ttl(k_load(tag), json.dumps({"queue_depth": depth}), ttl)


def test_autoscaler_bootstrap_grow_shrink(kv_pair):
    from tpu_sandbox.runtime.scheduler import k_cancel, list_jobs
    from tpu_sandbox.serve.autoscale import (AutoscaleConfig,
                                             ReplicaAutoscaler,
                                             autoscale_events)

    _, kv, _ = kv_pair
    cfg = AutoscaleConfig(min_replicas=1, max_replicas=2, hysteresis_ticks=2,
                          cooldown_s=0.0)
    a = ReplicaAutoscaler(kv, ARGV, cfg=cfg)
    # bootstrap to the floor, no hysteresis needed
    ev = a.tick()
    assert ev and ev["action"] == "scale_up" and ev["reason"] == "min_replicas"
    assert len(a.replica_jobs()) == 1
    # sustained overload: needs hysteresis_ticks consecutive signals
    _reports(kv, {"w0": 10.0})
    assert a.tick() is None
    ev = a.tick()
    assert ev and ev["action"] == "scale_up" and ev["reason"] == "queue_depth"
    assert len(a.replica_jobs()) == 2
    # capped at max_replicas even under continued overload
    assert a.tick() is None and a.tick() is None
    assert len(a.replica_jobs()) == 2
    # drained queues: scale back down to the floor, never below
    _reports(kv, {"w0": 0.0})
    assert a.tick() is None
    ev = a.tick()
    assert ev and ev["action"] == "scale_down"
    cancelled = ev["job_id"]
    assert kv.try_get(k_cancel(cancelled)) is not None
    # timeline reconstructable from the store
    actions = [e["action"] for e in autoscale_events(kv)]
    assert actions == ["scale_up", "scale_up", "scale_down"]
    # the gang jobs carry the serve tenancy for colocation
    for j in list_jobs(kv):
        if j["state"] == "queued":
            assert j["tenant"] == "serve" and j["priority"] == cfg.priority


def test_autoscaler_only_leader_acts(kv_pair):
    from tpu_sandbox.serve.autoscale import (AutoscaleConfig,
                                             ReplicaAutoscaler)

    _, kv, _ = kv_pair
    cfg = AutoscaleConfig(min_replicas=1, cooldown_s=0.0)
    leader = ReplicaAutoscaler(kv, ARGV, cfg=cfg, member_id="m0")
    follower = ReplicaAutoscaler(kv, ARGV, cfg=cfg, member_id="m1")
    assert leader.tick() is not None       # m0 wins the first election
    assert follower.tick() is None         # m1 observes, never acts
    assert len(leader.replica_jobs()) == 1


def test_autoscaler_hysteresis_resets_on_mixed_signal(kv_pair):
    from tpu_sandbox.serve.autoscale import (AutoscaleConfig,
                                             ReplicaAutoscaler)

    _, kv, _ = kv_pair
    cfg = AutoscaleConfig(min_replicas=1, max_replicas=3, hysteresis_ticks=2,
                          cooldown_s=0.0)
    a = ReplicaAutoscaler(kv, ARGV, cfg=cfg)
    a.tick()  # bootstrap
    _reports(kv, {"w0": 10.0})
    assert a.tick() is None
    _reports(kv, {"w0": 2.0})  # back inside the band: streak resets
    assert a.tick() is None
    _reports(kv, {"w0": 10.0})
    assert a.tick() is None    # streak restarted from zero
    ev = a.tick()
    assert ev and ev["action"] == "scale_up"


def test_autoscaler_prewarms_compile_cache(kv_pair, tmp_path):
    """Satellite: scale-ups point every replica at one shared XLA compile
    cache, and each event records whether the new replica finds it warm
    (deserialize executables) or cold (first compile pays full price)."""
    from tpu_sandbox.runtime.scheduler import JobSpec, k_spec
    from tpu_sandbox.serve.autoscale import (AutoscaleConfig,
                                             ReplicaAutoscaler)

    _, kv, _ = kv_pair
    cache = tmp_path / "xla-cache"
    cfg = AutoscaleConfig(min_replicas=1, max_replicas=2, hysteresis_ticks=1,
                          cooldown_s=0.0, compile_cache_dir=str(cache))
    a = ReplicaAutoscaler(kv, ARGV, cfg=cfg)
    ev = a.tick()  # bootstrap replica: nothing cached yet
    assert ev and ev["compile_cache"] == "cold"
    spec = JobSpec.from_json(kv.try_get(k_spec(ev["job_id"])).decode())
    assert spec.env["JAX_COMPILATION_CACHE_DIR"] == str(cache)
    # the bootstrap replica compiled and persisted its executables
    (cache / "xla_dump").write_bytes(b"cached executable")
    _reports(kv, {"w0": 10.0})
    ev = a.tick()  # load-driven scale-up reacts to a WARM cache
    assert ev and ev["action"] == "scale_up"
    assert ev["compile_cache"] == "warm"
    spec = JobSpec.from_json(kv.try_get(k_spec(ev["job_id"])).decode())
    assert spec.env["JAX_COMPILATION_CACHE_DIR"] == str(cache)
    # no cache dir configured -> events say so instead of guessing
    assert ReplicaAutoscaler(
        kv, ARGV, cfg=AutoscaleConfig(), member_id="m9",
    ).compile_cache_state() == "disabled"


# -- sampling (satellite: replay-exact requeue) ------------------------------


def test_sample_token_is_deterministic_and_top_k_bounded():
    from tpu_sandbox.serve.decode import sample_token

    rng = np.random.default_rng(0)
    logits = rng.normal(size=64).astype(np.float32)
    draws = {sample_token(logits, seed=7, step_index=3, temperature=0.8,
                          top_k=5) for _ in range(4)}
    assert len(draws) == 1  # same (seed, step) -> same token, always
    # different step indices decorrelate the stream
    seq = [sample_token(logits, seed=7, step_index=i, temperature=0.8)
           for i in range(32)]
    assert len(set(seq)) > 1
    # top_k=1 degenerates to argmax regardless of temperature
    assert sample_token(logits, seed=7, step_index=0, temperature=5.0,
                        top_k=1) == int(logits.argmax())


def test_sampled_request_replays_bitwise_after_requeue():
    """Kill-and-requeue a temperature/top-k request mid-decode (stub step):
    the replayed trajectory is identical because the sampler key folds the
    request seed with the decode-step index, both of which replay."""
    kw = dict(temperature=0.9, top_k=8, seed=42)
    ref = _engine()
    ref.submit(_req("s0", n=12, **kw))
    ref.run_until_idle()
    want = ref.results["s0"].tokens

    eng = _engine()
    eng.submit(_req("s0", n=12, **kw))
    for _ in range(5):
        eng.step()
    # replica death: everything in flight goes back to request form...
    reqs = eng.drain_to_requests()
    assert len(reqs) == 1 and reqs[0].temperature == 0.9
    # ...and replays from the original prompt on a fresh engine
    eng2 = _engine()
    eng2.submit(reqs[0])
    eng2.run_until_idle()
    assert eng2.results["s0"].tokens == want


# -- client: canary-share pinning before enqueue ------------------------------


def test_client_pins_canary_share_before_enqueue(kv_pair):
    from tpu_sandbox.deploy.registry import k_shares
    from tpu_sandbox.serve import replica as R
    from tpu_sandbox.serve.client import ServeClient

    _, kv, _ = kv_pair
    # no live shares (the common case): one try_get, no pin written
    quiet = ServeClient(kv)
    quiet.submit("r0", [1, 2, 3], 2)
    assert kv.try_get(R.k_pin("r0")) is None
    # a live canary split with all weight on version 7: every submit
    # pins to 7 BEFORE the enqueue, so the first claimer sees it
    kv.set(k_shares(""), json.dumps(
        {"seq": 7, "shares": {"7": 1.0, "0": 0.0}}))
    client = ServeClient(kv, share_seed=42)
    client.submit("r1", [1, 2, 3], 2)
    assert int(kv.get(R.k_pin("r1"))) == 7


def test_client_share_draws_seeded_and_split(kv_pair):
    from tpu_sandbox.deploy.registry import k_shares
    from tpu_sandbox.serve import replica as R
    from tpu_sandbox.serve.client import ServeClient

    _, kv, _ = kv_pair
    kv.set(k_shares(""), json.dumps(
        {"seq": 7, "shares": {"7": 0.5, "0": 0.5}}))

    def draw_sequence(seed, tag):
        c = ServeClient(kv, share_seed=seed)
        pins = []
        for i in range(8):
            rid = f"{tag}-{i}"
            c.submit(rid, [1, 2, 3], 2)
            pins.append(int(kv.get(R.k_pin(rid))))
        return pins

    a = draw_sequence(1234, "a")
    b = draw_sequence(1234, "b")
    assert a == b  # same seed -> same version sequence (replayable)
    assert set(a) == {0, 7}  # a 50/50 split actually splits in 8 draws


def test_client_fleet_view_reads_root_shares(kv_pair):
    from tpu_sandbox.deploy.registry import k_shares
    from tpu_sandbox.gateway.fleet import fleet_kv
    from tpu_sandbox.serve import replica as R
    from tpu_sandbox.serve.client import ServeClient

    _, kv, _ = kv_pair
    # deploy keys live at the store ROOT keyed by fleet; the serve pin
    # lands inside the fleet namespace the client was built over
    kv.set(k_shares("chat"), json.dumps(
        {"seq": 3, "shares": {"3": 1.0}}))
    client = ServeClient(fleet_kv(kv, "chat"), share_seed=0)
    client.submit("r0", [1, 2, 3], 2)
    assert int(kv.get("fleet/chat/" + R.k_pin("r0"))) == 3
    assert kv.try_get(R.k_pin("r0")) is None  # nothing at the root
