"""Chaos-campaign harness, fast and in-process (tier-1).

One quick seeded campaign runs here so the harness itself is
regression-gated: 2 gateways + 2 stub-engine replicas, a tiny replayed
trace, a shed_storm and a gateway kill mid-load — then the full audit
(zero lost, exactly-one verdict per rid, alert claims, byte-identical
audit across two same-seed runs). The full fault matrix (every action
family, multiple seeds, prefix probes) lives slow-marked in
test_chaos_integration.py.
"""

import time

import pytest

from tests.helpers import StubStep, kv_pair, pumping  # noqa: F401 (kv_pair: fixture)
from tpu_sandbox.gateway.client import GatewayClient
from tpu_sandbox.gateway.fleet import FleetSpec
from tpu_sandbox.gateway.server import Gateway
from tpu_sandbox.models.transformer import TransformerConfig
from tpu_sandbox.obs import workload
from tpu_sandbox.runtime.chaos import (CHAOS_ACTIONS, ChaosCampaign,
                                       ChaosFault, build_schedule,
                                       check_alert_claims)
from tpu_sandbox.serve.cache import CacheConfig
from tpu_sandbox.serve.engine import ContinuousEngine, ServeConfig

MCFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                         d_ff=64, max_len=128)
CCFG = CacheConfig(num_blocks=24, block_size=4, max_blocks_per_seq=8)
BLOCK = CCFG.block_size


def _engine():
    cfg = ServeConfig(model=MCFG, cache=CCFG, max_batch=2, buckets=(8, 16))
    return ContinuousEngine(None, cfg, step=StubStep(), clock=time.monotonic)


def _worker(kv, tag):
    from tpu_sandbox.serve.replica import ReplicaWorker

    return ReplicaWorker(kv, _engine(), tag=tag, lease_ttl=1.0,
                         load_interval=0.02)


# -- schedule expansion: pure + seeded ----------------------------------------


def test_build_schedule_same_seed_same_faults():
    targets = {"kill_gateway": ["gw0", "gw1"], "shed_storm": ["w0"],
               "stall_replica": ["w0:0.1", "w1:0.2"]}
    a = build_schedule(7, duration_s=2.0, targets=targets, n_faults=6)
    b = build_schedule(7, duration_s=2.0, targets=targets, n_faults=6)
    assert a == b
    assert len(a) == 6
    assert all(f.action in CHAOS_ACTIONS for f in a)
    assert [f.at_s for f in a] == sorted(f.at_s for f in a)
    c = build_schedule(8, duration_s=2.0, targets=targets, n_faults=6)
    assert a != c  # a different seed draws a different campaign


def test_build_schedule_rejects_bad_inputs():
    with pytest.raises(ValueError, match="no action"):
        build_schedule(1, duration_s=1.0, targets={})
    with pytest.raises(ValueError, match="unknown chaos actions"):
        build_schedule(1, duration_s=1.0,
                       targets={"kill_everything": ["x"]})


def test_campaign_refuses_hookless_actions(kv_pair):
    _, kv, _ = kv_pair
    trace = workload.synthesize(3, 1)
    sched = [ChaosFault(at_s=0.1, action="kill_gateway", target="gw0")]
    with pytest.raises(ValueError, match="has no hook"):
        ChaosCampaign(kv, trace, lambda *a: True, seed=3, schedule=sched)


# -- the tier-1 smoke campaign ------------------------------------------------

SMOKE_SEED = 1013


def _run_smoke_campaign(kv, clone):
    """One seeded campaign: 2 gateways, 2 stub replicas, 10 requests,
    a replica shed_storm then a gateway SIGKILL stand-in mid-load."""
    trace = workload.synthesize(SMOKE_SEED, 10, duration_s=0.5,
                                prompt_tokens=(4, 10),
                                decode_tokens=(2, 4))
    schedule = [
        ChaosFault(at_s=0.18, action="shed_storm", target="w0"),
        ChaosFault(at_s=0.30, action="kill_gateway", target="gw0"),
    ]
    fleets = [FleetSpec(block_size=BLOCK)]
    gws = {
        gid: Gateway(kv, fleets, gateway_id=gid, hb_ttl=0.5,
                     refresh_min_s=0.005).start()
        for gid in ("gw0", "gw1")
    }
    w0, w1 = _worker(clone(), "w0"), _worker(clone(), "w1")
    client = None
    try:
        with pumping(w0, w1):
            client = GatewayClient(
                endpoints=[("127.0.0.1", gws["gw0"].port),
                           ("127.0.0.1", gws["gw1"].port)],
                backoff_base=0.01)
            campaign = ChaosCampaign(
                clone(), trace, client.submit, seed=SMOKE_SEED,
                schedule=schedule,
                hooks={"kill_gateway": lambda gid: gws[gid].kill()},
                block_size=BLOCK, verdict_timeout=60.0)
            res = campaign.run()
        alert_failures = check_alert_claims(kv)
    finally:
        if client is not None:
            client.close()
        for g in gws.values():
            g.close()
    return res, alert_failures


def test_smoke_campaign_zero_loss_exactly_once(kv_pair):
    _, kv, clone = kv_pair
    res, alert_failures = _run_smoke_campaign(kv, clone)
    assert res.ok, res.failures
    assert res.lost == []
    assert res.submitted == 10
    # every rid converged to a terminal "ok" verdict with real tokens —
    # the shed_storm cost retries, never answers
    assert len(res.verdicts) == 10
    assert all(v["verdict"] == "ok" and v["tokens"]
               for v in res.verdicts.values())
    assert [f["action"] for f in res.fired] == ["shed_storm",
                                                "kill_gateway"]
    assert alert_failures == []


@pytest.mark.slow
def test_smoke_campaign_audit_bytes_identical_across_fleets():
    """Same seed, two fresh fleets -> byte-identical claim audit. The
    wall-clock interleavings differ; the audit must not notice."""
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer

    audits = []
    for _ in range(2):
        server = KVServer()
        kv = KVClient(port=server.port)
        clones = []

        def clone():
            c = kv.clone()
            clones.append(c)
            return c

        try:
            res, alert_failures = _run_smoke_campaign(kv, clone)
            assert res.ok, res.failures
            assert alert_failures == []
            audits.append(res.audit_bytes())
        finally:
            for c in clones:
                c.close()
            kv.close()
            server.stop()
    assert audits[0] == audits[1]
