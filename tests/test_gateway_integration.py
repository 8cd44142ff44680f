"""Gateway end to end against real replica processes (slow).

The tier-1 file (test_gateway.py) runs the gateway over real sockets but
with in-process stub-step replicas. This file closes the remaining gaps:

- the replica-death kill matrix: a request routed to a replica that is
  then SIGKILLed mid-load must still terminate with exactly one verdict,
  rescued by the client's retry/hedge path or a peer's scavenge — the
  gateway's targeted routing is a hint, never a trap;
- the gateway's own process entrypoint (``python -m
  tpu_sandbox.gateway.server``), hello auth over the printed port, and a
  clean SIGTERM shutdown;
- two real gateway processes behind TLS, the connected one SIGKILLed
  mid-load: the client fails over and no request is lost.

Real subprocesses + cold jax compiles: slow-marked, out of tier-1.
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parent.parent

REPLICA_CFG = {
    "cache": {"num_blocks": 24, "block_size": 4, "max_blocks_per_seq": 8},
    "max_batch": 3,
    "buckets": [8, 16],
    "param_seed": 0,
    "lease_ttl": 1.0,
    "timeout": 240.0,
}

N_REQUESTS = 30


def _replica_env(kv_port):
    from tpu_sandbox.runtime.supervisor import ENV_KV_PORT

    return {
        **os.environ,
        ENV_KV_PORT: str(kv_port),
        "JAX_PLATFORMS": "cpu",
        "JAX_THREEFRY_PARTITIONABLE": "1",
        "PYTHONPATH": str(REPO) + os.pathsep
        + os.environ.get("PYTHONPATH", ""),
    }


def _spawn_replica(kv_port, tag):
    return subprocess.Popen(
        [sys.executable, "-m", "tpu_sandbox.serve.replica",
         "--config", json.dumps(REPLICA_CFG), "--tag", tag],
        env=_replica_env(kv_port), cwd=str(REPO),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def test_replica_kill_mid_load_every_request_verdicts_once():
    import numpy as np

    from tpu_sandbox.gateway.client import GatewayClient
    from tpu_sandbox.gateway.fleet import FleetSpec
    from tpu_sandbox.gateway.server import Gateway
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.serve import replica as R

    rng = np.random.default_rng(0)
    server = KVServer()
    kv = KVClient(port=server.port)
    procs = []
    try:
        procs = [_spawn_replica(server.port, f"p{i}") for i in range(2)]
        gw = Gateway(kv, [FleetSpec(block_size=4, service_rate_rps=50.0)],
                     refresh_min_s=0.01).start()
        client = GatewayClient(gw.port, max_retries=2, hedge_after=2.0)
        try:
            # wait out the cold compiles: both replicas reporting
            deadline = time.monotonic() + 180
            while len(R.read_load_reports(kv)) < 2:
                assert time.monotonic() < deadline, "replicas never reported"
                for p in procs:
                    assert p.poll() is None, p.communicate()[0]
                time.sleep(0.1)

            rids = []
            for i in range(N_REQUESTS):
                rid = f"r{i}"
                prompt = [int(t) for t in
                          rng.integers(1, 64, size=int(rng.integers(4, 13)))]
                assert client.submit(rid, prompt, int(rng.integers(4, 9)))
                rids.append(rid)
            R.announce_total(kv, N_REQUESTS)

            # kill replica 1 once the fleet is demonstrably mid-load
            while len(kv.keys("serve/result/")) < 3:
                assert time.monotonic() < deadline, "no results before kill"
                time.sleep(0.02)
            n_at_kill = len(kv.keys("serve/result/"))
            assert n_at_kill < N_REQUESTS, "too fast: no mid-load window"
            procs[1].kill()

            verdicts = {rid: client.result(rid, timeout=180.0)
                        for rid in rids}
        finally:
            client.close()
            gw.close()

        # exactly one terminal verdict each, none lost to the kill
        assert set(verdicts) == set(rids)
        for rid, v in verdicts.items():
            assert v["verdict"] in ("ok", "SHED"), (rid, v)
            if v["verdict"] == "ok":
                assert len(v["tokens"]) >= 1, (rid, v)
        by_replica = {v["replica"] for v in verdicts.values()
                      if v["verdict"] == "ok"}
        assert "p0" in by_replica, "survivor served nothing"
        # the rescue machinery ran: the killed replica's stranded requests
        # come back via client retries/hedges or a peer scavenge requeueing
        # them onto the shared queue — some combination must have fired
        rescued = (client.stats.retries + client.stats.hedges
                   + int(kv.try_get(R.K_TAIL) or b"0"))
        assert rescued > 0, "kill mid-load exercised no rescue path"
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=10)
            p.stdout.close()
        kv.close()
        server.stop()


def test_gateway_process_entrypoint_serves_and_shuts_down():
    from tpu_sandbox.gateway.client import GatewayAuthError, GatewayClient
    from tpu_sandbox.runtime.kvstore import KVServer

    server = KVServer()
    proc = subprocess.Popen(
        [sys.executable, "-m", "tpu_sandbox.gateway",
         "--kv-port", str(server.port), "--token", "sesame"],
        env=_replica_env(server.port), cwd=str(REPO),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        line = proc.stdout.readline()
        assert "listening on" in line, line
        port = int(line.split("listening on ")[1].split()[0].rsplit(":", 1)[1])
        with GatewayClient(port, token="sesame") as c:
            stats = c.gateway_stats()
            assert stats["admission"] == "feasible"
        with pytest.raises(GatewayAuthError):
            GatewayClient(port, token="wrong")
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=30) == 0
        rest = proc.stdout.read()
        assert "closed" in rest, rest
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
        proc.stdout.close()
        server.stop()


def test_gateway_process_sigkilled_mid_load_over_tls_loses_nothing():
    """Two real gateway processes behind TLS, stub-step replicas in this
    process, and the gateway the client is connected to SIGKILLed
    half-way through the submits: the client fails over to the survivor
    and every request still ends in exactly one ``ok`` verdict."""
    from tests.helpers import pumping
    from tests.test_gateway import TLSDIR, _wait_for_report, _worker
    from tpu_sandbox.gateway.client import GatewayClient
    from tpu_sandbox.gateway.wire import make_client_ssl_context
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.serve.replica import k_done

    server = KVServer()
    kv = KVClient(port=server.port)
    clones = [kv.clone(), kv.clone()]
    workers = [_worker(c, tag=f"w{i}") for i, c in enumerate(clones)]
    procs, endpoints = {}, []
    try:
        for gid in ("gw0", "gw1"):
            proc = subprocess.Popen(
                [sys.executable, "-m", "tpu_sandbox.gateway",
                 "--kv-port", str(server.port), "--gateway-id", gid,
                 "--token", "sesame", "--admission", "none",
                 "--tls-cert", os.path.join(TLSDIR, "server.pem"),
                 "--tls-key", os.path.join(TLSDIR, "server.key")],
                env=_replica_env(server.port), cwd=str(REPO),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
            line = proc.stdout.readline()
            assert "listening on" in line and "tls=on" in line, line
            port = int(line.split("listening on ")[1]
                       .split()[0].rsplit(":", 1)[1])
            procs[port] = proc
            endpoints.append(("127.0.0.1", port))
        with pumping(*workers):
            for w in workers:
                _wait_for_report(kv, w.tag)
            with GatewayClient(
                    endpoints=endpoints, token="sesame", backoff_base=0.02,
                    tls=make_client_ssl_context(
                        os.path.join(TLSDIR, "ca.pem"))) as client:
                rids = [f"k{i}" for i in range(24)]
                for i, rid in enumerate(rids):
                    if i == len(rids) // 2:
                        procs[client.endpoint[1]].send_signal(signal.SIGKILL)
                    assert client.submit(rid, [1 + i % 5, 2, 3], 3) is True
                verdicts = {rid: client.result(rid, timeout=60.0)
                            for rid in rids}
                assert client.stats.failovers >= 1
        assert all(v["verdict"] == "ok" for v in verdicts.values())
        assert all(kv.try_get(k_done(rid)) == b"1" for rid in rids)
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
            proc.wait(timeout=10)
            proc.stdout.close()
        for w in workers:
            w.engine.drain_to_requests()
        for c in clones:
            c.close()
        kv.close()
        server.stop()
