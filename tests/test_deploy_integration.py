"""Slow-tier deployment integration: real weights, real faults.

Everything test_deploy.py proves with stub engines is re-proven here with
real transformer weights flowing through the full artifact path — export,
seal, registry, controller verify, replica checksum-verified load — under
the two worst faults at once:

- the deploy controller is killed mid-rollout (after the begin record,
  lease left to lapse) and a successor completes the promotion with
  exactly one event per decision;
- the serving replica is killed mid-swap (command in the mailbox, never
  applied) with claimed work in flight; its respawn lands on the target
  version while the orphaned requests are scavenged and replayed
  **bitwise** on the version they pinned — compared against a one-shot
  forward reference, not against another engine run.

Plus the first closed-loop workload: generate -> train -> publish ->
promote, two generations, the distillation objective strictly improving
and each generation's requests served on that generation's weights.

Module name ends in _integration: conftest marks everything here slow.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from tests.helpers import kv_pair  # noqa: F401 (kv_pair: fixture)
from tpu_sandbox.deploy.controller import DeployConfig, DeployController
from tpu_sandbox.deploy.registry import (current_target, deploy_events,
                                         registry_versions)
from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
from tpu_sandbox.serve.cache import CacheConfig
from tpu_sandbox.serve.decode import build_decode_step
from tpu_sandbox.serve.engine import ContinuousEngine, ServeConfig
from tpu_sandbox.serve.replica import (ReplicaWorker, k_cmd, k_pin,
                                       read_load_reports, read_result,
                                       submit_request)
from tpu_sandbox.train.trainer import publish_checkpoint

MCFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                         d_ff=64, max_len=128, dtype=jnp.float32)
CCFG = CacheConfig(num_blocks=24, block_size=4, max_blocks_per_seq=8)
MAX_CTX = CCFG.max_context


@pytest.fixture(scope="module")
def model():
    return TransformerLM(MCFG)


@pytest.fixture(scope="module")
def step():
    return build_decode_step(MCFG, CCFG, max_batch=2, buckets=(8, 16))


@pytest.fixture(scope="module")
def fwd(model):
    return jax.jit(lambda params, toks: model.apply({"params": params}, toks))


def _params(seed):
    return TransformerLM(MCFG).init(
        jax.random.key(seed), jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(params, step):
    return ContinuousEngine(params, ServeConfig(
        model=MCFG, cache=CCFG, max_batch=2, buckets=(8, 16)), step=step)


def _worker(kv, params, step, **over):
    over.setdefault("lease_ttl", 0.4)
    over.setdefault("load_interval", 0.02)
    over.setdefault("publish_ts", False)
    # swap_loader stays None: swaps go through the real artifact path
    # (controller verify, then the replica's checksum-verified load)
    return ReplicaWorker(kv, _engine(params, step), tag="w0", **over)


def _controller(kv, member_id):
    return DeployController(
        kv, member_id=member_id, election_ttl=0.6,
        cfg=DeployConfig(swap_resend_s=0.05))


def _greedy(fwd, params, prompt, max_new):
    """One-shot-forward greedy continuation: the bitwise reference the
    paged serve path must reproduce exactly (test_serve.py's parity
    oracle, here used across a weight swap and a replica death)."""
    toks = list(prompt)
    out = []
    for _ in range(max_new):
        padded = np.zeros((1, MAX_CTX), np.int32)
        padded[0, :len(toks)] = toks
        logits = np.asarray(fwd(params, jnp.asarray(padded)))[0, len(toks) - 1]
        out.append(int(logits.argmax()))
        toks.append(out[-1])
    return out


def _drive(until, *actors, timeout=90.0, poll=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for a in actors:
            a.tick()
        if until():
            return
        time.sleep(poll)
    raise AssertionError("drive condition not reached in time")


def _actions(kv):
    return [e["action"] for e in deploy_events(kv)]


def test_rollout_survives_controller_and_replica_kills_bitwise(
        kv_pair, tmp_path, model, step, fwd):
    _, kv, clone = kv_pair
    params_v0 = _params(0)
    prompts = {f"r{i}": [1 + i, 2, 3, 4, 5] for i in range(3)}

    # the doomed replica claims real work on the boot weights (pins v0)
    dead = _worker(clone(), params_v0, step)
    for rid, prompt in prompts.items():
        submit_request(kv, rid, prompt, 4)
    _drive(lambda: dead.stats.claimed == 3, dead, timeout=60.0)
    assert all(kv.get(k_pin(r)) == b"0" for r in prompts)

    # a new version is published; controller A begins the rollout and
    # lands the swap command in the mailbox...
    params_v1 = _params(1)
    ver = publish_checkpoint(kv, params_v1, export_dir=tmp_path, step=1)
    a = _controller(clone(), "a")
    _drive(lambda: kv.try_get(k_cmd("w0")) is not None, a, timeout=60.0)
    assert _actions(kv) == ["published", "promote_begin"]
    # ...then BOTH die: A's lease lapses unreleased, the replica never
    # applies the command. Leases and the load report expire.
    del a
    time.sleep(0.8)
    assert read_load_reports(kv) == {}

    # successor controller + respawned replica finish the rollout
    respawn = _worker(clone(), _params(0), step)
    b = _controller(clone(), "b")
    _drive(lambda: current_target(kv) == ver
           and all(kv.try_get(f"serve/result/{r}") is not None
                   for r in prompts),
           respawn, b, timeout=120.0)

    # exactly-once: one begin, one verdict, one done — across two
    # controllers and a replica death
    assert _actions(kv) == ["published", "promote_begin", "canary_pass",
                            "promoted"]
    assert respawn.engine.version == ver
    # the orphaned requests replayed BITWISE on their pinned version:
    # token-identical to the v0 one-shot-forward reference, even though
    # the serving engine promoted to v1 mid-replay
    for rid, prompt in prompts.items():
        got = read_result(kv, rid)
        assert got["verdict"] == "ok" and got["ver"] == 0
        assert got["tokens"] == _greedy(fwd, params_v0, prompt, 4)
    # fresh traffic decodes on the promoted artifact, bitwise v1: the
    # round trip export -> seal -> verify -> load lost nothing
    submit_request(kv, "fresh", [9, 8, 7], 4)
    _drive(lambda: kv.try_get("serve/result/fresh") is not None,
           respawn, b, timeout=60.0)
    got = read_result(kv, "fresh")
    assert got["ver"] == ver
    assert got["tokens"] == _greedy(fwd, params_v1, [9, 8, 7], 4)
    b.resign()
    dead.engine.drain_to_requests()  # release the killed replica's engine


def test_generate_train_promote_improves_across_generations(
        kv_pair, tmp_path, model, step, fwd):
    """The closed loop: a teacher generates data, the student trains on
    it, the checkpoint publishes, the controller promotes, and the NEXT
    generation's data is served by the freshly promoted weights. The
    distillation objective must strictly improve across generations."""
    _, kv, clone = kv_pair
    teacher = _params(7)
    student = _params(0)
    opt = optax.adam(3e-3)
    opt_state = opt.init(student)
    rng = np.random.default_rng(0)
    eval_toks = jnp.asarray(rng.integers(0, MCFG.vocab_size, (8, 16)),
                            jnp.int32)

    @jax.jit
    def distill_loss(params, toks):
        t_logits = model.apply({"params": teacher}, toks)
        s_logits = model.apply({"params": params}, toks)
        t_prob = jax.nn.softmax(t_logits, -1)
        return -jnp.mean(jnp.sum(
            t_prob * jax.nn.log_softmax(s_logits, -1), -1))

    grad_fn = jax.jit(jax.value_and_grad(distill_loss))

    worker = _worker(clone(), _params(0), step)
    ctrl = _controller(clone(), "loop")
    losses = [float(distill_loss(student, eval_toks))]
    served_vers = []
    try:
        for gen in range(2):
            # generate -> train: fresh batches each generation
            for _ in range(30):
                batch = jnp.asarray(
                    rng.integers(0, MCFG.vocab_size, (8, 16)), jnp.int32)
                _, grads = grad_fn(student, batch)
                updates, opt_state = opt.update(grads, opt_state)
                student = optax.apply_updates(student, updates)
            losses.append(float(distill_loss(student, eval_toks)))
            # publish -> promote: the real rolling-update machinery
            ver = publish_checkpoint(kv, student, export_dir=tmp_path,
                                     step=gen + 1)
            _drive(lambda: current_target(kv) == ver, worker, ctrl,
                   timeout=120.0)
            # serve on the promoted weights, bitwise: the loop is closed
            rid = f"gen{gen}"
            submit_request(kv, rid, [3, 1, 4, 1, 5], 3)
            _drive(lambda: kv.try_get(f"serve/result/{rid}") is not None,
                   worker, ctrl, timeout=60.0)
            got = read_result(kv, rid)
            served_vers.append(got["ver"])
            assert got["ver"] == ver
            loaded = registry_versions(kv)[ver]
            assert got["tokens"] == _greedy(
                fwd, worker.engine._params_by_ver[ver], [3, 1, 4, 1, 5], 3)
            assert loaded["step"] == gen + 1
    finally:
        ctrl.resign()
    assert served_vers == [1, 2]
    # the objective strictly improves generation over generation
    assert losses[2] < losses[1] < losses[0]
