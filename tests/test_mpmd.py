"""MPMD pipeline vs the SPMD baseline: parity to rounding, fault recovery
to the bit, and the disaggregated prefill/decode handoff.

The parity contract: the per-stage programs compute PipelineParallel's
step on a ``{'data': 1, 'pipe': S}`` mesh — the same sums, in two sets of
compiled programs, so parameters and losses agree to rounding over 21 adam
steps and not to the bit (the limits and what was measured are at
``test_mpmd_parity_with_spmd_to_rounding``). Between two runs of the SAME
programs bitwise is the right claim: recovery must land on the bits of the
unfaulted MPMD run with every slot claimed exactly once per generation — a
microbatch applied twice or dropped shows up here, not in a flaky
convergence plot.
"""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from tests.helpers import ulps_apart
from tpu_sandbox.models.transformer import TransformerConfig
from tpu_sandbox.mpmd import MPMDPipeline
from tpu_sandbox.parallel.pipeline import PipelineParallel
from tpu_sandbox.runtime.mesh import make_mesh

CFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=4,
                        d_ff=64, max_len=64)
M = 4
STEPS = 21
#: parameters within this many ulps of their leaf's largest entry, losses
#: within this much, of the SPMD run's (measured over six seeds at this
#: size: 1225 ulps, 3.6e-6; a microbatch's gradient halved: 1e7 ulps, 0.05)
PARAM_ULPS, LOSS_ATOL = 8192, 2e-5


def _batch():
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, size=(8, 16)).astype(np.int32)
    return tokens, ((tokens + 7) % 64).astype(np.int32)


def _without_key_bias(params):
    """The key bias moves every score of a row alike and softmax forgets
    it: its gradient is zero but for rounding, and adam steps a full
    learning rate along whatever sign the rounding has. No two programs
    agree on it, and no loss depends on it."""
    def drop(path, leaf):
        on_qkv_bias = jax.tree_util.keystr(path).endswith("['qkv']['bias']")
        return np.asarray(leaf)[[0, 2]] if on_qkv_bias else leaf
    return jax.tree_util.tree_map_with_path(drop, params)


def _apart_from_spmd(spmd_ref, params, losses):
    return (ulps_apart(_without_key_bias(params),
                       _without_key_bias(spmd_ref["params"])),
            float(np.abs(np.subtract(losses, spmd_ref["losses"])).max()))


def _assert_trees_bitwise(ref, got):
    bad = []

    def cmp(path, a, b):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            bad.append(jax.tree_util.keystr(path))

    jax.tree_util.tree_map_with_path(cmp, ref, got)
    assert not bad, f"{len(bad)} leaves differ, e.g. {bad[:4]}"


@pytest.fixture(scope="module")
def spmd_ref():
    """The SPMD pipeline baseline: initial flat params, trained params,
    per-step losses. Computed once; every parity test compares to it."""
    tokens, targets = _batch()
    tx = optax.adam(1e-2)
    mesh = make_mesh({"data": 1, "pipe": 2}, devices=jax.devices()[:2])
    pp = PipelineParallel(CFG, tx, mesh, microbatches=M, donate=False)
    state = pp.init_state(jax.random.key(0), jnp.asarray(tokens))
    flat = pp.merged_params(state)
    sstate = pp.shard_state(state)
    batch = pp.shard_batch(tokens, targets)
    losses = []
    for _ in range(STEPS):
        sstate, loss = pp.train_step(sstate, *batch)
        losses.append(float(loss))
    return {"flat": flat, "params": pp.merged_params(sstate),
            "losses": losses}


@pytest.fixture(scope="module")
def mpmd_clean(spmd_ref):
    """The unfaulted MPMD run: two per-stage programs on two single-device
    meshes, activations/grads over the transport."""
    pipe = MPMDPipeline(CFG, optax.adam(1e-2), n_stages=2, microbatches=M,
                        devices=jax.devices()[2:4])
    pipe.init_from_flat(spmd_ref["flat"])
    return pipe, pipe.train(STEPS, *_batch())


def test_mpmd_parity_with_spmd_to_rounding(spmd_ref, mpmd_clean):
    """Same step as the fused SPMD program after 21 adam steps, to
    ``PARAM_ULPS`` and ``LOSS_ATOL`` (the key bias aside)."""
    pipe, losses = mpmd_clean
    ulps, loss_gap = _apart_from_spmd(spmd_ref, pipe.merged_params(), losses)
    assert ulps <= PARAM_ULPS and loss_gap <= LOSS_ATOL, (ulps, loss_gap)
    # each stage ran its own program: the wire actually carried payloads
    s = pipe.transport.stats
    assert s.puts == s.gets > 0 and s.bytes_out == s.bytes_in > 0
    assert 0.0 < pipe.bubble_fraction() < 1.0
    # clean run: every slot claimed exactly once, all in generation 0
    claims = pipe.transport.audit()["claims"]
    assert claims and all(v == 1 for v in claims.values())


def test_mpmd_parity_limits_catch_a_halved_microbatch(spmd_ref, monkeypatch):
    """The planted fault: every stage counts its last microbatch's gradient
    half. Both limits of the parity test fail on it by orders."""
    from tpu_sandbox.mpmd import driver

    whole = driver.accumulate_descending

    def halves_the_last(per_mb):
        last = max(per_mb)
        return whole({**per_mb, last: jax.tree.map(
            lambda g: np.asarray(g) * 0.5, per_mb[last])})

    monkeypatch.setattr(driver, "accumulate_descending", halves_the_last)
    pipe = MPMDPipeline(CFG, optax.adam(1e-2), n_stages=2, microbatches=M,
                        devices=jax.devices()[2:4])
    pipe.init_from_flat(spmd_ref["flat"])
    losses = pipe.train(STEPS, *_batch())
    ulps, loss_gap = _apart_from_spmd(spmd_ref, pipe.merged_params(), losses)
    assert ulps > 100 * PARAM_ULPS and loss_gap > 100 * LOSS_ATOL


def test_mpmd_stage_kill_recovers_bitwise(spmd_ref, mpmd_clean, tmp_path):
    """Stage 1 dies mid-step (between two transport ops); the driver
    respawns it at generation 1, it restores its own checkpoint and
    replays from durable slots. End state: bitwise the unfaulted MPMD
    run's params and losses (the same programs ran), within the parity
    limits of the SPMD run's, no microbatch lost or double-applied."""
    tokens, targets = _batch()
    pipe = MPMDPipeline(CFG, optax.adam(1e-2), n_stages=2, microbatches=M,
                        devices=jax.devices()[4:6], ckpt_root=str(tmp_path),
                        get_timeout=30.0)
    pipe.init_from_flat(spmd_ref["flat"])
    pipe.workers[1].fail_at = (7, 3)  # step 7, mid-schedule op
    losses = pipe.train(STEPS, tokens, targets, recover=True)
    clean, clean_losses = mpmd_clean
    _assert_trees_bitwise(clean.merged_params(), pipe.merged_params())
    assert losses == clean_losses
    ulps, loss_gap = _apart_from_spmd(spmd_ref, pipe.merged_params(), losses)
    assert ulps <= PARAM_ULPS and loss_gap <= LOSS_ATOL, (ulps, loss_gap)
    # the relaunch actually happened and replayed under a new generation
    assert pipe.workers[1].generation == 1
    # zero duplicate deliveries across BOTH generations
    claims = pipe.transport.audit()["claims"]
    dup = {k: v for k, v in claims.items() if v != 1}
    assert not dup, f"duplicate claims: {dup}"
    # every microbatch of every step applied exactly once per stage
    for w in pipe.workers:
        assert sorted(set(w.applied_steps)) == sorted(w.applied_steps)


def test_mpmd_leader_gc_releases_applied_slots(spmd_ref, tmp_path):
    """With checkpoints on, the driver advances a release watermark:
    slots for fully-applied steps are dropped from the wire."""
    tokens, targets = _batch()
    pipe = MPMDPipeline(CFG, optax.adam(1e-2), n_stages=2, microbatches=M,
                        devices=jax.devices()[6:8], ckpt_root=str(tmp_path))
    pipe.init_from_flat(spmd_ref["flat"])
    pipe.train(6, tokens, targets)
    assert pipe._released_through >= 0
    for step in range(pipe._released_through + 1):
        for mb in range(M):
            assert not pipe.transport.poll("act0", step, mb)
            assert not pipe.transport.poll("grad0", step, mb)


# -- disaggregated prefill/decode over the same transport ---------------------


@pytest.mark.parametrize("temperature,seed", [(0.0, 0), (0.8, 42)])
def test_disagg_tokens_identical_to_single_replica(temperature, seed):
    """Prefill on one replica, KV pages shipped over the stage transport,
    decode on another: the generated tokens are identical to a
    single-replica ContinuousEngine serving the same request."""
    from tpu_sandbox.mpmd.transport import LocalTransport
    from tpu_sandbox.serve.cache import CacheConfig
    from tpu_sandbox.serve.decode import build_decode_step
    from tpu_sandbox.serve.disagg import (DecodeReplica, DisaggRequest,
                                          PrefillReplica,
                                          serve_disaggregated)
    from tpu_sandbox.serve.engine import ContinuousEngine, Request, ServeConfig
    from tpu_sandbox.models.transformer import TransformerLM

    mcfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                             d_ff=64, max_len=128, dtype=jnp.float32)
    ccfg = CacheConfig(num_blocks=24, block_size=4, max_blocks_per_seq=8)
    params = TransformerLM(mcfg).init(jax.random.key(0),
                                      jnp.zeros((1, 8), jnp.int32))["params"]
    step = build_decode_step(mcfg, ccfg, max_batch=3, buckets=(8, 16))
    prompt = [5, 9, 3, 7, 11, 2]

    eng = ContinuousEngine(params, ServeConfig(model=mcfg, cache=ccfg,
                                               max_batch=3, buckets=(8, 16)),
                           step=step)
    eng.submit(Request(rid="a", prompt=list(prompt), max_new_tokens=9,
                       temperature=temperature, seed=seed))
    eng.run_until_idle()
    ref = eng.results["a"].tokens

    tr = LocalTransport()
    prefill = PrefillReplica(params, mcfg, ccfg, tr, step=step)
    decode = DecodeReplica(params, mcfg, ccfg, tr, step=step)
    req = DisaggRequest(rid="a", prompt=list(prompt), max_new_tokens=9,
                        temperature=temperature, seed=seed)
    out = serve_disaggregated(prefill, decode, req)
    assert out == ref
    assert tr.stats.bytes_out == tr.stats.bytes_in > 0
    # handoff is claim-once: a second decode of the same request in the
    # same generation is refused, a new generation (relaunched decode
    # replica) may replay it
    with pytest.raises(RuntimeError, match="already decoded"):
        decode.decode_from_handoff(req)
    prefill2 = PrefillReplica(params, mcfg, ccfg, tr, step=step)
    prefill2.prefill_and_ship(req)  # idempotent replay put
    decode2 = DecodeReplica(params, mcfg, ccfg, tr, step=step, generation=1)
    assert decode2.decode_from_handoff(req) == ref
