"""Runner kind ``lm_train``: the dense decoder LM trained as
``lm_train.py --parallelism dp`` trains it — ``TransformerLM`` +
``TrainState.create`` + ``PjitEngine(task="lm")`` on a ``data`` mesh over
the cell's chips, the step fed through ``eng.shard_batch``. ``lm_train.py``
has no ``build()``, so this mirrors its dp branch line for line (PERF.md
lists that under what the program should change)."""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any


from benchmark.lib import manifest, peaks, traffic
from benchmark.lib.observe import Observations
from benchmark.lib.train_window import (Window, compile_clocked,
                                        train_step_ms)


#: a window of fewer steps is too short to hold the loss to anything
MIN_STEPS_TO_LEARN = 10


@dataclass
class Session:
    eng: Any
    state: Any
    call: Any
    batches: Any
    window: Window | None = None


def build(config: dict, dep: dict, seq_len: int, seed: int, devices):
    """``lm_train.train``'s dp branch: model, optimizer, state, engine."""
    import jax
    import jax.numpy as jnp
    import optax

    from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
    from tpu_sandbox.ops.losses import _FUSED_CE_MIN_CLASSES
    from tpu_sandbox.parallel import PjitEngine
    from tpu_sandbox.runtime.mesh import make_mesh
    from tpu_sandbox.train import TrainState

    attention_fn = None
    if dep["flash"]:
        from tpu_sandbox.ops.pallas_attention import flash_attention_fn

        attention_fn = flash_attention_fn()
    vocab = config["vocab_size"]
    cfg = TransformerConfig(
        vocab_size=vocab, d_model=config["n_embd"], n_heads=config["n_head"],
        n_layers=config["n_layer"], d_ff=config["n_inner"], max_len=seq_len,
        dtype=jnp.bfloat16 if dep["dtype"] == "bf16" else jnp.float32,
        remat=dep["remat"], remat_policy=dep["remat_policy"],
        fp32_logits=vocab < _FUSED_CE_MIN_CLASSES)
    tx = optax.adam(dep["learning_rate"])
    mesh = make_mesh({"data": len(devices)}, devices=devices)
    model = TransformerLM(cfg, attention_fn=attention_fn)
    state = TrainState.create(model, jax.random.key(seed),
                              jnp.zeros((1, seq_len), jnp.int32), tx)
    eng = PjitEngine(model, tx, mesh, task="lm")
    return model, eng, eng.shard_state(state)


def check_against_reference(obs: Observations, model, params, tokens,
                            targets) -> None:
    """Logits of two seeded sequences and their loss — through the
    program's model (flash attention) and its ``cross_entropy_loss`` (the
    fused kernel) — against the plain float32 forward."""
    import jax

    from tpu_sandbox.ops.losses import cross_entropy_loss

    config = obs.cell["config"]
    reference = manifest.module("reference", obs.cell["reference"])

    def system(p, tok, tgt):
        logits = model.apply({"params": p}, tok)
        loss = cross_entropy_loss(logits.reshape(-1, logits.shape[-1]),
                                  tgt.reshape(-1))
        return logits, loss

    logits, loss = jax.jit(system)(params, tokens, targets)
    ref_logits, ref_loss = reference.logits_and_loss(
        reference.from_program_tree(params, config["n_layer"]), tokens,
        targets, n_head=config["n_head"], eps=config["layer_norm_epsilon"])
    dev, bad = reference.compare(logits, ref_logits, loss, ref_loss)
    obs.notes["reference_deviation"] = dev
    for text in bad:
        obs.problem(text)


def setup(obs: Observations) -> Session:
    import jax

    cell = obs.cell
    config, dep, spec = cell["config"], cell["deployment"], cell["traffic"]
    seq_len, batch = int(spec["seq_len"]), int(spec["batch"])
    devices = jax.devices()[:cell["chips"]]

    t0 = time.perf_counter()
    model, eng, state = build(config, dep, seq_len, obs.seed, devices)
    jax.block_until_ready(state)
    obs.facts["init_s"] = time.perf_counter() - t0

    batches = traffic.token_batches(spec, obs.seed, config["vocab_size"])
    first = eng.shard_batch(*next(batches))
    compiled = compile_clocked(obs, lambda: eng.lower_step(state, *first))
    obs.note_program(compiled.as_text())
    obs.facts["pallas_calls"] = len(obs.op_scopes)
    if dep["flash"] and not any("/attn/" in s for s in obs.op_scopes.values()):
        obs.problem("no Pallas attention kernel in the compiled step")

    t0 = time.perf_counter()
    # two sequences of the cell's own stream, from another seed
    tokens, targets = next(traffic.token_batches(
        {**spec, "batch": 2}, obs.seed + 1, config["vocab_size"]))
    check_against_reference(obs, model, state.params, tokens, targets)
    obs.facts["reference_check_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(2):  # warm-up; the first loss is that of the initial weights
        state, loss = compiled(state, *eng.shard_batch(*next(batches)))
    jax.block_until_ready(state)
    obs.facts["warmup_s"] = time.perf_counter() - t0
    obs.facts["flops_per_step"] = batch * seq_len * (
        peaks.transformer_train_flops_per_token(
            config["n_layer"], config["n_embd"], config["n_inner"], seq_len,
            config["vocab_size"]))
    obs.facts["attn_flops_per_step"] = peaks.causal_attention_train_flops(
        batch, config["n_head"], seq_len, config["n_embd"] // config["n_head"],
        config["n_layer"])
    return Session(eng, state, compiled, batches)


def measure(obs: Observations, session: Session, seconds: float) -> None:
    import jax

    window = Window(obs, seconds, obs.cell["traffic"]["steps_per_chunk"])
    session.window = window
    state, eng = session.state, session.eng
    # lm_train.train's loop
    for tokens, targets in window.batches(session.batches):
        with obs.span("place_batch"):
            placed = eng.shard_batch(tokens, targets)
        state, _ = window.step(session.call, state, *placed)
    with obs.span("wait_device"):
        jax.block_until_ready(state)
    session.state = state


def finish(obs: Observations, session: Session) -> None:
    """The stream is learnable (targets are the tokens shifted by position),
    so training must lower the loss inside the window: the first loss of the
    window against its last. On the chip the loss fell by 0.14 in 12 steps,
    0.23 in 20 and 0.29-0.34 in 40, in every one of nine runs (PR 22). This
    is all that holds the backward pass and the optimizer: it catches
    gradients that are garbage, missing or of the wrong sign, not a backward
    kernel that is subtly wrong (PERF.md section 7)."""
    session.window.close()
    first, last = obs.notes.get("first_loss"), obs.notes.get("last_loss")
    if obs.notes["steps"] >= MIN_STEPS_TO_LEARN and not last < first:
        obs.problem(f"training did not lower the loss: {first:.4f} at the "
                    f"window's first step, {last:.4f} at its last "
                    f"({obs.notes['steps']} steps)")


def end_to_end(obs: Observations) -> dict:
    return {"train_step_ms": train_step_ms(obs)}
