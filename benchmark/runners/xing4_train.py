"""Runner kind ``xing4_train``: the Xing4.0 decoder trained as
``lm_train.py --model xing4 --config <file> --parallelism dp`` trains it.
The construction is the program's own ``lm_train.build`` (model,
optimizer, ``TrainState.create``, ``PjitEngine(task="lm")`` on a ``data``
mesh over the cell's chips), the loop is ``lm_train.train``'s, and the
on-chip check holds the compiled train step itself, at the timed shapes,
against the plain float32 reference: its loss, and its gradients as the
first step leaves them in Adam's first moment."""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any

from benchmark.lib import manifest, traffic, xing4_counts
from benchmark.lib.observe import Observations
from benchmark.lib.train_window import Window, compile_clocked
# the window's loop (``lm_train.train``'s), the rule that a window long
# enough must lower the loss, and the end-to-end metric are GPT-2's runner's
from benchmark.runners import lm_train as gpt2_runner
from benchmark.runners.lm_train import end_to_end, measure  # noqa: F401

#: gradients compared on the chip: parameters of each mechanism, in the
#: second block and its expert layer (in the first block every stream is
#: still the embedding, and the gradient of its input mix is zero by
#: symmetry), at the point ``reference.off_start`` gives. Of the mHC the
#: three Phi matrices (Sinkhorn's backward pass behind ``phi_res``, the
#: input mix behind ``phi_pre``, the output mix behind ``phi_post``), not
#: the alphas: d/d alpha = <Phi, d/d Phi> / alpha exactly, one number that
#: cancels to near nothing on some seeds, so that its relative deviation
#: says more about the seed than about the program (PERF.md section 4)
GRADIENTS = (
    "block1/mhc_attn/phi_res", "block1/mhc_attn/phi_pre",
    "block1/mhc_attn/phi_post",
    "block1/mla/kv_b/kernel", "block1/mla/q_a/kernel", "block1/moe/router",
    "block1/moe/w_down", "block1/moe/shared_up/kernel", "tok_emb/embedding")
#: ``optax.adam``'s first-moment decay as ``lm_train.build`` leaves it: after
#: one step from zero moments, mu = (1 - b1) x the gradient, exactly
ADAM_B1 = 0.9
COUNTERS = ("rows_held", "rows_dropped", "steps")


@dataclass
class Session:
    eng: Any
    state: Any
    call: Any
    batches: Any
    counters: dict
    window: Window | None = None


def build(cell: dict, seed: int, devices):
    """``lm_train.build`` with the flags the cell stands for."""
    import lm_train

    if not hasattr(lm_train, "build"):  # a program from before this cell
        raise SystemExit("benchmark: this program's lm_train has no build(): "
                         "it cannot run a xing4_train cell")
    dep, spec = cell["deployment"], cell["traffic"]
    flags = ["--model", "xing4", "--parallelism", "dp",
             "--batch", str(spec["batch"]), "--seq-len", str(spec["seq_len"]),
             "--dtype", dep["dtype"], "--lr", str(dep["learning_rate"]),
             "--seed", str(seed % 2 ** 31)]
    flags += ["--flash"] * bool(dep["flash"]) + ["--remat"] * bool(dep["remat"])
    args = lm_train.build_parser().parse_args(flags)
    args.config = {**cell["config"], "deployment": dep}
    return lm_train.build(args, devices)


def moe_counters(state) -> dict:
    """The expert layers' device counters, summed over the layers (one
    device-to-host read; never inside the window)."""
    import numpy as np

    layers = [s["moe"] for s in state.batch_stats.values()]
    out = {name: float(sum(np.asarray(layer[name]) for layer in layers))
           for name in COUNTERS}
    out["expert_rows_max"] = float(max(
        np.asarray(layer["expert_rows_max"]) for layer in layers))
    out["layers"] = len(layers)
    return out


def check_point(reference, params, seed: int):
    """``params`` with the mHC sub-layers' small parameters moved off their
    starting values (``reference.off_start``): the point of the check."""
    import jax

    flat = reference.flat_paths(params)
    for path, value in reference.off_start(params, seed).items():
        flat[path] = jax.device_put(value, flat[path].sharding)
    return reference.unflatten(flat)


def reference_side(obs: Observations, model, eng, params, stats, tokens,
                   targets) -> tuple[dict, dict, list[str]]:
    """What needs no train step: the reference's logits, chosen experts,
    loss and gradients of ``GRADIENTS`` (differentiated block by block so
    that it fits), the program's logits and chosen experts from a forward
    pass, and the float32 parts on one seeded input. Needs the optimizer's
    moments out of the way (the caller frees them). Returns ``(system, ref,
    wanted)``, as ``reference.compare`` takes them, less the step's part."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_sandbox.models import xing4 as program
    from tpu_sandbox.parallel.expert import router_scores

    reference = manifest.module("reference", obs.cell["reference"])
    cfg = model.config
    ref_cfg = {**obs.cell["config"], "held": list(cfg.held),
               "local_rows": cfg.local_rows,
               "n_routed_experts": cfg.n_routed_experts}
    flat = reference.flat_paths(params)
    wanted = [g for g in GRADIENTS if g in flat]
    tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)

    def forward(params, stats, tokens):
        logits, sown = model.apply(
            {"params": params, "batch_stats": stats}, tokens,
            mutable=["intermediates", "mtp_logits", "batch_stats"])
        chosen = [leaf for path, leaf in sorted(
            reference.flat_paths(sown["intermediates"]).items(),
            key=lambda kv: (kv[0].startswith("mtp"), kv[0]))]
        return logits, chosen

    logits, chosen = jax.jit(forward)(params, stats, tokens)
    system = {"logits": np.asarray(logits, np.float32),
              "chosen": [np.asarray(c).reshape(*tokens.shape, -1)
                         for c in chosen]}
    del logits

    ref_loss, ref_logits, ref_chosen, ref_grads = reference.loss_and_grads(
        reference.from_program_tree(params, stats), tokens, targets, ref_cfg,
        wanted, mtp_loss_weight=eng.mtp_weight, wrap=jax.checkpoint,
        head_block=obs.cell["deployment"].get("reference_head_block"))
    ref = {"logits": np.asarray(ref_logits), "loss": float(ref_loss),
           "chosen": [np.asarray(c) for c in ref_chosen],
           "grads": {k: np.asarray(v) for k, v in ref_grads.items()}}
    del ref_logits, ref_grads

    # the float32 parts on one seeded input, program against reference
    rng = np.random.default_rng(obs.seed + 2)
    n, c = cfg.hc_mult, cfg.hidden_size
    x = jnp.asarray(rng.standard_normal((512, c)), jnp.bfloat16)
    raw = jnp.asarray(8.0 * rng.standard_normal((n, n, 512)), jnp.float32)
    w_r = next(v for k, v in flat.items() if k.endswith("moe/router"))
    with jax.default_matmul_precision("highest"):
        ref["fp32"] = {
            "router": np.asarray(jax.nn.sigmoid(x.astype(jnp.float32) @ w_r)),
            "sinkhorn": np.asarray(reference.sinkhorn(
                raw, cfg.hc_sinkhorn_iters, cfg.hc_eps)),
            "rmsnorm": np.asarray(reference.rms_norm(
                x.astype(jnp.float32), cfg.rms_norm_eps))}
    system["fp32"] = {
        "router": np.asarray(jax.jit(router_scores)(x, w_r)),
        "sinkhorn": np.asarray(jax.jit(
            lambda r: program.sinkhorn(r, cfg.hc_sinkhorn_iters, cfg.hc_eps))(raw)),
        "rmsnorm": np.asarray(jax.jit(
            lambda x: program.rms_norm(x, cfg.rms_norm_eps))(x))}
    return system, ref, wanted


def check_against_reference(obs: Observations, model, tx, eng, state,
                            compiled, tokens, targets):
    """The compiled train step against the float32 reference, at the timed
    shapes on another seed's two sequences, from ``check_point``: logits and
    chosen experts of a forward pass, then **one step of the program under
    test** for its loss and, out of Adam's first moment, its gradients. No
    second gradient program is compiled. The state the run started with
    waits on the host meanwhile and comes back as it was; the moments are
    out of the way while the reference needs their 6 GB."""
    import jax
    import numpy as np
    import optax

    reference = manifest.module("reference", obs.cell["reference"])
    shardings = jax.tree.map(lambda x: x.sharding, state)
    host = jax.device_get((state.step, state.params, state.batch_stats))
    jax.tree.map(lambda x: x.delete(), state.opt_state)
    init_moments = jax.jit(tx.init, out_shardings=shardings.opt_state)

    at = check_point(reference, state.params, obs.seed + 3)
    system, ref, wanted = reference_side(
        obs, model, eng, at, state.batch_stats, tokens, targets)
    after, loss = compiled(
        state.replace(params=at, opt_state=init_moments(at)),
        *eng.shard_batch(tokens, targets))
    mu = reference.flat_paths(optax.tree_utils.tree_get(after.opt_state, "mu"))
    system.update(loss=float(loss), grads={
        k: np.asarray(mu[k]) / (1.0 - ADAM_B1) for k in wanted})
    del mu
    jax.tree.map(lambda x: x.delete(), after)

    dev, bad = reference.compare(system, ref)
    obs.notes["reference_deviation"] = dev
    for text in bad:
        obs.problem(text)
    step, params, stats = jax.device_put(
        host, (shardings.step, shardings.params, shardings.batch_stats))
    return state.replace(step=step, params=params, batch_stats=stats,
                         opt_state=init_moments(params))


def setup(obs: Observations) -> Session:
    import jax

    cell = obs.cell
    config, spec = cell["config"], cell["traffic"]
    seq_len, batch = int(spec["seq_len"]), int(spec["batch"])
    devices = jax.devices()[:cell["chips"]]

    t0 = time.perf_counter()
    model, tx, state, eng = build(cell, obs.seed, devices)
    jax.block_until_ready(state)
    obs.facts["init_s"] = time.perf_counter() - t0

    batches = traffic.token_batches(spec, obs.seed, config["vocab_size"])
    first = eng.shard_batch(*next(batches))
    compiled = compile_clocked(obs, lambda: eng.lower_step(state, *first))
    obs.note_program(compiled.as_text())
    obs.facts["pallas_calls"] = len(obs.op_scopes)
    if cell["deployment"]["flash"] and not any(
            "/mla/" in s for s in obs.op_scopes.values()):
        obs.problem("no Pallas attention kernel in the compiled step")

    t0 = time.perf_counter()
    tokens, targets = next(traffic.token_batches(
        {**spec, "batch": batch}, obs.seed + 1, config["vocab_size"]))
    state = check_against_reference(obs, model, tx, eng, state, compiled,
                                    tokens, targets)
    obs.facts["reference_check_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(2):  # warm-up; the first loss is that of the initial weights
        state, loss = compiled(state, *eng.shard_batch(*next(batches)))
    jax.block_until_ready(state)
    obs.facts["warmup_s"] = time.perf_counter() - t0

    cfg = model.config
    layers, dense = cfg.num_hidden_layers, cfg.first_k_dense_replace
    full = {**config, "deployment": cell["deployment"]}
    obs.facts["flops_per_step"] = xing4_counts.train_flops(
        full, batch, seq_len, cfg.local_rows)
    obs.facts["mla_core_flops_per_step"] = (
        xing4_counts.causal_attention_train_flops(
            batch, cfg.num_attention_heads, seq_len, cfg.qk_head_dim,
            cfg.v_head_dim, layers))
    obs.facts["mhc_bytes_per_step"] = xing4_counts.mhc_bytes(
        batch * seq_len, cfg.hc_mult, cfg.hidden_size, 2 * layers)
    obs.facts["moe_expert_flops_per_step"] = xing4_counts.expert_flops(
        cfg.local_rows, cfg.hidden_size, cfg.moe_intermediate_size,
        layers - dense)
    obs.facts["moe_local_rows"] = cfg.local_rows
    return Session(eng, state, compiled, batches, moe_counters(state))


def finish(obs: Observations, session: Session) -> None:
    """``runners/lm_train``'s rule (losses finite; the loss falls over a
    window long enough to judge: the backward pass is held by the gradient
    check of set-up), then the window's rows from the device counters."""
    gpt2_runner.finish(obs, session)
    before, after = session.counters, moe_counters(session.state)
    steps = after["steps"] - before["steps"]   # summed over the layers
    if steps:
        rows = obs.facts["moe_local_rows"]
        held = (after["rows_held"] - before["rows_held"]) / steps
        obs.facts["moe_pad_pct"] = 100.0 * (1.0 - held / rows)
        obs.facts["moe_rows_dropped"] = (
            (after["rows_dropped"] - before["rows_dropped"])
            / (steps / after["layers"]))
        obs.notes["moe_rows"] = {
            "held_per_layer_step": held, "local_rows": rows,
            "expert_rows_max": after["expert_rows_max"],
            "dropped_per_step": obs.facts["moe_rows_dropped"]}
