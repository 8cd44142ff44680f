"""Runner kind ``nemotron_h_train``: the Nemotron-H hybrid decoder trained as
``lm_train.py --model nemotron_h --config <file> --parallelism dp`` trains
it. The construction is the program's own ``lm_train.build``, the loop is
``lm_train.train``'s, and the on-chip check holds the compiled train step
itself, at the timed shapes, against the plain float32 reference (the
state-space layers token by token): its loss, and its gradients as the
first step leaves them in Adam's first moment. What it shares with the
other LM runners it imports from them."""

from __future__ import annotations

import re
import time

from benchmark.lib import manifest, nemotron_h_counts, traffic
from benchmark.lib.observe import Observations
from benchmark.lib.train_window import compile_clocked
from benchmark.runners.lm_train import end_to_end, measure  # noqa: F401
from benchmark.runners.xing4_train import (  # noqa: F401
    ADAM_B1, Session, finish, moe_counters)

MODEL = "nemotron_h"


def gradients(pattern: str) -> tuple[str, ...]:
    """The gradients compared on the chip: parameters of each mechanism --
    of the first Mamba-2 block (its float32 scalars a head, the convolution,
    the input projection, the gated norm's scale), of the first attention
    block (q; k and v), of the second expert block where there is one
    (router, the way into the latent, the held experts' second product, the
    shared expert), and the embedding."""
    moe = [i for i, kind in enumerate(pattern) if kind == "E"]
    mamba, attn = f"block{pattern.index('M')}/mamba", f"block{pattern.index('*')}/attn"
    moe = f"block{moe[min(1, len(moe) - 1)]}/moe"
    return (f"{mamba}/A_log", f"{mamba}/dt_bias", f"{mamba}/D",
            f"{mamba}/conv_kernel", f"{mamba}/in_proj/kernel",
            f"{mamba}/norm_scale", f"{attn}/q/kernel", f"{attn}/kv/kernel",
            f"{moe}/router", f"{moe}/latent_down/kernel", f"{moe}/w_down",
            f"{moe}/shared_up/kernel", "tok_emb/embedding")


def build(cell: dict, seed: int, devices):
    """``lm_train.build`` with the flags the cell stands for."""
    import lm_train

    if MODEL not in getattr(lm_train, "CONFIG_MODELS", ()):
        raise SystemExit(f"benchmark: this program's lm_train builds no "
                         f"{MODEL} model: it cannot run a {MODEL}_train cell")
    dep, spec = cell["deployment"], cell["traffic"]
    flags = ["--model", MODEL, "--parallelism", "dp",
             "--batch", str(spec["batch"]), "--seq-len", str(spec["seq_len"]),
             "--dtype", dep["dtype"], "--lr", str(dep["learning_rate"]),
             "--seed", str(seed % 2 ** 31)]
    flags += ["--flash"] * bool(dep["flash"]) + ["--remat"] * bool(dep["remat"])
    args = lm_train.build_parser().parse_args(flags)
    args.config = {**cell["config"], "deployment": dep}
    return lm_train.build(args, devices)


def reference_config(cell: dict, cfg) -> dict:
    """The published keys plus the share as the program sized it."""
    return {**cell["config"], "held": list(cfg.held),
            "local_rows": cfg.local_rows,
            "n_routed_experts": cfg.n_routed_experts}


def reference_hooks(dep: dict) -> dict:
    """What lets the reference fit the chip and changes no number."""
    import jax

    return {"wrap": jax.checkpoint,
            "head_block": dep.get("reference_head_block"),
            "scan_segment": dep.get("reference_scan_segment")}


def _block_order(path: str):
    return path.startswith("mtp"), int(re.search(r"\d+", path).group())


def reference_side(obs: Observations, model, eng, params, stats, tokens,
                   targets) -> tuple[dict, dict, list[str]]:
    """What needs no train step: the reference's logits, chosen experts,
    loss and gradients (differentiated block by block so that it fits), the
    program's logits and chosen experts from a forward pass, and the float32
    parts on one seeded input. Needs the optimizer's moments out of the way
    (the caller frees them). Returns ``(system, ref, wanted)``, as
    ``reference.compare`` takes them, less the step's part."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_sandbox.models import nemotron_h as program
    from tpu_sandbox.ops.ssd import decay_exponents
    from tpu_sandbox.parallel.expert import router_scores

    reference = manifest.module("reference", obs.cell["reference"])
    cfg = model.config
    flat = reference.flat_paths(params)
    wanted = [g for g in gradients(cfg.hybrid_override_pattern) if g in flat]
    tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)

    def forward(params, stats, tokens):
        logits, sown = model.apply(
            {"params": params, "batch_stats": stats}, tokens,
            mutable=["intermediates", "mtp_logits", "batch_stats"])
        chosen = [leaf for _, leaf in sorted(
            reference.flat_paths(sown["intermediates"]).items(),
            key=lambda kv: _block_order(kv[0]))]
        return logits, chosen

    logits, chosen = jax.jit(forward)(params, stats, tokens)
    system = {"logits": np.asarray(logits, np.float32),
              "chosen": [np.asarray(c).reshape(*tokens.shape, -1)
                         for c in chosen]}
    del logits

    ref_loss, ref_logits, ref_chosen, ref_grads = reference.loss_and_grads(
        reference.from_program_tree(params, stats), tokens, targets,
        reference_config(obs.cell, cfg), wanted, mtp_loss_weight=eng.mtp_weight,
        **reference_hooks(obs.cell["deployment"]))
    ref = {"logits": np.asarray(ref_logits), "loss": float(ref_loss),
           "chosen": [np.asarray(c) for c in ref_chosen],
           "grads": {k: np.asarray(v) for k, v in ref_grads.items()}}
    del ref_logits, ref_grads

    # the float32 parts on one seeded input, program against reference: the
    # router's scores, the time steps, and the decays inside a chunk (the
    # reference's in float64, one token after another)
    rng = np.random.default_rng(obs.seed + 2)
    h, q = cfg.mamba_num_heads, cfg.chunk_size
    x = jnp.asarray(rng.standard_normal((512, cfg.hidden_size)), jnp.bfloat16)
    raw = jnp.asarray(rng.standard_normal((1, 4 * q, h)), jnp.bfloat16)
    w_r = next(v for k, v in flat.items() if k.endswith("moe/router"))
    mamba = {k.rsplit("/", 1)[1]: v for k, v in flat.items()
             if k.startswith(wanted[0].rsplit("/", 1)[0] + "/")}
    a = -jnp.exp(mamba["A_log"])
    with jax.default_matmul_precision("highest"):
        steps = reference.time_step(raw.astype(jnp.float32), mamba["dt_bias"])
        ref["fp32"] = {
            "router": np.asarray(jax.nn.sigmoid(x.astype(jnp.float32) @ w_r)),
            "time_step": np.asarray(steps),
            "decay": np.exp(np.cumsum(
                (np.asarray(steps, np.float64) * np.asarray(a, np.float64)
                 ).reshape(1, 4, q, h), axis=2))}
    system["fp32"] = {
        "router": np.asarray(jax.jit(router_scores)(x, w_r)),
        "time_step": np.asarray(jax.jit(program.time_step)(
            raw, mamba["dt_bias"])),
        "decay": np.moveaxis(np.asarray(jax.jit(lambda dt: jnp.exp(
            decay_exponents(program.time_step(dt, mamba["dt_bias"]), a,
                            chunk=q)))(raw)), -1, 2)}
    return system, ref, wanted


def check_against_reference(obs: Observations, model, tx, eng, state,
                            compiled, tokens, targets):
    """The compiled train step against the float32 reference, at the timed
    shapes on another seed's sequence, from the weights the run starts with:
    logits and chosen experts of a forward pass, then **one step of the
    program under test** for its loss and, out of Adam's first moment, its
    gradients. No second gradient program is compiled. The state the run
    started with waits on the host meanwhile and comes back as it was; the
    moments are out of the way while the reference needs their room."""
    import jax
    import numpy as np
    import optax

    reference = manifest.module("reference", obs.cell["reference"])
    shardings = jax.tree.map(lambda x: x.sharding, state)
    host = jax.device_get((state.step, state.params, state.batch_stats))
    jax.tree.map(lambda x: x.delete(), state.opt_state)
    init_moments = jax.jit(tx.init, out_shardings=shardings.opt_state)

    system, ref, wanted = reference_side(
        obs, model, eng, state.params, state.batch_stats, tokens, targets)
    after, loss = compiled(
        state.replace(opt_state=init_moments(state.params)),
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
            "/attn/" in s for s in obs.op_scopes.values()):
        obs.problem("no Pallas attention kernel in the compiled step")

    t0 = time.perf_counter()
    tokens, targets = next(traffic.token_batches(
        spec, obs.seed + 1, config["vocab_size"]))
    state = check_against_reference(obs, model, tx, eng, state, compiled,
                                    tokens, targets)
    obs.facts["reference_check_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(2):  # warm-up; the first loss is that of the initial weights
        state, loss = compiled(state, *eng.shard_batch(*next(batches)))
    jax.block_until_ready(state)
    obs.facts["warmup_s"] = time.perf_counter() - t0

    cfg = model.config
    layers = nemotron_h_counts.layer_counts(cfg.hybrid_override_pattern)
    tokens_per_step = batch * seq_len
    obs.facts["flops_per_step"] = nemotron_h_counts.train_flops(
        {**config, "deployment": cell["deployment"]}, batch, seq_len,
        cfg.local_rows)
    obs.facts["attn_flops_per_step"] = (
        nemotron_h_counts.causal_attention_train_flops(
            batch, cfg.num_attention_heads, seq_len, cfg.head_dim,
            cfg.head_dim, layers["*"]))
    obs.facts["moe_expert_flops_per_step"] = nemotron_h_counts.expert_flops(
        cfg.local_rows, cfg.moe_latent_size, cfg.moe_intermediate_size,
        layers["E"])
    obs.facts["moe_local_rows"] = cfg.local_rows
    obs.facts["ssd_flops_per_step"] = nemotron_h_counts.ssd_flops(
        tokens_per_step, cfg.mamba_num_heads, cfg.mamba_head_dim,
        cfg.ssm_state_size, cfg.n_groups, cfg.chunk_size, layers["M"])
    obs.facts["ssd_bytes_per_step"] = nemotron_h_counts.ssd_bytes(
        tokens_per_step, cfg.mamba_num_heads, cfg.mamba_head_dim,
        cfg.ssm_state_size, cfg.n_groups, layers["M"])
    return Session(eng, state, compiled, batches, moe_counters(state))
