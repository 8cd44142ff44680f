"""Runner kind ``olmo_hybrid_train``: the Olmo-Hybrid decoder trained as
``lm_train.py --model olmo_hybrid --config <file> --parallelism dp`` trains
it. The construction is the program's own ``lm_train.build``, the loop is
``lm_train.train``'s, and the on-chip check holds the compiled train step
itself, at the timed shapes, against the plain float32 reference (the
linear-attention layers token by token): its loss, and its gradients as the
first step leaves them in Adam's first moment. What it shares with the
other LM runners it imports from them."""

from __future__ import annotations

import time

from benchmark.lib import manifest, olmo_hybrid_counts, traffic
from benchmark.lib.observe import Observations
from benchmark.lib.train_window import compile_clocked
from benchmark.runners.lm_train import (  # noqa: F401
    end_to_end, finish, measure)
from benchmark.runners.xing4_train import ADAM_B1, Session

MODEL = "olmo_hybrid"
#: the float32 parts are checked on this many chunks of a seeded input
FP32_CHUNKS = 4


def gradients(layer_types) -> tuple[str, ...]:
    """The gradients compared on the chip: parameters of each mechanism -- of
    the first linear-attention block (the decay's float32 scalars a head,
    the write strength's and two more projections, the convolution's taps,
    the head norm's scale), of the first full-attention block (q and k,
    behind their norms), of the second block's MLP, and the embedding."""
    kinds = list(layer_types)
    gdn = f"block{kinds.index('linear_attention')}/gdn"
    attn = f"block{kinds.index('full_attention')}/attn"
    return (f"{gdn}/A_log", f"{gdn}/dt_bias", f"{gdn}/b/kernel",
            f"{gdn}/conv_kernel", f"{gdn}/q/kernel", f"{gdn}/v/kernel",
            f"{gdn}/norm_scale", f"{attn}/q/kernel", f"{attn}/k/kernel",
            f"block{min(1, len(kinds) - 1)}/mlp/down/kernel",
            "tok_emb/embedding")


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


def reference_hooks(dep: dict) -> dict:
    """What lets the reference fit the chip and changes no number."""
    import jax

    return {"wrap": jax.checkpoint,
            "head_block": dep.get("reference_head_block"),
            "scan_segment": dep.get("reference_scan_segment"),
            "token_block": dep.get("reference_token_block")}


def float32_parts(cfg, gdn: dict, seed: int) -> tuple[dict, dict]:
    """The float32 parts on one seeded input, program against float64: the
    write strength, the log of the decay, the decays inside a chunk, and the
    triangular inverse of the chunks' transitions (the program's own ``A``,
    inverted by ``numpy`` in float64). ``gdn``: the first linear-attention
    block's ``A_log`` and ``dt_bias``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_sandbox.models import olmo_hybrid as program
    from tpu_sandbox.ops import delta_rule

    rng = np.random.default_rng(seed)
    h, dk, c = cfg.linear_num_key_heads, cfg.linear_key_head_dim, cfg.chunk
    s = FP32_CHUNKS * c
    raw_a = jnp.asarray(rng.standard_normal((1, s, h)), jnp.bfloat16)
    raw_b = jnp.asarray(rng.standard_normal((1, s, h)), jnp.bfloat16)
    # keys that lean one way, as silu leaves them
    k = jnp.asarray(0.3 + rng.standard_normal((1, s, h, dk)), jnp.float32)
    a_log, dt_bias = gdn["A_log"], gdn["dt_bias"]

    # (the parameters as arguments: closed over, every seed is a new program)
    def parts(raw_a, raw_b, k, a_log, dt_bias):
        beta = program.write_strength(raw_b, cfg.linear_allow_neg_eigval)
        g = program.log_decay(raw_a, a_log, dt_bias)
        cum = delta_rule.chunk_decays(g, chunk=c)               # [1,H,c,C]
        keys = jnp.moveaxis(program.l2_normalise(k).astype(jnp.bfloat16)
                            .reshape(1, FP32_CHUNKS, c, h, dk), 3, 1)
        a = delta_rule.chunk_transition(
            keys, jnp.moveaxis(beta.reshape(1, FP32_CHUNKS, c, h), -1, 1), cum)
        return {"beta": beta, "log_decay": g, "decay": jnp.exp(cum),
                "transition": a, "inverse": delta_rule.unit_lower_inverse(a)}

    system = {name: np.asarray(v)
              for name, v in jax.jit(parts)(raw_a, raw_b, k, a_log,
                                            dt_bias).items()}
    f64 = lambda x: np.asarray(x.astype(jnp.float32), np.float64)  # noqa: E731
    g = -np.exp(f64(a_log)) * np.logaddexp(0.0, f64(raw_a) + f64(dt_bias))
    a = np.tril(system.pop("transition").astype(np.float64), -1)
    ref = {"beta": (2.0 if cfg.linear_allow_neg_eigval else 1.0)
           / (1.0 + np.exp(-f64(raw_b))),
           "log_decay": g,
           "decay": np.exp(np.moveaxis(np.cumsum(
               g.reshape(1, FP32_CHUNKS, c, h), 2), -1, 1)),
           "inverse": np.linalg.inv(np.eye(c) + a)}
    return system, ref


def reference_side(obs: Observations, model, params, tokens,
                   targets) -> tuple[dict, dict, list[str]]:
    """What needs no train step: the reference's logits, loss and gradients
    (differentiated block by block so that it fits), the program's logits
    from a forward pass, and the float32 parts on one seeded input. Needs the
    optimizer's moments out of the way (the caller frees them). Returns
    ``(system, ref, wanted)``, as ``reference.compare`` takes them, less the
    step's part."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = manifest.module("reference", obs.cell["reference"])
    cfg = model.config
    flat = reference.flat_paths(params)
    wanted = [g for g in gradients(cfg.layer_types) if g in flat]
    tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)

    logits = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)
    system = {"logits": np.asarray(logits, np.float32)}
    del logits

    ref_loss, ref_logits, ref_grads = reference.loss_and_grads(
        reference.from_program_tree(params), tokens, targets,
        obs.cell["config"], wanted, **reference_hooks(obs.cell["deployment"]))
    ref = {"logits": np.asarray(ref_logits), "loss": float(ref_loss),
           "grads": {k: np.asarray(v) for k, v in ref_grads.items()}}
    del ref_logits, ref_grads

    gdn = wanted[0].rsplit("/", 1)[0]
    system["fp32"], ref["fp32"] = float32_parts(
        cfg, {k: flat[f"{gdn}/{k}"] for k in ("A_log", "dt_bias")},
        obs.seed + 2)
    return system, ref, wanted


def check_against_reference(obs: Observations, model, tx, eng, state,
                            compiled, tokens, targets):
    """The compiled train step against the float32 reference, at the timed
    shapes on another seed's sequence, from the weights the run starts with:
    logits of a forward pass, then **one step of the program under test**
    for its loss and, out of Adam's first moment, its gradients. No second
    gradient program is compiled. The state the run started with waits on
    the host meanwhile and comes back as it was; the moments are out of the
    way while the reference needs their room."""
    import jax
    import numpy as np
    import optax

    reference = manifest.module("reference", obs.cell["reference"])
    shardings = jax.tree.map(lambda x: x.sharding, state)
    host = jax.device_get((state.step, state.params))
    jax.tree.map(lambda x: x.delete(), state.opt_state)
    init_moments = jax.jit(tx.init, out_shardings=shardings.opt_state)

    system, ref, wanted = reference_side(obs, model, state.params, tokens,
                                         targets)
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
    step, params = jax.device_put(host, (shardings.step, shardings.params))
    return state.replace(step=step, params=params,
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
    layers = olmo_hybrid_counts.layer_counts(cfg.layer_types)
    rule = (batch * seq_len, cfg.linear_num_key_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, layers["linear_attention"])
    obs.facts["flops_per_step"] = olmo_hybrid_counts.train_flops(
        config, batch, seq_len)
    obs.facts["attn_flops_per_step"] = (
        olmo_hybrid_counts.causal_attention_train_flops(
            batch, cfg.num_attention_heads, seq_len, cfg.head_dim,
            cfg.head_dim, layers["full_attention"]))
    obs.facts["delta_rule_flops_per_step"] = (
        olmo_hybrid_counts.delta_rule_flops(*rule))
    obs.facts["delta_rule_bytes_per_step"] = (
        olmo_hybrid_counts.delta_rule_bytes(*rule))
    return Session(eng, state, compiled, batches, {})
