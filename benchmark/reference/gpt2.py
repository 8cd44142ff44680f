"""Plain float32 reference of GPT-2 (Radford et al. 2019; the published
``modeling_gpt2`` equations): learned token and position embeddings; per
block pre-LayerNorm, fused-qkv causal multi-head attention, residual,
pre-LayerNorm, MLP with tanh-GELU, residual; final LayerNorm; linear head.
``jax.numpy`` only: no flax, no kernels, no cache, no batching tricks.

Departures from the published model, all forced by the program and listed
under ``assumed`` in ``benchmark/configs/gpt2-medium.json``: the head is
not tied to the token embedding and has a bias; the LayerNorm epsilon is
the configuration file's (the program's flax default 1e-6, published 1e-5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: The system computes in bf16 (8 bits of mantissa, 2^-9 relative rounding)
#: with fp32 accumulation and an fp32 LayerNorm, through 24 blocks whose
#: residual stream is itself rounded to bf16 twice a block: ~sqrt(48) x
#: 2^-9 ~ 1.4e-2 of the stream's size reaches the logits. On the chip PR 22
#: measured 1.16e-2 (training forward, flash attention), 1.13e-2 (prefill
#: and paged decode) and a loss 2.5e-4 off: the limits are about three and
#: eight times those. An fp8 matmul (2^-4) or a dropped term (a bias, a
#: LayerNorm, the 1/sqrt(d) scale) moves the logits by tens of percent of
#: their RMS, far outside.
TOLERANCE = {"logit_rms_rel": 3e-2, "loss_abs": 2e-3,
             # The serving check sees the served tokens and the engine's own
             # mean log-probability of them, not logits (``compare_served``,
             # ``compare_chosen_tokens``). Both read on the chip in PR 40, in
             # ``gpt2m_serve_decode_replay`` (64 sessions, 640 served tokens
             # a run, every one compared), as the contract sets a limit:
             # above the largest reading of sound runs, below the smallest
             # of the control -- the reference with both operands of every
             # product rounded to float8 e4m3 in the program's place
             # (``sweeps/gpt2_serve_precision.py``), which has to break one.
             # chosen_gap_rel: 40 seeds read 0.016-0.044 and once 0.087 (a
             # widest gap over 640 tokens swings by its nature: a near tie of
             # the two best logits at one position, decided by bf16
             # rounding); the control on four seeds 0.340 / 0.374 / 0.464 /
             # 0.524. Limit 0.2: 2.3 x the largest sound reading, 1.7 x
             # under the smallest of the control.
             # chosen_logprob_abs: 40 seeds read 0.0099-0.0253 (a mean over a
             # session's 10 tokens, the worst of 64 sessions); the control
             # 0.154 / 0.167 / 0.244 / 0.260. Limit 0.07: 2.8 x the largest
             # sound reading, 2.2 x under the smallest of the control.
             "chosen_logprob_abs": 7e-2, "chosen_gap_rel": 0.2}


def from_program_tree(params, n_layer: int) -> dict:
    """The program's flax parameter tree -> this reference's plain names.
    Only layouts change: qkv ``[D, 3, H, hd]`` -> ``[D, 3, D]``, out
    ``[H, hd, D]`` -> ``[D, D]``."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    d = params["tok_emb"]["embedding"].shape[1]
    blocks = []
    for i in range(n_layer):
        b = params[f"block{i}"]
        blocks.append({
            "ln1_g": f32(b["ln1"]["scale"]), "ln1_b": f32(b["ln1"]["bias"]),
            "w_qkv": f32(b["attn"]["qkv"]["kernel"]).reshape(d, 3, d),
            "b_qkv": f32(b["attn"]["qkv"]["bias"]).reshape(3, d),
            "w_o": f32(b["attn"]["out"]["kernel"]).reshape(d, d),
            "b_o": f32(b["attn"]["out"]["bias"]),
            "ln2_g": f32(b["ln2"]["scale"]), "ln2_b": f32(b["ln2"]["bias"]),
            "w_up": f32(b["mlp"]["up"]["kernel"]),
            "b_up": f32(b["mlp"]["up"]["bias"]),
            "w_down": f32(b["mlp"]["down"]["kernel"]),
            "b_down": f32(b["mlp"]["down"]["bias"]),
        })
    return {
        "wte": f32(params["tok_emb"]["embedding"]),
        "wpe": f32(params["pos_emb"]["embedding"]),
        "blocks": blocks,
        "lnf_g": f32(params["ln_f"]["scale"]),
        "lnf_b": f32(params["ln_f"]["bias"]),
        "w_head": f32(params["lm_head"]["kernel"]),
        "b_head": f32(params["lm_head"]["bias"]),
    }


def _layer_norm(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def forward(p: dict, tokens, *, n_head: int, eps: float, matmul_dtype=None):
    """tokens [B, S] int -> logits [B, S, vocab] float32. With
    ``matmul_dtype`` (the control of the serving check: ``float8_e4m3fn``)
    both operands of every matrix product are rounded to it; the products
    and all else stay float32."""
    def r(a):
        return a if matmul_dtype is None else a.astype(matmul_dtype).astype(
            jnp.float32)

    b, s = tokens.shape
    d = p["wte"].shape[1]
    hd = d // n_head
    x = p["wte"][tokens] + p["wpe"][jnp.arange(s)][None]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for blk in p["blocks"]:
        h = _layer_norm(x, blk["ln1_g"], blk["ln1_b"], eps)
        qkv = jnp.einsum("bsd,dke->bske", r(h), r(blk["w_qkv"])) + blk["b_qkv"]
        q, k, v = (qkv[:, :, j].reshape(b, s, n_head, hd) for j in range(3))
        scores = jnp.einsum("bqhd,bkhd->bhqk", r(q), r(k)) / np.sqrt(hd)
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", r(w), r(v)).reshape(b, s, d)
        x = x + r(a) @ r(blk["w_o"]) + blk["b_o"]
        h = _layer_norm(x, blk["ln2_g"], blk["ln2_b"], eps)
        h = _gelu_tanh(r(h) @ r(blk["w_up"]) + blk["b_up"])
        x = x + r(h) @ r(blk["w_down"]) + blk["b_down"]
    x = _layer_norm(x, p["lnf_g"], p["lnf_b"], eps)
    return r(x) @ r(p["w_head"]) + p["b_head"]


def logits_and_loss(p: dict, tokens, targets, *, n_head: int, eps: float):
    """Reference logits and mean next-token cross entropy, at ``highest``
    matmul precision (on a TPU a float32 matmul is otherwise bf16)."""
    def run(p, tokens, targets):
        logits = forward(p, tokens, n_head=n_head, eps=eps)
        logp = jax.nn.log_softmax(logits, axis=-1)
        loss = -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
        return logits, loss

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(p, jnp.asarray(tokens), jnp.asarray(targets))


def rms_rel(system, reference) -> float:
    """RMS of the difference over RMS of the reference."""
    system = np.asarray(system, np.float64)
    reference = np.asarray(reference, np.float64)
    return float(np.sqrt(np.mean(np.square(system - reference)))
                 / (np.sqrt(np.mean(np.square(reference))) or 1.0))


def compare(system_logits, ref_logits, system_loss=None,
            ref_loss=None) -> tuple[dict, list[str]]:
    dev = {"logit_rms_rel": rms_rel(system_logits, ref_logits)}
    if system_loss is not None:
        dev["loss_abs"] = abs(float(system_loss) - float(ref_loss))
    bad = [f"gpt2 vs float32 reference: {k} {v:.3g} > {TOLERANCE[k]}"
           for k, v in dev.items() if not v <= TOLERANCE[k]]
    return dev, bad


def compare_chosen_tokens(ref_logits, tokens,
                          system_mean_logprob: float) -> tuple[dict, list[str]]:
    """For a system that shows only the tokens it chose and the mean
    log-probability it gave them: ``ref_logits[i]`` are the reference's
    logits at the position that produced ``tokens[i]``. ``chosen_gap_rel``
    is how far the reference's logit of a chosen token lies under the
    reference's largest, in spreads (standard deviations) of that row, at
    worst; ``chosen_logprob_abs`` is the system's mean log-probability
    against the reference's log-softmax at the same tokens."""
    ref = np.asarray(ref_logits, np.float64)
    tokens = np.asarray(tokens)
    rows = np.arange(len(tokens))
    chosen = ref[rows, tokens]
    peak = ref.max(axis=-1)
    logp = chosen - peak - np.log(np.exp(ref - peak[:, None]).sum(axis=-1))
    dev = {"chosen_gap_rel": float(np.max((peak - chosen) / ref.std(axis=-1))),
           "chosen_logprob_abs": abs(float(system_mean_logprob)
                                     - float(logp.mean()))}
    bad = [f"gpt2 vs float32 reference: {k} {v:.3g} > {TOLERANCE[k]}"
           for k, v in dev.items() if not v <= TOLERANCE[k]]
    return dev, bad


@functools.cache
def _served_rows(n_head: int, eps: float, matmul_dtype):
    """One jitted program a configuration: a block after another runs the
    same one (a ``jax.jit`` made anew a call traces, lowers and loads anew)."""
    def run(p, tokens, rows, chosen):
        logits = forward(p, tokens, n_head=n_head, eps=eps,
                         matmul_dtype=matmul_dtype)
        picked = jnp.take_along_axis(logits, rows[..., None], axis=1)
        peak = picked.max(axis=-1)
        lse = peak + jnp.log(jnp.exp(picked - peak[..., None]).sum(axis=-1))
        at = jnp.take_along_axis(picked, chosen[..., None], axis=-1)[..., 0]
        return {"gap_rel": (peak - at) / picked.std(axis=-1),
                "logprob": at - lse, "argmax": picked.argmax(axis=-1),
                "argmax_logprob": peak - lse}

    return jax.jit(run)


def served_rows(p: dict, tokens, rows, chosen, *, n_head: int, eps: float,
                matmul_dtype=None) -> dict:
    """For a block of served sequences: ``tokens`` [B, S] (prompt and served
    tokens, zero padded: causal, so the padding reaches no row that counts),
    ``rows`` [B, R] the positions whose logits chose a served token and
    ``chosen`` [B, R] those tokens. One full forward at ``highest``, reduced
    on the device to what ``compare_served`` needs, each [B, R]: ``gap_rel``
    (how far the logit of the chosen token lies under the row's largest, in
    standard deviations of the row), ``logprob`` (log-softmax at the chosen
    token), and the row's own first choice, ``argmax``, with its
    ``argmax_logprob``."""
    with jax.default_matmul_precision("highest"):
        out = _served_rows(n_head, eps, matmul_dtype)(
            p, jnp.asarray(tokens), jnp.asarray(rows), jnp.asarray(chosen))
    return {k: np.asarray(v) for k, v in out.items()}


def compare_served(gap_rel, ref_logprob, counts,
                   system_mean_logprob) -> tuple[dict, list[str]]:
    """``compare_chosen_tokens`` over many served sequences, from
    ``served_rows``' [N, R] arrays: sequence ``i`` counts in its first
    ``counts[i]`` rows. ``chosen_gap_rel`` is the widest gap of any served
    token; ``chosen_logprob_abs`` the widest distance, over the sequences,
    between the system's mean log-probability of a sequence's tokens and
    the reference's."""
    counts = np.asarray(counts)
    valid = np.arange(np.shape(gap_rel)[1])[None, :] < counts[:, None]
    ref_mean = np.where(valid, ref_logprob, 0.0).sum(axis=1) / counts
    dev = {"chosen_gap_rel": float(np.max(np.where(valid, gap_rel, 0.0))),
           "chosen_logprob_abs": float(np.max(np.abs(
               np.asarray(system_mean_logprob, np.float64) - ref_mean)))}
    bad = [f"gpt2 vs float32 reference: {k} {v:.3g} > {TOLERANCE[k]}"
           for k, v in dev.items() if not v <= TOLERANCE[k]]
    return dev, bad
