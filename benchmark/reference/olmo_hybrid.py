"""Plain float32 reference of the Olmo-Hybrid decoder as this repository
runs it: blocks by ``layer_types``, each a mixer (a Gated DeltaNet
linear-attention layer or causal softmax attention) and a gated MLP under
the reordered norm. ``jax.numpy`` only: no flax, no kernel, no remat and
**no chunked rule** -- the linear-attention layer is the delta rule's
recurrence itself, token by token (``lax.scan`` over the sequence): no
triangular inverse, no chunk, no cumulative decay. Nothing is imported from
the program; the helpers that know no model (reading the program's tree,
rounded products, the loss, paths, RMS deviation) are ``reference/xing4.py``'s
and the causal convolution is ``reference/nemotron_h.py``'s.

It reads the program's parameter tree by its names (``from_program_tree``
only casts) and the published ``config.json`` keys as a dict ``cfg``. What
the published config does not settle is listed, word for word, under
``assumed`` in ``benchmark/configs/olmo-hybrid-7b.json``; the equations are
in ISSUE 38 and in ``tpu_sandbox/models/olmo_hybrid.py``'s docstring.

Hooks that let the on-chip check fit a 16 GB chip without changing a
number: ``wrap`` is applied to every block, to its mixer half, to every
``token_block`` tokens of its MLP half, to the loss, to every group of
``head_block`` query heads of the attention (computed one after another) and
to every ``scan_segment`` tokens of the recurrence (the runner passes
``jax.checkpoint``: the reference is then differentiated block by block, and
the recurrence keeps its state once a segment, not once a token);
``matmul_dtype`` rounds the operands of every product on the way forward
(float32 products of rounded operands, gradients as if unrounded), which is
how the tolerances' second reading -- the reference one precision below the
program's bf16 -- is made. In the recurrence the operands are what the
program's chunked form multiplies: ``q``, ``k``, ``v``, what is written
(``u``) and the state where a key or a query reads it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.nemotron_h import causal_conv
from benchmark.reference.xing4 import (  # noqa: F401  (the runner's API)
    _mm, _rounder, cross_entropy, flat_paths, from_program_tree, rms_norm,
    rms_rel, unflatten)

L2_EPS = 1e-6       # under the root of a key's or a query's length

#: The program computes in bf16 (2^-9 relative rounding) with float32
#: accumulation, float32 norms, gates, decays, triangular inverse and state,
#: and a bf16 residual rounded twice a block; the reference is float32
#: throughout. Each entry: what it bounds, and why that value; the two
#: readings each limit lies between are in PERF.md section 4.
TOLERANCE = {
    # RMS of the logit difference over the RMS of the reference's logits (no
    # choice can flip in this model: one number for all tokens): bf16 reads
    # 0.023 to 0.024 on every seed, the float8 reference 0.20
    "logit_rms_rel": 0.07,
    # |loss - reference loss|, nats, a mean over 8192 tokens: bf16 up to
    # 5.1e-4, float8 5.1e-3; the other LM cells' limit lies between
    "loss_abs": 2.5e-3,
    # RMS-relative deviation of a parameter's gradient, one limit for the
    # matrices, the taps, the head norm's scale and the decay's 30 numbers a
    # head alike: bf16 reads 0.030 to 0.080 on all eleven (four blocks'
    # rounding reaches block 0's gradients whole), float8 0.39 to 2.5
    "grad_rel": 0.16,
    # the float32 parts that are sums and products (the write strength, the
    # triangular inverse: six levels of float32 products), fed the same input
    # as float64: 1e-8 to 6e-7; computed in bf16 they read 1e-3 to 1e-2
    "fp32_rel": 1e-5,
    # the float32 parts behind the chip's own exp and softplus (the log of
    # the decay, the decays inside a chunk): against float64 the v5e's
    # softplus alone reads 3e-5 RMS and 2.6e-4 at worst, its exp 5e-6, so
    # these read 4e-6 to 1.0e-4 where the CPU reads 6e-8; in bf16 5e-3
    "fp32_rel_exp": 5e-4,
}
#: the float32 parts held to ``fp32_rel_exp``; the rest: ``fp32_rel``
BEHIND_EXP = ("log_decay", "decay")


# --- the Gated DeltaNet mixer ---

def l2_normalise(x, scale=1.0):
    return x * scale / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                                + L2_EPS)


def write_strength(raw, allow_neg_eigval=True):
    return (2.0 if allow_neg_eigval else 1.0) * jax.nn.sigmoid(raw)


def log_decay(raw, a_log, dt_bias):
    return -jnp.exp(a_log) * jax.nn.softplus(raw + dt_bias)


def delta_recurrence(q, k, v, g, beta, cfg=None, *, wrap=lambda f: f,
                     segment=None):
    """``S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T``,
    ``o_t = S_t q_t``, ``S_0 = 0``, one token after another: ``q``, ``k``
    ``[B, S, H, d_k]``, ``v [B, S, H, d_v]``, ``g``, ``beta`` ``[B, S, H]``
    -> ``o [B, S, H, d_v]``."""
    r = _rounder(cfg or {})
    bsz, s, h, dk = q.shape
    dv = v.shape[-1]
    segment = segment or s

    def step(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs     # [B,H,d] ... [B,H]
        state = jnp.exp(g_t)[..., None, None] * state
        # what the state holds for the key is taken out, the value put in
        u = beta_t[..., None] * (r(v_t) - jnp.einsum(
            "bhvd,bhd->bhv", r(state), r(k_t)))
        state = state + r(u)[..., :, None] * r(k_t)[..., None, :]
        return state, jnp.einsum("bhvd,bhd->bhv", r(state), r(q_t))

    def run_segment(state, inputs):
        return jax.lax.scan(step, state, inputs)

    def by_time(x):  # [B, S, ...] -> [segments, tokens a segment, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(s // segment, segment, *x.shape[1:])

    _, o = jax.lax.scan(wrap(run_segment), jnp.zeros((bsz, h, dv, dk), q.dtype),
                        tuple(by_time(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(s, bsz, h, dv), 0, 1)


def gdn_mixer(p, x, cfg, wrap=lambda f: f, segment=None):
    h, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    bsz, s, _ = x.shape

    def proj(name):
        return _mm(cfg, "bsc,cf->bsf", x, p[name]["kernel"])

    taps = jnp.split(p["conv_kernel"], [h * dk, 2 * h * dk], -1)
    q, k, v = (jax.nn.silu(causal_conv(proj(name), tap, 0.0)).reshape(
        bsz, s, h, -1) for name, tap in zip("qkv", taps))
    o = delta_recurrence(
        l2_normalise(q, dk ** -0.5), l2_normalise(k), v,
        log_decay(proj("a"), p["A_log"], p["dt_bias"]),
        write_strength(proj("b"), cfg.get("linear_allow_neg_eigval", True)),
        cfg, wrap=wrap, segment=segment)
    y = rms_norm(o, cfg["rms_norm_eps"], p["norm_scale"]) * jax.nn.silu(
        proj("g").reshape(bsz, s, h, dv))
    return _mm(cfg, "bsf,fc->bsc", y.reshape(bsz, s, h * dv),
               p["out_proj"]["kernel"])


# --- attention ---

def attention(p, x, cfg, wrap=lambda f: f, head_block=None):
    h = cfg["num_attention_heads"]
    bsz, s, c = x.shape
    d, eps = c // h, cfg["rms_norm_eps"]
    block = head_block or h
    if h % block:
        raise ValueError(f"head_block {block} does not divide {h} heads")

    def proj(name):
        return _mm(cfg, "bsc,cf->bsf", x, p[name]["kernel"])

    q = rms_norm(proj("q"), eps, p["q_norm"]["scale"])
    k = rms_norm(proj("k"), eps, p["k_norm"]["scale"])
    causal = jnp.tril(jnp.ones((s, s), bool))

    def heads(q, k, v):     # [B,S,block,D] each
        scores = _mm(cfg, "bqhd,bkhd->bhqk", q, k) * d ** -0.5
        w = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), -1)
        return _mm(cfg, "bhqk,bkhd->bqhd", w, v)

    # one group of query heads after another (``lax.map``), so that with
    # ``wrap`` only one group's S x S scores are alive at a time
    def grouped(a):
        return jnp.moveaxis(a.reshape(bsz, s, h // block, block, d), 2, 0)

    out = jax.lax.map(lambda a: wrap(heads)(*a),
                      (grouped(q), grouped(k), grouped(proj("v"))))
    return _mm(cfg, "bsf,fc->bsc", jnp.moveaxis(out, 0, 2).reshape(bsz, s, c),
               p["o"]["kernel"])


def mlp(p, x, cfg):
    def proj(name):
        return _mm(cfg, "bsc,cf->bsf", x, p[name]["kernel"])

    return _mm(cfg, "bsf,fc->bsc", jax.nn.silu(proj("gate")) * proj("up"),
               p["down"]["kernel"])


# --- the model ---

def block(p, x, kind, cfg, wrap, head_block, segment, token_block):
    """``h = x + RMSNorm(Mixer(x))``, ``y = h + RMSNorm(MLP(h))``; each half
    under ``wrap`` as well as the whole, and the MLP half (which knows one
    token at a time) over ``token_block`` tokens after another: at 8192
    tokens its float32 intermediates are 361 MB each, eight alive."""
    eps = cfg["rms_norm_eps"]

    def mixer_half(p, x):
        if kind == "linear_attention":
            mixed = gdn_mixer(p, x, cfg, wrap, segment)
        else:
            mixed = attention(p, x, cfg, wrap, head_block)
        return x + rms_norm(mixed, eps, p["post_norm"]["scale"])

    def mlp_half(p, h):
        def rows(h):
            return h + rms_norm(mlp(p, h, cfg), eps, p["post_norm"]["scale"])

        bsz, s, c = h.shape
        size = min(token_block or s, s)
        blocks = jax.lax.map(wrap(rows), jnp.moveaxis(
            h.reshape(bsz, s // size, size, c), 1, 0))
        return jnp.moveaxis(blocks, 0, 1).reshape(bsz, s, c)

    def run(p, x):
        mixer = p["gdn" if kind == "linear_attention" else "attn"]
        return mlp_half(p["mlp"], wrap(mixer_half)(mixer, x))

    return wrap(run)(p, x)


def forward(p, tokens, cfg, *, wrap=lambda f: f, head_block=None,
            scan_segment=None, token_block=None):
    """tokens [B,S] -> logits [B,S,V] float32."""
    def head(h):
        return _mm(cfg, "bsc,cv->bsv",
                   rms_norm(h, cfg["rms_norm_eps"], p["norm_f"]["scale"]),
                   p["lm_head"]["kernel"])

    h = p["tok_emb"]["embedding"][tokens]
    for i, kind in enumerate(cfg["layer_types"]):
        h = block(p[f"block{i}"], h, kind, cfg, wrap, head_block, scan_segment,
                  token_block)
    return wrap(head)(h)


def loss_fn(p, tokens, targets, cfg, **hooks):
    """(loss, logits): next-token cross entropy."""
    logits = forward(p, tokens, cfg, **hooks)
    return hooks.get("wrap", lambda f: f)(cross_entropy)(logits, targets), logits


def grad_program(cfg, **hooks):
    """The jitted ``(picked, rest, tokens, targets) -> (loss, logits, grads of
    picked)``: ``picked`` and ``rest`` are the parameter tree's leaves by
    '/'-joined path, in two dicts."""
    def run(picked, rest, tokens, targets):
        def objective(picked):
            return loss_fn(unflatten({**rest, **picked}), tokens, targets,
                           cfg, **hooks)

        (loss, logits), grads = jax.value_and_grad(
            objective, has_aux=True)(picked)
        return loss, logits, grads

    return jax.jit(run)


def loss_and_grads(p, tokens, targets, cfg, wanted=None, **hooks):
    """Loss, logits and the gradients of the leaves whose '/'-joined path is
    in ``wanted`` (all if None), at ``highest`` matmul precision (on a TPU a
    float32 product is otherwise bf16)."""
    flat = flat_paths(p)
    wanted = list(flat) if wanted is None else list(wanted)
    with jax.default_matmul_precision("highest"):
        return grad_program(cfg, **hooks)(
            {k: flat[k] for k in wanted},
            {k: v for k, v in flat.items() if k not in wanted},
            jnp.asarray(tokens), jnp.asarray(targets))


def compare(system: dict, ref: dict) -> tuple[dict, list[str]]:
    """``system`` / ``ref``: ``logits``, ``loss``, ``grads`` (path -> array),
    and optionally ``fp32`` (name -> array: a float32 part fed the same
    input). Returns the deviations and the limits broken."""
    dev = {"loss_abs": abs(float(system["loss"]) - float(ref["loss"])),
           "logit_rms_rel": rms_rel(np.asarray(system["logits"], np.float32),
                                    np.asarray(ref["logits"], np.float32))}
    limit = dict(TOLERANCE)
    for path, grad in ref.get("grads", {}).items():
        key = f"grad_rel:{path}"
        dev[key] = rms_rel(system["grads"][path], grad)
        limit[key] = TOLERANCE["grad_rel"]
    for name, value in ref.get("fp32", {}).items():
        dev[f"fp32_rel:{name}"] = rms_rel(system["fp32"][name], value)
        limit[f"fp32_rel:{name}"] = TOLERANCE[
            "fp32_rel_exp" if name in BEHIND_EXP else "fp32_rel"]
    bad = [f"olmo_hybrid vs float32 reference: {k} {v:.3g} > {limit[k]}"
           for k, v in dev.items() if not v <= limit[k]]
    return dev, bad
