"""Olmo-Hybrid-style decoder: a list of blocks by the published
``layer_types``, each a mixer and a gated MLP; the mixer is a Gated DeltaNet
(``linear_attention``: a delta-rule linear-attention layer) or causal softmax
attention (``full_attention``).

Built from the published ``config.json`` keys under their published names
(``OlmoHybridConfig.from_dict``). bf16 compute over fp32 parameters, a bf16
residual. With ``remat`` every block is recomputed in its backward pass, in
two halves (the mixer with its norm and residual, the MLP with its): the
recomputation of one region, but the peak holds one half's intermediates,
not both -- at 8192 tokens a whole Gated DeltaNet block's do not fit beside
this model's state on a 16 GB chip. All that a half keeps from its forward
pass is the flash kernel's output and logsumexp
(``ops/pallas_attention.py::FLASH_RESIDUALS``), so that kernel runs once a
full-attention layer. The equations
(``benchmark/configs/olmo-hybrid-7b.json`` lists what the published config
does not settle, under ``assumed``), ``x [T, hidden]``, no bias anywhere:

*Block* (the Olmo 2 / Olmo 3 reordered norm: the mixer reads the residual
itself, the norm is on what it returns): ``h = x + RMSNorm(Mixer(x))``,
``y = h + RMSNorm(MLP(h))``, ``MLP(h) = W_down(silu(W_gate h) * W_up h)``;
after the last block ``RMSNorm``, then the untied head.

*Full attention*: ``q, k, v = W_q x, W_k x, W_v x``; ``q <- RMSNorm(q)``,
``k <- RMSNorm(k)`` over the whole width, a learned scale each; heads of
``hidden / num_attention_heads``, causal, scale ``head_dim ** -0.5``, no
positional encoding (``rope_theta`` null: the linear layers carry the order);
``W_o``.

*Gated DeltaNet* (H heads, keys ``d_k``, values ``d_v``, kernel K):
``q~, k~, v~ = silu(conv_K(W_q x)), silu(conv_K(W_k x)), silu(conv_K(W_v
x))``, causal and depthwise; a head at a time ``q = q~ / |q~| d_k ** -0.5``,
``k = k~ / |k~|`` (``|.|`` with ``L2_EPS`` under the root), ``v = v~``;
``beta = 2 sigmoid(W_b x)`` (the 2 is ``linear_allow_neg_eigval``);
``g = -exp(A_log) * softplus(W_a x + dt_bias)``; the rule
``S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T``,
``o_t = S_t q_t`` (``ops/delta_rule.py``, by chunks);
``y = W_o(RMSNorm_head(o; scale [d_v]) * silu(W_g x))``. ``beta``, ``g``, the
norms and every decay in float32.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_sandbox.models.nemotron_h import (
    log_of_uniform, log_uniform_step, time_step)
from tpu_sandbox.models.xing4 import RMSNorm, rms_norm
from tpu_sandbox.ops import pallas_short_conv
from tpu_sandbox.ops.attention import causal_attention
from tpu_sandbox.ops.pallas_attention import (
    remat_saving, save_flash_residuals)
from tpu_sandbox.ops.delta_rule import gated_delta_rule

KINDS = {"linear_attention": "gdn", "full_attention": "attn"}
L2_EPS = 1e-6                       # under the root of a key's or query's norm
# what the published config does not settle (the file's ``assumed``:
# ``gdn_init``): Mamba-2's usual start for the decay
A_RANGE = (1.0, 16.0)
TIME_STEP = (1e-3, 1e-1, 1e-4)      # min, max, floor


@dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    layer_types: tuple[str, ...]
    num_attention_heads: int
    rms_norm_eps: float
    linear_num_key_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    linear_allow_neg_eigval: bool = True
    chunk: int = 64                 # tokens a chunk of the delta rule
    dtype: Any = jnp.bfloat16
    remat: bool = True
    flash: bool = False

    @classmethod
    def from_dict(cls, config: dict, *, tokens_per_step: int = 0,
                  dtype=jnp.bfloat16, remat: bool = True,
                  flash: bool = False) -> "OlmoHybridConfig":
        """From the published keys; ``deployment.delta_rule_chunk`` where the
        file sets the rule's chunk. Nothing of this model is sized by
        ``tokens_per_step``."""
        kinds = tuple(config["layer_types"])
        if set(kinds) - set(KINDS) or len(kinds) != config["num_hidden_layers"]:
            raise ValueError(
                f"layer_types: {len(kinds)} blocks for num_hidden_layers "
                f"{config['num_hidden_layers']}, kinds "
                f"{sorted(set(kinds) - set(KINDS))} unknown (known: "
                f"{sorted(KINDS)})")
        if config.get("hidden_act", "silu") != "silu":
            raise ValueError("only a silu MLP")
        if config.get("attention_bias") or config.get("tie_word_embeddings"):
            raise ValueError("no attention bias and no tied head")
        if (config.get("num_key_value_heads", config["num_attention_heads"])
                != config["num_attention_heads"]
                or config["linear_num_value_heads"]
                != config["linear_num_key_heads"]):
            raise ValueError("only as many key/value heads as query heads, "
                             "in both mixers")
        if (config.get("rope_parameters") or {}).get("rope_theta") is not None:
            raise ValueError("rope_theta is set: this model applies no "
                             "rotary embedding")
        if config["hidden_size"] % config["num_attention_heads"]:
            raise ValueError("hidden_size does not divide into the heads")
        chunk = config.get("deployment", {}).get("delta_rule_chunk", cls.chunk)
        return cls(**{key: config[key] for key in cls.__dataclass_fields__
                      if key in config} | {"layer_types": kinds},
                   chunk=chunk, dtype=dtype, remat=remat, flash=flash)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


# --- the float32 parts, as functions the benchmark checks on their own ---

def l2_normalise(x, scale: float = 1.0):
    """``x / |x| * scale`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * (scale * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS))


def write_strength(raw, allow_neg_eigval: bool = True):
    """``beta``: ``sigmoid``, doubled where the transition may flip a key's
    direction (eigenvalue ``1 - beta`` in (-1, 1)); float32."""
    beta = jax.nn.sigmoid(raw.astype(jnp.float32))
    return 2.0 * beta if allow_neg_eigval else beta


def log_decay(raw, a_log, dt_bias):
    """``g = -exp(A_log) softplus(raw + dt_bias)``, float32, <= 0."""
    return -jnp.exp(a_log) * time_step(raw, dt_bias)


def short_conv(x, taps, heads: int, unit: float | None):
    """``silu(conv(x))`` by heads, ``x [B, S, heads x width]``, ``taps [K,
    heads x width]``; a head's vector scaled to length ``unit`` if given.
    The convolution through ``ops/pallas_short_conv.py`` (float32 inside,
    its own backward pass), its result in float32 where the norm reads it,
    so that nothing is rounded between the two, and in ``x``'s dtype where
    nothing does; ``[B, S, heads, width]`` in ``x``'s dtype."""
    y = pallas_short_conv.short_conv(
        x, taps, dtype=x.dtype if unit is None else jnp.float32)
    y = y.reshape(*x.shape[:2], heads, -1)
    return y if unit is None else unit_heads(y, unit, x.dtype)


# each keeps its inputs for the backward pass and computes its float32
# passes again there (elementwise: cheap), instead of keeping four to six
# float32 arrays of [tokens, heads x width] a layer
@functools.partial(jax.checkpoint, static_argnums=(1, 2))
def unit_heads(y, unit: float, dtype):
    """``l2_normalise`` of every head's vector, in ``dtype``."""
    return l2_normalise(y, unit).astype(dtype)


@functools.partial(jax.checkpoint, static_argnums=(3,))
def gated_head_norm(o, z, scale, eps: float):
    """``RMSNorm(o) * scale * silu(z)`` over a head's values, the norm
    before the gate: float32 inside, ``o``'s dtype out."""
    return (rms_norm(o, eps, scale) * nn.silu(z.astype(jnp.float32))
            ).astype(o.dtype)


# --- the mixers ---

class GatedDeltaNet(nn.Module):
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        bsz, s, _ = x.shape
        h, dk, dv = (cfg.linear_num_key_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype)
        with jax.named_scope("in_proj"):
            q, k = dense(h * dk, name="q")(x), dense(h * dk, name="k")(x)
            v, z = dense(h * dv, name="v")(x), dense(h * dv, name="g")(x)
            raw_b, raw_a = dense(h, name="b")(x), dense(h, name="a")(x)
        with jax.named_scope("conv"):
            kernel = self.param(
                "conv_kernel", nn.initializers.variance_scaling(
                    1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                (cfg.linear_conv_kernel_dim, 2 * h * dk + h * dv), jnp.float32)
            k_q, k_k, k_v = jnp.split(kernel, [h * dk, 2 * h * dk], -1)
            q = short_conv(q, k_q, h, dk ** -0.5)
            k = short_conv(k, k_k, h, 1.0)
            v = short_conv(v, k_v, h, None)
        with jax.named_scope("gates"):
            a_log = self.param("A_log", log_of_uniform(*A_RANGE), (h,),
                               jnp.float32)
            dt_bias = self.param("dt_bias", log_uniform_step(*TIME_STEP),
                                 (h,), jnp.float32)
            beta = write_strength(raw_b, cfg.linear_allow_neg_eigval)
            g = log_decay(raw_a, a_log, dt_bias)
        # (``init`` traces a short sample: a sequence under one chunk is one)
        o = gated_delta_rule(q, k, v, g, beta, chunk=min(cfg.chunk, s))
        with jax.named_scope("norm"):
            scale = self.param("norm_scale", nn.initializers.ones, (dv,),
                               jnp.float32)
            y = gated_head_norm(
                o, z.reshape(bsz, s, h, dv), scale, cfg.rms_norm_eps)
        out = dense(cfg.hidden_size, name="out_proj")(
            y.reshape(bsz, s, h * dv))
        return _residual(self, x, out)


def _residual(module, x, y):
    """``x + RMSNorm(y)``: the reordered norm, its scale ``module``'s."""
    cfg = module.config
    return x + RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="post_norm")(y)


class Attention(nn.Module):
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        bsz, s, c = x.shape
        h, d = cfg.num_attention_heads, cfg.head_dim
        dense = functools.partial(nn.Dense, c, use_bias=False, dtype=cfg.dtype)
        norm = functools.partial(RMSNorm, cfg.rms_norm_eps, cfg.dtype)
        q = norm(name="q_norm")(dense(name="q")(x)).reshape(bsz, s, h, d)
        k = norm(name="k_norm")(dense(name="k")(x)).reshape(bsz, s, h, d)
        v = dense(name="v")(x).reshape(bsz, s, h, d)
        if cfg.flash:
            from tpu_sandbox.ops.pallas_attention import flash_attention

            out = flash_attention(q, k, v)
        else:
            out = causal_attention(q, k, v)
        return _residual(self, x, dense(name="o")(out.reshape(bsz, s, c)))


class Mlp(nn.Module):
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype)
        gate = dense(cfg.intermediate_size, name="gate")(x)
        up = dense(cfg.intermediate_size, name="up")(x)
        return _residual(self, x, dense(cfg.hidden_size, name="down")(
            nn.silu(gate) * up))


class Block(nn.Module):
    """Two halves, each a module that returns its residual sum
    (``post_norm`` is its norm) and each under an ``nn.remat`` that keeps
    the flash kernel's two results."""

    config: OlmoHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.config

        def half(cls):
            if not cfg.remat:
                return cls
            return remat_saving(
                nn.remat(cls, policy=save_flash_residuals()), "olmo_hybrid")

        name = KINDS[self.kind]
        mixer = GatedDeltaNet if name == "gdn" else Attention
        h = half(mixer)(cfg, name=name)(x)
        return half(Mlp)(cfg, name="mlp")(h)


class OlmoHybridLM(nn.Module):
    """tokens [B, S] -> logits [B, S, vocab] (compute dtype: the fused
    cross-entropy upcasts)."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        h = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="tok_emb")(tokens)
        for i, kind in enumerate(cfg.layer_types):
            h = Block(cfg, kind, name=f"block{i}")(h)
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(h)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        name="lm_head")(h)
