"""Nemotron-H-style hybrid decoder: a list of blocks of ONE mixer each, read
from the published ``hybrid_override_pattern`` (``M`` a Mamba-2 state-space
mixer, ``*`` grouped-query attention, ``E`` routed experts in a latent of
which this chip holds a share).

Built from the published ``config.json`` keys under their published names
(``NemotronHConfig.from_dict``); how many heads, groups, experts and
vocabulary rows are held here are plain numbers of the configuration file.
bf16 compute over fp32 parameters, a bf16 residual, every block under
``nn.remat`` when ``remat``, which keeps the flash kernel's output and
logsumexp (``ops/pallas_attention.py::FLASH_RESIDUALS``) and recomputes
everything else of a block. The equations
(``benchmark/configs/nemotron-3-super-120b-a12b.json`` lists what the
published config does not settle, under ``assumed``):

*Block*, kind k: ``x <- x + Mixer_k(RMSNorm(x))``; after the last block
``RMSNorm``, then the untied head. No bias but the convolution's.

*Mamba-2* (H heads of P, G groups of state N, kernel K, chunk Q):
``[z | xBC | dt] = u W_in`` (widths HP | HP + 2GN | H);
``xBC <- silu(conv_K(xBC) + b)``, causal and depthwise; ``x [H, P]``,
``B [G, N]``, ``C [G, N]``; ``dt = softplus(dt + dt_bias)``,
``A = -exp(A_log)``; head h reads group ``h G // H``;
``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t + D x_t``
(``ops/ssd.py``, by chunks); ``y <- RMSNorm_groups(y * silu(z)) * w`` over
groups of HP / G; ``out = y W_out``. dt, A and the decays in float32.

*Attention*: ``num_attention_heads`` query heads on ``num_key_value_heads``
key/value heads of ``head_dim``, causal, scale ``head_dim ** -0.5``, no
positional encoding; the key/value heads are broadcast to the query heads
in front of the kernel.

*LatentMoE*: sigmoid scores of the full-width input over all experts, the
``num_experts_per_tok`` largest of score + bias, weights normalised to sum
``routed_scaling_factor``; experts ``W2 relu(W1 l) ** 2`` on
``l = u W_down`` (``moe_latent_size``), the held experts' sum projected back
by ``W_up``; plus a shared expert ``V2 relu(V1 u) ** 2`` on the full width.

*MTP* (``num_nextn_predict_layers``): the blocks of
``mtp_hybrid_override_pattern`` over
``W_eh [RMSNorm(emb(t_{i+1})); RMSNorm(h_i)]``; logits sown into
``mtp_logits`` (``PjitEngine(task="lm", mtp_weight=...)``).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_sandbox.models.xing4 import RMSNorm, rms_norm
from tpu_sandbox.ops.attention import causal_attention
from tpu_sandbox.ops.pallas_attention import (
    remat_saving, save_flash_residuals)
# ``causal_conv``: the plain form, here for the tests that compare with it
from tpu_sandbox.ops.pallas_short_conv import causal_conv, short_conv
from tpu_sandbox.ops.ssd import ssd_scan
from tpu_sandbox.parallel.expert import ExpertShare, share_rows

# what the published config does not settle (the configuration file's
# ``assumed``: ``router_bias_update``, ``mtp``)
BIAS_UPDATE_RATE = 1e-3          # gamma of the router's balancing bias
MTP_LOSS_WEIGHT = 0.3            # total = main + this x the MTP module's loss
KINDS = {"M": "mamba", "*": "attn", "E": "moe"}


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    hybrid_override_pattern: str
    layer_norm_epsilon: float
    # Mamba-2
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    time_step_min: float
    time_step_max: float
    time_step_floor: float
    # attention
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    # experts
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    moe_latent_size: int
    moe_shared_expert_intermediate_size: int
    routed_scaling_factor: float
    num_nextn_predict_layers: int = 0
    mtp_hybrid_override_pattern: str = ""
    # the deployment: which experts live here, the static row buffer they
    # share (rows), and how the program computes
    held: tuple[int, ...] = ()
    local_rows: int = 0
    dtype: Any = jnp.bfloat16
    remat: bool = True
    flash: bool = False

    @classmethod
    def from_dict(cls, config: dict, *, tokens_per_step: int,
                  dtype=jnp.bfloat16, remat: bool = True,
                  flash: bool = False) -> "NemotronHConfig":
        """From the published keys plus the file's ``deployment``: ``held``,
        ``local_rows_factor`` and, where the file's ``n_routed_experts``
        counts the experts held here, the router's published width
        ``routed_experts_total``. ``tokens_per_step`` sizes the experts' row
        buffer (``parallel.expert.share_rows``)."""
        dep = config.get("deployment", {})
        pattern = config["hybrid_override_pattern"]
        unknown = set(pattern + config.get("mtp_hybrid_override_pattern", "")
                      ) - set(KINDS)
        if unknown or len(pattern) != config["num_hidden_layers"]:
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: {len(pattern)} blocks "
                f"for num_hidden_layers {config['num_hidden_layers']}, kinds "
                f"{sorted(unknown)} unknown (known: {sorted(KINDS)})")
        if (config.get("n_group", 1), config.get("topk_group", 1)) != (1, 1):
            raise ValueError("only n_group = topk_group = 1")
        if (config.get("mlp_hidden_act", "relu2"),
                config.get("mamba_hidden_act", "silu")) != ("relu2", "silu"):
            raise ValueError("only relu2 experts and a silu Mamba-2")
        biased = [key for key in ("attention_bias", "mlp_bias", "use_bias",
                                  "mamba_proj_bias") if config.get(key)]
        if biased or not config.get("use_conv_bias", True):
            raise ValueError(f"biases {biased}: only the convolution has one")
        if config["num_attention_heads"] % config["num_key_value_heads"] or (
                config["mamba_num_heads"] % config["n_groups"]):
            raise ValueError("heads do not divide into their groups")
        e = dep.get("routed_experts_total", config["n_routed_experts"])
        held = tuple(dep.get("held", range(e)))
        rows = share_rows(tokens_per_step, config["num_experts_per_tok"],
                          len(held), e, dep.get("local_rows_factor", 2),
                          ExpertShare.row_tile)
        return cls(**{key: config[key] for key in cls.__dataclass_fields__
                      if key in config} | {"n_routed_experts": e},
                   held=held, local_rows=rows, dtype=dtype, remat=remat,
                   flash=flash)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim


# --- the float32 parts, as functions the benchmark checks on their own ---

def time_step(dt, dt_bias):
    """``softplus(dt + dt_bias)`` in float32."""
    return jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm_groups(y * silu(z)) * scale`` over ``groups`` equal slices
    of the last axis, in float32: the gate before the norm."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = gated.reshape(*gated.shape[:-1], groups, -1)
    return rms_norm(grouped, eps).reshape(gated.shape) * scale


def log_uniform_step(lo: float, hi: float, floor: float):
    """``dt_bias`` = softplus^-1 of a step drawn log-uniformly in [lo, hi],
    floored (Mamba-2's usual start)."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


def log_of_uniform(lo: float, hi: float):
    def init(key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(key, shape, dtype, lo, hi))
    return init


# --- the mixers ---

class Mamba2Mixer(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        b, s, _ = u.shape
        h, p, g, n = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                      cfg.ssm_state_size)
        d_in, conv_dim = h * p, h * p + 2 * g * n
        dense = functools.partial(nn.Dense, use_bias=False, dtype=cfg.dtype)
        zxbcdt = dense(d_in + conv_dim + h, name="in_proj")(u)
        z, dt = zxbcdt[..., :d_in], zxbcdt[..., d_in + conv_dim:]
        with jax.named_scope("conv"):
            kernel = self.param(
                "conv_kernel", nn.initializers.variance_scaling(
                    1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                (cfg.conv_kernel, conv_dim), jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros, (conv_dim,),
                              jnp.float32)
            # xBC where it lies in ``in_proj``'s result: no slice is made
            xbc = short_conv(zxbcdt, kernel, bias, start=d_in)
        x, b_in, c_in = jnp.split(xbc, [d_in, d_in + g * n], -1)
        a_log = self.param("A_log", log_of_uniform(1.0, 16.0), (h,),
                           jnp.float32)
        dt_bias = self.param("dt_bias", log_uniform_step(
            cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor), (h,),
            jnp.float32)
        skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        x = x.reshape(b, s, h, p)
        y = ssd_scan(x, time_step(dt, dt_bias), -jnp.exp(a_log),
                     b_in.reshape(b, s, g, n), c_in.reshape(b, s, g, n),
                     chunk=cfg.chunk_size)
        with jax.named_scope("norm"):
            scale = self.param("norm_scale", nn.initializers.ones, (d_in,),
                               jnp.float32)
            y = (y.astype(jnp.float32)
                 + skip[:, None] * x.astype(jnp.float32)).reshape(b, s, d_in)
            y = gated_group_norm(y, z, scale, g, cfg.layer_norm_epsilon)
        return dense(cfg.hidden_size, name="out_proj")(y.astype(cfg.dtype))


class Attention(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        b, s, _ = u.shape
        hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        dense = functools.partial(nn.DenseGeneral, use_bias=False,
                                  dtype=cfg.dtype)
        q = dense((hq, d), name="q")(u)                           # [B,S,Hq,D]
        kv = dense((2, hkv, d), name="kv")(u)                     # [B,S,2,Hkv,D]

        def per_query_head(a):  # query head i reads key/value head i Hkv // Hq
            return jnp.broadcast_to(
                a[:, :, :, None], (b, s, hkv, hq // hkv, d)).reshape(b, s, hq, d)

        k, v = per_query_head(kv[:, :, 0]), per_query_head(kv[:, :, 1])
        if cfg.flash:
            from tpu_sandbox.ops.pallas_attention import flash_attention

            out = flash_attention(q, k, v)
        else:
            out = causal_attention(q, k, v)
        return dense(cfg.hidden_size, axis=(-2, -1), name="o")(out)


class LatentMoE(ExpertShare):
    """``ExpertShare`` with its experts in a latent: ``d_model`` is the
    latent's width, the router scores the full-width input, and the shared
    expert (``shared_width``, un-gated, on the full width) is this
    module's."""

    shared_width: int = 0

    @nn.compact
    def __call__(self, u):
        lead, c = u.shape[:-1], u.shape[-1]
        u = u.reshape(-1, c).astype(self.dtype)
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        latent = dense(self.d_model, name="latent_down")(u)
        y = dense(c, name="latent_up")(self.routed(latent, route_on=u))
        with jax.named_scope("shared"):
            y = y + self.dense_expert(u, self.shared_width, c, "shared")
        return y.reshape(*lead, c)


def latent_moe(cfg: NemotronHConfig, name: str) -> LatentMoE:
    """This chip's share of an expert layer, from the model's config."""
    return LatentMoE(
        d_model=cfg.moe_latent_size, d_ff=cfg.moe_intermediate_size,
        n_routed_experts=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
        held=cfg.held, local_rows=cfg.local_rows, kind="relu2",
        shared_width=cfg.moe_shared_expert_intermediate_size,
        routed_scaling_factor=cfg.routed_scaling_factor,
        bias_update_rate=BIAS_UPDATE_RATE, dtype=cfg.dtype, name=name)


class Block(nn.Module):
    config: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        u = RMSNorm(cfg.layer_norm_epsilon, cfg.dtype, name="norm")(x)
        name = KINDS[self.kind]
        mixer = (Mamba2Mixer(cfg, name=name) if self.kind == "M"
                 else Attention(cfg, name=name) if self.kind == "*"
                 else latent_moe(cfg, name))
        return x + mixer(u)


class NemotronHLM(nn.Module):
    """tokens [B, S] -> logits [B, S, vocab] (compute dtype: the fused
    cross-entropy upcasts)."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        norm = functools.partial(RMSNorm, cfg.layer_norm_epsilon, cfg.dtype)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         name="tok_emb")
        norm_f = norm(name="norm_f")
        head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        name="lm_head")
        block_cls = (remat_saving(
            nn.remat(Block, policy=save_flash_residuals()), "nemotron_h")
            if cfg.remat else Block)

        emb = embed(tokens)
        h = emb
        for i, kind in enumerate(cfg.hybrid_override_pattern):
            h = block_cls(cfg, kind, name=f"block{i}")(h)
        logits = head(norm_f(h))
        if cfg.num_nextn_predict_layers:
            # position i sees the next token's embedding and h_i, and its
            # logits are held to the target of position i + 1
            nxt = jnp.roll(emb, -1, axis=1)
            out = nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                           name="mtp_proj")(jnp.concatenate(
                               [norm(name="mtp_norm_emb")(nxt),
                                norm(name="mtp_norm_h")(h)], -1))
            for j, kind in enumerate(cfg.mtp_hybrid_override_pattern):
                out = block_cls(cfg, kind, name=f"mtp_block{j}")(out)
            self.sow("mtp_logits", "logits", head(norm_f(out)))
        return logits
