"""Xing4.0-style decoder: hyper-connected residual streams (mHC), RoPE
latent attention (MLA), leading dense layers then routed experts of which
this chip holds a share.

A model described by layer kinds, built from the published ``config.json``
keys (``Xing4Config.from_dict``); ``TransformerLM`` stays GPT-2's. bf16
compute over fp32 parameters; every block under ``nn.remat`` when
``remat``, which keeps the flash kernel's output and logsumexp
(``ops/pallas_attention.py::FLASH_RESIDUALS``) and recomputes everything
else of a block. The equations (``benchmark/configs/xing4.0-29b-a4b.json`` lists
what the published config does not settle, under ``assumed``):

*Streams.* The residual is ``n = hc_mult`` streams of width C, held as
``[n, B, S, C]`` (the stream index leads, so that no tile of the layout is
padded). Entry: every stream is the token embedding. Exit: the streams'
sum, RMSNorm, head.

*mHC*, once for each sub-layer F with its own parameters, per token and in
float32: ``x~ = RMSNorm_nC(vec X)`` (no learned scale);
``H_pre = sigmoid(a_pre x~ Phi_pre + b_pre)``,
``H_post = 2 sigmoid(a_post x~ Phi_post + b_post)``,
``H_res = Sinkhorn(clip(a_res mat(x~ Phi_res) + B_res))``;
``u = sum_i H_pre[i] X[i]``; ``y = F(RMSNorm(u))``;
``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``. The passes that walk the
streams (norm, projection, both mixes, and their backward) are
``ops/pallas_mhc.py``'s: fused kernels where the shape tiles, ``jnp`` where
it does not; the coefficient arithmetic on ``[n*n + 2n, B, S]`` is here.

*MLA.* Low-rank query (``q_lora_rank``) and key/value (``kv_lora_rank``)
paths with RMSNorm on the latents, per-head ``[nope | rope]`` queries and
keys (the rope key shared by all heads), YaRN-blended RoPE frequencies,
softmax scale ``qk_head_dim ** -0.5 * mscale ** 2``.

*Feed-forward.* SwiGLU; in expert layers ``parallel.expert.ExpertShare``.

*MTP* (``num_nextn_predict_layers``): one more expert block over
``W_eh [RMSNorm(emb(t_{i+1})); RMSNorm(h_i)]``; its logits are sown into the
collection ``mtp_logits``, which ``PjitEngine(task="lm", mtp_weight=...)``
holds to the target one further on (``MTP_LOSS_WEIGHT``).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_sandbox.models.latent import (  # noqa: F401  (RMSNorm, rms_norm:
    # the other models import them from here)
    GatedMlp, RMSNorm, apply_rope, low_rank_kv, low_rank_queries, rms_norm,
    yarn_inv_freq, yarn_mscale)
from tpu_sandbox.ops import pallas_mhc
from tpu_sandbox.ops.attention import causal_attention
from tpu_sandbox.ops.pallas_attention import (
    remat_saving, save_flash_residuals)
from tpu_sandbox.parallel.expert import ExpertShare, share_rows

# what the published config does not settle (the configuration file's
# ``assumed``: ``router_bias_update``, ``mtp``, ``mhc_init``)
BIAS_UPDATE_RATE = 1e-3          # gamma of the router's balancing bias
MTP_LOSS_WEIGHT = 0.3            # total = main + this x the MTP module's loss
MHC_ALPHA_INIT = 0.01
MHC_RES_OFF_DIAGONAL_INIT = -8.0  # B_res off the diagonal: H_res ~ I


@dataclass(frozen=True)
class Xing4Config:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    hc_mult: int
    hc_sinkhorn_iters: int
    hc_eps: float
    mhc_h_res_clamp_min: float
    mhc_h_res_clamp_max: float
    rms_norm_eps: float
    rope_theta: float
    rope_factor: float
    rope_beta_fast: float
    rope_beta_slow: float
    rope_mscale: float
    rope_mscale_all_dim: float
    rope_original_max_position_embeddings: int
    num_nextn_predict_layers: int = 0
    # the deployment: which experts live here, the static row buffer they
    # share (rows), and how the program computes
    held: tuple[int, ...] = ()
    local_rows: int = 0
    dtype: Any = jnp.bfloat16
    remat: bool = True
    flash: bool = False

    @classmethod
    def from_dict(cls, config: dict, *, tokens_per_step: int, dtype=jnp.bfloat16,
                  remat: bool = True, flash: bool = False) -> "Xing4Config":
        """From the published keys plus the file's ``deployment``: ``held``,
        ``local_rows_factor`` and, where the file's ``n_routed_experts``
        counts the experts held here (a chip's share), the router's
        published width ``routed_experts_total``. ``tokens_per_step`` sizes
        the experts' row buffer: ``local_rows_factor`` times the mean share
        of assignments, rounded up to the row tile."""
        dep = config.get("deployment", {})
        rope = config["rope_scaling"]
        if rope["type"] != "yarn":
            raise ValueError(f"rope_scaling type {rope['type']!r}: only yarn")
        if config.get("scoring_func", "sigmoid") != "sigmoid" or (
                config.get("n_group", 1), config.get("topk_group", 1)) != (1, 1):
            raise ValueError("only sigmoid scores with n_group = topk_group = 1")
        e = dep.get("routed_experts_total", config["n_routed_experts"])
        held = tuple(dep.get("held", range(e)))
        rows = share_rows(tokens_per_step, config["num_experts_per_tok"],
                          len(held), e, dep.get("local_rows_factor", 2),
                          ExpertShare.row_tile)
        return cls(
            **{key: config[key] for key in cls.__dataclass_fields__
               if key in config}
            | {"n_routed_experts": e},
            **{f"rope_{key}": rope[key] for key in (
                "factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim",
                "original_max_position_embeddings")},
            held=held, local_rows=rows, dtype=dtype, remat=remat, flash=flash)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


# --- the float32 parts, as functions the benchmark checks on their own
# (``rms_norm`` and ``apply_rope`` are ``models/latent.py``'s) ---

def sinkhorn(logits, iters: int, eps: float):
    """``logits [n, n, ...]`` -> matrices that are doubly stochastic over
    the two leading axes: ``exp``, then ``iters`` times rows divided by
    their sums, then columns by theirs (``eps`` in the denominators).
    The matrix axes lead so that the tokens fill the lanes."""
    m = jnp.exp(logits.astype(jnp.float32))

    def once(m, _):
        m = m / (m.sum(1, keepdims=True) + eps)
        return m / (m.sum(0, keepdims=True) + eps), None

    # a loop of ``iters`` steps, not ``iters`` copies of the step: unrolled,
    # the copies are most of the compiled train step's instructions
    return jax.lax.scan(once, m, None, length=iters)[0]


# --- modules ---

class HyperConnection(nn.Module):
    """One sub-layer's mHC: ``pre`` gives the sub-layer's input and the
    mixing coefficients, ``post`` the new streams. Streams ``[n, B, S, C]``."""

    config: Xing4Config

    def setup(self):
        cfg = self.config
        n, c = cfg.hc_mult, cfg.hidden_size
        phi = nn.initializers.normal(1.0 / math.sqrt(n * c))
        alpha = nn.initializers.constant(MHC_ALPHA_INIT)
        self.phi_pre = self.param("phi_pre", phi, (n, c, n), jnp.float32)
        self.phi_post = self.param("phi_post", phi, (n, c, n), jnp.float32)
        self.phi_res = self.param("phi_res", phi, (n, c, n * n), jnp.float32)
        self.alpha_pre = self.param("alpha_pre", alpha, (), jnp.float32)
        self.alpha_post = self.param("alpha_post", alpha, (), jnp.float32)
        self.alpha_res = self.param("alpha_res", alpha, (), jnp.float32)
        # H_pre = 1/n, H_post = 1, H_res ~ I at the start
        self.b_pre = self.param(
            "b_pre", nn.initializers.constant(-math.log(n - 1.0)), (n,),
            jnp.float32)
        self.b_post = self.param("b_post", nn.initializers.zeros, (n,),
                                 jnp.float32)
        self.b_res = self.param(
            "b_res", lambda *_: MHC_RES_OFF_DIAGONAL_INIT
            * (1.0 - jnp.eye(n, dtype=jnp.float32)), (n, n))

    def pre(self, streams):
        """-> the sub-layer's input ``u``, the streams for ``post`` (as they
        came; ``pallas_mhc.pre`` says why ``post`` reads this copy) and the
        coefficients ``(h_res, h_post)``."""
        cfg = self.config
        n = cfg.hc_mult
        phi = jnp.concatenate([self.phi_pre, self.phi_post, self.phi_res], -1)
        # proj [n*n + 2n, B, S], float32: x~ Phi, the coefficient index
        # leads, tokens fill the lanes
        u, proj, streams = pallas_mhc.pre(
            streams, phi, self.alpha_pre, self.b_pre, eps=cfg.hc_eps,
            dtype=cfg.dtype)
        h_post = 2.0 * jax.nn.sigmoid(
            self.alpha_post * proj[n:2 * n] + self.b_post[:, None, None])
        h_res = sinkhorn(
            jnp.clip(self.alpha_res * proj[2 * n:].reshape(n, n, *proj.shape[1:])
                     + self.b_res[:, :, None, None],
                     cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max),
            cfg.hc_sinkhorn_iters, cfg.hc_eps)
        return u, streams, (h_res, h_post)

    def post(self, streams, y, coefficients):
        return pallas_mhc.post(streams, y, *coefficients)


class LatentAttention(nn.Module):
    config: Xing4Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, _ = x.shape
        h, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
        dense = functools.partial(nn.DenseGeneral, use_bias=False,
                                  dtype=cfg.dtype)
        q = low_rank_queries(
            x, heads=h, rank=cfg.q_lora_rank, head_dim=nope + rope,
            eps=cfg.rms_norm_eps, dtype=cfg.dtype)                # [B,S,H,192]
        c_kv, k_rope = low_rank_kv(
            x, rank=cfg.kv_lora_rank, rope_dim=rope, eps=cfg.rms_norm_eps,
            dtype=cfg.dtype)
        kv = dense((h, nope + dv), name="kv_b")(c_kv)             # [B,S,H,256]
        inv_freq, _, _ = yarn_inv_freq(
            rope, cfg.rope_theta, cfg.rope_factor, cfg.rope_beta_fast,
            cfg.rope_beta_slow, cfg.rope_original_max_position_embeddings)
        m_all = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
        cs = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / m_all
        q_pe = apply_rope(q[..., nope:], inv_freq, cs).astype(cfg.dtype)
        k_pe = apply_rope(k_rope, inv_freq, cs)
        q = jnp.concatenate([q[..., :nope], q_pe], -1)
        k = jnp.concatenate([
            kv[..., :nope],
            jnp.broadcast_to(k_pe.astype(cfg.dtype), (b, s, h, rope))], -1)
        scale = cfg.qk_head_dim ** -0.5 * m_all * m_all
        if cfg.flash:
            from tpu_sandbox.ops.pallas_attention import flash_attention

            out = flash_attention(q, k, kv[..., nope:], scale=scale)
        else:
            out = causal_attention(q, k, kv[..., nope:], scale=scale)
        return dense(cfg.hidden_size, axis=(-2, -1), name="o")(out)


def expert_share(cfg: Xing4Config, name: str) -> ExpertShare:
    """This chip's share of an expert layer, from the model's config."""
    return ExpertShare(
        d_model=cfg.hidden_size, d_ff=cfg.moe_intermediate_size,
        n_routed_experts=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
        held=cfg.held, local_rows=cfg.local_rows,
        n_shared_experts=cfg.n_shared_experts,
        routed_scaling_factor=cfg.routed_scaling_factor,
        bias_update_rate=BIAS_UPDATE_RATE, dtype=cfg.dtype, name=name)


class Block(nn.Module):
    config: Xing4Config
    dense: bool

    @nn.compact
    def __call__(self, streams):
        cfg = self.config
        norm = functools.partial(RMSNorm, cfg.rms_norm_eps, cfg.dtype)
        mhc = HyperConnection(cfg, name="mhc_attn")
        u, streams, coefficients = mhc.pre(streams)
        y = LatentAttention(cfg, name="mla")(norm(name="attn_norm")(u))
        streams = mhc.post(streams, y, coefficients)
        mhc = HyperConnection(cfg, name="mhc_ffn")
        u, streams, coefficients = mhc.pre(streams)
        ffn = (GatedMlp(cfg.intermediate_size, cfg.hidden_size, cfg.dtype,
                        name="mlp") if self.dense
               else expert_share(cfg, "moe"))
        return mhc.post(streams, ffn(norm(name="ffn_norm")(u)), coefficients)


class Xing4LM(nn.Module):
    """tokens [B, S] -> logits [B, S, vocab] (compute dtype: the fused
    cross-entropy upcasts)."""

    config: Xing4Config

    @nn.compact
    def __call__(self, tokens):
        cfg = self.config
        n = cfg.hc_mult
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         name="tok_emb")
        norm_f = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")
        head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        name="lm_head")
        block_cls = (remat_saving(
            nn.remat(Block, policy=save_flash_residuals()), "xing4")
            if cfg.remat else Block)

        def enter(x):
            return jnp.broadcast_to(x[None], (n, *x.shape))

        def leave(streams):
            return streams.astype(jnp.float32).sum(0).astype(cfg.dtype)

        emb = embed(tokens)
        streams = enter(emb)
        for i in range(cfg.num_hidden_layers):
            streams = block_cls(cfg, i < cfg.first_k_dense_replace,
                                name=f"block{i}")(streams)
        h = leave(streams)
        logits = head(norm_f(h))
        if cfg.num_nextn_predict_layers:
            # position i sees the next token's embedding and h_i, and its
            # logits are held to the target of position i + 1
            norm = functools.partial(RMSNorm, cfg.rms_norm_eps, cfg.dtype)
            nxt = jnp.roll(emb, -1, axis=1)
            merged = nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                              name="mtp_proj")(jnp.concatenate(
                                  [norm(name="mtp_norm_emb")(nxt),
                                   norm(name="mtp_norm_h")(h)], -1))
            out = leave(block_cls(cfg, False, name="mtp_block")(enter(merged)))
            self.sow("mtp_logits", "logits", head(norm_f(out)))
        return logits
