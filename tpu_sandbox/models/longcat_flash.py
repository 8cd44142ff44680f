"""LongCat-Flash-style decoder (the language model of ``LongCat-Flash-Omni``):
shortcut-connected **double layers** -- two latent-attention (MLA)
sub-layers and two dense gated MLPs in series, a routed-expert layer that
reads the first sub-layer's input to its dense MLP and joins the stream
after the second -- over a router of ``n_routed_experts`` real and
``zero_expert_num`` identity ("zero-compute") experts. Built for
**serving**: the same module runs a whole sequence (the full forward, and
prefill, which taps each sub-layer's latent row for the cache) and one token
a row against a cache it does not own (decode, through ``attention_fn``).

Built from the published ``config.json`` keys under their published names
(``LongcatFlashConfig.from_dict``). Matrices and activations in ``dtype`` /
``param_dtype`` (bfloat16 as served), norms, softmax and the router in
float32. The equations (``benchmark/configs/longcat-flash-omni.json`` lists
what the published config does not settle, under ``assumed``):

*Double layer* (``RMSNorm`` with a learned scale everywhere)::

    h  = x + MLA_0(RMSNorm_a0(x))
    u0 = RMSNorm_f0(h)
    s  = MoE(u0)                    # the shortcut: from the first sub-layer
    h  = h + MLP_0(u0)
    h  = h + MLA_1(RMSNorm_a1(h))
    h  = h + MLP_1(RMSNorm_f1(h))
    x' = h + s                      # ... joined after the second

*MLA* (``models/latent.py``'s low-rank paths): ``q = W_qb(RMSNorm(W_qa u) *
sqrt(hidden / q_lora_rank))`` split a head into ``[nope | rope]``; ``[c |
k_pe] = W_kva u``, ``c <- RMSNorm(c) * sqrt(hidden / kv_lora_rank)``; plain
RoPE (``rope_theta``, rotate-half pairing) on ``q_pe`` and the one ``k_pe``
all heads share; ``[k_nope | v] = W_kvb c`` a head; scores ``(q_nope k_nope
+ q_pe k_pe) / sqrt(nope + rope)``, causal softmax in float32.

**What a position leaves behind is one row** ``[c | k_pe]`` of
``kv_lora_rank + qk_rope_head_dim`` values, as it enters ``W_kvb`` and the
score (``latent`` in the ``kv_cache`` collection). A whole sequence attends
in the *expanded* form above; one token against cached rows in the
*absorbed* form: ``q~ = q_nope W_kvb^K`` (``[heads, kv_lora_rank]``), score
``(q~ c + q_pe k_pe) / sqrt(nope + rope)``, ``o = (softmax(score) c)
W_kvb^V`` -- the same numbers, the products in another order
(``absorbed_attention`` is that form in plain ``jnp`` over rows given whole).

*Routed layer*: ``parallel.expert.ExpertShare`` with the ``softmax`` score
rule (weights ``routed_scaling_factor * p``, not normalised), the zero
experts' term whole on every chip, and this chip's ``held`` experts; its
row buffer and row tile follow the tokens of a decode call (``share_rows``,
``share_row_tile``); a whole sequence runs every held expert over all its
tokens (``parallel.expert.PromptShare``: a prompt's positions choose alike,
and no row may drop).

Scopes in a device trace: ``block{i}/mla{0,1}/{q_a, q_b, kv_a, rope, kv_b |
absorb, unabsorb, o}``, ``block{i}/mlp{0,1}/{gate, up, down}``,
``block{i}/moe/{router, dispatch, experts, combine, zero}``, ``lm_head``.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_sandbox.models.latent import (GatedMlp, RMSNorm, apply_rope,
                                       low_rank_kv, low_rank_queries)
from tpu_sandbox.ops.attention import causal_attention
from tpu_sandbox.parallel.expert import (ExpertShare, PromptShare,
                                         share_row_tile, share_rows)

_F32 = jnp.float32


@dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int
    hidden_size: int
    ffn_hidden_size: int
    expert_ffn_hidden_size: int
    num_layers: int
    num_attention_heads: int
    kv_lora_rank: int
    q_lora_rank: int
    qk_rope_head_dim: int
    v_head_dim: int
    qk_nope_head_dim: int
    mla_scale_q_lora: bool
    mla_scale_kv_lora: bool
    routed_scaling_factor: float
    n_routed_experts: int         # the router's real experts (published)
    zero_expert_num: int
    moe_topk: int
    rms_norm_eps: float
    rope_theta: float
    # the deployment: which experts live here, how much room their rows get
    # over the mean, and how the program computes
    held: tuple[int, ...] = ()
    local_rows_factor: float = 4.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    flash: bool = False

    @classmethod
    def from_dict(cls, config: dict, **deployment) -> "LongcatFlashConfig":
        """From the published keys plus the file's ``deployment``: ``held``,
        ``local_rows_factor`` and, where the file's ``n_routed_experts``
        counts the experts held here (a chip's share), the router's
        published width ``routed_experts_total``. ``deployment`` keyword
        arguments set ``dtype``, ``param_dtype`` and ``flash``."""
        wrong = [f"{key}={config.get(key)!r}" for key, want in (
            ("attention_bias", False), ("zero_expert_type", "identity"),
            ("attention_method", "MLA")) if config.get(key, want) != want]
        if wrong or "rope_scaling" in config:
            raise ValueError(f"longcat_flash: only MLA without bias, identity "
                             f"zero experts and plain RoPE: {wrong}")
        dep = config.get("deployment", {})
        e = dep.get("routed_experts_total", config["n_routed_experts"])
        return cls(
            **{key: config[key] for key in cls.__dataclass_fields__
               if key in config} | {"n_routed_experts": e},
            held=tuple(dep.get("held", range(e))),
            local_rows_factor=dep.get("local_rows_factor", 4.0), **deployment)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """The values a position leaves in the cache, a sub-layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def router_width(self) -> int:
        return self.n_routed_experts + self.zero_expert_num


#: positions x heads a whole sequence's expanded attention holds at once: a
#: longer prompt attends a group of heads at a time (``_head_groups``)
HEAD_GROUP_BUDGET = 2048 * 64


def _head_groups(positions: int, heads: int) -> int:
    """The groups of heads a sequence's expanded attention runs in, one
    after another, from the shapes alone: the expanded keys and values of
    all 64 heads of a 6144-token prompt, the padded operands of the flash
    kernel and their transposes are 1.4 GB the chip does not have beside
    the weights and the pool (``tools/aot_serve_step.py``); a quarter of
    the heads at a time is what a 2048-token prompt holds."""
    groups = 1
    while positions * (heads // groups) > HEAD_GROUP_BUDGET \
            and heads % (2 * groups) == 0:
        groups *= 2
    return groups


def inv_freq(cfg: LongcatFlashConfig):
    d = cfg.qk_rope_head_dim
    return 1.0 / cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=_F32) / d)


def absorbed_attention(q, rows, lengths, *, v_dim: int, scale: float):
    """The absorbed form over rows given whole: ``q [B, H, d]`` (``[q~ |
    q_pe]``) against ``rows [B, S, d]`` (``[c | k_pe]``), the first
    ``lengths [B]`` of them; the values are a row's first ``v_dim`` entries.
    ``[B, H, v_dim]`` in ``q``'s type; ``lengths == 0`` gives zeros."""
    rows = rows.astype(q.dtype)
    scores = jnp.einsum("bhd,bkd->bhk", q, rows,
                        preferred_element_type=_F32) * scale
    live = jnp.arange(rows.shape[1])[None, :] < lengths[:, None]
    scores = jnp.where(live[:, None, :], scores, -jnp.inf)
    w = jnp.nan_to_num(jnp.exp(scores - scores.max(-1, keepdims=True)))
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bhk,bkc->bhc", w.astype(rows.dtype),
                      rows[..., :v_dim])


class LatentAttention(nn.Module):
    """``u [B, S, C]`` at ``positions [B, S]`` (0..S-1 where None) -> ``[B,
    S, C]``. ``attention_fn(q [B, H, latent_dim], row [B, latent_dim]) ->
    [B, H, kv_lora_rank]`` stands for one token's attention over what its
    sequence has cached, its own row among it (S is 1): the absorbed form.
    Without it the sequence attends to itself, causally, in the expanded
    form."""

    config: LongcatFlashConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, u, positions=None):
        cfg = self.config
        b, s, c = u.shape
        h, nope, rope, dv, rank = (
            cfg.num_attention_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank)
        kinds = {"eps": cfg.rms_norm_eps, "dtype": cfg.dtype,
                 "param_dtype": cfg.param_dtype}
        q = low_rank_queries(
            u, heads=h, rank=cfg.q_lora_rank, head_dim=nope + rope, **kinds,
            scale=math.sqrt(c / cfg.q_lora_rank)
            if cfg.mla_scale_q_lora else 1.0)                     # [B,S,H,192]
        latent, k_rope = low_rank_kv(
            u, rank=rank, rope_dim=rope, **kinds,
            scale=math.sqrt(c / rank) if cfg.mla_scale_kv_lora else 1.0)
        with jax.named_scope("rope"):
            freq = inv_freq(cfg)
            q_pe = apply_rope(q[..., nope:], freq,
                              positions=positions).astype(cfg.dtype)
            k_pe = apply_rope(k_rope, freq,
                              positions=positions)[..., 0, :].astype(cfg.dtype)
        # what the position leaves behind: serving's prefill taps it here
        # (a no-op unless ``kv_cache`` is mutable)
        row = jnp.concatenate([latent, k_pe], -1)                 # [B,S,576]
        self.sow("kv_cache", "latent", row, reduce_fn=lambda _, x: x)
        w_kvb = self.param(
            "kv_b", nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal", in_axis=0, out_axis=(1, 2)),
            (rank, h, nope + dv), cfg.param_dtype).astype(cfg.dtype)
        scale = cfg.qk_head_dim ** -0.5
        if self.attention_fn is not None:
            with jax.named_scope("absorb"):
                q_lat = jnp.einsum("bhd,chd->bhc", q[:, 0, :, :nope],
                                   w_kvb[..., :nope])             # [B,H,512]
                q_full = jnp.concatenate([q_lat, q_pe[:, 0]], -1)
            ctx = self.attention_fn(q_full, row[:, 0])            # [B,H,512]
            with jax.named_scope("unabsorb"):
                out = jnp.einsum("bhc,chd->bhd", ctx, w_kvb[..., nope:])
            out = out[:, None]
        else:
            if cfg.flash:
                from tpu_sandbox.ops.pallas_attention import flash_attention

                attend = flash_attention
            else:
                attend = causal_attention

            def expanded(args):
                """``q_g [B, S, Hg, 192]``, ``w_g [rank, Hg, 256]`` -> the
                heads' outputs ``[B, S, Hg, dv]``."""
                q_g, w_g = args
                with jax.named_scope("kv_b"):
                    kv = jnp.einsum("bsc,chd->bshd", latent, w_g)
                k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                    k_pe[:, :, None, :], (*kv.shape[:3], rope))], -1)
                return attend(q_g, k, kv[..., nope:], scale=scale)

            q = jnp.concatenate([q[..., :nope], q_pe], -1)
            groups = _head_groups(s, h)
            if groups == 1:
                out = expanded((q, w_kvb))
            else:
                hg = h // groups
                out = jax.lax.map(expanded, (
                    jnp.moveaxis(q.reshape(b, s, groups, hg, nope + rope), 2, 0),
                    jnp.moveaxis(w_kvb.reshape(rank, groups, hg, nope + dv),
                                 1, 0)))                          # [G,B,S,Hg,dv]
                out = jnp.moveaxis(out, 0, 2).reshape(b, s, h, dv)
        return nn.DenseGeneral(c, axis=(-2, -1), use_bias=False,
                               dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                               name="o")(out)


def expert_share(cfg: LongcatFlashConfig, tokens: int, name: str | None,
                 whole_sequence: bool = False) -> ExpertShare:
    """This chip's share of a double layer's routed experts for a call of
    ``tokens`` tokens. A decode step's share (one token a session) works
    over the static row buffer, its rows and row tile sized by the call
    (``share_rows``, ``share_row_tile``: shapes alone) -- the form its
    deployment runs, where the rows of 32 chips' tokens arrive by the
    exchange. A whole sequence's is a ``PromptShare``."""
    sizes = (tokens, cfg.moe_topk, len(cfg.held), cfg.router_width,
             cfg.local_rows_factor)
    tile = share_row_tile(*sizes)
    return (PromptShare if whole_sequence else ExpertShare)(
        d_model=cfg.hidden_size, d_ff=cfg.expert_ffn_hidden_size,
        n_routed_experts=cfg.n_routed_experts, top_k=cfg.moe_topk,
        held=cfg.held, local_rows=share_rows(*sizes, tile), row_tile=tile,
        routed_scaling_factor=cfg.routed_scaling_factor,
        bias_update_rate=0.0, dtype=cfg.dtype, score_rule="softmax",
        n_zero_experts=cfg.zero_expert_num, param_dtype=cfg.param_dtype,
        name=name)


class DoubleLayer(nn.Module):
    config: LongcatFlashConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.config
        norm = functools.partial(RMSNorm, cfg.rms_norm_eps, cfg.dtype)
        mla = functools.partial(LatentAttention, cfg, self.attention_fn)
        mlp = functools.partial(GatedMlp, cfg.ffn_hidden_size,
                                cfg.hidden_size, cfg.dtype, cfg.param_dtype)
        # a whole sequence's stream is settled behind every residual add:
        # left to itself the compiler keeps every sub-layer's output of
        # every layer to the program's end and sums them there (0.38 GB a
        # double layer at 6144 tokens, ``tools/aot_serve_step.py``)
        settle = (jax.lax.optimization_barrier if self.attention_fn is None
                  else lambda h: h)
        h = settle(x + mla(name="mla0")(norm(name="attn_norm0")(x), positions))
        u0 = norm(name="ffn_norm0")(h)
        shortcut = expert_share(cfg, u0.shape[0] * u0.shape[1], "moe",
                                self.attention_fn is None)(u0)
        h = settle(h + mlp(name="mlp0")(u0))
        h = settle(h + mla(name="mla1")(norm(name="attn_norm1")(h), positions))
        h = settle(h + mlp(name="mlp1")(norm(name="ffn_norm1")(h)))
        return settle(h + shortcut)


class Head(nn.Module):
    """The untied output head: float32 logits of bfloat16 operands."""

    vocab_size: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.vocab_size), self.param_dtype)
        return jnp.einsum("bsc,cv->bsv", x, kernel.astype(self.dtype),
                          preferred_element_type=_F32)


class LongcatFlashLM(nn.Module):
    """``tokens [B, S]`` at ``positions [B, S]`` (0..S-1 where None) ->
    float32 logits ``[B, S, vocab]``, or ``[B, 1, vocab]`` at ``last_pos``
    where it is given (prefill keeps the last real position's). With an
    ``attention_fn`` S is 1 and every sub-layer attends through it
    (``LatentAttention``). The router's bias and the share's counters live
    in ``batch_stats`` (``split_stats``)."""

    config: LongcatFlashConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, tokens, positions=None, *, last_pos=None):
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="tok_emb")(tokens)
        for i in range(cfg.num_layers):
            x = DoubleLayer(cfg, self.attention_fn, name=f"block{i}")(
                x, positions)
        if last_pos is not None:
            x = jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(x)
        return Head(cfg.vocab_size, cfg.dtype, cfg.param_dtype,
                    name="lm_head")(x)


_BIAS = "e_score_correction_bias"


def split_stats(batch_stats: dict) -> tuple[dict, dict]:
    """``batch_stats`` as ``(bias, counters)``: what a served model holds
    with its weights (the router's bias a layer) and what its programs move
    (the share's counters a layer)."""
    bias = {name: layer["moe"][_BIAS] for name, layer in batch_stats.items()}
    counters = {name: {k: v for k, v in layer["moe"].items() if k != _BIAS}
                for name, layer in batch_stats.items()}
    return bias, counters


def join_stats(bias: dict, counters: dict) -> dict:
    """``split_stats`` undone."""
    return {name: {"moe": dict(counters[name], **{_BIAS: bias[name]})}
            for name in bias}


def counter_shapes(cfg: LongcatFlashConfig) -> dict:
    """The share's counters a double layer, as the programs carry them."""
    names = ("rows_held", "rows_dropped", "expert_rows_max", "steps",
             "real_choices", "zero_choices")
    return {f"block{i}": {n: jax.ShapeDtypeStruct((), jnp.int32)
                          for n in names}
            for i in range(cfg.num_layers)}
