"""Decoder-only transformer LM — the long-context / wider-parallelism model.

The reference's only model is a CNN (SURVEY §2.2: TP/PP/SP/EP and attention
all absent). This framework treats long-context and multi-axis parallelism
as first-class, so it ships a transformer whose attention implementation is
*injected*: the same module runs

- single-device with ops.attention.causal_attention (the reference math),
- sequence-parallel with parallel.ring_attention inside a shard_map over an
  'sp' mesh axis (see parallel/seq_parallel.py),
- tensor-parallel via PjitEngine rules on the Dense kernels (qkv/mlp),
- and with a MoE MLP for expert parallelism (parallel/expert.py).

TPU-first: bf16 compute / fp32 params option, LayerNorm stats in fp32,
static shapes, no data-dependent control flow.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_sandbox.ops.attention import causal_attention
from tpu_sandbox.ops.pallas_attention import (
    FLASH_RESIDUALS, remat_saving, save_flash_residuals)


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 256
    max_len: int = 2048
    dtype: Any = jnp.float32
    # MoE: 0 experts = dense MLP everywhere; >0 = MoE MLP in every block
    n_experts: int = 0
    capacity_factor: float = 2.0
    # 1 = Switch top-1 (gate = router prob); >1 = GShard-style top-k with
    # normalized gates and choice-major capacity priority
    router_top_k: int = 1
    # rematerialize each block's activations in backward (jax.checkpoint):
    # trades recompute FLOPs for O(n_layers) less activation memory — the
    # TPU-first long-context memory lever (HBM, not sequence sharding)
    remat: bool = False
    # remat policy: "full" recomputes everything (max memory savings; a
    # flash attention_fn's forward kernel runs a second time in backward);
    # "dots" = jax.checkpoint_policies.checkpoint_dots — matmul outputs are
    # SAVED, and with them attention's product where a kernel makes it:
    # the flash kernel's output and logsumexp (ops/pallas_attention.py::
    # FLASH_RESIDUALS, 50 MB a layer at 8 x 1024 tokens of 1024) — and
    # only cheap elementwise work is recomputed, so the backward pays no
    # extra MXU FLOPs, for a modest memory give-back. Ignored when
    # remat=False.
    remat_policy: str = "full"
    # emit logits in fp32 (the safe default for any consumer). False
    # skips the cast and returns compute-dtype logits — at b16/s2048/
    # v32768 the fp32 [32768, 32768] materialization is a 4.3 GB
    # write+read (~32 ms/step in the r04 AOT cycle ranking) that the
    # fused Pallas CE makes redundant: it upcasts per row-block in VMEM
    # (ops/losses.py casts explicitly on the plain path, so loss math is
    # bit-identical either way — bf16->f32 casts are exact).
    fp32_logits: bool = True


class HeadsDense(nn.Module):
    """``nn.DenseGeneral``'s parameters — ``kernel`` ``(*inputs,
    *features)`` and ``bias`` ``(*features,)``, the same initial values —
    for a projection to or from heads, whose (heads, head_dim) pair the
    product sees as one dimension H·D: ``x [..., C] -> [..., 3, H·D]`` for
    ``features=(3, H, D)``, and ``x [..., H, D] -> [..., C]`` for
    ``features=(C,)`` with ``contract=2``. The same numbers. But a
    ``[..., H, 64]`` array of its own is laid out on a TPU with the 64-wide
    minor dimension padded to a lane tile or moved off the minor place, and
    copied into the form a kernel reads; as ``[..., H·D]`` the projection's
    result is what the flash kernels' packed form reads where it lies
    (``ops/pallas_attention.py``: its reshape ``[B, S, H, D] -> [B, S, H·D]``
    cancels against the caller's), and under tensor parallelism a kernel
    sharded by heads stays sharded through the merge, heads being the major
    of the two."""
    features: tuple[int, ...]
    contract: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        inputs, features = x.shape[x.ndim - self.contract:], self.features
        fan_in = math.prod(inputs)

        def kernel_init(rng, shape, dtype):  # as DenseGeneral: drawn flat
            return nn.initializers.lecun_normal()(
                rng, (fan_in, math.prod(features)), dtype).reshape(shape)

        kernel = self.param("kernel", kernel_init, (*inputs, *features),
                            jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, features, jnp.float32)
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias,
                                                  dtype=self.dtype)
        seen = (*features[:-2], math.prod(features[-2:]))
        x = x.reshape(*x.shape[:x.ndim - self.contract], fan_in)
        return jax.lax.dot_general(
            x, kernel.reshape(fan_in, *seen), (((x.ndim - 1,), (0,)), ((), ()))
        ) + bias.reshape(seen)


class SelfAttention(nn.Module):
    config: TransformerConfig
    attention_fn: Callable | None = None  # None -> local causal attention

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        heads = (cfg.n_heads, cfg.d_model // cfg.n_heads)
        qkv = HeadsDense((3, *heads), dtype=cfg.dtype, name="qkv")(x)
        q, k, v = (qkv[:, :, i].reshape(*x.shape[:2], *heads)
                   for i in range(3))
        # Serving prefill taps per-layer K/V here. A no-op unless the caller
        # passes mutable=["kv_cache"] (training never does), so the trained
        # step graphs are untouched.
        self.sow("kv_cache", "kv", (k, v), reduce_fn=lambda _, x: x)
        attn = self.attention_fn or (lambda q, k, v: causal_attention(q, k, v))
        out = attn(q, k, v)  # [B, S, H, D]
        return HeadsDense((cfg.d_model,), contract=2, dtype=cfg.dtype,
                          name="out")(out)


class Mlp(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        h = nn.Dense(cfg.d_ff, dtype=cfg.dtype, name="up")(x)
        h = nn.gelu(h)
        return nn.Dense(cfg.d_model, dtype=cfg.dtype, name="down")(h)


class Block(nn.Module):
    config: TransformerConfig
    attention_fn: Callable | None = None
    mlp_cls: Any = Mlp

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        h = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x)
        x = x + SelfAttention(cfg, self.attention_fn, name="attn")(h)
        h = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x)
        x = x + self.mlp_cls(cfg, name="mlp")(h)
        return x


class TransformerLM(nn.Module):
    """tokens [B, S] (+ global positions [B, S] when sequence-sharded)
    -> logits [B, S, vocab]."""

    config: TransformerConfig
    attention_fn: Callable | None = None
    mlp_cls: Any = Mlp

    @nn.compact
    def __call__(
        self, tokens: jnp.ndarray, positions: jnp.ndarray | None = None
    ) -> jnp.ndarray:
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape
            )
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="tok_emb")(
            tokens
        )
        x = x + nn.Embed(cfg.max_len, cfg.d_model, dtype=cfg.dtype, name="pos_emb")(
            positions
        )
        if cfg.remat:
            dots = cfg.remat_policy == "dots"
            policy = (save_flash_residuals(
                also=jax.checkpoint_policies.checkpoint_dots) if dots
                else None)
            block_cls = remat_saving(nn.remat(Block, policy=policy),
                                     "transformer",
                                     FLASH_RESIDUALS if dots else ())
        else:
            block_cls = Block
        for i in range(cfg.n_layers):
            x = block_cls(cfg, self.attention_fn, self.mlp_cls, name=f"block{i}")(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        logits = nn.Dense(cfg.vocab_size, dtype=cfg.dtype, name="lm_head")(x)
        if cfg.fp32_logits:
            return jnp.asarray(logits, jnp.float32)
        return logits
