"""Attention ops: the single-device reference implementation.

The reference repo has no attention at all (its model is a CNN —
SURVEY §2.2 "CP/ring attention: ABSENT"); this framework treats
long-context as first-class, so the op exists at the ops layer with a
distributed ring implementation in tpu_sandbox.parallel.ring_attention
(verified against this one in tests).

Math: standard scaled dot-product attention with optional causal mask,
softmax statistics accumulated in fp32 regardless of input dtype (the
bf16-on-MXU pattern: matmuls in bf16, reductions in fp32).
"""

from __future__ import annotations

import jax.numpy as jnp


def causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: float | None = None,
    q_offset: int | jnp.ndarray = 0,
    kv_offset: int | jnp.ndarray = 0,
) -> jnp.ndarray:
    """q,k: [B, S, H, D], v: [B, S, H, Dv] -> [B, S, H, Dv]. ``scale``
    multiplies q.k before the softmax; None is ``D ** -0.5``.

    ``q_offset``/``kv_offset`` are the global positions of the first local
    query/key — the hooks sequence-sharded callers use to mask correctly.
    """
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    if scale is None:
        scores = scores / jnp.sqrt(jnp.asarray(d, jnp.float32))
    else:
        scores = scores * scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        kv_pos = kv_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= kv_pos[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    weights = jnp.nan_to_num(jnp.exp(scores - scores.max(-1, keepdims=True)))
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v)
    return out
