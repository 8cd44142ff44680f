"""Ring attention: causal attention with the sequence sharded over a mesh
axis — the long-context workhorse.

Each device holds a contiguous S/n slice of Q, K, V. K/V blocks rotate
around the ring (``lax.ppermute``, which XLA maps onto neighbor ICI links)
while every device accumulates its queries' attention over each arriving
block with the online-softmax (flash-attention) update, fp32 statistics.
After n-1 rotations every query has attended to every key it is allowed to
see; memory per device stays O(S/n * S/n) per block instead of O(S^2).

Causality with a sharded sequence is handled by *global* positions: local
query i on shard r has global position r*(S/n)+i, and each arriving K/V
block knows which shard it came from, so masking needs no full-sequence
materialization.

The reference has no analogue (no attention, no send/recv — SURVEY §2.2);
this is capability the TPU build adds because long context is first-class
here. Verified in tests against ops.attention.causal_attention.

``ring_attention`` must run inside a shard_map with ``axis_name`` bound;
``make_ring_attention`` wraps it for standalone use.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def varying(x, axis_name):
    """Mark an array device-varying over ``axis_name`` for jax's VMA typing
    (pcast on newer jax, pvary fallback). Shared by both ring variants."""
    try:
        return lax.pcast(x, axis_name, to="varying")
    except (AttributeError, TypeError):
        pass
    try:
        return lax.pvary(x, axis_name)
    except AttributeError:
        return x  # pre-VMA jax: nothing to annotate (check is off anyway)


def _online_update(carry, kv_block, q, src_index, *, local_len, causal):
    """Accumulate one arriving K/V block into the online-softmax state."""
    o, m, l, q_offset = carry
    k, v = kv_block
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(d, jnp.float32))
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        kv_pos = src_index * local_len + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= kv_pos[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)

    block_max = scores.max(-1)  # [B,H,Q]
    new_m = jnp.maximum(m, block_max)
    # guard: fully-masked rows have new_m == -inf; keep math finite
    safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
    alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - safe_m, -jnp.inf))
    p = jnp.exp(scores - safe_m[..., None])  # exp(-inf)=0 handles masked
    l_new = l * alpha + p.sum(-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32)
    )
    return (o_new, new_m, l_new, q_offset)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    *,
    causal: bool = True,
) -> jnp.ndarray:
    """q,k,v: local shards [B, S/n, H, D] (inside shard_map). -> [B, S/n, H, D]."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    q_offset = idx * s_local

    # the accumulators are device-varying state: jax's VMA typing needs the
    # initial zeros cast as such or the fori_loop carry types mismatch
    o = varying(jnp.zeros((b, h, s_local, d), jnp.float32), axis_name)
    m = varying(jnp.full((b, h, s_local), -jnp.inf, jnp.float32), axis_name)
    l = varying(jnp.zeros((b, h, s_local), jnp.float32), axis_name)

    # neighbor ring: shift K/V to rank+1 each step, so at step j we hold the
    # block that originated at rank (idx - j) mod n
    shift = [(i, (i + 1) % n) for i in range(n)]

    def body(j, carry):
        o, m, l, k_cur, v_cur = carry
        src = (idx - j) % n
        o, m, l, _ = _online_update(
            (o, m, l, q_offset), (k_cur, v_cur), q, src,
            local_len=s_local, causal=causal,
        )
        k_nxt = lax.ppermute(k_cur, axis_name, shift)
        v_nxt = lax.ppermute(v_cur, axis_name, shift)
        return (o, m, l, k_nxt, v_nxt)

    o, m, l, _, _ = lax.fori_loop(0, n, body, (o, m, l, k, v))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B,S/n,H,D]


def make_ring_attention(mesh: Mesh, axis: str, *, causal: bool = True):
    """Standalone jit'd ring attention over global [B, S, H, D] arrays
    sharded on dim 1."""
    if axis not in mesh.axis_names:
        raise ValueError(f"axis {axis!r} not in mesh axes {mesh.axis_names}")
    fn = jax.shard_map(
        partial(ring_attention, axis_name=axis, causal=causal),
        mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis),
    )
    return jax.jit(fn)
