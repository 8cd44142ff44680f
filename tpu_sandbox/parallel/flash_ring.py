"""Flash-ring attention: the Pallas flash kernel as ring attention's
per-block compute, with a hand-written ring backward.

parallel/ring_attention.py keeps its per-arriving-block math in jnp and
lets jax autodiff the loop — correct, but the [B,H,Sq,Sk]-per-step score
blocks are XLA's to schedule. This variant runs every block pair through
ops.pallas_attention's forward kernel (MXU matmuls, VMEM-resident online
softmax, O(block) memory) and merges the per-block partials with their
logsumexps:

    lse' = logaddexp(lse, lse_b)
    o'   = o·exp(lse−lse') + o_b·exp(lse_b−lse')

Backward is the standard ring-attention backward, written explicitly
because pallas_call is opaque to autodiff: K/V (and their gradient
accumulators) make a second pass around the ring; each device adds its
block's contribution using the saved final logsumexp, and after n hops a
block's accumulated dK/dV arrives back at its owner. Residuals are
O(S/n · D) per device, and each hop's contribution runs through the Pallas
backward kernels (ops/pallas_attention._flash_bwd) — peak memory O(block)
per core; no score matrix is ever materialized, forward or backward.

Same contract as ring_attention: local shards [B, S/n, H, D] inside a
shard_map with ``axis_name`` bound; ``make_flash_ring_attention`` wraps
for standalone use. Verified against ring_attention and the single-device
reference in tests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpu_sandbox.ops.pallas_attention import (
    flash_attention_lse,
    make_flash_bwd_lse,
)
from tpu_sandbox.ops.pallas_common import NEG as _NEG
from tpu_sandbox.parallel.ring_attention import varying as _varying


def _merge(o, lse, o_b, lse_b):
    """Combine two attention partials by their logsumexps (fp32)."""
    new_lse = jnp.logaddexp(lse, lse_b)
    w_old = jnp.exp(lse - new_lse)[..., None]
    w_new = jnp.exp(lse_b - new_lse)[..., None]
    return o * w_old + o_b.astype(jnp.float32) * w_new, new_lse


def _ring_fwd_pass(q, k, v, axis_name, causal, block_q, block_k, interpret):
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    q_off = idx * s_loc
    shift = [(i, (i + 1) % n) for i in range(n)]

    o0 = _varying(jnp.zeros((b, s_loc, h, d), jnp.float32), axis_name)
    lse0 = _varying(jnp.full((b, s_loc, h), _NEG, jnp.float32), axis_name)

    def body(j, carry):
        o, lse, k_cur, v_cur = carry
        src = (idx - j) % n
        o_b, lse_b = flash_attention_lse(
            q, k_cur, v_cur, causal=causal, q_offset=q_off,
            kv_offset=src * s_loc, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
        o, lse = _merge(o, lse, o_b, lse_b)
        k_nxt = lax.ppermute(k_cur, axis_name, shift)
        v_nxt = lax.ppermute(v_cur, axis_name, shift)
        return (o, lse, k_nxt, v_nxt)

    o, lse, _, _ = lax.fori_loop(0, n, body, (o0, lse0, k, v))
    return o.astype(q.dtype), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_ring_attention(
    q, k, v, axis_name: str, causal: bool = True,
    block_q: int = 128, block_k: int = 128, interpret: bool | None = None,
):
    """q,k,v: local shards [B, S/n, H, D] (inside shard_map) -> same shape."""
    out, _ = _ring_fwd_pass(q, k, v, axis_name, causal, block_q, block_k,
                            interpret)
    return out


def _fr_fwd(q, k, v, axis_name, causal, block_q, block_k, interpret):
    out, lse = _ring_fwd_pass(q, k, v, axis_name, causal, block_q, block_k,
                              interpret)
    return out, (q, k, v, out, lse)


def _fr_bwd(axis_name, causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    s_loc = q.shape[1]
    q_off = idx * s_loc
    shift = [(i, (i + 1) % n) for i in range(n)]

    # q-side padding and delta are loop-invariant: pad/compute them once,
    # per hop only the rotating K/V blocks are prepped
    partial_bwd = make_flash_bwd_lse(
        q, out, g, lse, causal=causal, q_offset=q_off,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    dq0 = _varying(jnp.zeros(q.shape, jnp.float32), axis_name)
    dkv0 = _varying(jnp.zeros(k.shape, jnp.float32), axis_name)

    def body(j, carry):
        dq, dk_acc, dv_acc, k_cur, v_cur = carry
        src = (idx - j) % n
        dq_c, dk_c, dv_c = partial_bwd(k_cur, v_cur, src * s_loc)
        dq = dq + dq_c
        dk_acc = dk_acc + dk_c
        dv_acc = dv_acc + dv_c
        # K/V and their gradient accumulators travel the ring TOGETHER, so
        # after n hops each block's accumulated dK/dV is back at its owner
        rotate = lambda x: lax.ppermute(x, axis_name, shift)  # noqa: E731
        return (dq, rotate(dk_acc), rotate(dv_acc), rotate(k_cur),
                rotate(v_cur))

    dq, dk, dv, _, _ = lax.fori_loop(
        0, n, body, (dq0, dkv0, jnp.zeros_like(dkv0), k, v)
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_ring_attention.defvjp(_fr_fwd, _fr_bwd)


def make_flash_ring_attention(
    mesh: Mesh, axis: str, *, causal: bool = True,
    block_q: int = 128, block_k: int = 128, interpret: bool | None = None,
):
    """Standalone jit'd flash-ring attention over global [B, S, H, D]
    arrays sharded on dim 1 (mirror of make_ring_attention)."""
    if axis not in mesh.axis_names:
        raise ValueError(f"axis {axis!r} not in mesh axes {mesh.axis_names}")

    # positional call: custom_vjp nondiff argnums must not arrive as kwargs
    def local(q, k, v):
        return flash_ring_attention(q, k, v, axis, causal, block_q, block_k,
                                    interpret)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis),
        check_vma=False,  # pallas_call outputs carry no vma annotation
    )
    return jax.jit(fn)
