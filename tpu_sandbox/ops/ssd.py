"""Chunked state-space scan (Mamba-2's SSD), in ``jax.numpy``.

The recurrence, a head at a time (``h`` is ``[P, N]``, ``h_0 = 0``)::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        y_t = h_t C_t

computed by chunks of ``Q`` tokens, so that nearly all of it is matrix
products (compute dtype operands, float32 accumulation) and only ``S / Q``
steps are sequential:

- inside a chunk ``Y_diag = (L o C B^T) (dt x)`` with
  ``L[i, j] = exp(sum_{j < m <= i} dt_m A)`` for ``i >= j``, else 0;
- each chunk's own state ``B^T (decay o dt x)``, ``decay`` to the chunk's end;
- the recurrence over the ``S / Q`` chunk states (a ``lax.scan``: one small
  loop in the compiled program, exact in float32);
- ``Y_off = (C h_in) o decay`` from the state a chunk starts with.

``dt``, ``A``, the cumulative sums and every decay are float32: a decay is
``exp`` of a sum of up to ``Q`` terms, and bf16's 8 bits in the exponent's
argument would be a relative error of the decay itself. Differentiated by
JAX. ``D x`` (the skip) is the caller's. Each traced call site counts what
it was built with into the registry (``ssd.chunk_choice``), as
``attn.tile_choice`` does for flash.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpu_sandbox.ops.pallas_common import kernel_site


def _choice(impl: str, heads: int, head_dim: int, state: int,
            groups: int, chunk: int, tokens: int):
    """The counter of what a scan call site is built with; the site counts
    it (``kernel_site``)."""
    from tpu_sandbox.obs import get_registry

    return get_registry().counter("ssd.chunk_choice", labels={
        "impl": impl, "heads": heads, "head_dim": head_dim, "state": state,
        "groups": groups, "chunk": chunk, "tokens": tokens})


def decay_exponents(dt, a, *, chunk: int):
    """``sum_{m <= i} dt_m A`` over the tokens of each chunk, float32:
    ``dt [B, S, H]``, ``a [H]`` -> ``[B, S / chunk, H, chunk]``. Every decay
    of the scan is ``exp`` of a difference of two of these."""
    bsz, s, h = dt.shape
    steps = dt.astype(jnp.float32).reshape(bsz, s // chunk, chunk, h)
    return jnp.cumsum(jnp.moveaxis(steps * a.astype(jnp.float32), 2, -1), -1)


def ssd_scan(x, dt, a, b, c, *, chunk: int):
    """``x [B, S, H, P]``, ``dt [B, S, H]`` (float32, positive), ``a [H]``
    (float32, negative), ``b``, ``c`` ``[B, S, G, N]`` -> ``y [B, S, H, P]``
    in ``x``'s dtype. Head ``h`` reads group ``h * G // H``. ``S`` must be a
    multiple of ``chunk``."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of the chunk {chunk}")
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    nc, r, dtype = s // chunk, h // g, x.dtype
    f32 = jnp.float32
    # the scan is `jnp` today; its site is recorded as a kernel's is, so
    # that a later kernel is judged by the same span and count
    with kernel_site("ssd_scan", _choice("jnp", h, p, n, g, chunk, bsz * s)), \
            jax.named_scope("ssd"):
        # tokens [B, chunks, Q, G, heads a group, ...]; the decays with the
        # chunk's tokens last, so that the [Q, Q] matrices fill whole tiles
        dt = dt.astype(f32).reshape(bsz, nc, chunk, g, r)
        xs = x.reshape(bsz, nc, chunk, g, r, p)
        bs = b.reshape(bsz, nc, chunk, g, n)
        cs = c.reshape(bsz, nc, chunk, g, n)
        dtx = (dt[..., None] * xs.astype(f32)).astype(dtype)
        cum = decay_exponents(dt.reshape(bsz, s, h), a, chunk=chunk).reshape(
            bsz, nc, g, r, chunk)                             # [B,c,G,R,Q]
        total = cum[..., -1]                                  # [B,c,G,R]

        # inside the chunks
        seg = cum[..., :, None] - cum[..., None, :]           # [B,c,G,R,i,j]
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        cb = jnp.einsum("bzign,bzjgn->bzgij", cs, bs,
                        preferred_element_type=f32)
        mix = (decay * cb[:, :, :, None]).astype(dtype)
        y = jnp.einsum("bzgrij,bzjgrp->bzigrp", mix, dtx,
                       preferred_element_type=f32)

        # the chunks' own states, then the state each chunk starts with
        to_end = jnp.moveaxis(jnp.exp(total[..., None] - cum), -1, 2)
        own = jnp.einsum("bzjgrp,bzjgn->bzgrpn",
                         (to_end[..., None] * dtx.astype(f32)).astype(dtype),
                         bs, preferred_element_type=f32)

        def carry_over(state, chunk_in):
            own_z, total_z = chunk_in
            return jnp.exp(total_z)[..., None, None] * state + own_z, state

        _, start = jax.lax.scan(
            carry_over, jnp.zeros_like(own[:, 0]),
            (jnp.moveaxis(own, 1, 0), jnp.moveaxis(total, 1, 0)))
        start = jnp.moveaxis(start, 0, 1).astype(dtype)       # [B,c,G,R,P,N]
        from_start = jnp.moveaxis(jnp.exp(cum), -1, 2)        # [B,c,Q,G,R]
        y = y + from_start[..., None] * jnp.einsum(
            "bzign,bzgrpn->bzigrp", cs, start, preferred_element_type=f32)
        return y.reshape(bsz, s, h, p).astype(dtype)
