"""Chunked gated delta rule (Gated DeltaNet's mixer), in ``jax.numpy``.

The recurrence, a head at a time (``S`` is ``[d_v, d_k]``, ``S_0 = 0``,
``a_t = exp(g_t)`` in (0, 1], ``b_t`` in (0, 2))::

    S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T      o_t = S_t q_t

With ``u_t = b_t (v_t - a_t S_{t-1} k_t)`` it reads ``S_t = a_t S_{t-1} +
u_t k_t^T``: a rank-one write of what the state did not already hold for the
key. Unlike ``ops/ssd.py``'s diagonal recurrence, what a chunk adds to the
state depends on the state it starts from, so the loop over the ``S / C``
chunks has matrix products in its body. Inside a chunk that starts from
``S_0``, with ``c_i = prod_{m <= i} a_m`` (``exp`` of a float32 cumulative
sum of ``g``):

- ``(I + A) U = diag(b) (V - diag(c) K S_0^T)`` with ``A[i, j] = b_i (c_i /
  c_j) (k_i . k_j)`` for ``j < i``, else 0; ``T = (I + A)^-1`` once a chunk
  (``unit_lower_inverse``), so ``U = T diag(b) V - (T diag(b c) K) S_0^T``;
- ``o_i = c_i S_0 q_i + sum_{j <= i} (c_i / c_j) (k_j . q_i) u_j``;
- ``S_C = c_C S_0 + sum_j (c_C / c_j) u_j k_j^T``.

The loop (a ``lax.scan``) carries the float32 state and computes ``U`` and
the next state, two products a chunk; every other product is batched over
the chunks in front of it (``A``, ``T``, ``T diag(b) V``, ``T diag(b c) K``,
``Q K^T``) or behind it (the outputs, from the ``U`` and the starting states
the loop emits).

**The inverse** is by block doubling, not by the product form ``(I - A)(I +
A^2)(I + A^4)...``: the product form is exact in exact arithmetic (``A`` is
nilpotent) but its factors hold powers of ``A`` whose entries grow like
``a^n binom(C, n)`` where the keys of a chunk resemble each other (``silu``
makes them all lean one way), and their alternating sum cancels in float32:
at ``C = 64``, ``b (k_i . k_j) = 0.4`` and a slow decay the far corner of
``T`` is lost entirely (``tests/test_delta_rule.py`` holds both to
``solve_triangular``). Doubling is block forward substitution: the inverse
of ``[[L11, 0], [A21, L22]]`` is ``[[T11, 0], [-T22 A21 T11, T22]]``, from
blocks of one row up, ``log2 C`` levels of two batched products each, all
float32 at ``highest`` precision; no sequential solve, and nothing grows.
Its backward pass is a ``custom_vjp`` (``dA = -T^T dT T^T``, two products,
only ``T`` kept); everything else is differentiated by JAX. In ``jnp``
(``_doubling``: the fallback and the tests' oracle) every level's products
go through HBM, 26 float32 passes over ``[3840, 64, 64]`` a layer and step
at the cell's shape; where the shape allows, inverse and cotangent are one
Pallas kernel each (``ops/pallas_tri_inverse.py``: blocks of chunks held
in VMEM, forward substitution inside the small diagonal blocks on the VPU
and the doubling levels above them on the MXU, still float32 throughout),
and ``A`` is read once and ``T`` written once. Which runs follows the shape
alone (``pallas_tri_inverse.choose_tile``: a chunk that is a power of two
from 16 to 128 and an even tile of chunks that divides a head's), counted
once a site in ``delta_rule.inverse_choice``; ``INVERSE`` below names the
method, which both share.

``g``, ``b``, the cumulative sums, every decay, ``A``, ``T`` and the state
are float32; the operands of the large products are the compute dtype with
float32 accumulation, as in ``ops/ssd.py``. Each traced call site counts
what it was built with (``delta_rule.chunk_choice``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from tpu_sandbox.ops import pallas_tri_inverse
from tpu_sandbox.ops.pallas_common import default_interpret, kernel_site

INVERSE = "block_doubling"


def _choice(impl: str, heads: int, key_dim: int, value_dim: int, chunk: int,
            tokens: int):
    """The counter of what a call site is built with; the site counts it
    (``kernel_site``)."""
    from tpu_sandbox.obs import get_registry

    return get_registry().counter("delta_rule.chunk_choice", labels={
        "impl": impl, "heads": heads, "key_dim": key_dim,
        "value_dim": value_dim, "chunk": chunk, "tokens": tokens,
        "inverse": INVERSE})


def _doubling(a):
    n = a.shape[-1]
    if n & (n - 1):
        raise ValueError(f"chunk {n} is not a power of two")
    a = a.astype(jnp.float32)
    row, col = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    inv = jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32), a.shape)
    size = 1
    while size < n:
        # A21 of every pair of diagonal blocks of ``size``, where it stands
        below = (row // size == col // size + 1) & (row // size % 2 == 1)
        inv = inv - jnp.einsum("...ij,...jk,...kl->...il", inv,
                               jnp.where(below, a, 0.0), inv,
                               precision="highest")
        size *= 2
    return inv


def _inverse_site(kernel: str, shape) -> int | None:
    """Counts a site of the inverse (``kernel``: ``fwd``) or of its
    cotangent (``bwd``) with what it is built with, and returns the
    kernel's tile: the chunks of a VMEM block, or None where the shape goes
    to ``jnp``."""
    from tpu_sandbox.obs import get_registry

    tile = pallas_tri_inverse.choose_tile(shape)
    kernel_site("tri_inverse_" + kernel, get_registry().counter(
        "delta_rule.inverse_choice", labels={
            "impl": "jnp" if tile is None else "pallas", "n": shape[-1],
            "matrices": math.prod(shape[:-2]), "tile": tile or 0,
            "kernel": kernel}))
    return tile


def _inverse(a):
    tile = _inverse_site("fwd", a.shape)
    if tile is None:
        return _doubling(a)
    with jax.named_scope("tri_inverse"):
        return pallas_tri_inverse.tri_inverse_fwd(
            a.astype(jnp.float32), tile=tile,
            interpret=default_interpret(None))


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a [..., n, n]`` strictly lower triangular (what
    lies on or above the diagonal is not read), ``n`` a power of two, in
    float32: one Pallas kernel over blocks of chunks held in VMEM
    (``ops/pallas_tri_inverse.py``) where the shape allows
    (``pallas_tri_inverse.choose_tile``), else ``_doubling``, block doubling
    in ``jnp``: every level two products of whole ``[n, n]`` matrices (the
    blocks are picked by a mask, not by a reshape: an array whose last axes
    are a block of 1 or 2 is padded to a whole tile on the chip, a
    thousandfold). Its cotangent is ``-T^T dT T^T`` below the diagonal, by
    the second kernel or two ``jnp`` products: only ``T`` is kept for the
    backward pass."""
    return _inverse(a)


def _inverse_fwd(a):
    inv = _inverse(a)
    return inv, inv


def _inverse_bwd(inv, d_inv):
    tile = _inverse_site("bwd", inv.shape)
    if tile is None:
        n = inv.shape[-1]
        d_a = -jnp.einsum("...ji,...jk,...lk->...il", inv, d_inv, inv,
                          precision="highest")
        return (jnp.where(jnp.tril(jnp.ones((n, n), bool), -1), d_a, 0.0),)
    with jax.named_scope("tri_inverse"):
        return (pallas_tri_inverse.tri_inverse_bwd(
            inv, d_inv.astype(jnp.float32), tile=tile,
            interpret=default_interpret(None)),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


@jax.custom_vjp
def _decayed(state, held, log_decay):
    """``exp(log_decay) state``. ``held`` is ``state`` in the compute dtype,
    which the loop keeps for its products anyway: the backward pass reads it
    for the decay's cotangent, so that the float32 state of every chunk
    (a third of a gigabyte a layer at 8192 tokens) is not kept as well."""
    return jnp.exp(log_decay)[..., None, None] * state


def _decayed_fwd(state, held, log_decay):
    return _decayed(state, held, log_decay), (held, log_decay)


def _decayed_bwd(res, d_out):
    held, log_decay = res
    decay = jnp.exp(log_decay)
    d_log = decay * jnp.sum(d_out * held.astype(jnp.float32), (-2, -1))
    return decay[..., None, None] * d_out, jnp.zeros_like(held), d_log


_decayed.defvjp(_decayed_fwd, _decayed_bwd)


def chunk_decays(g, *, chunk: int):
    """``sum_{m <= i} g_m`` over the tokens of each chunk, float32:
    ``g [B, S, H]`` -> ``[B, H, S / chunk, chunk]``. Every decay of the rule
    is ``exp`` of one of these or of a difference of two."""
    bsz, s, h = g.shape
    steps = g.astype(jnp.float32).reshape(bsz, s // chunk, chunk, h)
    return jnp.cumsum(jnp.moveaxis(steps, -1, 1), -1)


def chunk_transition(k, beta, cum):
    """``A`` of every chunk, float32: ``k [..., C, d_k]`` (compute dtype),
    ``beta``, ``cum`` ``[..., C]`` -> ``[..., C, C]``, strictly lower."""
    c = k.shape[-2]
    kk = jnp.einsum("...id,...jd->...ij", k, k,
                    preferred_element_type=jnp.float32)
    below = jnp.tril(jnp.ones((c, c), bool), -1)
    seg = jnp.where(below, cum[..., :, None] - cum[..., None, :], -jnp.inf)
    return beta[..., :, None] * jnp.exp(seg) * kk


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64):
    """``q``, ``k`` ``[B, S, H, d_k]``, ``v [B, S, H, d_v]`` (the compute
    dtype), ``g`` (log of the decay, <= 0) and ``beta`` ``[B, S, H]``
    (float32) -> ``o [B, S, H, d_v]`` in ``v``'s dtype. ``S`` must be a
    multiple of ``chunk``, ``chunk`` a power of two."""
    bsz, s, h, dk = q.shape
    dv, dtype, f32 = v.shape[-1], v.dtype, jnp.float32
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of the chunk {chunk}")
    nc = s // chunk
    # the rule is `jnp` today; its site is recorded as a kernel's is, so that
    # a later kernel is judged by the same span, scope and count
    with kernel_site("delta_rule", _choice("jnp", h, dk, dv, chunk, bsz * s)), \
            jax.named_scope("delta_rule"):
        def by_chunk(x):    # [B, S, H, d] -> [B, H, chunks, C, d]
            return jnp.moveaxis(x.reshape(bsz, nc, chunk, h, -1), 3, 1)

        qs, ks, vs = by_chunk(q), by_chunk(k), by_chunk(v)
        beta = jnp.moveaxis(beta.astype(f32).reshape(bsz, nc, chunk, h), -1, 1)
        cum = chunk_decays(g, chunk=chunk)                    # [B,H,c,C]
        total = cum[..., -1]                                  # [B,H,c]

        # in front of the loop: T, and what U is made of
        t = unit_lower_inverse(chunk_transition(ks, beta, cum))
        u_own = jnp.einsum(
            "...ij,...jv->...iv", (t * beta[..., None, :]).astype(dtype), vs,
            preferred_element_type=f32)                       # T diag(b) V
        w = jnp.einsum(
            "...ij,...jd->...id",
            (t * (beta * jnp.exp(cum))[..., None, :]).astype(dtype), ks,
            preferred_element_type=f32).astype(dtype)         # T diag(b c) K
        k_end = (jnp.exp(total[..., None] - cum)[..., None]
                 * ks.astype(f32)).astype(dtype)              # (c_C / c_j) k_j

        def one_chunk(state, chunk_in):
            u_own_z, w_z, k_end_z, total_z = chunk_in
            held = state.astype(dtype)
            u = (u_own_z - jnp.einsum("bhid,bhvd->bhiv", w_z, held,
                                      preferred_element_type=f32)).astype(dtype)
            state = _decayed(state, held, total_z) + jnp.einsum(
                "bhiv,bhid->bhvd", u, k_end_z, preferred_element_type=f32)
            return state, (u, held)

        _, (u, start) = jax.lax.scan(
            one_chunk, jnp.zeros((bsz, h, dv, dk), f32),
            tuple(jnp.moveaxis(x, 2, 0) for x in (u_own, w, k_end, total)))
        u, start = jnp.moveaxis(u, 0, 2), jnp.moveaxis(start, 0, 2)

        # behind the loop: the outputs
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        seg = jnp.where(causal, cum[..., :, None] - cum[..., None, :], -jnp.inf)
        qk = jnp.einsum("...id,...jd->...ij", qs, ks, preferred_element_type=f32)
        o = jnp.einsum("...ij,...jv->...iv", (jnp.exp(seg) * qk).astype(dtype),
                       u, preferred_element_type=f32)
        q_start = (jnp.exp(cum)[..., None] * qs.astype(f32)).astype(dtype)
        o = o + jnp.einsum("...id,...vd->...iv", q_start, start,
                           preferred_element_type=f32)
        return jnp.moveaxis(o, 1, 3).reshape(bsz, s, h, dv).astype(dtype)
