"""Mamba-1's selective scan, in ``jax.numpy``: by chunks over a sequence
(prefill) and one token at a time (decode).

The recurrence, a sequence at a time (``h`` is ``[N, D]``: ``N`` states for
each of ``D`` channels)::

    h_t = exp(dt_t (x) A) o h_{t-1} + (dt_t o x_t) (x) B_t      y_t = h_t C_t

Every channel *and* state has a decay of its own (``A`` is ``[N, D]``), so
the recurrence has no matrix-product form as Mamba-2's has (``ops/ssd.py``:
one scalar decay a head): a token's ``[N, D]`` outer products are
elementwise work, and the tokens are sequential. ``selective_scan`` walks
the sequence a chunk of ``Q`` tokens at a time (one ``lax.scan`` step a
chunk): the chunk's decays ``exp(dt (x) A)`` and inputs ``(dt x) (x) B``
are made in bulk as ``[Q, N, D]`` arrays -- a chunk's, never the whole
sequence's ``[S, N, D]``, which is 1 GB in float32 at 3072 tokens of 5120
channels -- the ``Q`` multiply-adds of the state follow one another in
straight-line code, and the chunk's ``y`` is one contraction of its ``Q``
states with ``C``.

The state lies with its **channels minor** (``[B, N, D]``, not the
published ``[D, N]``): a TPU tiles the two minor dimensions to 8 x 128, and
16 states on the lanes would be padded eightfold.

``dt``, ``A``, the exponent, the state and its update are float32
(``state_dtype`` rounds the state after every token: float32 leaves it as
it is; anything narrower is the benchmark's control, never a deployment). A
position with ``dt = 0`` leaves the state where it was (decay 1, input 0):
that is how bucket padding behind a prompt's last token is kept out of the
state it hands to decode. ``D x`` (the skip) is the caller's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def _advance(h, decay, pushed, state_dtype):
    """One token: ``decay o h + pushed`` in float32, then as the state is
    stored (``reduce_precision``: a cast there and back is what XLA may
    drop as excess precision)."""
    h = decay * h + pushed
    if jnp.dtype(state_dtype) == _F32:
        return h
    kind = jnp.finfo(state_dtype)
    return jax.lax.reduce_precision(h, kind.nexp, kind.nmant)


def selective_step(h, x, dt, a, b, c, *, state_dtype=_F32):
    """One token a sequence: ``h [B, N, D]`` (as stored), ``x [B, D]``,
    ``dt [B, D]`` float32, ``a [N, D]`` float32 (negative), ``b``, ``c``
    ``[B, N]`` -> ``(y [B, D] float32, h [B, N, D]`` in ``state_dtype``)."""
    dt, x = dt.astype(_F32), x.astype(_F32)
    decay = jnp.exp(dt[:, None, :] * a)
    pushed = (dt * x)[:, None, :] * b.astype(_F32)[:, :, None]
    h = _advance(h.astype(_F32), decay, pushed, state_dtype)
    y = jnp.sum(h * c.astype(_F32)[:, :, None], axis=1)
    return y, h.astype(state_dtype)


def selective_scan(x, dt, a, b, c, *, chunk: int, h0=None, state_dtype=_F32):
    """``x [B, S, D]``, ``dt [B, S, D]`` float32 (0 where a position is
    padding), ``a [N, D]`` float32 (negative), ``b``, ``c`` ``[B, S, N]``,
    ``h0 [B, N, D]`` or None for zeros -> ``(y [B, S, D] float32,
    h_S [B, N, D]`` in ``state_dtype``). A sequence that the chunk does not
    divide is padded with ``dt = 0`` positions."""
    bsz, s, d = x.shape
    n = a.shape[0]
    q = min(chunk, s)
    pad = -s % q
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                       for t in (x, dt, b, c))

    def chunks(t):  # [B, S, W] -> [S / Q, Q, B, W]
        return jnp.moveaxis(t.reshape(bsz, -1, q, t.shape[-1]), 0, 2)

    def one_chunk(h, inputs):
        x_q, dt_q, b_q, c_q = inputs
        dt_q, x_q = dt_q.astype(_F32), x_q.astype(_F32)
        decay = jnp.exp(dt_q[:, :, None, :] * a)              # [Q, B, N, D]
        pushed = (dt_q * x_q)[:, :, None, :] * b_q.astype(_F32)[..., None]
        states = []
        for t in range(q):
            h = _advance(h, decay[t], pushed[t], state_dtype)
            states.append(h)
        # elementwise and a sum, not a product on the MXU: a float32
        # einsum there multiplies in bfloat16
        y_q = jnp.sum(jnp.stack(states) * c_q.astype(_F32)[..., None], axis=2)
        return h, y_q

    start = (jnp.zeros((bsz, n, d), _F32) if h0 is None
             else h0.astype(state_dtype).astype(_F32))
    last, y = jax.lax.scan(one_chunk, start,
                           (chunks(x), chunks(dt), chunks(b), chunks(c)))
    y = jnp.moveaxis(y.reshape(-1, bsz, d), 0, 1)[:, :s]
    return y, last.astype(state_dtype)
