"""What the models with latent attention share (``models/xing4.py``,
trained; ``models/longcat_flash.py``, served), and ``models/laguna.py`` of
it what is not latent: RMSNorm, rotary positions and YaRN's frequencies, the
low-rank query and key/value paths of MLA, and the gated MLP. One place,
so that a change for one model is a change for the other, and the benchmark
holds both.

The two paths are functions that build their ``Dense`` and ``RMSNorm``
sub-modules inside the calling module's ``@nn.compact`` method, under the
published names (``q_a``, ``q_a_norm``, ``q_b``; ``kv_a``, ``kv_a_norm``):
the parameters lie in the caller's scope, as they did when each model wrote
the lines itself.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


def rms_norm(x, eps: float, scale=None):
    """RMSNorm over the last axis in float32; the caller casts the result."""
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y if scale is None else y * scale


class RMSNorm(nn.Module):
    eps: float
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return rms_norm(x, self.eps, scale).astype(self.dtype)


def apply_rope(x, inv_freq, cos_sin_scale: float = 1.0, positions=None):
    """``x [B, S, H, d]``: rotate-half pairing (dimension i with i + d/2),
    in float32, at ``positions [B, S]`` (0..S-1 where None: a sequence from
    its start; a decode call gives each row's own)."""
    s, d = x.shape[1], x.shape[-1]
    if positions is None:
        angles = (jnp.arange(s, dtype=jnp.float32)[:, None]
                  * inv_freq[None, :])[None]                    # [1, S, d/2]
    else:
        angles = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = (jnp.cos(angles) * cos_sin_scale)[:, :, None, :]
    sin = (jnp.sin(angles) * cos_sin_scale)[:, :, None, :]
    x = x.astype(jnp.float32)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def yarn_inv_freq(dim: int, theta: float, factor: float, beta_fast: float,
                  beta_slow: float, original_max: int):
    """YaRN's blended inverse frequencies ``[dim / 2]`` (python floats in,
    a float32 array out): below ``low`` the published frequency, above
    ``high`` the interpolated one (divided by ``factor``), a linear ramp
    between. Returns ``(inv_freq, low, high)``."""
    def correction_dim(rotations):
        return dim * math.log(original_max / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp), low, high


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _parts(dtype, param_dtype, eps):
    dense = functools.partial(nn.DenseGeneral, use_bias=False, dtype=dtype,
                              param_dtype=param_dtype)
    return dense, functools.partial(RMSNorm, eps, dtype)


def low_rank_queries(x, *, heads: int, rank: int, head_dim: int, eps: float,
                     dtype, param_dtype=jnp.float32, scale: float = 1.0):
    """MLA's query path, inside the caller's ``@nn.compact``: ``q_b
    (RMSNorm(q_a x) * scale)`` as ``[B, S, heads, head_dim]`` (``scale``:
    LongCat's ``mla_scale_q_lora``, on the normed latent, so on both parts
    of every head's query)."""
    dense, norm = _parts(dtype, param_dtype, eps)
    c_q = norm(name="q_a_norm")(dense(rank, name="q_a")(x))
    if scale != 1.0:
        c_q = (c_q.astype(jnp.float32) * scale).astype(dtype)
    return dense((heads, head_dim), name="q_b")(c_q)


def low_rank_kv(x, *, rank: int, rope_dim: int, eps: float, dtype,
                param_dtype=jnp.float32, scale: float = 1.0):
    """MLA's key/value latent, inside the caller's ``@nn.compact``:
    ``kv_a x`` split into the latent, normed (``* scale``: LongCat's
    ``mla_scale_kv_lora``) ``[B, S, rank]``, and the rope key as it leaves
    the product, not yet rotated, as the one head all query heads share,
    ``[B, S, 1, rope_dim]``."""
    dense, norm = _parts(dtype, param_dtype, eps)
    kva = dense(rank + rope_dim, name="kv_a")(x)
    c_kv = norm(name="kv_a_norm")(kva[..., :rank])
    if scale != 1.0:
        c_kv = (c_kv.astype(jnp.float32) * scale).astype(dtype)
    return c_kv, kva[..., None, rank:]


class GatedMlp(nn.Module):
    """``down(silu(gate u) * up u)``, no bias."""

    width: int
    out: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype,
                                  param_dtype=self.param_dtype)
        gate = dense(self.width, name="gate")(x)
        up = dense(self.width, name="up")(x)
        return dense(self.out, name="down")(nn.silu(gate) * up)
