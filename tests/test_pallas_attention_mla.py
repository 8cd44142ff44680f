"""The flash-attention kernel with separate head sizes for q.k and v and a
softmax scale passed in (latent attention: 192 / 128, YaRN's scale), in
interpret mode against ``ops.attention``'s plain attention, forward and
backward; and unchanged at GPT-2's 64 / 64."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sandbox.ops.attention import causal_attention
from tpu_sandbox.ops.pallas_attention import flash_attention, flash_attention_fn


def qkv(d_qk, d_v, s=160, b=1, h=2, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(keys[0], (b, s, h, d_qk), dtype)
    k = jax.random.normal(keys[1], (b, s, h, d_qk), dtype)
    v = jax.random.normal(keys[2], (b, s, h, d_v), dtype)
    g = jax.random.normal(keys[3], (b, s, h, d_v), dtype)
    return q, k, v, g


SHAPES = [(192, 128, 192 ** -0.5 * 2.00474), (64, 64, None), (24, 16, 0.3),
          (128, 256, None)]


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("d_qk,d_v,scale", SHAPES)
def test_forward_matches_plain_attention(d_qk, d_v, scale):
    q, k, v, _ = qkv(d_qk, d_v)
    out = flash_attention(q, k, v, scale=scale)
    assert out.shape == v.shape
    np.testing.assert_allclose(out, causal_attention(q, k, v, scale=scale),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("d_qk,d_v,scale", SHAPES[:3])
def test_backward_matches_plain_attention(d_qk, d_v, scale):
    q, k, v, g = qkv(d_qk, d_v)
    got = jax.grad(lambda q, k, v: (flash_attention(
        q, k, v, scale=scale) * g).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: (causal_attention(
        q, k, v, scale=scale) * g).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_default_scale_is_unchanged_at_equal_head_sizes():
    """GPT-2's call: no scale given, one head size. The same numbers as the
    explicit ``D ** -0.5`` and as the drop-in ``flash_attention_fn``."""
    q, k, v, _ = qkv(64, 64)
    a = flash_attention(q, k, v)
    np.testing.assert_array_equal(a, flash_attention(q, k, v, scale=64 ** -0.5))
    np.testing.assert_array_equal(a, flash_attention_fn()(q, k, v))
    np.testing.assert_allclose(a, causal_attention(q, k, v), rtol=2e-5, atol=2e-5)


def test_bf16_inputs_keep_their_dtype_and_the_padding_is_cut():
    q, k, v, _ = qkv(192, 128, s=130, dtype=jnp.bfloat16)   # S not a block multiple
    out = flash_attention(q, k, v, scale=0.1)
    assert out.dtype == jnp.bfloat16 and out.shape == (1, 130, 2, 128)
    want = causal_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32), scale=0.1)
    np.testing.assert_allclose(out.astype(jnp.float32), want, atol=3e-2)


def test_plain_attention_takes_a_scale():
    q, k, v, _ = qkv(16, 16, s=8)
    np.testing.assert_allclose(causal_attention(q, k, v),
                               causal_attention(q, k, v, scale=0.25), rtol=1e-6)


# -- PR 28: the latent shape under the tile rule ------------------------------

YARN_SCALE = 192 ** -0.5 * 2.00474


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("s", [1400, 1100], ids=["eleven_tiles", "nine_tiles"])
def test_rule_tiles_at_the_latent_head_sizes(s):
    """No blocks given, an S whose lane count leaves the rule small tiles
    (11 x 128: only 128 divides; 9 x 128: 384 does), so that the default
    path itself walks interior, diagonal and padded-key tiles at 192 / 128
    with the model's own scale; forward and backward."""
    from tpu_sandbox.ops import pallas_attention as pa

    sp = pa._pad_len(s)
    tiles = {k: pa.choose_tiles(k, sp, sp, 256, 128, 4) for k in pa._KERNELS}
    assert all(1 < sp // bq and sp % bq == 0 and bq == bk
               for bq, bk in tiles.values()), tiles
    q, k, v, g = qkv(192, 128, s=s, h=1)
    got = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, scale=YARN_SCALE), q, k, v)
    want = jax.vjp(lambda q, k, v: causal_attention(
        q, k, v, scale=YARN_SCALE), q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
    for a, b in zip(got[1](g), want[1](g)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kernel", ["fwd", "dkv", "dq"])
def test_the_cell_shape_is_a_few_large_steps(kernel):
    """At the Xing4 cell's attention (2 x 32 heads, S 4096, 192 -> 256 /
    128, bf16) the rule's grid is at most a sixteenth of the 65,536 steps
    that 128 x 128 tiles made, and the tiles fit the budget with room."""
    from tpu_sandbox.ops import pallas_attention as pa

    bq, bk = pa.choose_tiles(kernel, 4096, 4096, 256, 128, 2)
    assert 2 * 32 * (4096 // bq) * (4096 // bk) <= 65536 // 16
    assert pa._vmem_bytes(kernel, bq, bk, 256, 128, 2) <= pa._VMEM_BUDGET
