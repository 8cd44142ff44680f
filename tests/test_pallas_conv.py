"""Pallas 3x3 conv kernels vs the lax.conv reference (interpret on CPU).

Same strategy as the other kernel suites (test_pallas_attention,
test_pallas_bn_tail): identical call path as TPU with interpret=True,
numerical parity against the jnp/lax reference the kernel replaces —
here conv3x3_reference, the exact conv call ConvNetS2D._Conv makes.
Covers the halo rows (top/bottom edge blocks), the W-edge zero columns,
block_h fallback for non-multiple heights, bf16, and the full custom VJP
(dx through the flipped-weight fwd kernel, fused dw/db).

The claims that the transposed-layout kernels share take a ``layout``:
this module's fixture gives the NHWC kernels, and
tests/test_pallas_conv_t.py collects the same tests with its own."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sandbox.ops.pallas_conv import (conv3x3, conv3x3_reference,
                                         conv3x3_stats)

#: a kernel family: the conv, the lax.conv reference it replaces, its stats
#: variant, and where an activation ``[n, h, ., .]`` keeps its channels
Layout = collections.namedtuple("Layout", "conv reference stats channel_axis")
NHWC = Layout(conv3x3, conv3x3_reference, conv3x3_stats, 3)


@pytest.fixture
def layout():
    return NHWC


def _data(layout, n=2, h=20, w=12, c=16, co=32, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    shape = (n, h, w, c) if layout.channel_axis == 3 else (n, h, c, w)
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    k = jnp.asarray(rng.standard_normal((3, 3, c, co)) * 0.1, dtype)
    b = jnp.asarray(rng.standard_normal((co,)), dtype)
    return x, k, b


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize(
    "h,w,c,co,dt,tol",
    [
        (20, 12, 16, 32, jnp.float32, 1e-5),
        (21, 9, 8, 16, jnp.float32, 1e-5),   # h=21 -> block_h fallback 3
        (20, 12, 16, 32, jnp.bfloat16, 0.03),
    ],
)
def test_forward_matches_reference(layout, h, w, c, co, dt, tol):
    x, k, b = _data(layout, h=h, w=w, c=c, co=co, dtype=dt)
    ref = layout.reference(x, k, b)
    out = layout.conv(x, k, b, True)
    assert out.dtype == x.dtype
    assert out.shape == x.shape[:2] + (
        (w, co) if layout.channel_axis == 3 else (co, w))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


def test_single_row_blocks_and_tiny_width(layout):
    # h prime -> block_h 1: every block is its own top/bottom halo case
    x, k, b = _data(layout, n=1, h=7, w=3, c=4, co=8)
    np.testing.assert_allclose(
        np.asarray(layout.conv(x, k, b, True)),
        np.asarray(layout.reference(x, k, b)), rtol=1e-5, atol=1e-5,
    )


@pytest.mark.usefixtures("light_compile")
def test_grads_match_reference(layout):
    x, k, b = _data(layout)
    w = jnp.asarray(np.random.default_rng(9).standard_normal(
        layout.reference(x, k, b).shape), jnp.float32)

    def loss_kernel(x, k, b):
        return jnp.sum(layout.conv(x, k, b, True) * w)

    def loss_ref(x, k, b):
        return jnp.sum(layout.reference(x, k, b) * w)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(x, k, b)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, k, b)
    for a, r, name in zip(gk, gr, ("dx", "dw", "db")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=2e-4, atol=2e-4,
            err_msg=name,
        )


@pytest.mark.usefixtures("light_compile")
def test_grads_bf16(layout):
    """bf16 grads against the F32-computed truth: the lax.conv reference
    itself is NOT a valid bf16 oracle — XLA accumulates its reductions in
    bf16, where e.g. db = sum of 480 ones saturates at 256 (256 + 1
    rounds back to 256); the kernel accumulates in f32 and gets 480
    exactly. Kernel bf16 grads must sit within bf16 rounding of the f32
    truth."""
    x, k, b = _data(layout, dtype=jnp.bfloat16)

    def tot(f):
        return lambda x, k, b: jnp.sum(f(x, k, b).astype(jnp.float32))

    gk = jax.grad(tot(lambda x, k, b: layout.conv(x, k, b, True)),
                  argnums=(0, 1, 2))(x, k, b)
    xf, kf, bf = (jnp.asarray(t, jnp.float32) for t in (x, k, b))
    gr = jax.grad(tot(layout.reference), argnums=(0, 1, 2))(xf, kf, bf)
    for a, r, name in zip(gk, gr, ("dx", "dw", "db")):
        assert a.dtype == jnp.bfloat16, name
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(r),
            rtol=0.05, atol=0.05, err_msg=name,
        )


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_stats_variant(layout, dt):
    """The stats variant: same y, and sum/sumsq (one number a channel, as a
    row or a column) equal the reductions over (N, H, W) of the ROUNDED
    output (what the BN stats pass would compute from stored y); grads
    still flow (stats cotangents are zero by contract)."""
    x, k, b = _data(layout, dtype=dt)
    y, s, ss = layout.stats(x, k, b, True)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(layout.conv(x, k, b, True)))
    co = y.shape[layout.channel_axis]
    yf = np.moveaxis(np.asarray(y, np.float32), layout.channel_axis, -1
                     ).reshape(-1, co)
    assert s.shape == ((1, co) if layout.channel_axis == 3 else (co, 1))
    np.testing.assert_allclose(np.asarray(s).reshape(-1), yf.sum(0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ss).reshape(-1), (yf * yf).sum(0),
                               rtol=1e-5)

    def loss(x, k, b):
        y, s, ss = layout.stats(x, k, b, True)
        return jnp.sum(y.astype(jnp.float32))

    gk = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(x, k, b)
    gr = jax.jit(jax.grad(
        lambda x, k, b: jnp.sum(
            layout.conv(x, k, b, True).astype(jnp.float32)),
        argnums=(0, 1, 2),
    ))(x, k, b)
    for a, r in zip(gk, gr):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))


def test_block_h_budget():
    """VMEM-budget regression pin: bh=10 at conv1-wgrad's real shape
    (W=750, 16->256) overflowed the Mosaic scoped-vmem stack (21.9 MB >
    16 MB) in the chipless AOT compile; the budget must keep the real
    ConvNet shapes at <= 4 rows while leaving tiny test shapes fast."""
    from tpu_sandbox.ops.pallas_conv import _pick_block_h

    assert _pick_block_h(750, 750, 16, 256) <= 4
    assert _pick_block_h(750, 750, 64, 128) <= 4
    assert _pick_block_h(750, 750, 128, 64) <= 4  # conv2 dgrad shape
    assert _pick_block_h(20, 12, 16, 32) == 10   # test shapes stay fast
    assert 750 % _pick_block_h(750, 750, 16, 256) == 0


@pytest.mark.usefixtures("light_compile")
def test_s2d_scattered_kernel_path():
    """The exact shapes ConvNetS2D uses: conv1's s2d-scattered 3x3 kernel
    (16->256, r=4) on a miniature image, against the reference conv."""
    from tpu_sandbox.models.convnet_s2d import scatter_kernel, space_to_depth

    rng = np.random.default_rng(3)
    img = jnp.asarray(rng.standard_normal((2, 40, 40)), jnp.float32)
    k5 = jnp.asarray(rng.standard_normal((5, 5, 1, 16)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.standard_normal((16,)), jnp.float32)
    x = space_to_depth(img, 4)
    kg = scatter_kernel(k5, 4)
    bg = jnp.tile(b, 16)
    np.testing.assert_allclose(
        np.asarray(conv3x3(x, kg, bg, True)),
        np.asarray(conv3x3_reference(x, kg, bg)), rtol=1e-5, atol=1e-5,
    )
