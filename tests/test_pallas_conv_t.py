"""Transposed-layout Pallas conv (ops/pallas_conv_t.py) vs the lax.conv
reference (interpret on CPU) — the tests of test_pallas_conv, collected
here with this layout's kernels: the TPU call path with interpret=True,
numerical parity against conv3x3_t_reference (transpose -> the exact NHWC
conv -> transpose). Covers halo rows, W-edge zero columns, block_h
fallback, bf16, the full custom VJP, the stats variant, and layout
round-trip against the NHWC kernel on the s2d-scattered shapes ConvNetS2D
uses."""

import jax.numpy as jnp
import numpy as np
import pytest

from tests import test_pallas_conv as nhwc
from tests.test_pallas_conv import (  # noqa: F401 (collected here too)
    test_forward_matches_reference, test_grads_match_reference,
    test_single_row_blocks_and_tiny_width)
from tpu_sandbox.ops.pallas_conv_t import (
    conv3x3_t,
    conv3x3_t_reference,
    conv3x3_t_stats,
)


@pytest.fixture
def layout():
    return nhwc.Layout(conv3x3_t, conv3x3_t_reference, conv3x3_t_stats, 2)


@pytest.mark.slow  # tier-1 keeps test_pallas_conv.py::test_grads_bf16
def test_grads_bf16(layout):
    nhwc.test_grads_bf16(layout)


@pytest.mark.slow  # tier-1 keeps test_pallas_conv.py::test_stats_variant
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_stats_variant(layout, dt):
    nhwc.test_stats_variant(layout, dt)


@pytest.mark.usefixtures("light_compile")
def test_matches_nhwc_kernel_on_s2d_shapes():
    """Transposed kernel == NHWC kernel (modulo layout) on the exact
    s2d-scattered conv1 shapes ConvNetS2D uses, miniature image."""
    from tpu_sandbox.models.convnet_s2d import scatter_kernel, space_to_depth
    from tpu_sandbox.ops.pallas_conv import conv3x3

    rng = np.random.default_rng(3)
    img = jnp.asarray(rng.standard_normal((2, 40, 40)), jnp.float32)
    k5 = jnp.asarray(rng.standard_normal((5, 5, 1, 16)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.standard_normal((16,)), jnp.float32)
    x = space_to_depth(img, 4)
    kg = scatter_kernel(k5, 4)
    bg = jnp.tile(b, 16)
    y_nhwc = conv3x3(x, kg, bg, True)
    y_t = conv3x3_t(x.transpose(0, 1, 3, 2), kg, bg, True)
    np.testing.assert_allclose(
        np.asarray(y_t.transpose(0, 1, 3, 2)), np.asarray(y_nhwc),
        rtol=1e-5, atol=1e-5,
    )


def test_wgrad_restage_variants_agree():
    """r05 wgrad restage: the explicit-gT native-dot variant and the
    Mosaic-auto lane-lane variant compute the SAME (dwT, db). Small
    interpret-mode shapes — equality is staging-independent math;
    production-geometry lowering of both variants is pinned in
    tests/test_mosaic_lowering.py."""
    from tpu_sandbox.ops.pallas_conv_t import conv3x3_t_wgrad

    rng = np.random.default_rng(7)
    for c, co in ((16, 32), (8, 16)):
        x = jnp.asarray(rng.standard_normal((2, 8, c, 32)), jnp.float32)
        g = jnp.asarray(rng.standard_normal((2, 8, co, 32)), jnp.float32)
        dw_gt, db_gt = conv3x3_t_wgrad(x, g, restage="gt")
        dw_auto, db_auto = conv3x3_t_wgrad(x, g, restage="auto")
        np.testing.assert_allclose(np.asarray(dw_gt), np.asarray(dw_auto),
                                   rtol=1e-6, atol=1e-4)
        np.testing.assert_allclose(np.asarray(db_gt), np.asarray(db_auto),
                                   rtol=1e-6, atol=1e-4)
