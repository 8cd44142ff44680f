"""fused_bn_relu_pool == the unfused _GroupedBN + relu + block_max_pool.

Pins the contract that lets ConvNetS2D(fused_tail=True) swap the Pallas
tail in: identical pooled output, batch stats, and gradients (y, gamma,
beta) vs the jnp chain, for both layer shapes (blk=4/co small, blk=2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sandbox.ops.pallas_bn_tail import (
    fused_bn_relu_pool,
    unfused_reference as ref_chain,
)

# every claim here is a tolerance: conftest's cheaper compile
pytestmark = pytest.mark.usefixtures("light_compile")


@pytest.mark.parametrize("blk,co,hw", [(4, 4, 12), (2, 16, 8), (4, 16, 8)])
def test_forward_matches_unfused(blk, co, hw):
    rng = np.random.default_rng(0)
    c = blk * blk * co
    y = jnp.asarray(rng.standard_normal((2, hw, hw, c)), jnp.float32)
    gamma = jnp.asarray(rng.standard_normal(co), jnp.float32)
    beta = jnp.asarray(rng.standard_normal(co), jnp.float32)
    out, mu, var = fused_bn_relu_pool(y, gamma, beta, co, blk)
    ref, mu_r, var_r = ref_chain(y, gamma, beta, co, blk)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mu_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(var), np.asarray(var_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("blk,co", [(4, 4), (2, 16)])
def test_gradients_match_unfused(blk, co):
    rng = np.random.default_rng(1)
    c = blk * blk * co
    y = jnp.asarray(rng.standard_normal((2, 8, 8, c)), jnp.float32)
    gamma = jnp.asarray(1 + 0.1 * rng.standard_normal(co), jnp.float32)
    beta = jnp.asarray(rng.standard_normal(co), jnp.float32)
    cot = jnp.asarray(
        rng.standard_normal((2, 8, 8, (blk // 2) ** 2 * co)), jnp.float32
    )

    def loss_fused(y, gamma, beta):
        out, _, _ = fused_bn_relu_pool(y, gamma, beta, co, blk)
        return jnp.sum(out * cot)

    def loss_ref(y, gamma, beta):
        out, _, _ = ref_chain(y, gamma, beta, co, blk)
        return jnp.sum(out * cot)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(y, gamma, beta)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(y, gamma, beta)
    for name, a, b in zip(("dy", "dgamma", "dbeta"), gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, err_msg=name
        )


def test_bf16_forward_close():
    rng = np.random.default_rng(2)
    co, blk = 16, 4
    c = blk * blk * co
    y = jnp.asarray(rng.standard_normal((1, 8, 8, c)), jnp.bfloat16)
    gamma = jnp.ones(co, jnp.float32)
    beta = jnp.zeros(co, jnp.float32)
    out, _, _ = fused_bn_relu_pool(y, gamma, beta, co, blk)
    ref, _, _ = ref_chain(y, gamma, beta, co, blk)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


def test_bf16_tie_gradients_match_unfused():
    """bf16 rounding creates exact pool ties; the kernel must split tied
    cotangents 0.5/0.5 like jnp.maximum's VJP, comparing values rounded to
    the activation dtype — winner-take-all would diverge here."""
    rng = np.random.default_rng(7)
    co, blk = 8, 2
    c = blk * blk * co
    # quantize the input so post-BN bf16 values tie often
    y = jnp.asarray(
        np.round(rng.standard_normal((2, 8, 8, c)) * 2) / 2, jnp.bfloat16
    )
    gamma = jnp.ones(co, jnp.float32)
    beta = jnp.zeros(co, jnp.float32)
    cot = jnp.asarray(
        rng.standard_normal((2, 8, 8, (blk // 2) ** 2 * co)), jnp.float32
    )

    def loss(fused):
        def f(y):
            if fused:
                out, _, _ = fused_bn_relu_pool(y, gamma, beta, co, blk)
            else:
                out, _, _ = ref_chain(y, gamma, beta, co, blk)
            return jnp.sum(out.astype(jnp.float32) * cot)
        return f

    gf = jax.grad(loss(True))(y)
    gr = jax.grad(loss(False))(y)
    # sanity: the test really exercises ties (some 0.5-weighted routing)
    assert float(jnp.sum(jnp.abs(gf.astype(jnp.float32)))) > 0
    np.testing.assert_allclose(
        np.asarray(gf, np.float32), np.asarray(gr, np.float32), atol=2e-2
    )
