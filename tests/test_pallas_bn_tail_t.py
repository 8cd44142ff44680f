"""fused_bn_relu_pool_t == the transposed unfused chain, and == the NHWC
fused pair through layout transposes.

Pins the contract that lets ConvNetS2DT(fused_tail=True) swap the
transposed Pallas tail in (ops/pallas_bn_tail_t.py): identical pooled
output, batch stats, and gradients (y, gamma, beta), including the bf16
tie-splitting semantics, plus the ysums (conv-fused statistics) path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sandbox.ops.pallas_bn_tail import (
    fused_bn_relu_pool,
    unfused_reference as ref_chain_nhwc,
)
from tpu_sandbox.ops.pallas_bn_tail_t import (
    fused_bn_relu_pool_t,
    unfused_reference_t as ref_chain,
)

# every claim here is a tolerance: conftest's cheaper compile
pytestmark = pytest.mark.usefixtures("light_compile")


def _data(blk, co, hw, dtype=jnp.float32, seed=0, n=2):
    rng = np.random.default_rng(seed)
    c = blk * blk * co
    y = jnp.asarray(rng.standard_normal((n, hw, c, hw)), dtype)
    gamma = jnp.asarray(1 + 0.1 * rng.standard_normal(co), jnp.float32)
    beta = jnp.asarray(rng.standard_normal(co), jnp.float32)
    return y, gamma, beta


@pytest.mark.parametrize("blk,co,hw", [(4, 4, 12), (2, 16, 8), (4, 16, 8)])
def test_forward_matches_unfused(blk, co, hw):
    y, gamma, beta = _data(blk, co, hw)
    out, mu, var = fused_bn_relu_pool_t(y, gamma, beta, co, blk)
    ref, mu_r, var_r = ref_chain(y, gamma, beta, co, blk)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mu_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(var), np.asarray(var_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_matches_nhwc_pair_through_transpose():
    blk, co, hw = 4, 4, 8
    y, gamma, beta = _data(blk, co, hw, seed=3)
    out_t, mu_t, var_t = fused_bn_relu_pool_t(y, gamma, beta, co, blk)
    out_n, mu_n, var_n = fused_bn_relu_pool(
        y.transpose(0, 1, 3, 2), gamma, beta, co, blk)
    np.testing.assert_allclose(np.asarray(mu_t), np.asarray(mu_n), atol=1e-6)
    np.testing.assert_allclose(np.asarray(var_t), np.asarray(var_n),
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(out_t), np.asarray(out_n.transpose(0, 1, 3, 2)),
        atol=1e-5)


@pytest.mark.parametrize("blk,co", [(4, 4), (2, 16)])
def test_gradients_match_unfused(blk, co):
    y, gamma, beta = _data(blk, co, 8, seed=1)
    rng = np.random.default_rng(11)
    cot = jnp.asarray(
        rng.standard_normal((2, 8, (blk // 2) ** 2 * co, 8)), jnp.float32
    )

    def loss_fused(y, gamma, beta):
        out, _, _ = fused_bn_relu_pool_t(y, gamma, beta, co, blk)
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


def test_bf16_tie_gradients_match_unfused():
    """bf16 rounding creates exact pool ties; the transposed kernel must
    split tied cotangents 0.5/0.5 on rounded values like the NHWC pair."""
    rng = np.random.default_rng(7)
    co, blk = 8, 2
    c = blk * blk * co
    y = jnp.asarray(
        np.round(rng.standard_normal((2, 4, c, 4)) * 4) / 4, jnp.bfloat16
    )
    gamma = jnp.ones(co, jnp.float32)
    beta = jnp.zeros(co, jnp.float32)
    cot = jnp.asarray(rng.standard_normal((2, 4, co, 4)), jnp.float32)

    def loss(f):
        def run(y):
            out, _, _ = f(y, gamma, beta, co, blk)
            return jnp.sum(out.astype(jnp.float32) * cot)
        return run

    gf = jax.grad(loss(fused_bn_relu_pool_t))(y)
    gr = jax.grad(loss(ref_chain))(y)
    np.testing.assert_allclose(
        np.asarray(gf, np.float32), np.asarray(gr, np.float32),
        atol=2e-2,
    )


def test_ysums_path_matches_self_computed_stats():
    """Stats handed in from the conv kernel ([C,1] sums of the rounded
    output) produce the same mu/var/output/grads as the tail's own pass,
    and the ysums cotangents are zero by contract."""
    blk, co, hw = 2, 16, 8
    y, gamma, beta = _data(blk, co, hw, seed=4)
    yf = np.asarray(y, np.float32)
    s = jnp.asarray(yf.transpose(0, 1, 3, 2).reshape(-1, y.shape[2])
                    .sum(0)[:, None])
    ss = jnp.asarray((yf ** 2).transpose(0, 1, 3, 2)
                     .reshape(-1, y.shape[2]).sum(0)[:, None])
    out_a, mu_a, var_a = fused_bn_relu_pool_t(y, gamma, beta, co, blk)
    out_b, mu_b, var_b = fused_bn_relu_pool_t(
        y, gamma, beta, co, blk, 1e-5, None, (s, ss))
    np.testing.assert_allclose(np.asarray(mu_b), np.asarray(mu_a),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(var_b), np.asarray(var_a),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_a),
                               atol=1e-5)

    def loss(y, s, ss):
        out, _, _ = fused_bn_relu_pool_t(
            y, gamma, beta, co, blk, 1e-5, None, (s, ss))
        return jnp.sum(out)

    dy, ds, dss = jax.grad(loss, argnums=(0, 1, 2))(y, s, ss)
    assert float(jnp.abs(ds).max()) == 0.0
    assert float(jnp.abs(dss).max()) == 0.0
    dy_ref = jax.grad(
        lambda y: jnp.sum(fused_bn_relu_pool_t(y, gamma, beta, co, blk)[0])
    )(y)
    np.testing.assert_allclose(np.asarray(dy), np.asarray(dy_ref),
                               atol=2e-4)
