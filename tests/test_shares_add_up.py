"""The share test of the model-configs guide, for every layer this repo
cuts across chips: the parts that all shares of a deployment give add up
to the uncut layer as the benchmark's plain float32 reference computes it.
One test; a case builds the layer, cuts it, and returns what the shares
sum to, what they should, and how close float32 brings them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as nemotron_h_ref
from benchmark.reference import xing4 as xing4_ref
from tests.test_expert_share import (E, K, REF_CFG, T, cut, reference_params,
                                     share, whole_layer)
from tests.test_nemotron_h_model import B, S, TINY, tiny
from tpu_sandbox.models import nemotron_h as nh

pytestmark = pytest.mark.usefixtures("light_compile")


def expert_shares(n_held):
    """``ExpertShare``: held = 0-1, 2-3, ..., 14-15 (fewer experts than a
    token chooses: a slot of the table a held expert), 0-3, ... (as many),
    0-7, 8-15 (more: a slot a choice). The shares' routed parts, each the
    reference's of that share, plus the shared expert counted once."""
    x, layer, variables = whole_layer()
    want, _ = xing4_ref.expert_share(
        reference_params(variables), x,
        {**REF_CFG, "held": list(range(E)), "local_rows": T * K})
    uncut = jax.jit(layer.apply)(variables, x)
    np.testing.assert_allclose(uncut, want, atol=5e-5)
    total = uncut - jax.jit(share(range(E), T * K, shared=0).apply)(variables, x)
    part_of = jax.jit(lambda v, held: share(held, T * K, shared=0).apply(v, x),
                      static_argnums=1)
    for first in range(0, E, n_held):
        held = tuple(range(first, first + n_held))
        part = part_of(cut(variables, held), held)
        ref_part, _ = xing4_ref.expert_share(
            reference_params(cut(variables, held)), x,
            {**REF_CFG, "held": list(held), "local_rows": T * K},
            with_shared=False)
        np.testing.assert_allclose(part, ref_part, atol=2e-5)
        total = total + part
    return total, want, 5e-5


def nemotron_input():
    return jax.random.normal(jax.random.key(7), (B, S, 64))


def nemotron_experts():
    """Four shares of four latent experts: their routed parts (each through
    ``latent_up``, which is linear) plus the shared expert counted once;
    the router and ``latent_down`` are computed alike by every share."""
    e, rows = 16, B * S * 4
    u = nemotron_input()

    def layer(held):
        cfg = nh.NemotronHConfig.from_dict(
            tiny(deployment={"held": list(held)}), tokens_per_step=B * S,
            dtype=jnp.float32)
        return nh.latent_moe(cfg, None)

    variables = jax.jit(layer(range(e)).init)(jax.random.key(0), u)
    bias = 0.05 * jax.random.normal(jax.random.key(2), (e,))
    stats = {**variables["batch_stats"], "e_score_correction_bias": bias}
    params = dict(variables["params"])
    ref_params = {**params, "bias": bias}
    cfg = {**TINY, "held": list(range(e)), "local_rows": rows}
    want, _ = nemotron_h_ref.latent_moe(ref_params, u, cfg)
    shared = want - nemotron_h_ref.latent_moe(
        ref_params, u, cfg, with_shared=False)[0]
    np.testing.assert_allclose(
        jax.jit(layer(range(e)).apply)({"params": params, "batch_stats": stats},
                                       u), want, atol=1e-4)
    total = shared
    for first in range(0, e, 4):
        held = tuple(range(first, first + 4))
        mine = {**params, "w_up": params["w_up"][first:first + 4],
                "w_down": params["w_down"][first:first + 4]}
        part = jax.jit(layer(held).apply)(
            {"params": mine, "batch_stats": stats}, u)
        ref_part, _ = nemotron_h_ref.latent_moe(
            {**mine, "bias": bias}, u, {**cfg, "held": list(held)},
            with_shared=False)
        np.testing.assert_allclose(part - shared, ref_part, atol=5e-5)
        total = total + part - shared
    return total, want, 1e-4


#: the uncut layers that two chips share by heads
WHOLE = tiny(mamba_num_heads=8, n_groups=4, num_attention_heads=8,
             num_key_value_heads=2)


def nemotron_mamba_heads():
    """Two chips share the Mamba heads: each holds 4 of 8 heads with their 2
    of 4 groups (columns of ``in_proj``, channels of the convolution, rows
    of ``out_proj``); the gated norm's groups stay whole."""
    h, p, g, n = 8, 8, 4, 16
    whole = nh.Mamba2Mixer(nh.NemotronHConfig.from_dict(
        WHOLE, tokens_per_step=B * S, dtype=jnp.float32))
    u = nemotron_input()
    params = jax.jit(lambda key: jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(a.size), a.shape),
        whole.init(key, u)["params"]))(jax.random.key(0))
    want = jax.jit(lambda p: nemotron_h_ref.mamba_mixer(p, u, WHOLE))(params)
    half = jax.jit(nh.Mamba2Mixer(nh.NemotronHConfig.from_dict(
        tiny(), tokens_per_step=B * S, dtype=jnp.float32)).apply)
    d_in = h * p
    total = 0.0
    for j in range(2):
        heads = np.arange(j * h // 2, (j + 1) * h // 2)
        inner = (heads[:, None] * p + np.arange(p)).reshape(-1)
        state = np.arange(j * g // 2 * n, (j + 1) * g // 2 * n)
        conv = np.concatenate([inner, d_in + state, d_in + g * n + state])
        columns = np.concatenate([inner, d_in + conv, 2 * d_in + 2 * g * n + heads])
        mine = {
            "in_proj": {"kernel": params["in_proj"]["kernel"][:, columns]},
            "conv_kernel": params["conv_kernel"][:, conv],
            "conv_bias": params["conv_bias"][conv],
            "A_log": params["A_log"][heads], "dt_bias": params["dt_bias"][heads],
            "D": params["D"][heads], "norm_scale": params["norm_scale"][inner],
            "out_proj": {"kernel": params["out_proj"]["kernel"][inner]}}
        total = total + half({"params": mine}, u)
    return total, want, 2e-5


def nemotron_attention_heads():
    """Two chips share the attention heads: each holds 4 of 8 query heads
    on its 1 of 2 key/value heads."""
    whole = nh.Attention(nh.NemotronHConfig.from_dict(
        WHOLE, tokens_per_step=B * S, dtype=jnp.float32, flash=True))
    u = nemotron_input()
    params = jax.jit(whole.init)(jax.random.key(0), u)["params"]
    want = jax.jit(lambda p: nemotron_h_ref.attention(p, u, WHOLE))(params)
    half = jax.jit(nh.Attention(nh.NemotronHConfig.from_dict(
        tiny(num_key_value_heads=1), tokens_per_step=B * S, dtype=jnp.float32,
        flash=True)).apply)
    total = 0.0
    for j in range(2):
        q_heads, kv_heads = slice(4 * j, 4 * j + 4), slice(j, j + 1)
        mine = {"q": {"kernel": params["q"]["kernel"][:, q_heads]},
                "kv": {"kernel": params["kv"]["kernel"][:, :, kv_heads]},
                "o": {"kernel": params["o"]["kernel"][q_heads]}}
        total = total + half({"params": mine}, u)
    return total, want, 2e-5


@pytest.mark.parametrize("shares", [
    pytest.param(lambda: expert_shares(2), id="experts-2_of_16"),
    pytest.param(lambda: expert_shares(4), id="experts-4_of_16"),
    pytest.param(lambda: expert_shares(8), id="experts-8_of_16"),
    pytest.param(nemotron_experts, id="nemotron_h-latent_experts"),
    pytest.param(nemotron_mamba_heads, id="nemotron_h-mamba_heads"),
    pytest.param(nemotron_attention_heads, id="nemotron_h-attention_heads")])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    total, want, atol = shares()
    np.testing.assert_allclose(total, want, atol=atol)
