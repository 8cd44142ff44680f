"""models/xing4.py at a small size on the CPU (hidden 64, 4 streams, 2
heads, 8 experts, seeded random weights): the float32 parts against hand
values, the point of the on-chip check, and the router's bias. The model
against the plain float32 reference of the benchmark is
tests/test_lm_models.py, ``lm_train.build`` tests/test_lm_train.py."""

import functools
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import xing4 as ref  # noqa: E402
from tpu_sandbox.models import xing4  # noqa: E402
from tpu_sandbox.ops.losses import cross_entropy_loss  # noqa: E402

TINY = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 2,
    "first_k_dense_replace": 1, "num_attention_heads": 2, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "routed_scaling_factor": 2, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "num_nextn_predict_layers": 1, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1,
    "deployment": {"held": [0, 1, 2, 3], "local_rows_factor": 2},
}
B, S = 2, 16


def tiny(**over):
    return {**TINY, **over}


def flat(tree):
    return {"/".join(str(k.key) for k in path): leaf for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


# --- the float32 parts ---

@pytest.mark.parametrize("noise,start,rows_atol", [
    (1.0, "none", 1e-5), (0.5, "identity", 1e-2), (100.0, "none", None)])
def test_sinkhorn_is_doubly_stochastic_inside_the_clamp(noise, start, rows_atol):
    """A matrix of moderate entries comes out doubly stochastic within 1e-5
    after 20 iterations. From the matrix the model starts at (0 on the
    diagonal, -8 off it: nearly decoupled, where Sinkhorn converges slowly)
    the columns, normalised last, hold to 1e-5 and the rows to the
    off-diagonal mass. From anything, clamped to +-30, nothing overflows."""
    raw = noise * jax.random.normal(jax.random.key(0), (4, 4, 257))
    if start == "identity":
        raw = raw - 8.0 * (1.0 - jnp.eye(4))[:, :, None]
    clamped = jnp.clip(raw, -30, 30)
    assert float(jnp.abs(clamped).max()) <= 30.0
    m = xing4.sinkhorn(clamped, 20, 1e-6)
    assert m.shape == raw.shape and bool((m >= 0).all())
    assert bool(jnp.isfinite(m).all())
    np.testing.assert_allclose(m.sum(0), 1.0, atol=1e-5)
    if rows_atol:
        np.testing.assert_allclose(m.sum(1), 1.0, atol=rows_atol)
    np.testing.assert_allclose(m, ref.sinkhorn(clamped, 20, 1e-6),
                               rtol=1e-5, atol=1e-7)


def test_hyper_connection_starts_as_a_plain_residual():
    cfg = xing4.Xing4Config.from_dict(TINY, tokens_per_step=B * S,
                                      dtype=jnp.float32)
    mhc = xing4.HyperConnection(cfg)
    streams = jax.random.normal(jax.random.key(1), (4, B, S, 64))
    variables = mhc.init(jax.random.key(0), streams, method="pre")
    u, _, (h_res, h_post) = mhc.apply(variables, streams, method="pre")
    # H_pre = 1/n, H_post = 1, H_res ~ I up to the small alpha x~ Phi term
    np.testing.assert_allclose(u, streams.mean(0), atol=5e-2)
    np.testing.assert_allclose(h_post, 1.0, atol=5e-2)
    np.testing.assert_allclose(
        h_res, jnp.broadcast_to(jnp.eye(4)[:, :, None, None], h_res.shape),
        atol=2e-3)


def test_yarn_frequencies_and_mscale_against_hand_values():
    inv, low, high = xing4.yarn_inv_freq(64, 10000.0, 64.0, 32.0, 1.0, 4096)
    # 64 ln(4096 / (32 x 2 pi)) / (2 ln 10000) = 10.47; with 1 rotation 22.51
    assert (low, high) == (10, 23)
    extra = 10000.0 ** -(np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-6)      # kept
    np.testing.assert_allclose(inv[23:], extra[23:] / 64, rtol=1e-6)  # / factor
    ramp = (16 - 10) / 13
    np.testing.assert_allclose(
        inv[16], extra[16] / 64 * ramp + extra[16] * (1 - ramp), rtol=1e-6)
    m = xing4.yarn_mscale(64.0, 1.0)
    assert m == pytest.approx(0.1 * math.log(64) + 1) == pytest.approx(1.415888)
    assert xing4.yarn_mscale(1.0, 1.0) == 1.0
    ref_inv, cs, scale = ref.yarn(dict(TINY, qk_rope_head_dim=64,
                                       qk_nope_head_dim=128))
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-6)
    assert cs == 1.0 and scale == pytest.approx(192 ** -0.5 * m * m)


def test_rope_rotates_pairs_i_and_i_plus_half():
    x = jnp.zeros((1, 3, 1, 8)).at[..., 0].set(1.0)
    inv = jnp.asarray([0.5, 0.0, 0.0, 0.0])
    out = xing4.apply_rope(x, inv)
    np.testing.assert_allclose(out[0, 2, 0, 0], math.cos(1.0), rtol=1e-6)
    np.testing.assert_allclose(out[0, 2, 0, 4], math.sin(1.0), rtol=1e-6)
    np.testing.assert_allclose(out, ref.rope(x, inv, 1.0), atol=1e-7)


def test_config_reads_the_published_keys_and_the_share():
    cfg = xing4.Xing4Config.from_dict(
        tiny(deployment={"held": [2, 3], "local_rows_factor": 2,
                         "routed_experts_total": 16}),
        tokens_per_step=4096)
    assert cfg.n_routed_experts == 16 and cfg.held == (2, 3)
    assert cfg.local_rows == 2 * 4096 * 2 * 2 // 16    # 2 x T k |held| / E
    assert cfg.qk_head_dim == 24 and cfg.rope_factor == 64
    with pytest.raises(ValueError, match="yarn"):
        xing4.Xing4Config.from_dict(
            tiny(rope_scaling={**TINY["rope_scaling"], "type": "linear"}),
            tokens_per_step=64)


# --- the point of the on-chip check ---

def _forgets_the_normalisations(logits, iters, eps):
    """Sinkhorn with a planted fault: the right matrices forward, the
    gradient of ``exp`` alone backward."""
    right = xing4_sinkhorn(logits, iters, eps)
    wrong = jnp.exp(jnp.clip(logits, -30, 30).astype(jnp.float32))
    return wrong + jax.lax.stop_gradient(right - wrong)


xing4_sinkhorn = xing4.sinkhorn


@functools.cache
def check_point(hidden):
    """What the planted fault and its absence share at a width: the model,
    its variables at ``ref.off_start``'s point, the batch, and what the
    reference computes there for the second block's attention mix."""
    config = tiny(num_nextn_predict_layers=0, hc_sinkhorn_iters=6,
                  hidden_size=hidden)
    cfg = xing4.Xing4Config.from_dict(config, tokens_per_step=B * S,
                                      dtype=jnp.float32, remat=False, flash=False)
    model = xing4.Xing4LM(cfg)
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, 256, (B, S)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, 256, (B, S)), jnp.int32)
    variables = jax.jit(model.init)(jax.random.key(2), tokens)
    moved = ref.off_start(variables["params"], 7)
    assert {k.split("/")[-1] for k in moved} == {
        "b_pre", "b_post", "b_res", "alpha_pre", "alpha_post", "alpha_res"}
    params = ref.unflatten({**flat(variables["params"]),
                            **{k: jnp.asarray(v) for k, v in moved.items()}})
    stats = variables["batch_stats"]
    wanted = [k for k in flat(params) if k.startswith("block1/mhc_attn/")]
    ref_cfg = {**config, "held": list(cfg.held), "local_rows": cfg.local_rows}
    ref_loss, ref_logits, ref_chosen, ref_grads = ref.loss_and_grads(
        ref.from_program_tree(params, stats), tokens, targets, ref_cfg, wanted)
    return (model, params, stats, tokens, targets), {
        "logits": ref_logits, "loss": ref_loss, "chosen": ref_chosen,
        "grads": ref_grads}


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("fault,hidden", [
    pytest.param(None, 64, id="None"),
    pytest.param("sinkhorn_backward", 64, id="sinkhorn_backward"),
    pytest.param(None, 128, id="None-kernels"),
    pytest.param("sinkhorn_backward", 128, id="sinkhorn_backward-kernels")])
def test_check_point_conditions_the_mhc_gradients(fault, hidden, monkeypatch):
    """At ``ref.off_start``'s point the gradients of Phi_res, alpha_res and
    Phi_pre agree with the reference like the others (float32: 1e-3), so a
    limit on them means something; and a Sinkhorn whose backward pass is
    wrong breaks ``ref.compare``'s limit on them, with the forward pass
    (logits, loss) untouched. At width 128 it is the mHC kernels, with
    Sinkhorn between them, that the reference and the fault are held to."""
    from tests.test_pallas_mhc import choices, new_choices

    (model, params, stats, tokens, targets), reference = check_point(hidden)
    before = choices()
    if fault:
        monkeypatch.setattr(xing4, "sinkhorn", _forgets_the_normalisations)

    def system(p):
        logits, sown = model.apply({"params": p, "batch_stats": stats}, tokens,
                                   mutable=["intermediates", "batch_stats"])
        return cross_entropy_loss(logits.reshape(-1, 256),
                                  targets.reshape(-1)), (logits, sown)

    (loss, (logits, sown)), grads = jax.jit(
        jax.value_and_grad(system, has_aux=True))(params)
    assert ("fallback" in new_choices(before)) == (hidden == 64)
    chosen = [np.asarray(c).reshape(B, S, -1)
              for c in jax.tree.leaves(sown["intermediates"])]
    dev, bad = ref.compare(
        {"logits": logits, "loss": loss, "chosen": chosen, "grads": flat(grads)},
        reference)
    ref_grads = reference["grads"]
    if fault is None:
        assert bad == [] and max(
            v for k, v in dev.items() if k.startswith("grad_rel:")) < 1e-3, dev
        # an alpha's gradient is its Phi's, projected on Phi: holding the
        # matrix holds the scalar, which the chip check therefore leaves out
        for name in ("res", "pre", "post"):
            phi = np.asarray(params["block1"]["mhc_attn"][f"phi_{name}"])
            want = (phi * np.asarray(ref_grads[f"block1/mhc_attn/phi_{name}"])
                    ).sum() / 0.1
            got = float(ref_grads[f"block1/mhc_attn/alpha_{name}"])
            assert got == pytest.approx(want, rel=1e-3, abs=1e-7), name
    else:
        assert any("phi_res" in b for b in bad), bad
        assert any("alpha_res" in b for b in bad), bad
        assert dev["logit_rms_rel"] < 1e-5 and dev["loss_abs"] < 1e-5


# --- the router's bias ---

@pytest.mark.usefixtures("light_compile")
def test_router_bias_moves_by_gamma_and_carries_no_gradient():
    cfg = xing4.Xing4Config.from_dict(TINY, tokens_per_step=B * S,
                                      dtype=jnp.float32)
    model = xing4.Xing4LM(cfg)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 256, (B, S)))
    variables = jax.jit(model.init)(jax.random.key(0), tokens)
    stats = variables["batch_stats"]
    assert not any("bias" in path for path in flat(variables["params"]))
    _, sown = jax.jit(lambda v: model.apply(
        v, tokens, mutable=["batch_stats", "intermediates"]))(variables)
    for name, layer in sown["batch_stats"].items():
        moved = np.asarray(layer["moe"]["e_score_correction_bias"]
                           - stats[name]["moe"]["e_score_correction_bias"])
        sel = np.asarray(sown["intermediates"][name]["moe"]["sel"][0])
        counts = np.bincount(sel.reshape(-1), minlength=8)
        want = xing4.BIAS_UPDATE_RATE * np.sign(counts.mean() - counts)
        np.testing.assert_allclose(moved, want, atol=1e-9)
        assert float(layer["moe"]["steps"]) == 1.0
        held = np.isin(sel, cfg.held).sum()
        assert float(layer["moe"]["rows_held"]) == held
        assert float(layer["moe"]["rows_dropped"]) == 0.0
    # without the collection being mutable (evaluation) nothing moves
    logits = jax.jit(model.apply)(variables, tokens)
    assert logits.shape == (B, S, 256)
