"""models/olmo_hybrid.py at a small size on the CPU (hidden 64, three
DeltaNet heads of 8 / 16, four attention heads, seeded random weights)
against the plain float32 reference of the benchmark: logits, loss and
every gradient; the layer kinds by ``layer_types``; the float32 parts; the
parameter count of the benchmark's cut, by hand. The chunked rule against
the recurrence is tests/test_delta_rule.py, ``lm_train.build``
tests/test_lm_train.py."""

import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import olmo_hybrid as ref  # noqa: E402
from tpu_sandbox.models import olmo_hybrid as oh  # noqa: E402
from tpu_sandbox.ops.losses import cross_entropy_loss  # noqa: E402

LINEAR, FULL = "linear_attention", "full_attention"
TINY = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 4, "layer_types": [LINEAR, LINEAR, FULL, LINEAR],
    "num_attention_heads": 4, "num_key_value_heads": 4, "hidden_act": "silu",
    "attention_bias": False, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "linear_num_key_heads": 3,
    "linear_num_value_heads": 3, "linear_key_head_dim": 8,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
    "deployment": {"delta_rule_chunk": 8},
}
B, S, VOCAB = 2, 32, 256
#: the gradients the chip check reads (``runners/olmo_hybrid_train.py``)
CHECKED = ("block0/gdn/A_log", "block0/gdn/dt_bias", "block0/gdn/b/kernel",
           "block0/gdn/conv_kernel", "block0/gdn/q/kernel",
           "block0/gdn/v/kernel", "block0/gdn/norm_scale",
           "block2/attn/q/kernel", "block2/attn/k/kernel",
           "block1/mlp/down/kernel", "tok_emb/embedding")

pytestmark = pytest.mark.usefixtures("light_compile")


def tiny(**over):
    return {**TINY, **over}


def built(**how):
    cfg = oh.OlmoHybridConfig.from_dict(TINY, **how)
    return cfg, oh.OlmoHybridLM(cfg)


@functools.cache
def reference():
    """Tokens, targets, parameters off the initial point (so that every
    scale and bias matters), and what the reference computes there."""
    _, model = built(dtype=jnp.float32)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (B, S)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, VOCAB, (B, S)), jnp.int32)

    def off_the_start(key):
        return jax.tree.map(
            lambda a: a + 0.05 * jax.random.normal(jax.random.key(a.size),
                                                   a.shape),
            model.init(key, tokens)["params"])

    params = jax.jit(off_the_start)(jax.random.key(1))
    return (tokens, targets, params), ref.loss_and_grads(
        ref.from_program_tree(params), tokens, targets, TINY)


@functools.cache
def system(dtype, remat, flash):
    """(loss, logits, gradients by path) of the model at ``reference``'s
    point."""
    _, model = built(dtype=dtype, remat=remat, flash=flash)
    (tokens, targets, params), _ = reference()

    def objective(p):
        logits = model.apply({"params": p}, tokens)
        return cross_entropy_loss(logits.reshape(-1, VOCAB),
                                  targets.reshape(-1)), logits

    (loss, logits), grads = jax.jit(
        jax.value_and_grad(objective, has_aux=True))(params)
    return loss, logits, ref.flat_paths(grads)


@pytest.mark.parametrize("remat,flash", [(True, True), (False, False)],
                         ids=["remat_flash", "plain"])
def test_model_matches_the_reference_in_float32(remat, flash):
    loss, logits, grads = system(jnp.float32, remat, flash)
    _, (ref_loss, ref_logits, ref_grads) = reference()
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    assert ref.rms_rel(logits, ref_logits) < 1e-5
    assert set(grads) == set(ref_grads) >= set(CHECKED)
    for path, grad in grads.items():
        assert ref.rms_rel(grad, ref_grads[path]) < 2e-3, path


def test_model_matches_the_reference_in_bf16_within_its_band():
    """bf16 through four blocks of width 64 and 32 tokens, where three heads
    of 8-wide keys see four chunks of keys that cannot be told apart: logits
    within 8 %, the loss within 0.03, and the wide matrices off the rule's
    key and query paths within 25 %."""
    loss, logits, grads = system(jnp.bfloat16, True, True)
    _, (ref_loss, ref_logits, ref_grads) = reference()
    assert logits.dtype == jnp.bfloat16
    assert abs(float(loss) - float(ref_loss)) < 3e-2
    assert ref.rms_rel(logits, ref_logits) < 8e-2
    for path in ("lm_head/kernel", "block3/mlp/down/kernel",
                 "block3/gdn/out_proj/kernel", "block3/gdn/g/kernel"):
        assert ref.rms_rel(grads[path], ref_grads[path]) < 0.25, path


def test_the_layer_kinds_follow_layer_types():
    (_, _, params), _ = reference()
    kinds = [next(k for k in ("gdn", "attn") if k in params[f"block{i}"])
             for i in range(4)]
    assert kinds == ["gdn", "gdn", "attn", "gdn"]
    assert all(set(params[f"block{i}"]) == {kinds[i], "mlp"} for i in range(4))
    gdn, attn = params["block0"]["gdn"], params["block2"]["attn"]
    assert set(gdn) == {"q", "k", "v", "g", "b", "a", "conv_kernel", "A_log",
                        "dt_bias", "norm_scale", "out_proj", "post_norm"}
    assert set(attn) == {"q", "k", "v", "o", "q_norm", "k_norm", "post_norm"}
    assert gdn["conv_kernel"].shape == (4, 2 * 3 * 8 + 3 * 16)
    assert gdn["q"]["kernel"].shape == (64, 24)
    assert gdn["v"]["kernel"].shape == gdn["g"]["kernel"].shape == (64, 48)
    assert gdn["norm_scale"].shape == (16,)
    assert attn["q_norm"]["scale"].shape == (64,)
    # a sliced vocabulary is a smaller vocabulary
    assert params["tok_emb"]["embedding"].shape == (VOCAB, 64)
    assert params["lm_head"]["kernel"].shape == (64, VOCAB)


def test_the_cut_has_the_parameters_a_hand_counts():
    """``benchmark/configs/olmo-hybrid-7b.json``: one period at published
    widths and an eighth of the vocabulary."""
    config = json.loads(
        (ROOT / "benchmark/configs/olmo-hybrid-7b.json").read_text())
    cfg = oh.OlmoHybridConfig.from_dict(config)
    assert cfg.layer_types == (LINEAR, LINEAR, LINEAR, FULL)
    assert (cfg.head_dim, cfg.chunk) == (128, 64)
    shapes = jax.eval_shape(oh.OlmoHybridLM(cfg).init, jax.random.key(0),
                            jnp.zeros((1, 64), jnp.int32))["params"]
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    c, ff, h, dk, dv, vocab = 3840, 11008, 30, 96, 192, 12544
    mlp = 3 * c * ff + c                                  # and its norm
    linear = (c * (2 * h * dk + 2 * h * dv) + h * dv * c  # q, k, v, g; out
              + 2 * c * h + 4 * (2 * h * dk + h * dv)     # b, a; the taps
              + 2 * h + dv + c)                           # A_log, dt_bias; norms
    full = 4 * c * c + 3 * c                              # q, k, v, o; norms
    assert linear + mlp == 215_570_172 and full + mlp == 185_809_920
    assert count == 3 * linear + full + 4 * mlp + 2 * vocab * c + c
    assert count == 928_862_196


@pytest.mark.parametrize("over,match", [
    ({"layer_types": [LINEAR, "sliding_attention", FULL, LINEAR]}, "unknown"),
    ({"layer_types": [LINEAR, FULL]}, "num_hidden_layers"),
    ({"hidden_act": "gelu"}, "silu"),
    ({"attention_bias": True}, "bias"),
    ({"num_key_value_heads": 2}, "key/value heads"),
    ({"linear_num_value_heads": 6}, "key/value heads"),
    ({"rope_parameters": {"rope_theta": 500000.0}}, "rotary"),
    ({"num_attention_heads": 5, "num_key_value_heads": 5}, "divide")])
def test_config_refuses_what_the_model_does_not_compute(over, match):
    with pytest.raises(ValueError, match=match):
        oh.OlmoHybridConfig.from_dict(tiny(**over))


# --- the float32 parts ---

def test_float32_parts_against_the_reference():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 16, 24)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((4, 24)), jnp.float32)
    plain = jax.nn.silu(ref.causal_conv(x, taps, 0.0)).reshape(2, 16, 3, 8)
    np.testing.assert_allclose(oh.short_conv(x, taps, 3, None), plain,
                               atol=1e-6)
    unit = oh.short_conv(x, taps, 3, 8 ** -0.5)
    np.testing.assert_allclose(unit, ref.l2_normalise(plain, 8 ** -0.5),
                               atol=1e-6)
    np.testing.assert_allclose(jnp.linalg.norm(unit, axis=-1), 8 ** -0.5,
                               rtol=1e-4)
    raw = jnp.asarray(rng.standard_normal((2, 16, 3)), jnp.float32)
    a_log, dt_bias = taps[0, :3], taps[1, :3]
    np.testing.assert_allclose(oh.log_decay(raw, a_log, dt_bias),
                               ref.log_decay(raw, a_log, dt_bias), rtol=1e-6)
    assert float(oh.log_decay(raw, a_log, dt_bias).max()) < 0
    for allow, top in ((True, 2.0), (False, 1.0)):
        beta = oh.write_strength(raw, allow)
        np.testing.assert_allclose(beta, ref.write_strength(raw, allow),
                                   rtol=1e-6)
        assert 0 < float(beta.min()) and float(beta.max()) < top
    o, z = plain, plain[..., ::-1]
    np.testing.assert_allclose(
        oh.gated_head_norm(o, z, taps[2, :8], 1e-6),
        ref.rms_norm(o, 1e-6, taps[2, :8]) * jax.nn.silu(z), rtol=1e-5,
        atol=1e-6)


def test_the_decay_starts_from_its_usual_values():
    cfg, _ = built()
    params = jax.jit(oh.GatedDeltaNet(cfg).init)(
        jax.random.key(0), jnp.zeros((1, 8, 64)))["params"]
    a = np.exp(np.asarray(params["A_log"]))
    assert (a >= 1).all() and (a <= 16).all()
    step = np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert (step >= 1e-3 - 1e-6).all() and (step <= 0.1 + 1e-6).all()
    assert bool((params["norm_scale"] == 1).all())
    assert "bias" not in params["q"] and "conv_bias" not in params
