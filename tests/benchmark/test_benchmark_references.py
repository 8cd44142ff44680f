"""The plain float32 references against the program at a tiny size, on the
CPU in float32, where the two must agree to rounding."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.reference import convnet as ref_convnet  # noqa: E402
from benchmark.reference import gpt2 as ref_gpt2  # noqa: E402


def test_convnet_reference_matches_the_programs_plain_net():
    import jax
    import jax.numpy as jnp

    from tpu_sandbox.models.convnet import ConvNet
    from tpu_sandbox.ops.losses import cross_entropy_loss

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 16, 24, 1)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, size=(3,)), jnp.int32)
    model = ConvNet(dtype=jnp.float32)
    variables = model.init(jax.random.key(1), x)
    params, stats = variables["params"], variables["batch_stats"]

    def system(p):
        logits, _ = model.apply({"params": p, "batch_stats": stats}, x,
                                train=True, mutable=["batch_stats"])
        return cross_entropy_loss(logits, labels), logits

    (loss, logits), grads = jax.value_and_grad(system, has_aux=True)(params)
    dev, bad = ref_convnet.compare(
        {"loss": loss, "logits": logits, "fc_grad": grads["fc"]["kernel"]},
        params, x, labels)
    assert bad == []
    assert dev["logit_rel"] < 1e-5 and dev["loss_abs"] < 1e-5
    assert dev["fc_grad_rel"] < 1e-4


def test_convnet_comparison_fails_a_wrong_system():
    import jax
    import jax.numpy as jnp

    from tpu_sandbox.models.convnet import ConvNet

    x = jnp.ones((2, 8, 8, 1), jnp.float32)
    labels = jnp.zeros((2,), jnp.int32)
    params = ConvNet().init(jax.random.key(0), x)["params"]
    loss, logits = ref_convnet.loss_and_logits(params, x, labels)
    grad = jax.grad(lambda p: ref_convnet.loss_and_logits(p, x, labels)[0])(
        params)["fc"]["kernel"]
    _, bad = ref_convnet.compare(
        {"loss": loss, "logits": logits * 1.05, "fc_grad": grad},
        params, x, labels)
    assert any("logit_rel" in b for b in bad)


@pytest.fixture(scope="module")
def tiny_lm():
    import jax
    import jax.numpy as jnp

    from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=61, d_model=32, n_heads=4, n_layers=3,
                            d_ff=48, max_len=32, dtype=jnp.float32)
    model = TransformerLM(cfg)
    tokens = np.random.default_rng(2).integers(0, 61, size=(2, 20), dtype=np.int32)
    params = model.init(jax.random.key(3), jnp.asarray(tokens))["params"]
    # flax initialises biases to zero: give every term something to do
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(4), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])
    return model, params, tokens


def test_gpt2_reference_matches_the_programs_transformer(tiny_lm):
    from tpu_sandbox.ops.losses import cross_entropy_loss

    model, params, tokens = tiny_lm
    targets = (tokens + 1) % 61
    logits = model.apply({"params": params}, tokens)
    loss = cross_entropy_loss(logits.reshape(-1, 61), targets.reshape(-1))
    ref_logits, ref_loss = ref_gpt2.logits_and_loss(
        ref_gpt2.from_program_tree(params, 3), tokens, targets, n_head=4,
        eps=1e-6)
    dev, bad = ref_gpt2.compare(logits, ref_logits, loss, ref_loss)
    assert bad == []
    assert dev["logit_rms_rel"] < 1e-5 and dev["loss_abs"] < 1e-5


@pytest.mark.parametrize("wrong", ["eps", "heads", "lower_precision"])
def test_gpt2_comparison_fails_a_wrong_system(tiny_lm, wrong):
    import jax.numpy as jnp

    model, params, tokens = tiny_lm
    logits = model.apply({"params": params}, tokens)
    p = ref_gpt2.from_program_tree(params, 3)
    if wrong == "eps":      # the published epsilon is not the program's
        ref, _ = ref_gpt2.logits_and_loss(p, tokens, tokens, n_head=4, eps=1e-1)
    elif wrong == "heads":
        ref, _ = ref_gpt2.logits_and_loss(p, tokens, tokens, n_head=2, eps=1e-6)
    else:                   # a system whose logits keep 3 bits of mantissa (fp8 e4m3)
        ref, _ = ref_gpt2.logits_and_loss(p, tokens, tokens, n_head=4, eps=1e-6)
        scale = 2.0 ** (jnp.floor(jnp.log2(jnp.abs(logits) + 1e-9)) - 2)
        logits = jnp.round(logits / scale) * scale
    _, bad = ref_gpt2.compare(logits, ref)
    assert bad
