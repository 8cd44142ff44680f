"""Transformer + sequence-parallel engine tests.

Correctness bar: the SP train step (ring attention + pmean'd grads over
('data','sp')) must match single-device training of the identical model
with local attention on the same global batch."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
from tpu_sandbox.ops.losses import cross_entropy_loss
from tpu_sandbox.parallel.seq_parallel import SeqParallel
from tpu_sandbox.runtime.mesh import make_mesh

# every claim here is a tolerance: conftest's cheaper compile
pytestmark = pytest.mark.usefixtures("light_compile")

CFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                        max_len=64)


def model_ctor(attention_fn):
    return TransformerLM(CFG, attention_fn)


def lm_data(b=4, s=32, seed=0):
    """Learnable task: next token = (token + 7) % vocab."""
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, CFG.vocab_size, size=(b, s)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    targets[:, -1] = (tokens[:, -1] + 7) % CFG.vocab_size
    targets = ((tokens + 7) % CFG.vocab_size).astype(np.int32)
    return tokens, targets


@pytest.fixture(scope="module")
def mesh_dp_sp():
    return make_mesh({"data": 2, "sp": 4})


def test_sp_step_matches_single_device(mesh_dp_sp):
    tx = optax.sgd(0.1)
    sp = SeqParallel(model_ctor, tx, mesh_dp_sp, donate=False)
    tokens, targets = lm_data()
    state = sp.init_state(jax.random.key(0), jnp.asarray(tokens))

    # single-device reference: same params, local attention, full batch
    local = sp.local_model

    def ref_loss(params):
        logits = local.apply({"params": params}, jnp.asarray(tokens))
        return cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), jnp.asarray(targets).reshape(-1)
        )

    @jax.jit  # one compiled program, not one a primitive
    def ref_step(params):
        loss, grads = jax.value_and_grad(ref_loss)(params)
        return loss, optax.apply_updates(
            params, tx.update(grads, tx.init(params), params)[0])

    ref_loss_val, ref_params = ref_step(state.params)

    sstate = sp.shard_state(state)
    new_state, loss = sp.train_step(sstate, *sp.shard_batch(tokens, targets))
    np.testing.assert_allclose(float(loss), float(ref_loss_val), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        new_state.params,
        ref_params,
    )


def test_sp_training_learns(mesh_dp_sp):
    tx = optax.adam(1e-2)
    sp = SeqParallel(model_ctor, tx, mesh_dp_sp, donate=False)
    tokens, targets = lm_data(b=8, s=32)
    state = sp.shard_state(sp.init_state(jax.random.key(1), jnp.asarray(tokens)))
    batch = sp.shard_batch(tokens, targets)
    losses = []
    for _ in range(30):
        state, loss = sp.train_step(state, *batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_sp_validates_axes():
    mesh = make_mesh({"data": 8})
    with pytest.raises(ValueError, match="not in mesh"):
        SeqParallel(model_ctor, optax.sgd(0.1), mesh)


def test_transformer_forward_shapes():
    model = TransformerLM(CFG)
    tokens = jnp.zeros((2, 16), jnp.int32)
    variables = jax.jit(model.init)(jax.random.key(0), tokens)
    logits = jax.jit(model.apply)(variables, tokens)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert logits.dtype == jnp.float32


def test_transformer_is_causal():
    model = TransformerLM(CFG)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (1, 16)), jnp.int32)
    variables = jax.jit(model.init)(jax.random.key(0), tokens)
    apply = jax.jit(model.apply)
    base = apply(variables, tokens)
    out = apply(variables, tokens.at[:, 10:].set(1))
    np.testing.assert_allclose(
        np.asarray(base)[:, :10], np.asarray(out)[:, :10], atol=1e-5
    )


@pytest.mark.parametrize("features,contract,x_shape", [
    ((3, 4, 8), 1, (2, 16, 32)),      # qkv: [B, S, C] -> [B, S, 3, H·D]
    ((32,), 2, (2, 16, 4, 8)),        # out: [B, S, H, D] -> [B, S, C]
], ids=["qkv", "out"])
def test_heads_dense_holds_dense_generals_parameters_and_numbers(
        features, contract, x_shape):
    """``HeadsDense`` is ``nn.DenseGeneral`` with the (heads, head_dim) pair
    seen as one dimension by the product: the same parameter names, shapes
    and initial values from the same key, and the same result."""
    import flax.linen as nn

    from tpu_sandbox.models.transformer import HeadsDense

    x = jax.random.normal(jax.random.key(1), x_shape)
    axis = tuple(range(-contract, 0))
    theirs = nn.DenseGeneral(features, axis=axis)
    ours = HeadsDense(features, contract=contract)
    want_vars = jax.jit(theirs.init)(jax.random.key(0), x)
    got_vars = jax.jit(ours.init)(jax.random.key(0), x)
    jax.tree.map(np.testing.assert_array_equal, got_vars, want_vars)
    bias = jax.random.normal(jax.random.key(2), features)
    variables = {"params": {**want_vars["params"], "bias": bias}}
    want = jax.jit(theirs.apply)(variables, x)
    got = jax.jit(ours.apply)(variables, x)
    np.testing.assert_allclose(got.reshape(want.shape), want, rtol=1e-5,
                               atol=1e-5)
