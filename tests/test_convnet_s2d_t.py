"""ConvNetS2DT == ConvNet: the transposed s2d plan is the same function.

The tests of test_convnet_s2d.py, collected here with the [N,H,C,W]-layout
plan (models/convnet_s2d_t.py): identical parameter tree, forward,
gradients, and batch-stats evolution as the reference-parity ConvNet, with
and without the fused tail pair; then what only this plan has."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import test_convnet_s2d as s2d
from tests.test_convnet_s2d import (  # noqa: F401 (collected here too)
    test_eval_mode_uses_running_stats, test_forward_matches_convnet,
    test_param_trees_compatible)
from tpu_sandbox.models import ConvNet
from tpu_sandbox.models.convnet_s2d_t import ConvNetS2DT
from tpu_sandbox.ops.losses import cross_entropy_loss


@pytest.fixture
def plan():
    return ConvNetS2DT


@pytest.mark.slow  # tier-1 keeps the test_convnet_s2d.py twin
def test_gradients_match_convnet(plan):
    s2d.test_gradients_match_convnet(plan)


@pytest.mark.slow  # tier-1 keeps the test_convnet_s2d.py twin
def test_fused_tail_matches_unfused_model(plan):
    """The production fused chain: conv stats fused in-kernel + fused tail
    pair."""
    s2d.test_fused_tail_matches_unfused_model(plan, {})


@pytest.mark.slow  # tier-1 keeps the test_convnet_s2d.py twin
def test_short_training_runs_stay_together(plan):
    s2d.test_short_training_runs_stay_together(plan)


def test_space_to_depth_t_is_transposed_space_to_depth():
    from tpu_sandbox.models.convnet_s2d import space_to_depth
    from tpu_sandbox.models.convnet_s2d_t import space_to_depth_t

    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 16, 12)),
                    jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(space_to_depth_t(x, 4)),
        np.asarray(space_to_depth(x, 4).transpose(0, 1, 3, 2)),
    )


def test_block_max_pool_t_is_transposed_block_max_pool():
    from tpu_sandbox.models.convnet_s2d import block_max_pool
    from tpu_sandbox.models.convnet_s2d_t import block_max_pool_t

    y = jnp.asarray(np.random.default_rng(1).standard_normal((2, 6, 5, 48)),
                    jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(block_max_pool_t(y.transpose(0, 1, 3, 2), 4, 3)),
        np.asarray(block_max_pool(y, 4, 3).transpose(0, 1, 3, 2)),
    )


@pytest.mark.slow  # tier-1 keeps test_data_parallel's fused-input parity
def test_fused_input_stage_matches_resize_plus_s2d():
    """fused_input_stage == resize_on_device + space_to_depth_t exactly
    (same bilinear weights via the resize-of-identity matrix): the
    production input path must be THE resize the other plans run, not an
    approximation of it."""
    from tpu_sandbox.models.convnet_s2d_t import space_to_depth_t
    from tpu_sandbox.train import resize_on_device

    rng = np.random.default_rng(0)
    x28 = jnp.asarray(rng.standard_normal((3, 28, 28, 1)), jnp.float32)
    m = ConvNetS2DT(dtype=jnp.float32)
    fused = m.fused_input_stage(x28, (96, 96))
    ref = space_to_depth_t(resize_on_device(x28, (96, 96))[..., 0], 4)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               atol=2e-5)
    # and the model consumes the pre-s2d tensor identically
    variables = m.init(jax.random.key(0), resize_on_device(x28, (96, 96)))
    out_full = m.apply(variables, resize_on_device(x28, (96, 96)),
                       train=False)
    out_pre = m.apply(variables, fused, train=False)
    np.testing.assert_allclose(np.asarray(out_pre), np.asarray(out_full),
                               atol=2e-4)


def test_prepare_inputs_dispatch():
    """prepare_inputs: fused stage for models that declare one (pre-s2d
    output shape), plain resize for everything else."""
    from tpu_sandbox.train import prepare_inputs

    x28 = jnp.zeros((2, 28, 28, 1), jnp.float32)
    assert prepare_inputs(ConvNetS2DT(), x28, (64, 64)).shape == (2, 16, 16, 16)
    assert prepare_inputs(ConvNet(), x28, (64, 64)).shape == (2, 64, 64, 1)


def test_checkpoint_refuses_pre_canonical_layout(tmp_path):
    """Checkpoints carry the fc row-order stamp; a directory without it
    (or with a different one) is refused loudly — same-shaped fc kernels
    with permuted rows must not restore silently."""
    import optax

    from tpu_sandbox.train import TrainState, checkpoint

    model = ConvNet()
    x = jnp.zeros((1, 16, 16, 1), jnp.float32)
    state = TrainState.create(model, jax.random.key(0), x, optax.sgd(0.1))
    d = tmp_path / "ck"
    checkpoint.save(d, state, 0)
    assert (d / "LAYOUT").read_text().strip() == "fc-row-order=hcw"
    restored = checkpoint.restore(d, state)
    np.testing.assert_array_equal(
        np.asarray(restored.params["fc"]["kernel"]),
        np.asarray(state.params["fc"]["kernel"]))
    (d / "LAYOUT").unlink()  # simulate a pre-stamp checkpoint
    with pytest.raises(ValueError, match="layout mismatch"):
        checkpoint.restore(d, state)


@pytest.mark.slow  # wide-row rerun of the equality tier-1 still pins
def test_equality_at_production_row_width_bf16():
    """VERDICT r03 weak-3: the 48x48 fp32 equality proves nothing about
    750-wide rows in bf16 (the production geometry at image 3000). This
    pins s2dt == plain at H=16, W=3000 — the exact 750-lane row width —
    in bf16, with tolerances ~3x the measured deviation (logits rel
    2.2e-3, loss 2.5e-3; fp32 at this width measures 4.4e-7 — pure bf16
    rounding, not a layout defect)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 16, 3000, 1)), jnp.bfloat16)
    yl = jnp.asarray(rng.integers(0, 10, size=(2,)), jnp.int32)
    ref = ConvNet(dtype=jnp.bfloat16)
    t = ConvNetS2DT(dtype=jnp.bfloat16, fused_tail=True)
    variables = ref.init(jax.random.key(0), x)
    params, stats = variables["params"], variables["batch_stats"]

    def run(model):
        def f(p):
            logits, _ = model.apply(
                {"params": p, "batch_stats": stats}, x, train=True,
                mutable=["batch_stats"])
            return cross_entropy_loss(logits, yl), logits
        (loss, logits), g = jax.value_and_grad(f, has_aux=True)(params)
        return loss, logits, g

    l_r, lo_r, g_r = run(ref)
    l_t, lo_t, g_t = run(t)
    assert abs(float(l_r) - float(l_t)) < 8e-3
    scale = float(np.max(np.abs(np.asarray(lo_r, np.float32))))
    dev = float(np.max(np.abs(np.asarray(lo_r, np.float32)
                              - np.asarray(lo_t, np.float32))))
    assert dev / scale < 8e-3, (dev, scale)
    # fc grads carry ~all the signal at this depth; conv-bias grads are
    # near-zero under BN so only relative-to-scale checks make sense
    fr = np.asarray(g_r["fc"]["kernel"], np.float32)
    ft = np.asarray(g_t["fc"]["kernel"], np.float32)
    assert np.max(np.abs(fr - ft)) / (np.max(np.abs(fr)) or 1.0) < 0.05


@pytest.mark.slow  # grads stay pinned by test_pallas_conv1_tail_t tier-1
def test_fused_conv1_bwd_matches_unfused_model():
    """r05 backward fusion A/B at the model level: ConvNetS2DT with
    fused_conv1_bwd True vs False — same loss, same grads (the fused
    path never materializes conv1's cotangent; dcbias excluded from
    tight comparison, it is analytically ~0 under BN and pure
    summation noise in both paths)."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 32, 32, 1)), jnp.float32)
    yl = jnp.asarray(rng.integers(0, 10, size=(2,)), jnp.int32)
    ref = ConvNetS2DT(features=(8, 8), fused_tail=True,
                      fused_conv1_bwd=False)
    fused = ConvNetS2DT(features=(8, 8), fused_tail=True)
    variables = ref.init(jax.random.key(0), x)
    params, stats = variables["params"], variables["batch_stats"]

    def run(model):
        # jit'd like the production trainer; also ~2x faster than eager
        # op-by-op dispatch on the CPU backend
        @jax.jit
        def go(p):
            def f(p):
                logits, mut = model.apply(
                    {"params": p, "batch_stats": stats}, x, train=True,
                    mutable=["batch_stats"])
                return cross_entropy_loss(logits, yl), mut["batch_stats"]
            (loss, new_stats), g = jax.value_and_grad(f, has_aux=True)(p)
            return loss, new_stats, g
        return go(params)

    l_r, st_r, g_r = run(ref)
    l_f, st_f, g_f = run(fused)
    assert abs(float(l_r) - float(l_f)) < 1e-5
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-5), st_r, st_f)
    for path in (("conv1", "kernel"), ("bn1", "scale"), ("bn1", "bias"),
                 ("conv2", "kernel"), ("fc", "kernel")):
        a = np.asarray(g_f[path[0]][path[1]], np.float32)
        b = np.asarray(g_r[path[0]][path[1]], np.float32)
        scale = float(np.max(np.abs(b))) or 1.0
        assert float(np.max(np.abs(a - b))) / scale < 3e-5, path
