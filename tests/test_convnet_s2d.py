"""ConvNetS2D == ConvNet: the space-to-depth plan is the same function.

The s2d model exists purely as an execution plan (models/convnet_s2d.py);
these tests pin the contract that lets the entry scripts swap
it in for the reference-parity ConvNet: identical parameter tree, identical
forward, identical gradients, identical batch-stats evolution.

What the transposed plan promises alike takes a ``plan`` (the model's
class): this module's fixture gives ConvNetS2D, and
tests/test_convnet_s2d_t.py collects the same tests with ConvNetS2DT.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpu_sandbox.models import ConvNet
from tpu_sandbox.models.convnet_s2d import ConvNetS2D, scatter_kernel
from tpu_sandbox.ops.losses import cross_entropy_loss


@pytest.fixture
def plan():
    return ConvNetS2D


def _data(n=3, hw=48, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, hw, hw, 1)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(n,)), jnp.int32)
    return x, y


# every claim here is a tolerance between two plans of one function
pytestmark = pytest.mark.usefixtures("light_compile")


@functools.cache
def _shared_init(use_bn=True, n=3, hw=48):
    """The variables both plans start from: ConvNet's, compiled once."""
    return jax.jit(ConvNet(use_bn=use_bn).init)(
        jax.random.key(0), _data(n, hw)[0])


def _applied(model, variables, x, **how):
    """``model.apply`` as one compiled program, not one a primitive."""
    return jax.jit(functools.partial(model.apply, **how))(variables, x)


def _loss_grads_stats(model, x, y):
    """The jitted ``(params, stats) -> ((loss, new stats), grads)``."""
    def f(p, stats):
        logits, upd = model.apply(
            {"params": p, "batch_stats": stats}, x, train=True,
            mutable=["batch_stats"])
        return cross_entropy_loss(logits, y), upd["batch_stats"]
    return jax.jit(jax.value_and_grad(f, has_aux=True))


def test_pick_convnet_plan_switch():
    from tpu_sandbox.models import pick_convnet, resolve_plan
    # on CPU (interpret-mode kernels) auto resolves to the NHWC s2d plan;
    # on TPU / forced-compile it resolves to the transposed plan
    assert type(pick_convnet(3000)).__name__ == "ConvNetS2D"
    assert type(pick_convnet(3000, plan="plain")).__name__ == "ConvNet"
    assert type(pick_convnet(3001)).__name__ == "ConvNet"  # not 4-divisible
    assert type(pick_convnet((128, 64))).__name__ == "ConvNetS2D"
    assert type(pick_convnet(3000, plan="s2dt")).__name__ == "ConvNetS2DT"
    from tpu_sandbox.ops.pallas_common import default_interpret
    # backend-dependent: interpret mode (CPU tests) -> NHWC s2d; compiled
    # (TPU / forced) -> transposed (ADVICE r03)
    assert resolve_plan(3000) == ("s2d" if default_interpret(None)
                                  else "s2dt")
    # and BOTH branches deterministically, via the force-compile override
    # (a regression hardcoding 's2d' must fail off-chip too)
    import os
    from unittest import mock

    with mock.patch.dict(os.environ,
                         {"TPU_SANDBOX_FORCE_COMPILED_KERNELS": "1"}):
        assert resolve_plan(3000) == "s2dt"
    # fused_conv=False must disable the Pallas convs even where 'auto'
    # resolves to the always-Pallas transposed plan
    assert type(pick_convnet(3000, plan="s2dt",
                             fused_conv=False)).__name__ == "ConvNetS2D"
    assert resolve_plan(3000, "s2dt") == "s2dt"
    assert resolve_plan(3001) == "plain"


def test_param_trees_compatible(plan):
    ref, s2d = ConvNet(), plan()
    x, _ = _data()
    vr = jax.eval_shape(ref.init, jax.random.key(0), x)
    vs = jax.eval_shape(s2d.init, jax.random.key(0), x)
    ref_shapes = jax.tree.map(jnp.shape, vr)
    s2d_shapes = jax.tree.map(jnp.shape, vs)
    assert ref_shapes == s2d_shapes


def test_scatter_kernel_reproduces_conv():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((5, 5, 1, 3)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 16, 16)), jnp.float32)
    ref = jax.lax.conv_general_dilated(
        x[..., None], w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    from tpu_sandbox.models.convnet_s2d import space_to_depth
    out = jax.lax.conv_general_dilated(
        space_to_depth(x, 4), scatter_kernel(w, 4), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    # undo s2d on the output: channel (a*4+b)*3+co at block (i,j)
    n, hb, wb, _ = out.shape
    out = out.reshape(n, hb, wb, 4, 4, 3).transpose(0, 1, 3, 2, 4, 5)
    out = out.reshape(n, hb * 4, wb * 4, 3)
    np.testing.assert_allclose(out, ref, atol=1e-5)


@pytest.mark.parametrize("use_bn", [True, False])
def test_forward_matches_convnet(plan, use_bn):
    ref, s2d = ConvNet(use_bn=use_bn), plan(use_bn=use_bn)
    x, _ = _data()
    variables = _shared_init(use_bn)
    if use_bn:
        lr = _applied(ref, variables, x, train=True, mutable=["batch_stats"])
        ls = _applied(s2d, variables, x, train=True, mutable=["batch_stats"])
        out_r, out_s = lr[0], ls[0]
    else:
        out_r = _applied(ref, variables, x, train=True)
        out_s = _applied(s2d, variables, x, train=True)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_r),
                               atol=2e-4)
    if use_bn:
        for k in ("bn1", "bn2"):
            for stat in ("mean", "var"):
                np.testing.assert_allclose(
                    np.asarray(ls[1]["batch_stats"][k][stat]),
                    np.asarray(lr[1]["batch_stats"][k][stat]),
                    atol=1e-5, err_msg=f"{k}/{stat}")


def test_eval_mode_uses_running_stats(plan):
    ref, s2d = ConvNet(), plan()
    x, _ = _data()
    variables = _shared_init()
    out_r = _applied(ref, variables, x, train=False)
    out_s = _applied(s2d, variables, x, train=False)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_r),
                               atol=2e-4)


def test_gradients_match_convnet(plan):
    ref, s2d = ConvNet(), plan()
    x, y = _data()
    variables = _shared_init()
    params, stats = variables["params"], variables["batch_stats"]
    (lr, _), gr = _loss_grads_stats(ref, x, y)(params, stats)
    (ls, _), gs = _loss_grads_stats(s2d, x, y)(params, stats)
    np.testing.assert_allclose(ls, lr, atol=1e-5)
    flat_r = jax.tree_util.tree_leaves_with_path(gr)
    flat_s = {jax.tree_util.keystr(k): v
              for k, v in jax.tree_util.tree_leaves_with_path(gs)}
    for k, v in flat_r:
        np.testing.assert_allclose(
            np.asarray(flat_s[jax.tree_util.keystr(k)]), np.asarray(v),
            atol=5e-4, err_msg=jax.tree_util.keystr(k))


def test_short_training_runs_stay_together(plan):
    """5 SGD steps from shared init: losses track to float tolerance (the
    steps compound the one-ulp conv and reduction differences between the
    plans; compiled as one program a step the drift reads 3e-6)."""
    ref, s2d = ConvNet(), plan()
    x, y = _data(n=4, hw=32)
    tx = optax.sgd(1e-2)
    variables = _shared_init(n=4, hw=32)

    def run(model):
        loss_grads_stats = _loss_grads_stats(model, x, y)

        @jax.jit
        def step(params, stats, opt):
            (loss, stats), g = loss_grads_stats(params, stats)
            updates, opt = tx.update(g, opt, params)
            return optax.apply_updates(params, updates), stats, opt, loss

        params, stats = variables["params"], variables["batch_stats"]
        opt = tx.init(params)
        losses = []
        for _ in range(5):
            params, stats, opt, loss = step(params, stats, opt)
            losses.append(float(loss))
        return losses

    np.testing.assert_allclose(run(s2d), run(ref), rtol=1e-4)


@functools.cache
def _three_steps_under_data_parallel(model, mesh):
    """Three steps' losses of ``model`` in DataParallel over the mesh's
    shards, from ConvNet's init (one run of the plain model serves every
    case it is compared with)."""
    from tpu_sandbox.data import synthetic_mnist
    from tpu_sandbox.data.mnist import normalize
    from tpu_sandbox.parallel import DataParallel
    from tpu_sandbox.train import TrainState

    images, labels = synthetic_mnist(n=16, seed=0)
    images, labels = normalize(images), labels.astype("int32")
    tx = optax.sgd(1e-2)
    variables = _shared_init(n=1, hw=32)
    state0 = TrainState(
        step=jnp.zeros((), jnp.int32), params=variables["params"],
        batch_stats=variables["batch_stats"],
        opt_state=tx.init(variables["params"]),
    )
    dp = DataParallel(model, tx, mesh, image_size=(32, 32), donate=False)
    st = dp.shard_state(state0)
    losses = []
    for _ in range(3):
        st, loss = dp.train_step(st, *dp.shard_batch(images, labels))
        losses.append(np.asarray(loss))
    return np.stack(losses)


@pytest.mark.parametrize(
    "fused_tail,fused_conv",
    [(False, False), (True, False), (True, True), (False, True)],
)
def test_s2d_under_data_parallel_matches_plain_model(mesh8, fused_tail,
                                                     fused_conv):
    """The headline-bench path: ConvNetS2D inside DataParallel over 8
    shards trains the same losses as ConvNet in the same engine (shared
    init; BN per-replica in both) — with and without the fused Pallas
    tail/conv, since pick_convnet defaults production entry points to
    both fused."""
    s2d = ConvNetS2D(fused_tail=fused_tail, fused_conv=fused_conv)
    np.testing.assert_allclose(
        _three_steps_under_data_parallel(s2d, mesh8),
        _three_steps_under_data_parallel(ConvNet(), mesh8),
        rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("fused", [{"fused_conv": False}, {"fused_conv": True}],
                         ids=["False", "True"])
def test_fused_tail_matches_unfused_model(plan, fused):
    """The plan with ``fused_tail=True`` (and, for ConvNetS2D, with or
    without ``fused_conv``) == the plan without: logits, grads, and BN
    running stats with shared init."""
    x, y = _data(n=2, hw=32, seed=5)
    plain = plan()
    fused = plan(fused_tail=True, **fused)
    variables = jax.jit(plain.init)(jax.random.key(0), x)
    params, stats = variables["params"], variables["batch_stats"]
    (lp, sp), gp = _loss_grads_stats(plain, x, y)(params, stats)
    (lf, sf), gf = _loss_grads_stats(fused, x, y)(params, stats)
    np.testing.assert_allclose(float(lf), float(lp), atol=1e-5)
    for (kp, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(gp),
        jax.tree_util.tree_leaves_with_path(gf),
    ):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=5e-4,
            err_msg=jax.tree_util.keystr(kp),
        )
    for (kp, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(sp),
        jax.tree_util.tree_leaves_with_path(sf),
    ):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=1e-5,
            err_msg=jax.tree_util.keystr(kp),
        )
