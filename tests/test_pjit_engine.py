"""PjitEngine tests: compiler-driven DP and TP on the virtual 8-device mesh.

The correctness bar mirrors test_data_parallel: sharded training must equal
single-device training on the same effective batch (BN-free model), and the
tensor-sharded head must actually be sharded (not silently replicated)."""

import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from tpu_sandbox.data import synthetic_mnist
from tpu_sandbox.data.mnist import normalize
from tpu_sandbox.models import ConvNet
from tpu_sandbox.parallel import PjitEngine
from tpu_sandbox.parallel.expert import ExpertShare
from tpu_sandbox.parallel.pjit_engine import param_specs
from tpu_sandbox.runtime.mesh import make_mesh
from tpu_sandbox.train import TrainState, make_train_step

# every claim here is a tolerance: conftest's cheaper compile
pytestmark = pytest.mark.usefixtures("light_compile")


def setup(lr=0.05, use_bn=False):
    model = ConvNet(use_bn=use_bn)
    tx = optax.sgd(lr)
    state = TrainState.create(model, jax.random.key(0), jnp.zeros((1, 28, 28, 1)), tx)
    images, labels = synthetic_mnist(n=16, seed=0)
    return model, tx, state, normalize(images), labels.astype("int32")


def test_param_specs_rules():
    model, _, state, _, _ = setup()
    specs = param_specs(state.params, [("fc/kernel", P(None, "model"))])
    assert specs["fc"]["kernel"] == P(None, "model")
    assert specs["fc"]["bias"] == P()
    assert specs["conv1"]["kernel"] == P()


def test_pjit_dp_matches_single_device(mesh8):
    model, tx, state, images, labels = setup()
    ref_state, ref_loss = make_train_step(model, tx, donate=False)(
        state, jnp.asarray(images), jnp.asarray(labels)
    )
    eng = PjitEngine(model, tx, mesh8, donate=False)
    sstate = eng.shard_state(state)
    new_state, loss = eng.train_step(sstate, *eng.shard_batch(images, labels))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6),
        new_state.params, ref_state.params,
    )


def test_pjit_tp_column_sharded_head():
    # column parallel: output dim (10) split over a 2-way model axis
    mesh = make_mesh({"data": 4, "model": 2})
    model, tx, state, images, labels = setup()
    eng = PjitEngine(
        model, tx, mesh, rules=[("fc/kernel", P(None, "model"))], donate=False
    )
    sstate = eng.shard_state(state)
    kernel = sstate.params["fc"]["kernel"]
    assert kernel.sharding.spec == P(None, "model")
    shard_shapes = {s.data.shape for s in kernel.addressable_shards}
    assert shard_shapes == {(1568, 5)}

    new_state, loss = eng.train_step(sstate, *eng.shard_batch(images, labels))
    assert np.isfinite(float(loss))
    assert new_state.params["fc"]["kernel"].sharding.spec == P(None, "model")


def test_pjit_tp_row_sharded_head_matches_single_device():
    """Row-parallel head (18M-dim analogue): kernel sharded on its input dim;
    XLA inserts the psum. Results must match the unsharded run."""
    mesh = make_mesh({"data": 2, "model": 4})
    model, tx, state, images, labels = setup()
    ref_state, ref_loss = make_train_step(model, tx, donate=False)(
        state, jnp.asarray(images), jnp.asarray(labels)
    )
    eng = PjitEngine(
        model, tx, mesh, rules=[("fc/kernel", P("model", None))], donate=False
    )
    sstate = eng.shard_state(state)
    shard_shapes = {s.data.shape for s in sstate.params["fc"]["kernel"].addressable_shards}
    assert shard_shapes == {(392, 10)}  # 1568/4 rows per model shard
    new_state, loss = eng.train_step(sstate, *eng.shard_batch(images, labels))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(new_state.params["fc"]["kernel"]),
        np.asarray(ref_state.params["fc"]["kernel"]),
        atol=1e-6,
    )


def test_pjit_spatial_sharding_matches_single_device():
    """The CNN's sequence-parallel analog: the image height dim sharded over
    a 'spatial' axis (XLA inserts conv halo exchanges). Must match the
    unsharded step."""
    mesh = make_mesh({"data": 2, "spatial": 4})
    model, tx, state, images, labels = setup()
    ref_state, ref_loss = make_train_step(model, tx, donate=False)(
        state, jnp.asarray(images), jnp.asarray(labels)
    )
    eng = PjitEngine(
        model, tx, mesh, input_spec=P("data", "spatial"), donate=False
    )
    sstate = eng.shard_state(state)
    si, sl = eng.shard_batch(images, labels)
    assert si.sharding.spec == P("data", "spatial")
    new_state, loss = eng.train_step(sstate, si, sl)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(new_state.params["conv1"]["kernel"]),
        np.asarray(ref_state.params["conv1"]["kernel"]),
        atol=1e-6,
    )


def test_pjit_with_bn_trains(mesh8):
    model, tx, state, images, labels = setup(use_bn=True)
    eng = PjitEngine(model, tx, mesh8, donate=False)
    sstate = eng.shard_state(state)
    s1, l1 = eng.train_step(sstate, *eng.shard_batch(images, labels))
    s2, l2 = eng.train_step(s1, *eng.shard_batch(images, labels))
    assert float(l2) < float(l1)  # SyncBN path trains


def test_pjit_validates_batch_axis(mesh8):
    model, tx, state, *_ = setup()
    with pytest.raises(ValueError, match="batch axis"):
        PjitEngine(model, tx, mesh8, batch_axis="model")


def _train_adamw(mesh8, n_steps=3, **engine_kw):
    """Shared harness for the ZeRO/FSDP exactness tests: AdamW ConvNet,
    3 engine steps from a fixed init; returns (final state, losses)."""
    import optax

    from tpu_sandbox.data import synthetic_mnist
    from tpu_sandbox.data.mnist import normalize
    from tpu_sandbox.models import ConvNet

    model = ConvNet(use_bn=False)
    tx = optax.adamw(1e-3)
    state0 = TrainState.create(
        model, jax.random.key(0), jnp.zeros((1, 28, 28, 1)), tx
    )
    images, labels = synthetic_mnist(n=16, seed=0)
    images, labels = normalize(images), labels.astype("int32")
    eng = PjitEngine(model, tx, mesh8, donate=False, **engine_kw)
    st = eng.shard_state(state0)
    losses = []
    for _ in range(n_steps):
        st, loss = eng.train_step(st, *eng.shard_batch(images, labels))
        losses.append(float(loss))
    return st, losses


def _assert_params_equal(a, b):
    for (kp, x), (_, y) in zip(
        jax.tree_util.tree_leaves_with_path(a),
        jax.tree_util.tree_leaves_with_path(b),
    ):
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(x), atol=1e-6,
            err_msg=jax.tree_util.keystr(kp),
        )


def test_zero_axis_shards_opt_state(mesh8):
    """Compiler-driven ZeRO-1: PjitEngine(zero_axis='data') trains the same
    losses as the replicated engine while AdamW moments of otherwise
    replicated params live sharded on the data axis."""
    st_rep, losses_rep = _train_adamw(mesh8)
    st_zero, losses_zero = _train_adamw(mesh8, zero_axis="data")
    np.testing.assert_allclose(losses_zero, losses_rep, rtol=1e-5)
    mu = st_zero.opt_state[0].mu
    fc_spec = mu["fc"]["kernel"].sharding.spec
    assert fc_spec and fc_spec[0] == "data", fc_spec
    conv_spec = mu["conv1"]["kernel"].sharding.spec
    assert not conv_spec or conv_spec[0] is None, conv_spec
    _assert_params_equal(st_rep.params, st_zero.params)


def test_fsdp_axis_shards_params(mesh8):
    """FSDP (ZeRO-3) as specs: params themselves live sharded on the data
    axis, GSPMD all-gathers at use; training matches the replicated engine
    and both params and AdamW moments carry the dim-0 'data' sharding."""
    st_rep, losses_rep = _train_adamw(mesh8)
    st_fsdp, losses_fsdp = _train_adamw(mesh8, fsdp_axis="data")
    np.testing.assert_allclose(losses_fsdp, losses_rep, rtol=1e-5)
    fc = st_fsdp.params["fc"]["kernel"]
    assert fc.sharding.spec and fc.sharding.spec[0] == "data", fc.sharding
    mu = st_fsdp.opt_state[0].mu["fc"]["kernel"]
    assert mu.sharding.spec and mu.sharding.spec[0] == "data", mu.sharding
    # conv kernels (dim0=5, not divisible by 8) stay replicated
    ck = st_fsdp.params["conv1"]["kernel"].sharding.spec
    assert not ck or ck[0] is None, ck
    _assert_params_equal(st_rep.params, st_fsdp.params)


# -- the one step, every sharding mode, both tasks ---------------------------


class _RoutedLM(nn.Module):
    """Embedding, one routed-expert layer, head: the smallest LM whose
    ``batch_stats`` hold a router's bias and counters."""

    @nn.compact
    def __call__(self, tokens):
        x = nn.Embed(64, 32, name="embed")(tokens)
        x = x + ExpertShare(
            d_model=32, d_ff=16, n_routed_experts=8, top_k=2,
            held=tuple(range(8)), local_rows=2 * tokens.size,
            n_shared_experts=1, dtype=jnp.float32, row_tile=8, name="moe")(x)
        return nn.Dense(64, name="head")(x)


def _lm_setup():
    model = _RoutedLM()
    tx = optax.sgd(0.05)
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, 64)
    state = TrainState.create(
        types.SimpleNamespace(init=jax.jit(model.init)), jax.random.key(0),
        tokens[:1], tx)
    return model, tx, state, tokens, (tokens + 1) % 64


#: mode -> (the mesh's axes, the engine's options); a rule that finds no
#: parameter of a model shards nothing there
MODES = {
    "dp": ({"data": 8}, {}),
    "zero_axis": ({"data": 8}, {"zero_axis": "data"}),
    "fsdp_axis": ({"data": 8}, {"fsdp_axis": "data"}),
    "tp_rule": ({"data": 2, "model": 4},
                {"rules": [("fc/kernel", P("model", None)),
                           ("head/kernel", P(None, "model")),
                           ("moe/shared_up/kernel", P(None, "model"))]}),
}


@pytest.mark.parametrize("task", ["image", "lm"])
@pytest.mark.parametrize("mode", MODES)
def test_one_step_returns_the_stats_in_every_mode(mode, task):
    """``PjitEngine`` has one step, and it hands the model's ``batch_stats``
    back moved -- BatchNorm's running moments, a router's bias and its
    counters -- however state and batch are laid out: under jit the
    batch axis is global, so loss and statistics are the one-device
    step's to rounding (``make_train_step`` for the image task; the same
    engine on a mesh of one device for the LM, which has no other)."""
    axes, kw = MODES[mode]
    if task == "image":
        model, tx, state, *batch = setup(use_bn=True)
        want_state, want_loss = make_train_step(model, tx, donate=False)(
            state, *map(jnp.asarray, batch))
    else:
        model, tx, state, *batch = _lm_setup()
        one_device = make_mesh({"data": 1}, devices=jax.devices()[:1])
        one = PjitEngine(model, tx, one_device, task="lm", donate=False)
        want_state, want_loss = one.train_step(
            one.shard_state(state), *one.shard_batch(*batch))
    eng = PjitEngine(model, tx, make_mesh(axes), task=task, donate=False,
                     **kw)
    new_state, loss = eng.train_step(
        eng.shard_state(state), *eng.shard_batch(*batch))
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    stats, was = new_state.batch_stats, state.batch_stats
    assert jax.tree.structure(stats) == jax.tree.structure(was)
    assert jax.tree.leaves(stats)
    moved = jax.tree.map(lambda a, b: bool((a != b).any()), stats, was)
    if task == "lm":
        assert int(stats["moe"]["steps"]) == 1
        moved["moe"].pop("rows_dropped")  # nothing dropped: local_rows is T k
    assert all(jax.tree.leaves(moved)), moved
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        stats, want_state.batch_stats)
