"""Data-parallel engine tests on the 8-virtual-device mesh.

The reference's correctness story was eyeballed loss curves; here it's
asserted: DP over 8 shards must match single-device training on the same
effective batch exactly (BN-free model — bitwise-level agreement up to fp
reassociation), per-replica BN stats must actually diverge per rank (DDP
does not sync BN), and the sharded loader must reproduce DistributedSampler
rank shards."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpu_sandbox.data import ShardedBatchLoader, synthetic_mnist
from tpu_sandbox.data.mnist import normalize
from tpu_sandbox.models import ConvNet
from tpu_sandbox.parallel import DataParallel
from tpu_sandbox.train import TrainState, make_train_step


def setup(use_bn, lr=0.05):
    model = ConvNet(use_bn=use_bn)
    tx = optax.sgd(lr)
    state = TrainState.create(model, jax.random.key(0), jnp.zeros((1, 28, 28, 1)), tx)
    return model, tx, state


@pytest.mark.usefixtures("light_compile")
def test_dp_matches_single_device_without_bn(mesh8):
    """Same params, same effective batch 16: one DP step over 8 shards ==
    one single-device step (pmean of shard grads == full-batch grad)."""
    model, tx, state = setup(use_bn=False)
    images, labels = synthetic_mnist(n=16, seed=0)
    images, labels = normalize(images), labels.astype("int32")

    single_step = make_train_step(model, tx, donate=False)
    ref_state, ref_loss = single_step(state, jnp.asarray(images), jnp.asarray(labels))

    dp = DataParallel(model, tx, mesh8, donate=False)
    dstate = dp.shard_state(state)
    di, dl = dp.shard_batch(images, labels)
    new_state, losses = dp.train_step(dstate, di, dl)

    assert losses.shape == (8,)
    # global mean loss == mean of shard losses (equal shard sizes)
    np.testing.assert_allclose(float(jnp.mean(losses)), float(ref_loss), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        ),
        new_state.params,
        ref_state.params,
    )


def test_dp_params_stay_replicated(mesh8):
    model, tx, state = setup(use_bn=True)
    dp = DataParallel(model, tx, mesh8, donate=False)
    dstate = dp.shard_state(state)
    images, labels = synthetic_mnist(n=16, seed=0)
    new_state, _ = dp.train_step(*((dstate,) + dp.shard_batch(normalize(images), labels.astype("int32"))))
    # every device must hold identical params after the step
    kernel = new_state.params["conv1"]["kernel"]
    shards = [np.asarray(s.data) for s in kernel.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)


def test_bn_stats_are_per_replica(mesh8):
    """Feed rank-dependent data: BN means must differ per rank (DDP parity:
    no cross-replica BN sync)."""
    model, tx, state = setup(use_bn=True)
    dp = DataParallel(model, tx, mesh8, donate=False)
    dstate = dp.shard_state(state)
    # biased batches: rank i sees images scaled by i/8
    images = np.concatenate(
        [normalize(synthetic_mnist(n=2, seed=0)[0]) * (i / 8) for i in range(8)]
    )
    labels = np.zeros(16, np.int32)
    new_state, _ = dp.train_step(dstate, *dp.shard_batch(images, labels))
    means = np.asarray(new_state.batch_stats["bn1"]["mean"])  # [8, 16]
    assert means.shape[0] == 8
    assert not np.allclose(means[0], means[7])
    # and unshard_state picks one rank's stats
    local = dp.unshard_state(new_state, rank=3)
    np.testing.assert_array_equal(
        np.asarray(local.batch_stats["bn1"]["mean"]), means[3]
    )


def test_dp_loss_vector_is_rank_local(mesh8):
    model, tx, state = setup(use_bn=False)
    dp = DataParallel(model, tx, mesh8, donate=False)
    dp_avg = DataParallel(model, tx, mesh8, donate=False, average_loss=True)
    images, labels = synthetic_mnist(n=16, seed=0)
    batch = (normalize(images), labels.astype("int32"))
    _, local = dp.train_step(dp.shard_state(state), *dp.shard_batch(*batch))
    _, avg = dp_avg.train_step(dp_avg.shard_state(state), *dp_avg.shard_batch(*batch))
    assert not np.allclose(np.asarray(local), np.asarray(local)[0])  # ranks differ
    np.testing.assert_allclose(np.asarray(avg), np.mean(np.asarray(local)), rtol=1e-6)


def test_dp_validates_axis(mesh8):
    model, tx, _ = setup(use_bn=False)
    with pytest.raises(ValueError, match="axis"):
        DataParallel(model, tx, mesh8, axis="model")


def test_sharded_loader_reproduces_rank_shards():
    images, labels = synthetic_mnist(n=64, seed=0)
    loader = ShardedBatchLoader(images, labels, batch_size=4, num_replicas=8)
    batch_i, batch_l = next(iter(loader))
    assert batch_i.shape == (32, 28, 28)
    # device r's slice must equal what rank r's own sampler yields
    from tpu_sandbox.data import DistributedSampler

    for r in [0, 3, 7]:
        idx = DistributedSampler(64, 8, r).indices(0)[:4]
        np.testing.assert_array_equal(batch_l[r * 4 : (r + 1) * 4], labels[idx])


def test_sharded_loader_epochs_and_len():
    images, labels = synthetic_mnist(n=30, seed=0)
    loader = ShardedBatchLoader(images, labels, batch_size=4, num_replicas=4)
    # ceil(30/4)=8 per rank -> ceil(8/4)=2 steps
    assert len(loader) == 2
    steps = list(loader)
    assert steps[0][0].shape[0] == 16
    assert steps[1][0].shape[0] == 16  # padded equal shards even at the tail


@pytest.mark.usefixtures("light_compile")
def test_dp_training_loss_decreases(mesh8):
    from tpu_sandbox.train import Trainer

    model, tx, state = setup(use_bn=True)
    dp = DataParallel(model, tx, mesh8)
    images, labels = synthetic_mnist(n=128, seed=0)
    loader = ShardedBatchLoader(
        normalize(images), labels.astype("int32"), batch_size=2, num_replicas=8
    )

    def step(s, i, l):
        return dp.train_step(s, *dp.shard_batch(i, l))

    trainer = Trainer(step, log_every=1, verbose=False)
    final = trainer.fit(dp.shard_state(state), loader, epochs=4)
    assert np.mean(trainer.losses[-4:]) < np.mean(trainer.losses[:4]) * 0.9
    assert int(final.step) == 4 * len(loader)


@pytest.mark.usefixtures("light_compile")
def test_zero1_matches_plain_dp(mesh8):
    """ZeRO-1 (sharded optimizer state) is the same math as plain DP: with
    AdamW (stateful, elementwise) the losses and final params agree to
    float tolerance over several steps, while the big dim-0-divisible
    optimizer moments actually live sharded across the axis."""
    model = ConvNet(use_bn=False)
    tx = optax.adamw(1e-3)
    state0 = TrainState.create(
        model, jax.random.key(0), jnp.zeros((1, 28, 28, 1)), tx
    )
    images, labels = synthetic_mnist(n=16, seed=0)
    images, labels = normalize(images), labels.astype("int32")

    def run(zero):
        dp = DataParallel(model, tx, mesh8, zero=zero, donate=False)
        st = dp.shard_state(state0)
        losses = []
        for _ in range(3):
            st, loss = dp.train_step(st, *dp.shard_batch(images, labels))
            losses.append(np.asarray(loss))
        return st, losses

    st_plain, losses_plain = run(zero=False)
    st_zero, losses_zero = run(zero=True)
    np.testing.assert_allclose(
        np.stack(losses_zero), np.stack(losses_plain), rtol=1e-5
    )
    for (kp, p), (_, z) in zip(
        jax.tree_util.tree_leaves_with_path(st_plain.params),
        jax.tree_util.tree_leaves_with_path(st_zero.params),
    ):
        np.testing.assert_allclose(
            np.asarray(z), np.asarray(p), atol=1e-6,
            err_msg=jax.tree_util.keystr(kp),
        )

    # the fc kernel's Adam moments (dim0 = flattened features, divisible by
    # 8) must be sharded over the data axis; conv kernels (dim0=5) must not
    mu = st_zero.opt_state[0].mu
    fc_mu = mu["fc"]["kernel"]
    conv_mu = mu["conv1"]["kernel"]
    fc_spec = fc_mu.sharding.spec
    assert fc_spec and fc_spec[0] == "data", fc_spec
    conv_spec = conv_mu.sharding.spec
    assert not conv_spec or conv_spec[0] is None, conv_spec


@pytest.mark.usefixtures("light_compile")
def test_dp_s2dt_fused_input_matches_plain_resize(mesh8):
    """The full r04 production input path under DataParallel — raw 28x28
    batch -> fused resize+s2d -> ConvNetS2DT (sparse-tap conv1, fused
    tails) — computes the same step as the plain ConvNet with
    resize_on_device, on an 8-shard mesh (fp32, 64x64 target)."""
    import optax

    from tpu_sandbox.models import ConvNet
    from tpu_sandbox.models.convnet_s2d_t import ConvNetS2DT
    from tpu_sandbox.train import TrainState

    tx = optax.sgd(1e-2)
    plain = ConvNet(dtype=jnp.float32)
    s2dt = ConvNetS2DT(dtype=jnp.float32, fused_tail=True)
    state = TrainState.create(
        plain, jax.random.key(0), jnp.zeros((1, 64, 64, 1)), tx)

    images, labels = synthetic_mnist(n=16, seed=3)
    images, labels = normalize(images), labels.astype("int32")

    results = {}
    for name, model in (("plain", plain), ("s2dt", s2dt)):
        dp = DataParallel(model, tx, mesh8, donate=False,
                          image_size=(64, 64))
        dstate = dp.shard_state(state)
        di, dl = dp.shard_batch(images, labels)
        new_state, losses = dp.train_step(dstate, di, dl)
        results[name] = (float(jnp.mean(losses)), new_state.params)

    np.testing.assert_allclose(results["s2dt"][0], results["plain"][0],
                               rtol=1e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5),
        results["s2dt"][1], results["plain"][1],
    )


def test_shard_state_local_refuses_single_controller(mesh8):
    """The partial-restore placement is only sound when each process owns
    exactly its own mesh slot; a single-controller 8-device process must
    be pushed to the full restore + shard_state path."""
    model, tx, state = setup(use_bn=True)
    dp = DataParallel(model, tx, mesh8, donate=False)
    with pytest.raises(ValueError, match="one process per mesh slot"):
        dp.shard_state_local(state, state)


def test_shard_state_local_places_rank_blocks(mesh8, monkeypatch):
    """Single-controller simulation of the multi-controller contract:
    with process_count==world and one local device, restore_partial's
    rank-local view (rep leaves global, shard0 leaves this rank's block)
    lands on the mesh with the same specs, shapes, and dtypes the full
    shard_state path produces — and the block itself bitwise."""
    model = ConvNet(use_bn=True)
    tx = optax.sgd(0.05, momentum=0.9)  # momentum: ZeRO-eligible opt state
    state = TrainState.create(
        model, jax.random.key(0), jnp.zeros((1, 28, 28, 1)), tx)
    dp = DataParallel(model, tx, mesh8, donate=False, zero=True)
    full = dp.shard_state(state)  # reference placement
    # rank 0's restore_partial view: device 0's addressable shard of every
    # leaf (full array for replicated leaves, the rank-0 block for sharded)
    local = jax.tree.map(
        lambda x: np.asarray(x.addressable_shards[0].data), full)

    monkeypatch.setattr(jax, "process_count", lambda: 8)
    monkeypatch.setattr(jax, "local_device_count", lambda: 1)
    placed = dp.shard_state_local(local, dp.checkpoint_template(state))

    def check(p, f):
        assert p.shape == f.shape and p.dtype == f.dtype
        assert p.sharding == f.sharding
        # device 0 holds rank 0's block (the only shard this simulated
        # process is authoritative for) — bitwise what the view held
        np.testing.assert_array_equal(
            np.asarray(p.addressable_shards[0].data),
            np.asarray(f.addressable_shards[0].data))
    jax.tree.map(check, placed, full)

    # a wrong-shaped block fails loudly instead of silently misplacing
    bad = local.replace(
        opt_state=jax.tree.map(
            lambda x: x[:1] if x.ndim >= 1 and x.shape[0] > 1 else x,
            local.opt_state))
    with pytest.raises(ValueError, match="local block|replicated leaf"):
        dp.shard_state_local(bad, dp.checkpoint_template(state))
