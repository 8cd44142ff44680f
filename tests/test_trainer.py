"""Trainer tests: the end-to-end single-device slice at toy scale —
loss decreases on learnable synthetic data, log-format parity, on-device
resize path, eval step."""

import re

import jax.numpy as jnp
import jax.random
import numpy as np
import optax
import pytest

from tpu_sandbox.data import BatchLoader, synthetic_mnist
from tpu_sandbox.data.mnist import normalize
from tpu_sandbox.models import ConvNet
from tpu_sandbox.train import Trainer, TrainState, make_train_step
from tpu_sandbox.train.trainer import make_eval_step

# every claim here is a tolerance: conftest's cheaper compile
pytestmark = pytest.mark.usefixtures("light_compile")


def make_setup(image_size=None, lr=0.05, n=128):
    model = ConvNet()
    tx = optax.sgd(lr)
    shape = (1, *(image_size or (28, 28)), 1)
    state = TrainState.create(model, jax.random.key(0), jnp.zeros(shape), tx)
    step = make_train_step(model, tx, image_size=image_size)
    images, labels = synthetic_mnist(n=n, seed=0)
    loader = BatchLoader(normalize(images), labels.astype("int32"), 16, shuffle=True)
    return model, state, step, loader


def test_loss_decreases_on_synthetic():
    _, state, step, loader = make_setup()
    trainer = Trainer(step, log_every=1, verbose=False)
    state = trainer.fit(state, loader, epochs=6)
    first = np.mean(trainer.losses[:4])
    last = np.mean(trainer.losses[-4:])
    assert last < first * 0.8, (first, last)
    assert int(state.step) == 6 * len(loader)


def test_log_format_matches_reference(capsys):
    _, state, step, loader = make_setup(n=32)
    Trainer(step, log_every=1).fit(state, loader, epochs=1)
    out = capsys.readouterr().out
    # reference mnist_onegpu.py:76 format
    assert re.search(r"Epoch \[1/1\], Step \[1/2\], Loss: \d+\.\d{4}", out)
    assert "Training complete in: " in out


def test_ddp_log_format(capsys):
    _, state, step, loader = make_setup(n=32)
    Trainer(step, log_every=1, log_rank=0).fit(state, loader, epochs=1)
    out = capsys.readouterr().out
    # reference mnist_distributed.py:105 format
    assert re.search(r"Rank \[0\], Epoch \[1/1\], Step \[1/2\], Loss: \d+\.\d{4}", out)


def test_on_device_resize_path():
    # feed 28x28, train at 64x64: the resize lives inside the jit'd step
    _, state, step, loader = make_setup(image_size=(64, 64), n=32)
    images, labels = next(iter(loader))
    new_state, loss = step(state, images, labels)
    assert np.isfinite(float(loss))
    assert int(new_state.step) == 1


def test_batch_stats_evolve_and_params_change():
    _, state, step, loader = make_setup(n=32)
    images, labels = next(iter(loader))
    # copy before stepping: the step donates its input state buffers
    old_kernel = np.asarray(state.params["conv1"]["kernel"]).copy()
    new_state, _ = step(state, jnp.asarray(images), jnp.asarray(labels))
    assert not np.allclose(np.asarray(new_state.params["conv1"]["kernel"]),
                           old_kernel)
    assert not np.allclose(np.asarray(new_state.batch_stats["bn1"]["mean"]), 0.0)


def test_eval_step_counts_correct():
    model, state, step, loader = make_setup()
    state = Trainer(step, verbose=False).fit(state, loader, epochs=6)
    eval_step = make_eval_step(model)
    images, labels = synthetic_mnist(n=64, seed=3)
    correct, loss = eval_step(state, normalize(images), labels.astype("int32"))
    assert float(correct) / 64 > 0.5  # learnable prototypes: well above chance
    assert np.isfinite(float(loss))


def test_grad_accumulation_matches_full_batch():
    """Without BN, k accumulated microbatches == one full-batch step exactly
    (mean CE is the mean of equal-size microbatch means; SGD is linear)."""
    model = ConvNet(use_bn=False)
    tx = optax.sgd(1e-2)
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.standard_normal((8, 32, 32, 1), dtype=np.float32))
    labels = jnp.asarray(rng.integers(0, 10, size=8), jnp.int32)

    state0 = TrainState.create(model, jax.random.key(0), jnp.zeros((1, 32, 32, 1)), tx)
    full = make_train_step(model, tx, donate=False)
    acc = make_train_step(model, tx, accum_steps=4, donate=False)

    s_full, loss_full = full(state0, images, labels)
    s_acc, loss_acc = acc(state0, images, labels)
    np.testing.assert_allclose(float(loss_full), float(loss_acc), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6
        ),
        s_full.params, s_acc.params,
    )


def test_grad_accumulation_with_bn_trains():
    """With BN the two are intentionally NOT identical (per-microbatch
    statistics, torch semantics); just check training progresses."""
    model = ConvNet()
    tx = optax.sgd(1e-2)
    images, labels = synthetic_mnist(n=16, seed=0)
    images = jnp.asarray(normalize(images))
    labels = jnp.asarray(labels.astype("int32"))
    state = TrainState.create(model, jax.random.key(0), jnp.zeros((1, 28, 28, 1)), tx)
    step = make_train_step(model, tx, accum_steps=2, donate=False)
    losses = []
    for _ in range(8):
        state, loss = step(state, images, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_periodic_checkpointing(tmp_path):
    from tpu_sandbox.train import checkpoint as ckpt

    model, state, step_fn, loader = make_setup(n=64)
    trainer = Trainer(step_fn, log_every=100, verbose=False,
                      ckpt_dir=str(tmp_path), ckpt_every=3)
    trainer.fit(state, loader, epochs=1)  # 64/16 = 4 steps -> save at 3
    assert ckpt.latest_step(tmp_path) == 3
    restored = ckpt.restore(tmp_path, state)
    assert int(restored.step) == 3


def test_remat_step_matches_plain_step():
    """make_train_step(remat=True) — the capacity lever — must be a pure
    memory/compute trade: identical loss, updated params, and BN stats to
    the plain step from the same state."""
    import jax

    model = ConvNet()
    tx = optax.sgd(1e-2)
    images, labels = synthetic_mnist(n=8, seed=3)
    images, labels = normalize(images), labels.astype("int32")
    state0 = TrainState.create(
        model, jax.random.key(0), jnp.zeros((1, 32, 32, 1)), tx
    )

    def run(remat):
        step = make_train_step(model, tx, image_size=(32, 32),
                               donate=False, remat=remat)
        return step(state0, jnp.asarray(images), jnp.asarray(labels))

    (sp, lp), (sr, lr) = run(False), run(True)
    np.testing.assert_allclose(float(lr), float(lp), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        ),
        (sr.params, sr.batch_stats), (sp.params, sp.batch_stats),
    )
