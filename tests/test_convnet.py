"""ConvNet parity tests — shapes, lazy head sizing, and a numerical
cross-check against a torch replica of the reference architecture
(torch-cpu is in the image; the reference model is mnist_onegpu.py:11-31)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sandbox.models import ConvNet
from tpu_sandbox.ops import cross_entropy_loss

# every claim here is a tolerance: conftest's cheaper compile
pytestmark = pytest.mark.usefixtures("light_compile")


def init_model(h=32, w=32):
    model = ConvNet()
    variables = model.init(jax.random.key(0), jnp.zeros((1, h, w, 1)), train=False)
    return model, variables


def test_forward_shapes_and_lazy_head():
    model, variables = init_model(32, 32)
    # 32x32 -> pool -> 16 -> pool -> 8; flatten = 32*8*8 = 2048
    assert variables["params"]["fc"]["kernel"].shape == (2048, 10)
    logits = model.apply(variables, jnp.ones((3, 32, 32, 1)), train=False)
    assert logits.shape == (3, 10)
    assert logits.dtype == jnp.float32

    # lazy semantics: a different input size gives a different head
    _, v2 = init_model(64, 64)
    assert v2["params"]["fc"]["kernel"].shape == (32 * 16 * 16, 10)


def test_param_count_matches_reference_at_3000():
    # At 3000x3000 the head must be 18M x 10 (SURVEY §2.1 C11).
    model = ConvNet()
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 3000, 3000, 1)), train=False)
    )
    assert shapes["params"]["fc"]["kernel"].shape == (32 * 750 * 750, 10)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(shapes["params"]))
    assert n_params > 180_000_000  # the ~180M-param OOM-demo matmul


def test_batch_stats_update_in_train_mode():
    model, variables = init_model()
    x = jax.random.normal(jax.random.key(1), (4, 32, 32, 1)) * 3 + 1
    _, mutated = model.apply(
        variables, x, train=True, mutable=["batch_stats"]
    )
    new_mean = mutated["batch_stats"]["bn1"]["mean"]
    assert not np.allclose(np.asarray(new_mean), 0.0)  # moved toward batch mean


def test_cross_entropy_matches_analytic():
    logits = jnp.log(jnp.array([[0.7, 0.2, 0.1], [0.1, 0.8, 0.1]]))
    labels = jnp.array([0, 1])
    expected = -(np.log(0.7) + np.log(0.8)) / 2
    np.testing.assert_allclose(float(cross_entropy_loss(logits, labels)), expected, rtol=1e-6)


def test_numerical_parity_with_torch_reference():
    """Copy weights into a torch replica of the reference stack and compare
    eval-mode forward outputs — verifies conv padding, BN eps, pool, and
    flatten-order semantics match the architecture the reference trains."""
    torch = pytest.importorskip("torch")
    from tpu_sandbox.utils.parity import torch_twin

    model, variables = init_model(16, 16)
    tm = torch_twin(torch, variables["params"], hw=4).eval()

    x = np.random.default_rng(0).normal(size=(2, 16, 16, 1)).astype(np.float32)
    jax_out = np.asarray(model.apply(variables, jnp.asarray(x), train=False))
    with torch.no_grad():
        torch_out = tm(torch.from_numpy(x.transpose(0, 3, 1, 2))).numpy()
    np.testing.assert_allclose(jax_out, torch_out, atol=1e-4)


def test_training_loss_curve_parity_with_torch():
    """SURVEY §7 hard-part 3: same init, same data, same SGD — the per-step
    *training* losses must track the torch reference step for step (train
    mode exercises conv/BN/pool/matmul gradients and the BN batch-stat
    path; SGD(lr, no momentum) is linear so drift would compound and show)."""
    torch = pytest.importorskip("torch")
    import optax

    from tpu_sandbox.train import TrainState, make_train_step
    from tpu_sandbox.utils.parity import torch_twin

    lr, steps, bs = 0.05, 8, 8
    model, variables = init_model(16, 16)
    tm = torch_twin(torch, variables["params"], hw=4)

    rng = np.random.default_rng(42)
    batches = [
        (rng.normal(size=(bs, 16, 16, 1)).astype(np.float32),
         rng.integers(0, 10, size=bs).astype(np.int64))
        for _ in range(steps)
    ]

    tx = optax.sgd(lr)
    state = TrainState.create(model, jax.random.key(0), jnp.zeros((1, 16, 16, 1)), tx)
    state = state.replace(params=variables["params"],
                          batch_stats=variables["batch_stats"])
    step = make_train_step(model, tx, donate=False)
    jax_losses = []
    for x, y in batches:
        state, loss = step(state, jnp.asarray(x), jnp.asarray(y.astype(np.int32)))
        jax_losses.append(float(loss))

    tm.train()
    opt = torch.optim.SGD(tm.parameters(), lr=lr)
    crit = torch.nn.CrossEntropyLoss()
    torch_losses = []
    for x, y in batches:
        opt.zero_grad()
        out = tm(torch.from_numpy(x.transpose(0, 3, 1, 2)))
        loss = crit(out, torch.from_numpy(y))
        loss.backward()
        opt.step()
        torch_losses.append(float(loss))

    np.testing.assert_allclose(jax_losses, torch_losses, rtol=2e-3, atol=2e-3)
