"""Aux subsystems: checkpoint round-trip + resume, metrics writer,
step timer, and the Pallas fused CE kernel (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpu_sandbox.models import ConvNet
from tpu_sandbox.train import TrainState, make_train_step
from tpu_sandbox.train import checkpoint as ckpt
from tpu_sandbox.utils.metrics import MetricsWriter, read_metrics


def small_state(lr=0.05):
    model = ConvNet()
    tx = optax.sgd(lr)
    state = TrainState.create(model, jax.random.key(0), jnp.zeros((1, 28, 28, 1)), tx)
    return model, tx, state


def test_checkpoint_roundtrip(tmp_path):
    model, tx, state = small_state()
    step_fn = make_train_step(model, tx, donate=False)
    from tpu_sandbox.data import synthetic_mnist
    from tpu_sandbox.data.mnist import normalize

    images, labels = synthetic_mnist(n=8)
    state, _ = step_fn(state, jnp.asarray(normalize(images)), jnp.asarray(labels.astype("int32")))

    saved_step = ckpt.save(tmp_path / "ck", state)
    assert saved_step == 1
    assert ckpt.latest_step(tmp_path / "ck") == 1

    _, _, template = small_state()
    restored = ckpt.restore(tmp_path / "ck", template)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        state, restored,
    )
    # resume: training continues from the restored state identically
    s1, l1 = step_fn(state, jnp.asarray(normalize(images)), jnp.asarray(labels.astype("int32")))
    s2, l2 = step_fn(restored, jnp.asarray(normalize(images)), jnp.asarray(labels.astype("int32")))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-7)


def test_checkpoint_restore_missing_raises(tmp_path):
    _, _, template = small_state()
    with pytest.raises(FileNotFoundError):
        ckpt.restore(tmp_path / "empty", template)


def test_metrics_writer_roundtrip(tmp_path):
    path = tmp_path / "m.jsonl"
    with MetricsWriter(path) as w:
        w.write(1, loss=1.5, note="a")
        w.write(2, loss=jnp.asarray(0.75))
    records = read_metrics(path)
    assert [r["step"] for r in records] == [1, 2]
    assert records[1]["loss"] == 0.75


def test_pallas_ce_matches_reference():
    from tpu_sandbox.ops.losses import cross_entropy_loss
    from tpu_sandbox.ops.pallas_ce import pallas_cross_entropy

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(37, 10)).astype(np.float32)) * 3
    labels = jnp.asarray(rng.integers(0, 10, size=37).astype(np.int32))
    ref = cross_entropy_loss(logits, labels)
    got = pallas_cross_entropy(logits, labels)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)


def test_pallas_ce_gradient_matches():
    from tpu_sandbox.ops.losses import cross_entropy_loss
    from tpu_sandbox.ops.pallas_ce import pallas_cross_entropy

    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(16, 64)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 64, size=16).astype(np.int32))
    g_ref = jax.grad(lambda l: cross_entropy_loss(l, labels))(logits)
    g_got = jax.grad(lambda l: pallas_cross_entropy(l, labels))(logits)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), atol=1e-6)


def test_pallas_ce_large_vocab_block_grid():
    from tpu_sandbox.ops.losses import cross_entropy_loss
    from tpu_sandbox.ops.pallas_ce import pallas_cross_entropy

    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(300, 257)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 257, size=300).astype(np.int32))
    np.testing.assert_allclose(
        float(pallas_cross_entropy(logits, labels)),
        float(cross_entropy_loss(logits, labels)),
        rtol=1e-6,
    )


def test_pallas_ce_huge_vocab_falls_back_to_jnp():
    """Beyond ~128k vocab no row block fits the VMEM budget; the call must
    fall back to the jnp loss with identical value and gradient."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_sandbox.ops.losses import cross_entropy_loss
    from tpu_sandbox.ops.pallas_ce import _block_rows, pallas_cross_entropy

    assert _block_rows(512 * 1024) is None
    assert _block_rows(32768) == 32
    assert _block_rows(1024) == 128
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(8, 200000)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 200000, size=(8,)), jnp.int32)
    v, g = jax.value_and_grad(pallas_cross_entropy)(logits, labels)
    v_ref, g_ref = jax.value_and_grad(cross_entropy_loss)(logits, labels)
    np.testing.assert_allclose(float(v), float(v_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-7)


def test_ensure_devices_never_falls_back_to_cpu_unasked():
    """Real devices or an error naming --force-cpu (utils/cli.py): asking
    for more ranks than the backend has must not quietly hand back virtual
    CPU devices. Under this harness the backend IS an 8-device CPU client,
    so 'real' means those eight."""
    from tpu_sandbox.utils.cli import ensure_devices

    assert ensure_devices(8) == jax.devices()
    with pytest.raises(RuntimeError, match="--force-cpu"):
        ensure_devices(9)
    with pytest.raises(ValueError):
        ensure_devices(0)
    # asked for explicitly, virtual CPU ranks are fine — up to the size the
    # already-initialized CPU client has
    assert ensure_devices(4, force_cpu=True) == jax.devices("cpu")[:4]
    with pytest.raises(RuntimeError, match="already holds 8"):
        ensure_devices(9, force_cpu=True)


def test_measure_per_step_repeated_min_and_spread():
    """Repeat protocol (VERDICT r03 next-7): min published with per-sample
    spread; any noise-negative repeat voids the spread claim and is
    counted, never averaged in."""
    from tpu_sandbox.utils.profiling import measure_per_step_repeated

    times = iter([0.040, 0.050, 0.045])
    import tpu_sandbox.utils.profiling as prof

    def fake(run_steps, n):
        return {"sec_per_step": next(times), "t_n_sec": 0.0,
                "t_2n_sec": 0.0, "n": n, "timing_method": "fake"}

    orig = prof.measure_per_step
    prof.measure_per_step = fake
    try:
        out = measure_per_step_repeated(lambda k: None, 2, repeats=3)
        assert out["sec_per_step"] == 0.040
        assert out["spread_frac"] == 0.25
        assert "nonpositive_samples" not in out

        times = iter([-0.001, 0.040, -0.002])
        out = measure_per_step_repeated(lambda k: None, 2, repeats=3)
        assert out["sec_per_step"] == 0.040
        assert out["spread_frac"] is None  # one sample is NOT repeatability
        assert out["nonpositive_samples"] == 2
    finally:
        prof.measure_per_step = orig


def test_hlo_traffic_classify_tags():
    """tools/hlo_traffic.py classify: the r04 input-stage class, conv
    fwd/bwd provenance, the pallas fallback, and the no-provenance copy
    bucket (the attribution the round-4 step surgery was driven by)."""
    import importlib
    import sys

    sys.path.insert(0, "tools")
    ht = importlib.import_module("hlo_traffic")

    def line(op_name, extra=""):
        return (f'  %x = bf16[1] fusion(%a), {extra}'
                f'metadata={{{{op_name="jit(train_step)/{op_name}"}}}}')

    assert ht.classify(
        "fusion", line("jvp(ConvNetS2DT.fused_input_stage)/dot"), 0
    ) == "input-stage-fwd"
    assert ht.classify(
        "fusion", line("jvp(M)/conv1/conv"), 1 << 30
    ) == "conv1-fwd"
    assert ht.classify(
        "fusion", line("transpose(jvp(M))/conv2/conv"), 1 << 30
    ) == "conv2-dgrad"
    assert ht.classify(
        "fusion", line("transpose(jvp(M))/conv2/conv"), 1 << 20
    ) == "conv2-wgrad"
    assert ht.classify(
        "custom-call",
        line("jvp(M)/M._tail/bn9x/pallas_call",
             extra="tpu_custom_call "),
        0,
    ) == "pallas-kernel"
    assert ht.classify("copy", "  %c = bf16[1] copy(%a)", 0) \
        == "copy/transpose(no-provenance)"
