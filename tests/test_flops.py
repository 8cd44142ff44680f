"""FLOP model / MFU accounting tests (utils/flops.py): the plausibility
cross-check must itself be correct."""

import pytest

from tpu_sandbox.utils.flops import (
    ConvNetFlops,
    conv2d_flops,
    convnet_flops,
    device_peak_tflops,
    mfu,
    transformer_flops,
)


def test_conv2d_flops_analytic():
    # 2 * H*W * C_out * k² * C_in
    assert conv2d_flops(10, 10, 3, 8, 5) == 2 * 100 * 8 * 25 * 3


def test_convnet_flops_at_3000_matches_verdict_analysis():
    """VERDICT r01 weak #1 derived conv1 ≈ 7.2, conv2 ≈ 57.6, fc ≈ 0.36
    GFLOP/img forward — the model must reproduce that analysis."""
    f = convnet_flops(3000)
    assert f.conv1 == pytest.approx(7.2e9)
    assert f.conv2 == pytest.approx(57.6e9)
    assert f.fc == pytest.approx(0.36e9)
    assert f.forward == pytest.approx(65.16e9)
    # training: 3x forward minus conv1's never-formed input gradient
    assert f.train == pytest.approx(3 * 65.16e9 - 7.2e9)


def test_convnet_flops_agrees_with_xla_cost_analysis():
    """The independent cross-check: XLA's own
    HLO FLOP count for one train step vs the analytic model (XLA also
    counts the resize/BN arithmetic, so it sits slightly above)."""
    import jax
    import jax.numpy as jnp
    import optax

    from tpu_sandbox.models import ConvNet
    from tpu_sandbox.train import TrainState, make_train_step

    size, bs = 64, 2
    model = ConvNet()
    tx = optax.sgd(1e-4)
    state = TrainState.create(
        model, jax.random.key(0), jnp.zeros((1, size, size, 1)), tx
    )
    step = make_train_step(model, tx, donate=False)
    lowered = jax.jit(step).lower(
        state, jnp.zeros((bs, size, size, 1)), jnp.zeros((bs,), jnp.int32)
    )
    cost = lowered.compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    if not cost or "flops" not in cost:
        pytest.skip("backend exposes no cost analysis")
    model_flops = convnet_flops(size).train * bs
    ratio = float(cost["flops"]) / model_flops
    assert 0.95 < ratio < 1.25, (cost["flops"], model_flops)


def test_peak_table_and_mfu_verdicts():
    assert device_peak_tflops("TPU v5 lite") == 197.0
    assert device_peak_tflops("TPU v4") == 275.0
    # exact kinds only: no published peak is an error on a measuring path,
    # never a default — and "TPU v5" is jax's name for a v5p, not a v5e
    for unknown in ("cpu", "TPU v5", "TPU v5 lite (simulated)"):
        with pytest.raises(ValueError, match="no published bf16 peak"):
            device_peak_tflops(unknown)

    # a sane measurement: 1 TFLOP in 10 ms on a v5e -> 100 TFLOP/s, ~51%
    r = mfu(1e12, 0.010, "TPU v5 lite")
    assert r["achieved_tflops"] == pytest.approx(100.0)
    assert r["mfu"] == pytest.approx(100 / 197, rel=1e-3)
    assert r["plausible"]

    # the r01 failure mode: 2 PFLOP/s claimed on one v5e -> flagged
    r = mfu(1e12, 0.0005, "TPU v5 lite")
    assert r["mfu"] > 1 and not r["plausible"]

    # unknown chip: no utilization at all
    with pytest.raises(ValueError, match="no published bf16 peak"):
        mfu(1e12, 0.010, "cpu")

    # multi-chip peak scales
    r = mfu(1e12, 0.010, "TPU v5 lite", n_devices=4)
    assert r["peak_tflops_bf16"] == pytest.approx(4 * 197.0)


def test_transformer_flops_shape():
    f = transformer_flops(n_layers=2, d_model=64, d_ff=256, seq=128, vocab=100)
    per_layer = 2 * 4 * 64 * 64 + 2 * 2 * 64 * 256 + 2 * 2 * 128 * 64
    assert f["forward"] == pytest.approx(2 * per_layer + 2 * 64 * 100)
    assert f["train"] == pytest.approx(3 * f["forward"])


def test_convnet_flops_dataclass_is_frozen():
    f = convnet_flops(100)
    assert isinstance(f, ConvNetFlops)
    with pytest.raises(Exception):
        f.conv1 = 0.0


def test_s2d_custom_call_flops_counts_pallas_calls_only():
    """VERDICT r03 next-8: the composed FLOP cross-check counts Pallas
    custom calls by kernel class from optimized HLO and must IGNORE plain
    XLA gathers/scatters under the same module paths."""
    from tpu_sandbox.utils.flops import s2d_custom_call_flops

    hlo = "\n".join([
        '  %conv1.2 = bf16[1] custom-call(%a), metadata={op_name='
        '"jit(s)/jvp(M)/conv1/pallas_call"}',
        '  %conv2.4 = bf16[1] custom-call(%a), metadata={op_name='
        '"jit(s)/transpose(jvp(M))/conv2/pallas_call"}',
        '  %bn1.fused.3 = bf16[1] custom-call(%a), metadata={op_name='
        '"jit(s)/jvp(M)/M._tail/bn1.fused/pallas_call"}',
        # must NOT count: an XLA gather under the conv1 path
        '  %gather.8 = bf16[1] gather(%a), metadata={op_name='
        '"jit(s)/jvp(M)/conv1/gather"}',
        # must NOT count: a non-pallas custom call
        '  %custom-call.5 = bf16[1] custom-call(%a), metadata={op_name='
        '"jit(s)/jvp(jit(take_along_axis))/gather"}',
    ])
    base = 2.0 * 16 * 750 * 750
    # transposed plan: conv1 is the sparse-tap union-tile kernel (K=64)
    c = s2d_custom_call_flops(hlo, 16, 3000, plan="ConvNetS2DT")
    assert c["custom_calls_counted"] == 3
    assert c["unmatched_pallas_calls"] == 0
    assert c["per_class"]["conv1"] == base * 64 * 256
    assert c["per_class"]["conv2"] == base * 9 * 64 * 128
    assert c["per_class"]["bn1.fused"] == base * 256 * 64
    # NHWC s2d plan: conv1 is the scattered 3x3 (K=9*16)
    c2 = s2d_custom_call_flops(hlo, 16, 3000, plan="ConvNetS2D")
    assert c2["per_class"]["conv1"] == base * 9 * 16 * 256
    # ADVICE r04 medium: the EXECUTED kernel choice overrides the class
    # name — ConvNetS2DT running the scattered-3x3 conv1 (the sweep's
    # s2dt_scat_conv1 A/B row) must count K=9*16, not the sparse K=64
    c3 = s2d_custom_call_flops(hlo, 16, 3000, plan="ConvNetS2DT",
                               sparse_conv1=False)
    assert c3["per_class"]["conv1"] == base * 9 * 16 * 256


def test_s2d_custom_call_flops_knows_every_kernel_of_the_production_step():
    """The step's 12 Pallas calls by op_name, as the v5e compile prints
    them: the conv1+tail composite (2 forward, 2 backward), conv2 (fwd,
    dgrad, wgrad), the bn2 tail (fwd, reduce, apply), the fc input-grad
    and — since PR 24 — the fc forward's flatten, a copy that counts no
    flops. None may be 'unmatched', or the composed cross-check is
    withheld."""
    from tpu_sandbox.utils.flops import s2d_custom_call_flops

    def call(path):
        return (f'  %k = bf16[1] custom-call(%a), custom_call_target='
                f'"tpu_custom_call", metadata={{op_name="jit(train_step)/'
                f'{path}/pallas_call"}}')

    fwd, bwd = "jvp(ConvNetS2DT)", "transpose(jvp(ConvNetS2DT))"
    hlo = "\n".join(
        [call(f"{fwd}/bn1.fused_conv1")] * 2 + [call(f"{fwd}/conv2")]
        + [call(f"{fwd}/ConvNetS2DT._tail/bn2.fused")]
        + [call(f"{fwd}/fc"), call(f"{bwd}/fc")]
        + [call(f"{bwd}/ConvNetS2DT._tail/bn2.fused")] * 2
        + [call(f"{bwd}/conv2")] * 2 + [call(f"{bwd}/bn1.fused_conv1")] * 2
    )
    c = s2d_custom_call_flops(hlo, 5, 3000, plan="ConvNetS2DT",
                              sparse_conv1=True)
    assert c["custom_calls_counted"] == 12
    assert c["unmatched_pallas_calls"] == 0
    base = 2.0 * 5 * 750 * 750
    # composite: conv + tail forward, reduce pass, then ONE backward
    # kernel running both the selection matmul and the conv1 wgrad dot
    assert c["per_class"]["bn1.fused_conv1"] == base * 64 * 256 * 5
    assert c["per_class"]["fc"] == base * 10 * 32
    assert c["per_class"]["conv2"] == base * 9 * 64 * 128 * 3
    assert c["per_class"]["bn2.fused"] == base * 128 * 32 * 3


def test_model_runs_sparse_conv1_tracks_field_and_env(monkeypatch):
    """The cross-check keys on the executed conv1 kernel: the model's
    sparse_conv1 field AND the TPU_SANDBOX_NO_SPARSE_CONV1 kill switch
    (ADVICE r04 medium)."""
    from tpu_sandbox.models.convnet_s2d_t import ConvNetS2DT
    from tpu_sandbox.utils.flops import model_runs_sparse_conv1

    monkeypatch.delenv("TPU_SANDBOX_NO_SPARSE_CONV1", raising=False)
    assert model_runs_sparse_conv1(ConvNetS2DT())
    assert not model_runs_sparse_conv1(ConvNetS2DT(sparse_conv1=False))
    monkeypatch.setenv("TPU_SANDBOX_NO_SPARSE_CONV1", "1")
    assert not model_runs_sparse_conv1(ConvNetS2DT())

    class NotS2DT:
        sparse_conv1 = True

    monkeypatch.delenv("TPU_SANDBOX_NO_SPARSE_CONV1", raising=False)
    assert not model_runs_sparse_conv1(NotS2DT())
