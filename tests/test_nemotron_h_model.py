"""models/nemotron_h.py and ops/ssd.py at a small size on the CPU (hidden
64, 4 Mamba heads in 2 groups, 4 query heads on 2 key/value heads, 16
experts in a latent of 32, seeded random weights): the chunked scan against
the token-by-token recurrence, the float32 parts against the plain float32
reference of the benchmark, and the published pattern. The model against
the reference is tests/test_lm_models.py, the parts that all shares of a
deployment give against the uncut layer tests/test_shares_add_up.py,
``lm_train.build`` tests/test_lm_train.py."""

import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import nemotron_h as ref  # noqa: E402
from tpu_sandbox.models import nemotron_h as nh  # noqa: E402
from tpu_sandbox.ops.ssd import ssd_scan  # noqa: E402

#: the catalog's pattern of NVIDIA-Nemotron-3-Super-120B-A12B (88 blocks)
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 4,
    "hybrid_override_pattern": "EM*E", "layer_norm_epsilon": 1e-5,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "routed_scaling_factor": 5,
    "num_nextn_predict_layers": 1, "mtp_hybrid_override_pattern": "*E",
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu", "n_group": 1,
    "topk_group": 1, "use_conv_bias": True,
    "deployment": {"held": [0, 1, 2, 3], "local_rows_factor": 2},
}
B, S = 2, 16

pytestmark = pytest.mark.usefixtures("light_compile")


def tiny(**over):
    return {**TINY, **over}



# --- the chunked scan against the recurrence ---

def scan_inputs(s, h, g, p=8, n=16, b=2, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(ks[0], (b, s, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0),
            -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.7)),
            jax.random.normal(ks[3], (b, s, g, n)),
            jax.random.normal(ks[4], (b, s, g, n)),
            jax.random.normal(ks[5], (b, s, h, p)))


@pytest.mark.parametrize("s,q,h,g", [
    (16, 16, 4, 2),      # one chunk: no state crosses a boundary
    (256, 4, 2, 2),      # S = 64 Q: the chunk recurrence does the work
    (64, 8, 4, 1),       # every head on one group
    (32, 8, 8, 4)])
def test_chunked_scan_is_the_recurrence_forward_and_backward(s, q, h, g):
    *args, weight = scan_inputs(s, h, g)

    def out_and_grads(scan):
        # one compiled program a side, not one a primitive
        def weighted(*a):
            out = scan(*a)
            return (out * weight).sum(), out
        return jax.jit(jax.value_and_grad(weighted, range(5), has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, got_out), got = out_and_grads(
            functools.partial(ssd_scan, chunk=q))(*args)
        (_, want_out), want = out_and_grads(ref.ssm_recurrence)(*args)
    np.testing.assert_allclose(got_out, want_out, rtol=2e-5, atol=2e-5)
    for name, a, b in zip(("x", "dt", "A", "B", "C"), got, want):
        assert ref.rms_rel(a, b) < 2e-5, name


def test_the_recurrence_in_segments_is_the_recurrence():
    *args, _ = scan_inputs(32, 4, 2)
    whole = jax.jit(ref.ssm_recurrence)(*args)
    in_segments = jax.jit(functools.partial(
        ref.ssm_recurrence, wrap=jax.checkpoint, segment=8))(*args)
    np.testing.assert_allclose(in_segments, whole, atol=1e-6)


def test_scan_refuses_a_ragged_sequence_and_counts_its_choice():
    from tpu_sandbox.obs import get_registry

    *args, _ = scan_inputs(24, 4, 2)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        ssd_scan(*args, chunk=16)
    labels = {"impl": "jnp", "heads": 4, "head_dim": 8, "state": 16,
              "groups": 2, "chunk": 8, "tokens": 48}
    counter = get_registry().counter("ssd.chunk_choice", labels=labels)
    before = counter.value
    jax.jit(functools.partial(ssd_scan, chunk=8))(*args)
    assert counter.value == before + 1      # one count a traced call site


# --- the float32 parts ---

def test_float32_parts_against_the_reference():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 16, 24)), jnp.float32)
    kernel = jnp.asarray(rng.standard_normal((4, 24)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(24), jnp.float32)
    np.testing.assert_allclose(nh.causal_conv(x, kernel, bias),
                               ref.causal_conv(x, kernel, bias), atol=1e-6)
    # tap K - 1 reads the current token, tap 0 the one three back
    np.testing.assert_allclose(
        nh.causal_conv(x, kernel, bias)[:, 0], bias + kernel[3] * x[:, 0],
        atol=1e-6)
    dt = jnp.asarray(rng.standard_normal((2, 16, 4)), jnp.float32)
    np.testing.assert_allclose(nh.time_step(dt, bias[:4]),
                               ref.time_step(dt, bias[:4]), rtol=1e-6)
    z = jnp.asarray(rng.standard_normal((2, 16, 24)), jnp.float32)
    want = ref.rms_norm((x * jax.nn.silu(z)).reshape(2, 16, 3, 8), 1e-5
                        ).reshape(2, 16, 24) * bias
    np.testing.assert_allclose(nh.gated_group_norm(x, z, bias, 3, 1e-5), want,
                               rtol=1e-5, atol=1e-6)


def test_mamba_starts_from_its_usual_values():
    cfg = nh.NemotronHConfig.from_dict(TINY, tokens_per_step=B * S)
    mixer = nh.Mamba2Mixer(cfg)
    params = jax.jit(mixer.init)(
        jax.random.key(0), jnp.zeros((1, 8, 64)))["params"]
    assert bool((params["D"] == 1).all())
    a = np.exp(np.asarray(params["A_log"]))
    assert (a >= 1).all() and (a <= 16).all()
    step = np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert (step >= 1e-3 - 1e-6).all() and (step <= 0.1 + 1e-6).all()
    assert params["in_proj"]["kernel"].shape == (64, 2 * 32 + 2 * 2 * 16 + 4)


# --- the configuration ---

def test_published_pattern_parses_and_the_period_is_part_of_it():
    counts = {kind: PUBLISHED_PATTERN.count(kind) for kind in nh.KINDS}
    assert len(PUBLISHED_PATTERN) == 88
    assert counts == {"M": 40, "E": 40, "*": 8}
    config = json.loads((ROOT / "benchmark/configs/"
                         "nemotron-3-super-120b-a12b.json").read_text())
    period = config["hybrid_override_pattern"]
    assert period == "EMEMEMEMEM*" == PUBLISHED_PATTERN[26:37]
    assert PUBLISHED_PATTERN[26:70] == period * 4   # it repeats four times
    cfg = nh.NemotronHConfig.from_dict(config, tokens_per_step=8192)
    assert cfg.num_hidden_layers == 11 and cfg.n_routed_experts == 512
    assert cfg.held == tuple(range(8)) and cfg.local_rows == 5632
    assert cfg.mamba_inner == 4096


@pytest.mark.parametrize("over,match", [
    ({"hybrid_override_pattern": "EM-E"}, "unknown"),
    ({"hybrid_override_pattern": "EM*"}, "num_hidden_layers"),
    ({"mlp_hidden_act": "silu"}, "relu2"),
    ({"attention_bias": True}, "biases"),
    ({"n_group": 2}, "n_group"),
    ({"num_key_value_heads": 3}, "divide")])
def test_config_refuses_what_the_model_does_not_compute(over, match):
    with pytest.raises(ValueError, match=match):
        nh.NemotronHConfig.from_dict(tiny(**over), tokens_per_step=64)
