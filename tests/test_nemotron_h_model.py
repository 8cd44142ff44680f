"""models/nemotron_h.py and ops/ssd.py at a small size on the CPU (hidden
64, 4 Mamba heads in 2 groups, 4 query heads on 2 key/value heads, 16
experts in a latent of 32, seeded random weights): the chunked scan against
the token-by-token recurrence, the float32 parts and the model against the
plain float32 reference of the benchmark on logits, loss and every
gradient, the parts that all shares of a deployment give against the uncut
layer, the published pattern, and ``lm_train.build``."""

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
from tpu_sandbox.ops.losses import cross_entropy_loss  # noqa: E402
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


def tiny(**over):
    return {**TINY, **over}


flat = ref.flat_paths


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

    def chunked(*a):
        return ssd_scan(*a, chunk=q)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(chunked(*args), ref.ssm_recurrence(*args),
                                   rtol=2e-5, atol=2e-5)
        got = jax.grad(lambda *a: (chunked(*a) * weight).sum(),
                       range(5))(*args)
        want = jax.grad(lambda *a: (ref.ssm_recurrence(*a) * weight).sum(),
                        range(5))(*args)
    for name, a, b in zip(("x", "dt", "A", "B", "C"), got, want):
        assert ref.rms_rel(a, b) < 2e-5, name


def test_the_recurrence_in_segments_is_the_recurrence():
    *args, _ = scan_inputs(32, 4, 2)
    whole = ref.ssm_recurrence(*args)
    np.testing.assert_allclose(
        ref.ssm_recurrence(*args, wrap=jax.checkpoint, segment=8), whole,
        atol=1e-6)


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
    params = mixer.init(jax.random.key(0), jnp.zeros((1, 8, 64)))["params"]
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


# --- the model against the reference ---

@functools.cache
def system_and_reference(dtype, mtp: bool, flash: bool = True):
    config = tiny(num_nextn_predict_layers=int(mtp))
    cfg = nh.NemotronHConfig.from_dict(
        config, tokens_per_step=B * S, dtype=dtype,
        remat=dtype == jnp.float32, flash=flash)
    model = nh.NemotronHLM(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 256, (B, S)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, 256, (B, S)), jnp.int32)
    variables = jax.jit(model.init)(jax.random.key(1), tokens)
    # off the initial point, so that every scale and bias matters
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.key(a.size), a.shape),
        variables["params"])
    stats = variables["batch_stats"]

    def system(p):
        logits, sown = model.apply(
            {"params": p, "batch_stats": stats}, tokens,
            mutable=["mtp_logits", "batch_stats", "intermediates"])
        loss = cross_entropy_loss(logits.reshape(-1, 256), targets.reshape(-1))
        for extra in jax.tree.leaves(sown.get("mtp_logits", {})):
            loss = loss + nh.MTP_LOSS_WEIGHT * cross_entropy_loss(
                extra[:, :-1].reshape(-1, 256), targets[:, 1:].reshape(-1))
        return loss, logits

    (loss, logits), grads = jax.jit(
        jax.value_and_grad(system, has_aux=True))(params)
    ref_cfg = {**config, "held": list(cfg.held), "local_rows": cfg.local_rows}
    ref_loss, ref_logits, _, ref_grads = ref.loss_and_grads(
        ref.from_program_tree(params, stats), tokens, targets, ref_cfg,
        mtp_loss_weight=nh.MTP_LOSS_WEIGHT)
    return (loss, logits, flat(grads)), (ref_loss, ref_logits, ref_grads)


@pytest.mark.parametrize("mtp", [False, True], ids=["main", "with_mtp"])
def test_model_matches_the_reference_in_float32(mtp):
    (loss, logits, grads), (ref_loss, ref_logits, ref_grads) = (
        system_and_reference(jnp.float32, mtp))
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    assert ref.rms_rel(logits, ref_logits) < 1e-5
    assert ("mtp_proj/kernel" in grads) == mtp
    assert set(grads) == {k for k in ref_grads if not k.endswith("/bias")}
    for path, grad in grads.items():
        assert ref.rms_rel(grad, ref_grads[path]) < 2e-3, path


@pytest.mark.parametrize("mtp", [False, True], ids=["main", "with_mtp"])
def test_model_matches_the_reference_in_bf16_within_its_band(mtp):
    """bf16 through four to six blocks of width 64 with four flippable
    choices of sixteen: logits within 6 %, the loss within 0.03, and the
    gradients of the wide parameters within 25 %."""
    (loss, logits, grads), (ref_loss, ref_logits, ref_grads) = (
        system_and_reference(jnp.bfloat16, mtp))
    assert abs(float(loss) - float(ref_loss)) < 3e-2
    assert ref.rms_rel(logits, ref_logits) < 6e-2
    for path in ("tok_emb/embedding", "lm_head/kernel",
                 "block1/mamba/in_proj/kernel", "block1/mamba/out_proj/kernel",
                 "block2/attn/q/kernel", "block2/attn/kv/kernel"):
        assert ref.rms_rel(grads[path], ref_grads[path]) < 0.25, path


def test_plain_attention_path_agrees_with_the_flash_path():
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 256, (B, S)), jnp.int32)
    out = []
    for flash in (True, False):
        model = nh.NemotronHLM(nh.NemotronHConfig.from_dict(
            tiny(num_nextn_predict_layers=0), tokens_per_step=B * S,
            dtype=jnp.float32, remat=False, flash=flash))
        variables = jax.jit(model.init)(jax.random.key(1), tokens)
        out.append(jax.jit(model.apply)(variables, tokens))
    assert ref.rms_rel(out[0], out[1]) < 1e-5


# --- the share test: the parts all shares give add up to the uncut layer ---

WHOLE = tiny(mamba_num_heads=8, n_groups=4, num_attention_heads=8,
             num_key_value_heads=2)


def _input():
    return jax.random.normal(jax.random.key(7), (B, S, 64))


def test_both_halves_of_the_mamba_heads_add_up_to_the_uncut_layer():
    """Two chips share the heads: each holds 4 of 8 heads with their 2 of 4
    groups (columns of ``in_proj``, channels of the convolution, rows of
    ``out_proj``); the gated norm's groups stay whole, so the halves' outputs
    add up to the uncut reference's."""
    h, p, g, n = 8, 8, 4, 16
    whole = nh.Mamba2Mixer(nh.NemotronHConfig.from_dict(
        WHOLE, tokens_per_step=B * S, dtype=jnp.float32))
    u = _input()
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(a.size), a.shape),
        whole.init(jax.random.key(0), u)["params"])
    want = ref.mamba_mixer(params, u, WHOLE)
    half = nh.Mamba2Mixer(nh.NemotronHConfig.from_dict(
        tiny(), tokens_per_step=B * S, dtype=jnp.float32))
    d_in = h * p
    total = 0.0
    for j in range(2):
        heads = np.arange(j * h // 2, (j + 1) * h // 2)
        inner = (heads[:, None] * p + np.arange(p)).reshape(-1)
        state = np.arange(j * g // 2 * n, (j + 1) * g // 2 * n)
        conv = np.concatenate([inner, d_in + state, d_in + g * n + state])
        columns = np.concatenate([inner, d_in + conv, 2 * d_in + 2 * g * n + heads])
        mine = {
            "in_proj": {"kernel": params["in_proj"]["kernel"][:, columns]},
            "conv_kernel": params["conv_kernel"][:, conv],
            "conv_bias": params["conv_bias"][conv],
            "A_log": params["A_log"][heads], "dt_bias": params["dt_bias"][heads],
            "D": params["D"][heads], "norm_scale": params["norm_scale"][inner],
            "out_proj": {"kernel": params["out_proj"]["kernel"][inner]}}
        total = total + half.apply({"params": mine}, u)
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_both_halves_of_the_attention_heads_add_up_to_the_uncut_layer():
    whole = nh.Attention(nh.NemotronHConfig.from_dict(
        WHOLE, tokens_per_step=B * S, dtype=jnp.float32, flash=True))
    u = _input()
    params = whole.init(jax.random.key(0), u)["params"]
    want = ref.attention(params, u, WHOLE)
    half = nh.Attention(nh.NemotronHConfig.from_dict(
        tiny(num_key_value_heads=1), tokens_per_step=B * S, dtype=jnp.float32,
        flash=True))
    total = 0.0
    for j in range(2):
        q_heads, kv_heads = slice(4 * j, 4 * j + 4), slice(j, j + 1)
        mine = {"q": {"kernel": params["q"]["kernel"][:, q_heads]},
                "kv": {"kernel": params["kv"]["kernel"][:, :, kv_heads]},
                "o": {"kernel": params["o"]["kernel"][q_heads]}}
        total = total + half.apply({"params": mine}, u)
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_all_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four shares of four experts: their routed parts (each through
    ``latent_up``, which is linear) plus the shared expert counted once are
    the uncut reference's whole layer; the router and ``latent_down`` are
    computed alike by every share."""
    e, rows = 16, B * S * 4
    u = _input()

    def layer(held):
        cfg = nh.NemotronHConfig.from_dict(
            tiny(deployment={"held": list(held)}), tokens_per_step=B * S,
            dtype=jnp.float32)
        return nh.latent_moe(cfg, None)

    variables = layer(range(e)).init(jax.random.key(0), u)
    bias = 0.05 * jax.random.normal(jax.random.key(2), (e,))
    stats = {**variables["batch_stats"], "e_score_correction_bias": bias}
    params = dict(variables["params"])
    ref_params = {**params, "bias": bias}
    cfg = {**TINY, "held": list(range(e)), "local_rows": rows}
    want, _ = ref.latent_moe(ref_params, u, cfg)
    shared = want - ref.latent_moe(ref_params, u, cfg, with_shared=False)[0]
    total = shared
    for first in range(0, e, 4):
        held = tuple(range(first, first + 4))
        mine = {**params, "w_up": params["w_up"][first:first + 4],
                "w_down": params["w_down"][first:first + 4]}
        part = layer(held).apply({"params": mine, "batch_stats": stats}, u)
        ref_part, _ = ref.latent_moe(
            {**mine, "bias": bias}, u, {**cfg, "held": list(held)},
            with_shared=False)
        np.testing.assert_allclose(part - shared, ref_part, atol=5e-5)
        total = total + part - shared
    np.testing.assert_allclose(total, want, atol=1e-4)
    np.testing.assert_allclose(
        layer(range(e)).apply({"params": params, "batch_stats": stats}, u),
        want, atol=1e-4)


# --- the entry script ---

def test_lm_train_builds_and_trains_the_hybrid(tmp_path):
    import lm_train

    assert "nemotron_h" in lm_train.CONFIG_MODELS
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY))
    args = lm_train.build_parser().parse_args(
        ["--force-cpu", "--batch", "2", "--seq-len", "16", "--model",
         "nemotron_h", "--config", str(path), "--remat"])
    built, tx, state, eng = lm_train.build(args, jax.devices()[:1])
    assert type(built).__name__ == "NemotronHLM"
    assert eng.mtp_weight == nh.MTP_LOSS_WEIGHT
    batch = next(lm_train.make_batches(built.config.vocab_size, 2, 16, 1, 0))
    new, loss = eng.train_step(state, *eng.shard_batch(*batch))
    assert np.isfinite(float(loss)) and int(new.step) == 1
    stats = flat(new.batch_stats)
    assert float(jnp.abs(
        stats["block0/moe/e_score_correction_bias"]).max()) == pytest.approx(1e-3)
    assert int(stats["block3/moe/steps"]) == 1
    assert int(stats["mtp_block1/moe/rows_dropped"]) == 0
