"""models/jamba.py and ops/selective_scan.py at a small size on the CPU
(hidden 64, 6 layers with attention at 1 and 4, 16 states, dt rank 8, 4
query heads on 1 key/value head, vocabulary 256; seeded random weights):
the chunked scan against the token-by-token recurrence, the model's forward
and its token steps against the plain float32 reference of the benchmark,
the published layer order and the parameter count of the published sizes.
The serving path is tests/test_serve_jamba.py."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import jamba as ref  # noqa: E402
from tpu_sandbox.models.jamba import (JambaConfig, JambaLM,  # noqa: E402
                                      state_shapes)
from tpu_sandbox.ops.selective_scan import (selective_scan,  # noqa: E402
                                            selective_step)

TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 6,
    "attn_layer_offset": 1, "attn_layer_period": 3, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 1, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_expand": 2,
    "rms_norm_eps": 1e-6, "hidden_act": "silu", "num_experts": 1,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "tie_word_embeddings": True, "sliding_window": None,
}
PUBLISHED = json.loads(
    (ROOT / "benchmark/configs/ai21-jamba2-3b.json").read_text())

pytestmark = pytest.mark.usefixtures("light_compile")


def tiny_config(**deployment) -> JambaConfig:
    return JambaConfig.from_dict(TINY, **{
        "dtype": jnp.float32, "param_dtype": jnp.float32, "scan_chunk": 4,
        **deployment})


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny_config()
    model = JambaLM(cfg)
    params = jax.jit(model.init)(jax.random.key(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


# --- the chunked scan against the recurrence ---

def scan_inputs(s, d=24, n=16, b=2, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(ks[0], (b, s, d)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, s, d)) - 2.0),
            -jnp.exp(jax.random.normal(ks[2], (n, d))),
            jax.random.normal(ks[3], (b, s, n)),
            jax.random.normal(ks[4], (b, s, n)))


def recurrence(x, dt, a, b, c, h0=None):
    """``h_t = exp(dt_t (x) A) h_{t-1} + (dt_t x_t) (x) B_t``, ``y_t = h_t
    C_t``, a token after another in float64."""
    x, dt, a, b, c = (np.asarray(t, np.float64) for t in (x, dt, a, b, c))
    h = np.zeros((x.shape[0], a.shape[0], a.shape[1])) if h0 is None \
        else np.asarray(h0, np.float64)
    ys = []
    for t in range(x.shape[1]):
        h = (np.exp(dt[:, t, None, :] * a) * h
             + (dt[:, t] * x[:, t])[:, None, :] * b[:, t, :, None])
        ys.append((h * c[:, t, :, None]).sum(1))
    return np.stack(ys, 1), h


@pytest.mark.parametrize("s,chunk", [
    (16, 4),     # whole chunks: every boundary a hand-over of the state
    (16, 16),    # one chunk
    (13, 4),     # the last chunk padded
    (3, 8),      # a sequence shorter than a chunk
    (1, 8),
])
def test_chunked_scan_is_the_recurrence(s, chunk):
    x, dt, a, b, c = scan_inputs(s)
    y, h = jax.jit(lambda *t: selective_scan(*t, chunk=chunk))(x, dt, a, b, c)
    want_y, want_h = recurrence(x, dt, a, b, c)
    assert y.shape == (2, s, 24) and h.shape == (2, 16, 24)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)


def test_scan_starts_from_a_state_and_a_step_is_one_token_of_it():
    x, dt, a, b, c = scan_inputs(9)
    _, h5 = selective_scan(x[:, :5], dt[:, :5], a, b[:, :5], c[:, :5], chunk=4)
    y_rest, h9 = selective_scan(x[:, 5:], dt[:, 5:], a, b[:, 5:], c[:, 5:],
                                chunk=4, h0=h5)
    want_y, want_h = recurrence(x, dt, a, b, c)
    np.testing.assert_allclose(y_rest, want_y[:, 5:], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h9, want_h, rtol=2e-5, atol=2e-5)
    y6, h6 = selective_step(h5, x[:, 5], dt[:, 5], a, b[:, 5], c[:, 5])
    np.testing.assert_allclose(y6, want_y[:, 5], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h6, recurrence(
        x[:, :6], dt[:, :6], a, b[:, :6], c[:, :6])[1], rtol=2e-5, atol=2e-5)


def test_a_position_with_no_time_step_leaves_the_state_bit_for_bit():
    """Bucket padding behind a prompt's last token: ``dt = 0`` is decay 1
    and input 0."""
    x, dt, a, b, c = scan_inputs(12)
    _, h7 = selective_scan(x[:, :7], dt[:, :7], a, b[:, :7], c[:, :7], chunk=4)
    padded = dt.at[:, 7:].set(0.0)
    _, h12 = selective_scan(x, padded, a, b, c, chunk=4)
    assert np.array_equal(np.asarray(h7), np.asarray(h12))


def test_a_narrower_state_is_rounded_after_every_token():
    x, dt, a, b, c = scan_inputs(8)
    _, h = selective_scan(x, dt, a, b, c, chunk=4, state_dtype=jnp.bfloat16)
    assert h.dtype == jnp.bfloat16
    full = recurrence(x, dt, a, b, c)[1]
    gap = np.abs(np.asarray(h, np.float64) - full).max() / np.abs(full).max()
    assert 1e-4 < gap < 2e-2      # rounded, and still the same recurrence


# --- the model against the reference ---

def test_forward_matches_the_reference(tiny_model):
    cfg, model, params = tiny_model
    tokens = jax.random.randint(jax.random.key(1), (2, 11), 0, 256)
    logits, state = jax.jit(
        lambda p, t: model.apply({"params": p}, t))(params, tokens)
    want = ref.forward(ref.from_program_tree(params, TINY), tokens, TINY)
    assert logits.shape == (2, 11, 256) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-4)
    shapes = state_shapes(cfg, 2)
    assert jax.tree.map(lambda a: a.shape, state) == jax.tree.map(
        lambda s: s.shape, shapes)
    assert [s.shape for s in shapes["ssm"]] == [
        (1, 2, 16, 128), (2, 2, 16, 128), (1, 2, 16, 128)]
    assert [s.shape for s in shapes["conv"]] == [
        (1, 3, 2, 128), (2, 3, 2, 128), (1, 3, 2, 128)]


def test_last_pos_cuts_the_state_and_the_logits_there(tiny_model):
    """A padded bucket: the state and the logits are those of the prompt
    alone, whatever stands behind ``last_pos``."""
    cfg, model, params = tiny_model
    tokens = jax.random.randint(jax.random.key(2), (1, 16), 1, 256)
    run = jax.jit(lambda p, t, last: model.apply({"params": p}, t,
                                                 last_pos=last))
    for plen in (2, 7, 16):
        padded = tokens.at[:, plen:].set(0)
        logits, state = run(params, padded, plen - 1)
        alone, want = jax.jit(lambda p, t: model.apply({"params": p}, t))(
            params, tokens[:, :plen])
        assert logits.shape == (1, 1, 256)
        np.testing.assert_allclose(logits[:, 0], alone[:, -1], rtol=1e-4,
                                   atol=1e-4)
        for got, exp in zip(jax.tree.leaves(state), jax.tree.leaves(want)):
            np.testing.assert_allclose(got, exp, rtol=1e-4, atol=1e-5)


def test_layer_order_follows_the_published_rule():
    cfg = tiny_config()
    assert cfg.layer_kinds == ("mamba", "attn", "mamba", "mamba", "attn",
                               "mamba")
    assert cfg.runs == (("mamba", 0, 1), ("attn", 1, 1), ("mamba", 2, 2),
                        ("attn", 4, 1), ("mamba", 5, 1))
    full = JambaConfig.from_dict(PUBLISHED)
    assert [i for i, k in enumerate(full.layer_kinds) if k == "attn"] == [7, 21]
    assert full.mamba_runs == (7, 13, 6)
    assert (full.d_inner, full.head_dim) == (5120, 128)


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("num_experts", 16), ("mamba_proj_bias", True),
    ("tie_word_embeddings", False), ("sliding_window", 4096)])
def test_what_the_model_does_not_compute_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        JambaConfig.from_dict({**TINY, key: value})


def test_published_sizes_count_3_029_337_472_parameters():
    """26 Mamba blocks of 104,161,472 + 2 attention blocks of 76,682,240 +
    the tied vocabulary 65,536 x 2560 + the final norm, by hand and by the
    program's own init."""
    c, d, n, r, k, f = 2560, 5120, 16, 160, 4, 8192
    mlp_and_norms = 3 * c * f + 2 * c
    mamba = (c * 2 * d + (k * d + d) + d * (r + 2 * n) + (r * d + d)
             + n * d + d + (r + 2 * n) + d * c + mlp_and_norms)
    attn = 2 * c * c + 2 * c * 128 + mlp_and_norms
    assert (mamba, attn) == (104_161_472, 76_682_240)
    by_hand = 26 * mamba + 2 * attn + 65_536 * c + c
    assert by_hand == 3_029_337_472
    cfg = JambaConfig.from_dict(PUBLISHED)
    shapes = jax.eval_shape(lambda: JambaLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    leaves = jax.tree.leaves(shapes)
    assert sum(int(np.prod(x.shape)) for x in leaves) == by_hand
    # the matrices in bfloat16: 6.06 GB on the chip
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)
    assert 6.05e9 < held < 6.08e9
    state = jax.tree.leaves(state_shapes(cfg, 1))
    assert sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in state) == 26 * (16 * 5120 * 4 + 3 * 5120 * 2) == 9_318_400
