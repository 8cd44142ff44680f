"""models/laguna.py at a small size on the CPU (hidden 64, 6 and 8 query
heads on 2 key/value heads of 16, window 8, 8 experts 2 a token, layers
``[full, window, window, window]`` with a dense first MLP), float32, against
the plain reference (``benchmark/reference/laguna.py``): the full forward on
logits; one token through ``attention_fn`` against the whole sequence at the
same position; the four shares of the routed layer, the shared expert
counted once, against the uncut layer; the rotary rules; the share's row
tile; and the four faults the benchmark's comparison must see."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import laguna as ref  # noqa: E402
from tpu_sandbox.models import laguna  # noqa: E402
from tpu_sandbox.models.longcat_flash import (join_stats,  # noqa: E402
                                              split_stats)

pytestmark = pytest.mark.usefixtures("light_compile")

#: the catalog row's keys at a tiny size; this chip holds 4 of 8 experts
TINY = dict(
    model_type="laguna", vocab_size=96, hidden_size=64, intermediate_size=96,
    num_hidden_layers=4, num_attention_heads=6, num_key_value_heads=2,
    head_dim=16, max_position_embeddings=4096, attention_bias=False,
    rms_norm_eps=1e-6, num_experts=4, num_experts_per_tok=2,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    tie_word_embeddings=False, gating=True, sliding_window=8,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 16},
    layer_types=["full_attention"] + 3 * ["sliding_attention"],
    moe_apply_router_weight_on_input=False, partial_rotary_factor=0.5,
    mlp_layer_types=["dense"] + 3 * ["sparse"],
    moe_routed_scaling_factor=2.5, num_attention_heads_per_layer=[6, 8, 8, 8],
    deployment=dict(routed_experts_total=8, held=[0, 1, 2, 3],
                    local_rows_factor=4))
F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)


def tiny_config(**deployment) -> laguna.LagunaConfig:
    return laguna.LagunaConfig.from_dict(TINY, **{**F32, **deployment})


def init_variables(cfg: laguna.LagunaConfig, key) -> dict:
    """The model's own init with a router bias drawn small: the init's is
    zero, which would leave its add to the scores untested."""
    k_init, k_bias = jax.random.split(key)
    variables = laguna.LagunaLM(cfg).init(k_init,
                                          jnp.zeros((1, 8), jnp.int32))
    bias, counters = split_stats(variables["batch_stats"])
    drawn = {name: 1e-2 * jax.random.normal(
        jax.random.fold_in(k_bias, i), (cfg.num_experts,))
        for i, name in enumerate(sorted(bias))}
    return {"params": variables["params"],
            "batch_stats": join_stats(drawn, counters)}


def engine_params(variables: dict) -> dict:
    """The variables as the engine and the reference hold them."""
    bias, _ = split_stats(variables["batch_stats"])
    return {"params": variables["params"], "router_bias": bias}


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    variables = jax.jit(lambda k: init_variables(cfg, k))(jax.random.key(0))
    return cfg, variables, ref.from_program_tree(engine_params(variables), TINY)


@pytest.fixture(autouse=True)
def small_query_blocks(monkeypatch):
    """The reference's blocks of queries at this size: several a sequence,
    so that a window layer's block is given fewer keys than there are."""
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)


def tokens(batch: int, length: int, seed: int = 0):
    return np.random.default_rng(seed).integers(1, 96, (batch, length))


def test_config_reads_the_published_lists_and_the_share():
    cfg = tiny_config()
    assert cfg.layer_kinds == ("full", "window", "window", "window")
    assert cfg.heads == (6, 8, 8, 8) and cfg.mlp_kinds[0] == "dense"
    assert cfg.num_experts == 8 and cfg.held == (0, 1, 2, 3)
    assert cfg.rope_full.rotary_dim == 8 and cfg.rope_full.yarn == (
        64.0, 64.0, 1.0, 16)
    assert cfg.rope_window.rotary_dim == 16 and cfg.rope_window.yarn is None
    assert cfg.window("full") is None and cfg.window("window") == 8
    for key, bad in (("attention_bias", True), ("gating", False),
                     ("layer_types", ["full_attention"]),
                     ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="laguna"):
            laguna.LagunaConfig.from_dict({**TINY, key: bad})


def test_the_rotary_rules_are_the_references():
    cfg = tiny_config()
    for kind, name in (("full", "full_attention"),
                       ("window", "sliding_attention")):
        rule = cfg.rope(kind)
        want = ref.inv_freq(TINY["rope_parameters"][name], 16)
        np.testing.assert_allclose(rule.inv_freq(), want, rtol=1e-6)
        x = jax.random.normal(jax.random.key(3), (1, 12, 3, 16))
        got = laguna.rotate(x, rule)
        np.testing.assert_allclose(
            got[0], ref.rope(x[0], want, rule.attention_factor), rtol=1e-5,
            atol=1e-6)
    # the full layers' second half passes, the rotated half carries the factor
    x = jnp.ones((1, 1, 1, 16))
    got = laguna.rotate(x, cfg.rope_full)
    np.testing.assert_allclose(got[0, 0, 0, 8:], 1.0)
    np.testing.assert_allclose(got[0, 0, 0, :8], 1.4158883, rtol=1e-6)


@pytest.mark.parametrize("length", [24, 7])
def test_full_forward_is_the_references(model, length):
    """24 positions: three windows, three of the reference's query blocks.
    7: every position inside the first window."""
    cfg, variables, tree = model
    toks = tokens(2, length)
    got = jax.jit(laguna.LagunaLM(cfg).apply)(variables, toks)
    if length % 8:   # the reference takes whole blocks: causal, so zeros do
        toks = np.pad(toks, ((0, 0), (0, -length % 8)))
    want = ref.forward(tree, toks, TINY)[:, :length]
    assert got.shape == (2, length, 96) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_last_pos_keeps_that_positions_logits(model):
    cfg, variables, _ = model
    toks = tokens(1, 16, seed=2)
    lm = laguna.LagunaLM(cfg)
    whole = lm.apply(variables, toks)
    at = lm.apply(variables, toks, last_pos=jnp.asarray(9))
    np.testing.assert_allclose(at[:, 0], whole[:, 9], rtol=1e-5, atol=1e-5)


def test_one_token_through_attention_fn_is_the_sequences_last(model):
    """Decode's form: the new token's query against every key and value the
    sequence left (what ``kv_fn`` was handed), masked as the layer's kind says, is the
    whole sequence's output at that position."""
    cfg, variables, _ = model
    toks = tokens(1, 13, seed=4)
    left = []          # a layer: the keys and values its positions left

    def kv_fn(k, v, out):
        left.append((k, v))
        return out

    whole = laguna.LagunaLM(cfg, kv_fn=kv_fn).apply(variables, toks)
    layers = iter(range(cfg.num_hidden_layers))

    def attention_fn(q, k, v):
        i = next(layers)
        keys, values = left[i]
        np.testing.assert_allclose(k, keys[:, -1], rtol=1e-5, atol=1e-5)
        window = cfg.window(cfg.layer_kinds[i])
        lo = 0 if window is None else max(0, 13 - window)
        g = q.shape[1] // keys.shape[2]
        qg = q.reshape(1, keys.shape[2], g, -1)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, keys[:, lo:]) / 4.0
        out = jnp.einsum("bhgk,bkhd->bhgd", jax.nn.softmax(s, -1),
                         values[:, lo:])
        return out.reshape(q.shape)

    one = laguna.LagunaLM(cfg, attention_fn=attention_fn).apply(
        variables, toks[:, -1:], jnp.asarray([[12]]))
    np.testing.assert_allclose(one[:, 0], whole[:, -1], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("fault", [
    {"window": False}, {"gate": False}, {"window_rope": "full"},
    {"cache_dtype": jnp.float8_e4m3fn}])
def test_each_fault_moves_the_references_logits(model, fault):
    """What the benchmark's four controls put in the reference's place is
    seen in the logits at this size (the limits are the cell's own:
    ``tests/benchmark/test_benchmark_laguna_serve.py``)."""
    _, _, tree = model
    toks = tokens(1, 24, seed=5)
    want = ref.forward(tree, toks, TINY)
    got = ref.forward(tree, toks, TINY, **fault)
    assert float(jnp.abs(got - want).max()) > 0.05 * float(want.std())
    # ... and the first window's positions see no window fault
    if fault == {"window": False}:
        np.testing.assert_allclose(got[:, :8], want[:, :8], atol=1e-5)


# --- the routed layer: shares of the whole ---

def share(held, kind=None, tokens_=40):
    cfg = laguna.LagunaConfig.from_dict(
        {**TINY, "deployment": {"routed_experts_total": 8,
                                "held": list(held)}}, **F32)
    return laguna.expert_share(cfg, tokens_, None,
                               whole_sequence=kind == "prompt")


@pytest.fixture(scope="module")
def whole_layer():
    """One routed layer with all 8 experts held, its router's bias drawn,
    and inputs."""
    x = jax.random.normal(jax.random.key(1), (40, 64))
    variables = share(range(8)).init(jax.random.key(2), x)
    bias = 2e-2 * jax.random.normal(jax.random.key(3), (8,))
    stats = dict(variables["batch_stats"], e_score_correction_bias=bias)
    return x, {"params": variables["params"], "batch_stats": stats}


def reference_moe(variables, x, held, shared=True):
    with jax.default_matmul_precision("highest"):
        return ref.moe(variables["params"],
                       variables["batch_stats"]["e_score_correction_bias"], x,
                       top_k=2, factor=2.5, held=tuple(held),
                       stored=tuple(range(8)), shared=shared)


@pytest.mark.parametrize("kind", ["buffer", "prompt"])
def test_four_shares_and_the_shared_expert_once_add_up_to_the_whole_layer(
        whole_layer, kind):
    """The share ties to the model: the routed parts of the four chips'
    shares (2 experts each) plus the shared expert, counted once, are the
    uncut reference's whole layer; a prompt's share gives what the buffered
    one gives."""
    x, variables = whole_layer
    whole = reference_moe(variables, x, range(8))
    p = variables["params"]
    total, shared_term = np.zeros_like(whole), None
    for chip in range(4):
        held = (2 * chip, 2 * chip + 1)
        mine = {"params": {**p, **{
            name: p[name][jnp.asarray(held)]
            for name in ("w_gate", "w_up", "w_down")}},
            "batch_stats": variables["batch_stats"]}
        got = share(held, kind).apply(mine, x)
        np.testing.assert_allclose(got, reference_moe(variables, x, held),
                                   rtol=2e-4, atol=2e-5)
        routed = np.asarray(reference_moe(variables, x, held, shared=False))
        shared_term = np.asarray(got) - routed   # every chip computes it alike
        total += routed
    np.testing.assert_allclose(total + shared_term, whole, rtol=2e-4,
                               atol=5e-5)
    assert np.abs(shared_term).max() > 1e-3 and np.abs(total).max() > 1e-3


def test_the_share_chooses_by_score_plus_bias_and_weighs_by_score(whole_layer):
    """The 2 largest of sigmoid + bias (the drawn bias reorders some), and
    weights of the sigmoids alone that sum to the scaling factor: with every
    expert held and the shared expert's term taken off, scaling the experts'
    outputs by 1 / 2.5 is a convex mix."""
    x, variables = whole_layer
    scores = jax.nn.sigmoid(x @ variables["params"]["router"])
    bias = variables["batch_stats"]["e_score_correction_bias"]
    _, sel = jax.lax.top_k(scores + bias, 2)
    _, plain = jax.lax.top_k(scores, 2)
    _, got = share(range(8)).apply(variables, x, mutable=["intermediates"])
    np.testing.assert_array_equal(got["intermediates"]["sel"][0], sel)
    assert (np.asarray(sel) != np.asarray(plain)).any()
    chosen = jnp.take_along_axis(scores, sel, -1)
    w = 2.5 * chosen / chosen.sum(-1, keepdims=True)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-6)


@pytest.mark.parametrize("tokens_,tile,rows", [
    (64, 16, 512),      # the cell's decode call: 2 rows an expert, 8 x 4 a tile
    (4, 16, 32), (4096, 256, 32768)])
def test_the_row_tile_is_one_held_experts_rows(tokens_, tile, rows):
    cfg = laguna.LagunaConfig.from_dict(
        {**TINY, "num_experts_per_tok": 8, "deployment": {
            "routed_experts_total": 256, "held": list(range(64))}}, **F32)
    got = laguna.expert_share(cfg, tokens_, None)
    assert (got.row_tile, got.local_rows) == (tile, rows)
    # what a decode call's 64 tokens can hold here at most is the buffer
    assert tokens_ != 64 or got.local_rows == 64 * 8
