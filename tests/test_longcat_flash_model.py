"""models/longcat_flash.py at a small size on the CPU (hidden 64, 4 heads, 2
double layers, 8 real + 4 zero experts, 3 a token), float32, against the
plain reference (``benchmark/reference/longcat_flash.py``): the full
forward; the absorbed decode form against the expanded one at the same
positions; the shortcut's order; the expert share against the whole layer
(all shares and the zero experts' term counted once); a token whose choices
all fall on zero experts; and ``ExpertShare`` under the settings of the two
training models, which must give what it gave."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import longcat_flash as ref  # noqa: E402
from tpu_sandbox.models import longcat_flash as lf  # noqa: E402
from tpu_sandbox.parallel import expert  # noqa: E402

pytestmark = pytest.mark.usefixtures("light_compile")

#: the catalog row's keys at a tiny size; this chip holds 4 of 8 real experts
TINY = dict(
    attention_bias=False, vocab_size=96, hidden_size=64, ffn_hidden_size=96,
    expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
    kv_lora_rank=32, q_lora_rank=24, qk_rope_head_dim=8, v_head_dim=16,
    qk_nope_head_dim=16, mla_scale_q_lora=True, mla_scale_kv_lora=True,
    routed_scaling_factor=6, n_routed_experts=4,
    max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=1e7,
    attention_method="MLA", zero_expert_num=4, zero_expert_type="identity",
    moe_topk=3,
    deployment=dict(routed_experts_total=8, held=[0, 1, 2, 3],
                    local_rows_factor=4))
F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)


def tiny_config(**deployment) -> lf.LongcatFlashConfig:
    return lf.LongcatFlashConfig.from_dict(TINY, **{**F32, **deployment})


def init_variables(cfg: lf.LongcatFlashConfig, key) -> dict:
    """The model's own init with a router bias drawn small: the init's is
    zero, which would leave its add to the scores untested."""
    k_init, k_bias = jax.random.split(key)
    variables = lf.LongcatFlashLM(cfg).init(k_init,
                                            jnp.zeros((1, 8), jnp.int32))
    bias, counters = lf.split_stats(variables["batch_stats"])
    drawn = {name: 1e-3 * jax.random.normal(
        jax.random.fold_in(k_bias, i), (cfg.router_width,))
        for i, name in enumerate(sorted(bias))}
    return {"params": variables["params"],
            "batch_stats": lf.join_stats(drawn, counters)}


def engine_params(variables: dict) -> dict:
    """The variables as the engine and the reference hold them."""
    bias, _ = lf.split_stats(variables["batch_stats"])
    return {"params": variables["params"], "router_bias": bias}


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    variables = jax.jit(lambda k: init_variables(cfg, k))(jax.random.key(0))
    return cfg, variables, ref.from_program_tree(engine_params(variables), TINY)


def tokens(batch: int, length: int, seed: int = 0):
    return np.random.default_rng(seed).integers(1, 96, (batch, length))


def test_config_reads_the_published_keys_and_the_share():
    cfg = tiny_config()
    assert (cfg.n_routed_experts, cfg.zero_expert_num, cfg.router_width,
            cfg.held) == (8, 4, 12, (0, 1, 2, 3))
    assert (cfg.latent_dim, cfg.qk_head_dim) == (40, 24)
    with pytest.raises(ValueError, match="plain RoPE"):
        lf.LongcatFlashConfig.from_dict({**TINY, "rope_scaling": {}}, **F32)
    with pytest.raises(ValueError, match="zero_expert_type"):
        lf.LongcatFlashConfig.from_dict(
            {**TINY, "zero_expert_type": "copy"}, **F32)


def test_the_bias_is_drawn_and_the_counters_start_at_zero(model):
    _, variables, _ = model
    bias, counters = lf.split_stats(variables["batch_stats"])
    assert set(bias) == {"block0", "block1"}
    for layer in bias.values():
        assert layer.shape == (12,) and 1e-4 < float(jnp.std(layer)) < 1e-2
    assert not any(int(v) for v in jax.tree.leaves(counters))
    assert jax.tree.structure(lf.join_stats(bias, counters)) == \
        jax.tree.structure(dict(variables["batch_stats"]))
    assert set(lf.counter_shapes(tiny_config())["block0"]) == \
        set(counters["block0"])


def test_full_forward_is_the_references(model):
    cfg, variables, tree = model
    toks = tokens(2, 19)
    got = jax.jit(lf.LongcatFlashLM(cfg).apply)(variables, jnp.asarray(toks))
    want = ref.forward(tree, toks, TINY)
    assert got.dtype == jnp.float32 and got.shape == (2, 19, 96)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_a_long_sequence_attends_a_group_of_heads_at_a_time(model,
                                                           monkeypatch):
    """Past ``HEAD_GROUP_BUDGET`` positions x heads the expanded attention
    runs its heads in groups, one after another: the same logits."""
    cfg, variables, tree = model
    toks = tokens(1, 24, seed=4)
    assert lf._head_groups(24, 4) == 1 and lf._head_groups(6144, 64) == 4
    assert lf._head_groups(4096, 64) == 2 and lf._head_groups(2048, 64) == 1
    monkeypatch.setattr(lf, "HEAD_GROUP_BUDGET", 24)       # 4 groups of one
    assert lf._head_groups(24, 4) == 4
    got = jax.jit(lf.LongcatFlashLM(cfg).apply)(variables, jnp.asarray(toks))
    np.testing.assert_allclose(got, ref.forward(tree, toks, TINY), rtol=2e-4,
                               atol=2e-4)


def test_last_pos_keeps_that_positions_logits(model):
    cfg, variables, _ = model
    toks = jnp.asarray(tokens(1, 16))
    apply = lf.LongcatFlashLM(cfg).apply
    whole = apply(variables, toks)
    last = apply(variables, toks, last_pos=jnp.asarray(9))
    np.testing.assert_allclose(last[0, 0], whole[0, 9], rtol=1e-5, atol=1e-5)


def test_absorbed_decode_is_the_expanded_form_at_the_same_positions(model):
    """One token a row through ``attention_fn`` over the latent rows a
    full forward left behind (``absorbed_attention`` over them whole)
    against the same positions of the expanded forward."""
    cfg, variables, _ = model
    toks = jnp.asarray(tokens(3, 13, seed=2))
    plain = lf.LongcatFlashLM(cfg)
    want, taps = plain.apply(variables, toks, mutable=["kv_cache"])
    rows = [taps["kv_cache"][f"block{i}"][f"mla{j}"]["latent"]
            for i in range(cfg.num_layers) for j in (0, 1)]
    assert rows[0].shape == (3, 13, cfg.latent_dim)
    lengths = jnp.asarray([13, 7, 1])                  # each row's position
    layer = iter(range(len(rows)))

    def attention_fn(q, row):
        cached = rows[next(layer)]
        # the new token's own row is what the forward cached at its place
        at = jnp.take_along_axis(cached, (lengths - 1)[:, None, None], 1)[:, 0]
        np.testing.assert_allclose(row, at, rtol=1e-5, atol=1e-5)
        return lf.absorbed_attention(q, cached, lengths, v_dim=cfg.kv_lora_rank,
                                     scale=cfg.qk_head_dim ** -0.5)

    fed = jnp.take_along_axis(toks, (lengths - 1)[:, None], 1)
    got = lf.LongcatFlashLM(cfg, attention_fn=attention_fn).apply(
        variables, fed, (lengths - 1)[:, None])
    for b, n in enumerate([13, 7, 1]):
        np.testing.assert_allclose(got[b, 0], want[b, n - 1], rtol=2e-4,
                                   atol=2e-4)


def test_absorbed_attention_of_an_empty_row_is_zero():
    q = jnp.ones((2, 4, 40))
    rows = jnp.full((2, 8, 40), jnp.nan).at[0, :3].set(1.0)
    out = lf.absorbed_attention(q, jnp.nan_to_num(rows), jnp.asarray([3, 0]),
                                v_dim=32, scale=0.2)
    assert out.shape == (2, 4, 32) and not np.asarray(out[1]).any()
    np.testing.assert_allclose(out[0], 1.0, rtol=1e-6)


def test_the_shortcut_reads_the_first_sublayer_and_joins_after_the_second(
        model):
    """``s = MoE(u0)`` is computed from the first sub-layer's ``u0``:
    perturbing ``MLA_1``'s and ``MLP_1``'s weights leaves what the routed
    layer chose and counted unchanged; and it is added after ``MLP_1``: the
    layer's output moves by exactly the second half's change."""
    cfg, variables, _ = model
    layer0 = lf.DoubleLayer(cfg)
    x = jax.random.normal(jax.random.key(5), (1, 11, 64))
    stats = {"batch_stats": variables["batch_stats"]["block0"]}
    p = variables["params"]["block0"]

    def run(params):
        return layer0.apply({"params": params, **stats}, x,
                            mutable=["intermediates", "batch_stats"])

    out, kept = run(p)
    shaken = dict(p, mla1=jax.tree.map(lambda w: w * 1.5, p["mla1"]),
                  mlp1=jax.tree.map(lambda w: w * 0.5, p["mlp1"]))
    out2, kept2 = run(shaken)
    assert not np.allclose(out, out2)
    sel = kept["intermediates"]["moe"]["sel"][0]
    assert np.array_equal(sel, kept2["intermediates"]["moe"]["sel"][0])
    for name in ("rows_held", "zero_choices", "real_choices"):
        assert int(kept["batch_stats"]["moe"][name]) == \
            int(kept2["batch_stats"]["moe"][name])
    # without the routed layer's output the two differ by the same amount:
    # the shortcut is one additive term behind MLP_1
    silent = dict(p, moe=dict(p["moe"], w_down=p["moe"]["w_down"] * 0))
    silent2 = dict(shaken, moe=silent["moe"])
    zero_off = {"batch_stats": {"moe": dict(
        stats["batch_stats"]["moe"],
        e_score_correction_bias=stats["batch_stats"]["moe"][
            "e_score_correction_bias"].at[8:].set(-1.0))}}

    def run_without_zero(params):
        return layer0.apply({"params": params, **zero_off}, x,
                            mutable=["batch_stats"])[0]

    np.testing.assert_allclose(
        run_without_zero(p) - run_without_zero(silent),
        run_without_zero(shaken) - run_without_zero(silent2),
        rtol=1e-4, atol=1e-5)


# --- the expert share: softmax scores, zero experts, the sum of the shares ---

def share(held, kind=expert.ExpertShare, **kw) -> expert.ExpertShare:
    sizes = dict(d_model=64, d_ff=32, n_routed_experts=8, top_k=3, held=held,
                 local_rows=64, row_tile=16, routed_scaling_factor=6.0,
                 bias_update_rate=0.0, dtype=jnp.float32,
                 score_rule="softmax", n_zero_experts=4)
    return kind(**{**sizes, **kw})


@pytest.fixture(scope="module")
def whole_layer():
    """One routed layer with all 8 real experts held, its router's bias
    drawn, and inputs."""
    x = jax.random.normal(jax.random.key(1), (40, 64))
    full = share(tuple(range(8)))
    variables = full.init(jax.random.key(2), x)
    bias = 2e-3 * jax.random.normal(jax.random.key(3), (12,))
    stats = dict(variables["batch_stats"], e_score_correction_bias=bias)
    return x, {"params": variables["params"], "batch_stats": stats}


def reference_moe(variables, x, held, zero=True):
    with jax.default_matmul_precision("highest"):
        return ref.moe(variables["params"],
                       variables["batch_stats"]["e_score_correction_bias"], x,
                       n_real=8, top_k=3, factor=6.0, held=tuple(held),
                       stored=tuple(range(8)), zero=zero)


def test_all_shares_and_the_zero_term_once_add_up_to_the_whole_layer(
        whole_layer):
    """The share ties to the model: the routed parts of all the shares (4
    chips of 2 experts) plus the zero experts' term, counted once, are the
    uncut reference's whole ``MoE(u0)``."""
    x, variables = whole_layer
    whole = reference_moe(variables, x, range(8))
    p = variables["params"]
    total = np.zeros_like(whole)
    zero_term = None
    for chip in range(4):
        held = (2 * chip, 2 * chip + 1)
        mine = {"params": {"router": p["router"], **{
            name: p[name][jnp.asarray(held)]
            for name in ("w_gate", "w_up", "w_down")}},
            "batch_stats": variables["batch_stats"]}
        got = share(held).apply(mine, x)
        with_zero = reference_moe(variables, x, held)
        np.testing.assert_allclose(got, with_zero, rtol=2e-4, atol=2e-5)
        routed = np.asarray(reference_moe(variables, x, held, zero=False))
        zero_term = np.asarray(got) - routed    # what every chip computes alike
        total += routed
    np.testing.assert_allclose(total + zero_term, whole, rtol=2e-4, atol=5e-5)
    # the zero term is not nothing, and neither is the routed part
    assert np.abs(zero_term).max() > 1e-3 and np.abs(total).max() > 1e-3


def test_weights_are_not_normalised_and_carry_the_factor(whole_layer):
    x, variables = whole_layer
    full = share(tuple(range(8)))
    _, kept = full.apply(variables, x, mutable=["intermediates"])
    sel = np.asarray(kept["intermediates"]["sel"][0])
    probs = np.asarray(jax.nn.softmax(
        np.asarray(x) @ np.asarray(variables["params"]["router"]), -1))
    bias = np.asarray(variables["batch_stats"]["e_score_correction_bias"])
    want = np.argsort(-(probs + bias), -1)[:, :3]
    assert np.array_equal(np.sort(sel, -1), np.sort(want, -1))
    # all-zero-expert output: scale the weights by hand
    w = 6.0 * np.take_along_axis(probs, sel, -1)
    assert 0.1 < w.sum(-1).mean() < 6.0 and not np.allclose(w.sum(-1), 6.0)


def test_a_token_that_chooses_only_zero_experts_takes_no_row(whole_layer):
    """With the bias pushing every choice onto the zero experts, no buffer
    row is held and the layer gives ``(sum w) u0``, ``w = 6 p``."""
    x, variables = whole_layer
    bias = jnp.zeros((12,)).at[8:].set(1.0)
    pushed = {"params": variables["params"], "batch_stats": dict(
        variables["batch_stats"], e_score_correction_bias=bias)}
    out, kept = share(tuple(range(8))).apply(
        pushed, x, mutable=["batch_stats", "intermediates"])
    sel = np.asarray(kept["intermediates"]["sel"][0])
    assert (sel >= 8).all()
    stats = kept["batch_stats"]
    assert int(stats["rows_held"]) == 0 and int(stats["rows_dropped"]) == 0
    assert (int(stats["zero_choices"]), int(stats["real_choices"])) == (120, 0)
    probs = np.asarray(jax.nn.softmax(
        np.asarray(x) @ np.asarray(variables["params"]["router"]), -1))
    w = 6.0 * np.take_along_axis(probs, sel, -1).sum(-1, keepdims=True)
    np.testing.assert_allclose(out, w * np.asarray(x), rtol=1e-4, atol=1e-5)


def test_the_counters_count_choices_and_rows(whole_layer):
    x, variables = whole_layer
    _, kept = share((0, 1, 2)).apply(
        {"params": {"router": variables["params"]["router"], **{
            n: variables["params"][n][:3]
            for n in ("w_gate", "w_up", "w_down")}},
         "batch_stats": variables["batch_stats"]}, x,
        mutable=["batch_stats", "intermediates"])
    sel = np.asarray(kept["intermediates"]["sel"][0])
    stats = {k: int(v) for k, v in kept["batch_stats"].items()
             if k != "e_score_correction_bias"}
    assert stats["zero_choices"] == int((sel >= 8).sum())
    assert stats["real_choices"] == 120 - stats["zero_choices"]
    assert stats["rows_held"] == int((sel < 3).sum())
    assert (stats["rows_dropped"], stats["steps"]) == (0, 1)


def test_a_prompts_share_is_the_buffered_one_and_drops_nothing(whole_layer):
    """A whole sequence's share (``PromptShare``): every held expert over
    all the tokens. The buffered share's numbers where that drops nothing; and
    where every token chooses alike -- a prompt under random weights -- a
    buffer of four times the mean drops rows and the dense share none."""
    x, variables = whole_layer
    held = tuple(range(8))
    buffered = share(held, local_rows=128).apply(variables, x)
    dense, kept = share(held, lf.PromptShare, local_rows=0).apply(
        variables, x, mutable=["batch_stats"])
    np.testing.assert_allclose(dense, buffered, rtol=2e-5, atol=2e-6)
    assert int(kept["batch_stats"]["rows_dropped"]) == 0
    alike = jnp.broadcast_to(x[:1], x.shape)             # 40 tokens, one state
    _, tight = share(held, local_rows=32).apply(
        variables, alike, mutable=["batch_stats", "intermediates"])
    chosen = int((np.asarray(tight["intermediates"]["sel"][0]) < 8).sum())
    assert chosen >= 40 and int(tight["batch_stats"]["rows_dropped"]) \
        == chosen - 32
    out, loose = share(held, lf.PromptShare, local_rows=0).apply(
        variables, alike, mutable=["batch_stats"])
    stats = {k: int(v) for k, v in loose["batch_stats"].items()
             if k != "e_score_correction_bias"}
    assert (stats["rows_held"], stats["rows_dropped"]) == (chosen, 0)
    assert stats["expert_rows_max"] == 40
    np.testing.assert_allclose(out, reference_moe(variables, alike, held),
                               rtol=2e-4, atol=2e-5)


def test_param_dtype_holds_the_matrices_narrow_and_the_router_wide():
    x = jnp.ones((8, 64), jnp.bfloat16)
    shapes = jax.eval_shape(
        share((0, 1), dtype=jnp.bfloat16, param_dtype=jnp.bfloat16).init,
        jax.random.key(0), x)["params"]
    assert {k: v.dtype for k, v in shapes.items()} == {
        "router": jnp.float32, "w_gate": jnp.bfloat16, "w_up": jnp.bfloat16,
        "w_down": jnp.bfloat16}
    default = jax.eval_shape(share((0, 1)).init, jax.random.key(0), x)
    assert all(v.dtype == jnp.float32
               for v in jax.tree.leaves(default["params"]))


@pytest.mark.parametrize("tokens_, tile, rows", [
    (128, 128, 128),      # the cell's decode step: R is one tile of 128
    (512, 256, 512), (6144, 256, 6144),      # its prompts: training's tile
    (1, 16, 16), (8, 16, 16),                # never under the sublane tile
])
def test_the_row_tile_follows_the_tokens_of_a_call(tokens_, tile, rows):
    sizes = (tokens_, 12, 16, 768, 4)
    assert expert.share_row_tile(*sizes) == tile
    assert expert.share_rows(*sizes, tile) == rows
    cfg = lf.LongcatFlashConfig.from_dict(
        {**TINY, "moe_topk": 12, "zero_expert_num": 256,
         "deployment": dict(routed_experts_total=512, held=list(range(16)),
                            local_rows_factor=4)}, **F32)
    made = lf.expert_share(cfg, tokens_, "moe")
    assert (made.row_tile, made.local_rows, type(made)) == (
        tile, rows, expert.ExpertShare)
    assert type(lf.expert_share(cfg, tokens_, "moe", True)) is lf.PromptShare


@pytest.mark.parametrize("model_name", ["xing4", "nemotron_h"])
def test_the_training_models_shares_keep_their_settings(model_name):
    """``ExpertShare`` as Xing4's and Nemotron's models build it: the
    sigmoid-normalised rule, no zero experts, float32 parameters, the
    256-row tile, and a ``batch_stats`` tree without the new counters."""
    if model_name == "xing4":
        from test_xing4_model import TINY as cfg_dict
        from tpu_sandbox.models import xing4 as program

        cfg = program.Xing4Config.from_dict(cfg_dict, tokens_per_step=64,
                                            dtype=jnp.float32)
        made = program.expert_share(cfg, "moe")
    else:
        from test_nemotron_h_model import TINY as cfg_dict
        from tpu_sandbox.models import nemotron_h as program

        cfg = program.NemotronHConfig.from_dict(
            cfg_dict, tokens_per_step=64, dtype=jnp.float32)
        made = program.latent_moe(cfg, "moe")
    assert (made.score_rule, made.n_zero_experts, made.param_dtype,
            made.row_tile) == ("sigmoid_norm", 0, jnp.float32, 256)
    x = jnp.ones((16, made.d_model), jnp.float32)
    stats = jax.eval_shape(made.init, jax.random.key(0), x)["batch_stats"]
    assert set(stats) == {"e_score_correction_bias", "rows_held",
                          "rows_dropped", "expert_rows_max", "steps"}
