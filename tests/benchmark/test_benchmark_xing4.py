"""The Xing4.0 cell: its files resolve and say what the contract asks, its
runner drives the program's ``lm_train.build`` at a tiny size on the CPU,
its readers return numbers on a hand-written trace, its counts are what a
hand computes, and its comparison fails a system that is wrong. Numbers
from these runs are counts and correctness only."""

import functools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark.lib import manifest, observe, xing4_counts  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402
from benchmark.reference import xing4 as ref  # noqa: E402

CELL = "xing4_train_s4096"
NEW_METRICS = ("mla_ms", "mla_core_roofline", "mhc_ms", "mhc_roofline",
               "moe_ms", "moe_experts_ms", "moe_experts_roofline",
               "moe_pad_pct", "moe_rows_dropped")
#: the catalog's ``config`` of Xing4.0-29B-A4B (model-configs guide)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 8, "vocab_size": 16384,
           "num_nextn_predict_layers": 0}


def test_the_cell_resolves_and_reports_its_metrics():
    cell = manifest.cell(CELL)
    assert cell["chips"] == 1 and cell["runner"] == "xing4_train"
    assert cell["reference"] == "xing4" and cell["traffic"]["seq_len"] == 4096
    assert cell["traffic"]["batch"] == 2 and cell["traffic"]["steps_per_chunk"] == 1
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) <= reported
    assert {"device_step_ms", "pallas_ms", "mfu_pct", "place_batch_ms",
            "state_place_s", "model_init_s", "opt_init_s", "loader_wait_ms",
            "device_idle_pct", "compile_cache_misses"} <= reported
    assert {m["name"] for m in cell["end_to_end"]} == {"train_step_ms", "setup_s"}
    entries = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in NEW_METRICS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "train_step_ms"
        if name.endswith("_roofline"):
            assert entries[name]["unit"] == "%"


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_holds_every_published_key(key):
    config = manifest.cell(CELL)["config"]
    assert set(config["reduced"]) == set(REDUCED)
    assert config[key] == REDUCED.get(key, PUBLISHED[key])
    if key in REDUCED:
        assert key in config["reduced_how"]
        assert config["published"].get(key, PUBLISHED[key]) == PUBLISHED[key]


def test_the_configuration_states_its_deployment_and_assumptions():
    config = manifest.cell(CELL)["config"]
    dep = config["deployment"]
    assert "8 chips share each layer" in dep["stands_for"]
    assert dep["held"] == list(range(8)) and dep["local_rows_factor"] == 2
    assert dep["routed_experts_total"] == 64
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "xing4.0-29b-a4b")
    assert entry["source"] in config["source"] and "xing4_0" in config["source"]
    assert set(entry["reduced"]) == set(REDUCED)
    for topic in ("streams_entry", "streams_exit", "mhc_norm", "mhc_sinkhorn",
                  "mhc_init", "rope_pairing", "router_bias_update",
                  "local_rows", "mtp", "weights"):
        assert config["assumed"][topic]


def test_the_reference_imports_nothing_of_the_program():
    text = (ROOT / "benchmark/reference/xing4.py").read_text()
    assert "tpu_sandbox" not in text.replace("``tpu_sandbox/models/xing4.py``", "")
    assert "import flax" not in text and "pallas" not in text
    assert ref.TOLERANCE and all(v > 0 for v in ref.TOLERANCE.values())


# --- the runner at a tiny size ---

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 2, "q_lora_rank": 24, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "num_experts_per_tok": 2, "hc_sinkhorn_iters": 3}


@functools.cache
def tiny_xing4():
    from test_benchmark_runners import drive, tiny_cell

    cell = tiny_cell(
        CELL, config=TINY,
        deployment={"held": [0, 1, 2, 3], "routed_experts_total": 8,
                    "dtype": "fp32", "remat": False,
                    "reference_head_block": 1},
        traffic={"batch": 2, "seq_len": 16, "steps_per_chunk": 1})
    return drive(cell, seconds=1.5)


def test_xing4_runner_tiny():
    obs = tiny_xing4()
    # 32 tokens a step: whether a noisy loss fell is not this test's subject
    assert [p for p in obs.problems if "did not lower the loss" not in p
            and "no Pallas attention kernel" not in p] == []
    assert obs.attempted >= 2 and obs.failed == 0
    assert obs.end_to_end["train_step_ms"] > 0
    dev = obs.notes["reference_deviation"]
    assert dev["logit_rms_rel"] < 1e-4 and dev["loss_abs"] < 1e-4
    assert dev["route_flips"] == 0.0
    grads = {k: v for k, v in dev.items() if k.startswith("grad_rel:")}
    assert len(grads) == 9
    # every one of them, the mHC's too: at ``ref.off_start``'s point none is
    # a difference of nearly equal sums (float32 against float32)
    assert max(grads.values()) < 1e-3, grads
    assert max(v for k, v in dev.items() if k.startswith("fp32_rel:")) < 1e-5
    rows = obs.notes["moe_rows"]
    assert rows["local_rows"] == 256 and rows["dropped_per_step"] == 0.0
    assert 0 < rows["held_per_layer_step"] <= 2 * 16 * 2
    assert 0 <= obs.facts["moe_pad_pct"] < 100
    assert obs.facts["moe_rows_dropped"] == 0.0


@pytest.mark.parametrize("pattern", [
    r"/mla(/|$)", r"/mhc_", r"/moe/", r"/moe/experts", r"/moe/router",
    r"/moe/dispatch", r"/moe/combine", r"/moe/shared", r"lm_head",
    r"(^|/)optimizer(/|$)", r"loss"])
def test_the_compiled_step_carries_the_scopes_the_readers_match(pattern):
    import re

    obs = tiny_xing4()
    (program, scopes), = obs.scopes.items()
    assert program == "jit_step"
    assert any(re.search(pattern, s) for s in scopes.values())


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_read_nothing_from_an_untraced_run_or_a_program_without_them(name):
    reader = manifest.module("layer_metrics", name)
    obs = tiny_xing4()
    if name in ("moe_pad_pct", "moe_rows_dropped"):
        assert reader.read(obs) is not None       # counters: any run has them
    else:
        assert reader.read(obs) is None           # no trace was taken
    # the parent's program: no such fact, no such scope; nothing raised
    bare = observe.Observations(cell={"chips": 1}, seed=0, seconds=1.0,
                                traced=False, device_kind="TPU v5 lite")
    assert reader.read(bare) is None and bare.problems == []


# --- the readers on a hand-written trace ---

HLO = '''HloModule jit_step, is_scheduled=true

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %mla.1 = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(Xing4LM)/block1/mla/pallas_call"}
  %fusion.2 = f32[8]{0} fusion(%mla.1), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(Xing4LM)/block1/mla/q_b/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%f, metadata={op_name="jit(step)/transpose(jvp(Xing4LM))/jvp(Xing4LM)/checkpoint/rematted_computation/block1/mhc_attn.pre/mul"}
  %fusion.4 = f32[8]{0} fusion(%fusion.3), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(Xing4LM)/block1/mhc_ffn.post/add"}
  %gmm.5 = f32[8]{0} custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(Xing4LM)/block1/moe/experts/pallas_call"}
  %fusion.6 = f32[8]{0} fusion(%gmm.5), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(Xing4LM)/block1/moe/combine/gather"}
  ROOT %fusion.7 = f32[8]{0} fusion(%fusion.6), kind=kLoop, calls=%f, metadata={op_name="jit(step)/optimizer/add"}
}
'''
#: one chip, two steps; ns per op: mla kernel 400, its projection 100, the
#: two mHC fusions 60 + 40, the experts' kernel 200, combine 50, Adam 10
DURATIONS = [("mla.1", 400), ("fusion.2", 100), ("fusion.3", 60),
             ("fusion.4", 40), ("gmm.5", 200), ("fusion.6", 50),
             ("fusion.7", 10)]


@functools.cache
def traced():
    from jax.profiler import ProfileData

    events, meta, offset = [], [], 0
    for i, (name, ns) in enumerate(DURATIONS, 1):
        events.append(f"events {{ metadata_id: {i} offset_ps: {offset * 1000} "
                      f"duration_ps: {ns * 1000} }}")
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                    f'"%{name} = f32[8]{{0}} fusion()" }} }}')
        offset += ns
    text = f"""
planes {{ name: "/device:TPU:0"
  lines {{ name: "XLA Ops" timestamp_ns: 1000 {' '.join(events)} }}
  lines {{ name: "XLA Modules" timestamp_ns: 1000
    events {{ metadata_id: 99 offset_ps: 0 duration_ps: {offset * 1000} }} }}
  {' '.join(meta)}
  event_metadata {{ key: 99 value {{ id: 99 name: "jit_step(7)" }} }}
}}"""
    obs = observe.Observations(cell={"chips": 1}, seed=0, seconds=1.0,
                               traced=True, device_kind="TPU v5 lite")
    obs.note_program(HLO)
    obs.trace = tr.reduce(tr.planes_of(ProfileData.from_text_proto(text)),
                          scopes=obs.scopes)
    obs.attempted = 2
    obs.facts.update(mla_core_flops_per_step=197e12 * 100e-9,   # 50 % of 200 ns
                     mhc_bytes_per_step=819e9 * 10e-9,          # 20 % of 50 ns
                     moe_expert_flops_per_step=197e12 * 25e-9,  # 25 % of 100 ns
                     moe_pad_pct=50.5, moe_rows_dropped=0.0)
    return obs


@pytest.mark.parametrize("name,want", [
    ("mla_ms", 250e-6), ("mla_core_roofline", 50.0), ("mhc_ms", 50e-6),
    ("mhc_roofline", 20.0), ("moe_ms", 125e-6), ("moe_experts_ms", 100e-6),
    ("moe_experts_roofline", 25.0), ("moe_pad_pct", 50.5),
    ("moe_rows_dropped", 0.0)])
def test_readers_on_a_hand_written_trace(name, want):
    obs = traced()
    got = manifest.module("layer_metrics", name).read(obs)
    assert got == pytest.approx(want)
    assert obs.problems == []


# --- the counts ---

def test_counts_against_hand_values():
    # 2 x 32 heads x 4096^2 / 2 products of unit width, x (4 x 192 + 3 x 128)
    assert xing4_counts.causal_attention_train_flops(
        2, 32, 4096, 192, 128, 5) == 2 * 2 * 32 * 4096 ** 2 / 2 * 1152 * 5
    from benchmark.lib import peaks

    assert xing4_counts.causal_attention_train_flops(
        8, 16, 1024, 64, 64, 24) == peaks.causal_attention_train_flops(
            8, 16, 1024, 64, 24)
    # 14 stream-wide rows a token forward, 14 backward, bf16, 10 sub-layers
    assert xing4_counts.mhc_bytes(8192, 4, 3584, 10) == 28 * 8192 * 3584 * 2 * 10
    assert xing4_counts.expert_flops(8192, 3584, 1024, 4) == (
        3 * 2 * 3584 * 1024 * 8192 * 3 * 4)
    config = manifest.cell(CELL)["config"]
    total = xing4_counts.train_flops(config, 2, 4096, 8192)
    parts = (xing4_counts.causal_attention_train_flops(2, 32, 4096, 192, 128, 5)
             + xing4_counts.expert_flops(8192, 3584, 1024, 4))
    mla = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 + 4096 * 3584
    per_token = (5 * (mla + 2 * 4 * 3584 * 24) + 3 * 3584 * 9216
                 + 4 * (3 * 3584 * 1024 + 3584 * 64) + 3584 * 16384)
    assert total == pytest.approx(parts + 6.0 * per_token * 8192)
    assert 20e12 < total < 30e12


# --- the comparison ---

def _pair():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((2, 8, 32)).astype(np.float32)
    chosen = [rng.integers(0, 8, (2, 8, 2))]
    grads = {"block1/moe/router": rng.standard_normal((4, 8)),
             "block1/mla/q_a/kernel": rng.standard_normal((4, 4))}
    fp32 = {"router": rng.random((4, 8))}
    return {"logits": logits, "loss": 3.0, "chosen": chosen, "grads": grads,
            "fp32": fp32}


@pytest.mark.parametrize("wrong,names", [
    (None, ()),
    ("logits", ("logit_rms_rel",)),
    ("loss", ("loss_abs",)),
    ("dense_grad", ("grad_rel:block1/mla/q_a/kernel",)),
    ("bf16_router", ("fp32_rel:router",)),
    ("routing", ("route_flips",)),
])
def test_comparison_fails_a_wrong_system(wrong, names):
    import copy

    want = _pair()
    system = copy.deepcopy(want)
    if wrong == "logits":
        system["logits"] = system["logits"] * 1.1
    elif wrong == "loss":
        system["loss"] += 0.05
    elif wrong == "dense_grad":
        system["grads"]["block1/mla/q_a/kernel"] *= 1.3
    elif wrong == "bf16_router":   # scores rounded to bf16's 8 bits
        system["fp32"]["router"] = np.round(system["fp32"]["router"] * 256) / 256
    elif wrong == "routing":       # every choice another expert
        system["chosen"] = [(c + 1) % 8 for c in system["chosen"]]
    dev, bad = ref.compare(system, want)
    assert len(bad) == len(names)
    for name in names:
        assert any(name in b for b in bad), (name, bad)
    if wrong is None:
        assert dev["route_flips"] == 0.0 and "logit_rms_rel_flipped" not in dev


def test_route_flips_counts_sets_not_orders():
    a = np.asarray([[[1, 2], [3, 4]]])
    share, flipped = ref.route_flips([a], [a[..., ::-1]])
    assert share == 0.0 and not flipped.any()
    share, flipped = ref.route_flips([a], [np.asarray([[[1, 2], [3, 5]]])])
    assert share == 0.5 and flipped.tolist() == [[False, True]]


def test_kept_assignments_keep_the_first_rows_in_expert_then_position_order():
    import jax.numpy as jnp

    sel = jnp.asarray([[5, 2], [2, 7], [5, 2], [2, 5]])
    kept = np.asarray(ref.kept_assignments(sel, [2, 5], 5))
    # expert 2 first (4 rows, all kept), then expert 5 by position: one of 3
    assert kept.tolist() == [[True, True], [True, False], [False, True],
                             [True, False]]
    assert np.asarray(ref.kept_assignments(sel, [2, 5], 99)).sum() == 7
