"""The Nemotron-H cell: its files resolve and say what the contract asks,
its runner drives the program's ``lm_train.build`` at a tiny size on the
CPU, its readers return numbers on a hand-written trace and nothing from a
program without their scopes, its counts are what a hand computes. Numbers
from these runs are counts and correctness only."""

import functools
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark.lib import manifest, nemotron_h_counts, observe  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402
from benchmark.reference import nemotron_h as ref  # noqa: E402

CELL = "nemotron3s_train_s8192"
CONFIG = "nemotron-3-super-120b-a12b"
NEW_METRICS = ("ssm_ms", "ssd_ms", "ssd_roofline", "moe_router_ms",
               "moe_latent_ms", "latent_moe_ms", "latent_moe_experts_ms",
               "latent_moe_experts_roofline", "latent_moe_pad_pct",
               "latent_moe_rows_dropped")
REDUCED = {"num_hidden_layers": 11, "hybrid_override_pattern": "EMEMEMEMEM*",
           "mamba_num_heads": 64, "n_groups": 4, "num_attention_heads": 16,
           "num_key_value_heads": 1, "n_routed_experts": 8,
           "vocab_size": 16384, "num_nextn_predict_layers": 0}
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/"
          "blob/main/config.json")
#: the catalog's ``config`` of the model (model-configs guide)
PUBLISHED = {'attention_bias': False,
 'chunk_size': 128,
 'conv_kernel': 4,
 'expand': 2,
 'head_dim': 128,
 'hidden_size': 4096,
 'hybrid_override_pattern': 'MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME',
 'intermediate_size': 2688,
 'layer_norm_epsilon': 1e-05,
 'mamba_head_dim': 64,
 'mamba_hidden_act': 'silu',
 'mamba_num_heads': 128,
 'mamba_proj_bias': False,
 'max_position_embeddings': 262144,
 'mlp_bias': False,
 'mlp_hidden_act': 'relu2',
 'model_type': 'nemotron_h',
 'moe_intermediate_size': 2688,
 'moe_latent_size': 1024,
 'moe_shared_expert_intermediate_size': 5376,
 'moe_shared_expert_overlap': False,
 'mtp_hybrid_override_pattern': '*E',
 'n_group': 1,
 'n_groups': 8,
 'n_routed_experts': 512,
 'n_shared_experts': 1,
 'norm_eps': 1e-05,
 'norm_topk_prob': True,
 'num_attention_heads': 32,
 'num_experts_per_tok': 22,
 'num_hidden_layers': 88,
 'num_key_value_heads': 2,
 'num_logits_to_keep': 1,
 'num_nextn_predict_layers': 1,
 'partial_rotary_factor': 1,
 'rescale_prenorm_residual': True,
 'residual_in_fp32': False,
 'rope_theta': 10000,
 'routed_scaling_factor': 5,
 'sliding_window': None,
 'ssm_state_size': 128,
 'tie_word_embeddings': False,
 'time_step_floor': 0.0001,
 'time_step_max': 0.1,
 'time_step_min': 0.001,
 'topk_group': 1,
 'use_bias': False,
 'use_conv_bias': True,
 'use_mamba_kernels': True,
 'vocab_size': 131072}


def test_the_cell_resolves_and_reports_its_metrics():
    cell = manifest.cell(CELL)
    assert cell["chips"] == 1 and cell["runner"] == "nemotron_h_train"
    assert cell["reference"] == "nemotron_h"
    assert cell["traffic"]["seq_len"] == 8192 and cell["traffic"]["batch"] == 1
    assert cell["traffic"]["steps_per_chunk"] == 1
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) <= reported
    assert {"device_step_ms", "pallas_ms", "mfu_pct", "place_batch_ms",
            "state_place_s", "model_init_s", "opt_init_s", "loader_wait_ms",
            "device_idle_pct", "compile_cache_misses", "attn_ms",
            "flash_attn_roofline"} <= reported
    assert {m["name"] for m in cell["end_to_end"]} == {"train_step_ms", "setup_s"}
    entries = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in NEW_METRICS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "train_step_ms"
        if name.endswith("_roofline"):
            assert entries[name]["unit"] == "%"
    cells = manifest.load()["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


def test_the_configuration_holds_every_published_key():
    config = manifest.cell(CELL)["config"]
    assert SOURCE in config["source"]
    assert set(config["reduced"]) == set(REDUCED)
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
    for key, value in REDUCED.items():
        assert key in config["reduced_how"]
        assert config["published"][key] == PUBLISHED[key] != value
    # no width among the cuts
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]


def test_the_configuration_states_its_deployment_and_assumptions():
    config = manifest.cell(CELL)["config"]
    dep = config["deployment"]
    for words in ("512 chips", "8 pipeline stages", "64 chips share each layer",
                  "expert-parallel 64 ways", "split 2 ways",
                  "vocabulary 8 ways", "chip 0"):
        assert words in dep["stands_for"], words
    assert dep["held"] == list(range(8)) and dep["local_rows_factor"] == 2
    assert dep["routed_experts_total"] == 512
    entry = next(c for c in manifest.load()["configs"] if c["name"] == CONFIG)
    assert entry["source"] in config["source"] and "nemotron_h" in config["source"]
    assert entry["reduced"] == config["reduced"]
    for topic in ("block", "mamba", "mamba_init", "attention", "router",
                  "router_bias_update", "latent_moe", "local_rows", "mtp",
                  "weights", "optimizer"):
        assert config["assumed"][topic]
    assert "NO positional encoding" in config["assumed"]["attention"]


def test_the_reference_imports_nothing_of_the_program():
    text = (ROOT / "benchmark/reference/nemotron_h.py").read_text()
    assert "tpu_sandbox" not in text.replace(
        "``tpu_sandbox/models/nemotron_h.py``", "")
    assert "import flax" not in text and "pallas" not in text
    assert "lax.scan(step" in text          # the recurrence, token by token
    assert ref.TOLERANCE and all(v > 0 for v in ref.TOLERANCE.values())


# --- the runner at a tiny size ---

TINY = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 4,
        "hybrid_override_pattern": "EM*E", "mamba_num_heads": 4,
        "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
        "chunk_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "n_routed_experts": 16, "num_experts_per_tok": 4,
        "moe_intermediate_size": 48, "moe_latent_size": 32,
        "moe_shared_expert_intermediate_size": 96}


@functools.cache
def tiny_run():
    from test_benchmark_runners import drive, tiny_cell

    cell = tiny_cell(
        CELL, config=TINY,
        deployment={"held": [0, 1, 2, 3], "routed_experts_total": 16,
                    "dtype": "fp32", "remat": False,
                    "reference_head_block": 1, "reference_scan_segment": 8},
        traffic={"batch": 2, "seq_len": 16, "steps_per_chunk": 1})
    return drive(cell, seconds=1.5)


def test_runner_tiny():
    obs = tiny_run()
    # 32 tokens a step: whether a noisy loss fell is not this test's subject
    assert [p for p in obs.problems if "did not lower the loss" not in p
            and "no Pallas attention kernel" not in p] == []
    assert obs.attempted >= 2 and obs.failed == 0
    assert obs.end_to_end["train_step_ms"] > 0
    dev = obs.notes["reference_deviation"]
    assert dev["logit_rms_rel"] < 1e-4 and dev["loss_abs"] < 1e-4
    assert dev["route_flips"] == 0.0
    grads = {k: v for k, v in dev.items() if k.startswith("grad_rel:")}
    assert len(grads) == 13
    assert {k.rsplit("/", 1)[1] for k in grads} >= {
        "A_log", "dt_bias", "D", "conv_kernel", "norm_scale", "router", "w_down"}
    assert max(grads.values()) < 1e-3, grads
    fp32 = {k: v for k, v in dev.items() if k.startswith("fp32_rel:")}
    assert set(fp32) == {"fp32_rel:router", "fp32_rel:time_step",
                         "fp32_rel:decay"}
    assert max(fp32.values()) < 1e-5, fp32
    rows = obs.notes["moe_rows"]
    assert rows["local_rows"] == 256 and rows["dropped_per_step"] == 0.0
    assert 0 < rows["held_per_layer_step"] <= 2 * 16 * 4
    assert 0 <= obs.facts["moe_pad_pct"] < 100
    for fact in ("flops_per_step", "attn_flops_per_step", "ssd_flops_per_step",
                 "ssd_bytes_per_step", "moe_expert_flops_per_step"):
        assert obs.facts[fact] > 0


@pytest.mark.parametrize("pattern", [
    r"/mamba/", r"/mamba/ssd", r"/mamba/in_proj", r"/mamba/conv",
    r"/mamba/norm", r"/mamba/out_proj", r"/attn/", r"/moe/", r"/moe/router",
    r"/moe/latent_(down|up)", r"/moe/dispatch", r"/moe/experts",
    r"/moe/combine", r"/moe/shared", r"lm_head", r"(^|/)optimizer(/|$)",
    r"loss"])
def test_the_compiled_step_carries_the_scopes_the_readers_match(pattern):
    import re

    obs = tiny_run()
    (program, scopes), = obs.scopes.items()
    assert program == "jit_step"
    assert any(re.search(pattern, s) for s in scopes.values())


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_read_nothing_from_an_untraced_run_or_a_program_without_them(name):
    reader = manifest.module("layer_metrics", name)
    obs = tiny_run()
    if name in ("latent_moe_pad_pct", "latent_moe_rows_dropped"):
        assert reader.read(obs) is not None       # counters: any run has them
    else:
        assert reader.read(obs) is None           # no trace was taken
    # the parent's program: no such fact, no such scope; nothing raised
    bare = observe.Observations(cell={"chips": 1}, seed=0, seconds=1.0,
                                traced=False, device_kind="TPU v5 lite")
    assert reader.read(bare) is None and bare.problems == []


def test_the_runner_refuses_a_program_without_the_model(monkeypatch):
    import lm_train

    runner = manifest.module("runners", "nemotron_h_train")
    monkeypatch.setattr(lm_train, "CONFIG_MODELS", {"xing4": None})
    with pytest.raises(SystemExit, match="builds no nemotron_h model"):
        runner.build(manifest.cell(CELL), 0, [])


# --- the readers on a hand-written trace ---

HLO = '''HloModule jit_step, is_scheduled=true

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(NemotronHLM)/block1/mamba/in_proj/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(NemotronHLM)/block1/mamba/ssd/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%f, metadata={op_name="jit(step)/transpose(jvp(NemotronHLM))/block1/mamba/ssd/exp"}
  %fusion.4 = f32[8]{0} fusion(%fusion.3), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(NemotronHLM)/block2/moe/router/top_k"}
  %fusion.5 = f32[8]{0} fusion(%fusion.4), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(NemotronHLM)/block2/moe/latent_down/dot_general"}
  %gmm.6 = f32[8]{0} custom-call(%fusion.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(NemotronHLM)/block2/moe/experts/pallas_call"}
  %fusion.7 = f32[8]{0} fusion(%gmm.6), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(NemotronHLM)/block2/moe/latent_up/dot_general"}
  ROOT %fusion.8 = f32[8]{0} fusion(%fusion.7), kind=kLoop, calls=%f, metadata={op_name="jit(step)/optimizer/add"}
}
'''
#: one chip, two steps; ns per op
DURATIONS = [("fusion.1", 100), ("fusion.2", 300), ("fusion.3", 100),
             ("fusion.4", 80), ("fusion.5", 40), ("gmm.6", 200),
             ("fusion.7", 20), ("fusion.8", 10)]


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
    obs.facts.update(ssd_flops_per_step=197e12 * 10e-9,         # 5 % of 200 ns
                     ssd_bytes_per_step=819e9 * 50e-9,          # 25 %: binds
                     moe_expert_flops_per_step=197e12 * 25e-9,  # 25 % of 100 ns
                     moe_pad_pct=50.5, moe_rows_dropped=3.0)
    return obs


@pytest.mark.parametrize("name,want", [
    ("ssm_ms", 250e-6), ("ssd_ms", 200e-6), ("ssd_roofline", 25.0),
    ("moe_router_ms", 40e-6), ("moe_latent_ms", 30e-6),
    ("latent_moe_ms", 170e-6), ("latent_moe_experts_ms", 100e-6),
    ("latent_moe_experts_roofline", 25.0), ("latent_moe_pad_pct", 50.5),
    ("latent_moe_rows_dropped", 3.0)])
def test_readers_on_a_hand_written_trace(name, want):
    obs = traced()
    got = manifest.module("layer_metrics", name).read(obs)
    assert got == pytest.approx(want)
    assert obs.problems == []


# --- the counts ---

def test_counts_against_hand_values():
    counts = nemotron_h_counts
    # 16 heads x 8192^2 / 2 products of unit width, x 7 x 128, one layer
    assert counts.causal_attention_train_flops(
        1, 16, 8192, 128, 128, 1) == 2 * 16 * 8192 ** 2 / 2 * 7 * 128
    # two products of 2 x 1024 x 2688 a row, forward and twice backward
    assert counts.expert_flops(5632, 1024, 2688, 5) == (
        2 * 2 * 1024 * 2688 * 5632 * 3 * 5)
    # a chunk: C B^T a group, (L o CB)(dt x), the state, C h a head
    a_chunk = (2 * 128 * 128 * 128 * 4 + 2 * 128 * 128 * 64 * 64
               + 2 * 2 * 128 * 64 * 128 * 64)
    assert counts.ssd_flops(8192, 64, 64, 128, 4, 128, 5) == a_chunk * 64 * 3 * 5
    # x and y 4096 wide, B and C 512 wide in bf16, dt 64 float32, a token
    assert counts.ssd_bytes(8192, 64, 64, 128, 4, 5) == (
        3 * (2 * 4096 * 2 + 2 * 512 * 2 + 64 * 4) * 8192 * 5)
    config = manifest.cell(CELL)["config"]
    total = counts.train_flops(config, 1, 8192, 5632)
    mamba = 4096 * (2 * 4096 + 2 * 4 * 128 + 64) + 4096 * 4096
    attn = 4096 * 16 * 128 + 4096 * 2 * 128 + 16 * 128 * 4096
    moe = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    per_token = 5 * mamba + attn + 5 * moe + 4096 * 16384
    parts = (counts.causal_attention_train_flops(1, 16, 8192, 128, 128, 1)
             + counts.expert_flops(5632, 1024, 2688, 5)
             + counts.ssd_flops(8192, 64, 64, 128, 4, 128, 5))
    assert total == pytest.approx(parts + 6.0 * per_token * 8192)
    assert 33.0e12 < total < 33.6e12
    # the parameters the matrices above hold, with the experts held here
    held = (5 * (mamba + 2 * 5120 * 4 // 2 + 5120 + 3 * 64 + 4096 + 4096)
            + attn + 4096 + 5 * (moe + 8 * 2 * 1024 * 2688 + 4096)
            + 2 * 4096 * 16384 + 4096)
    assert held == 919_013_312
