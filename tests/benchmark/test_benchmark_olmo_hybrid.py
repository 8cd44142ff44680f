"""The Olmo-Hybrid cell: its files resolve and say what the contract asks,
its runner drives the program's ``lm_train.build`` at a tiny size on the
CPU, its readers return numbers on a hand-written trace and nothing from a
program without their scopes, its counts are what a hand computes. Numbers
from these runs are counts and correctness only."""

import functools
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark.lib import manifest, observe, olmo_hybrid_counts  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402
from benchmark.reference import olmo_hybrid as ref  # noqa: E402

CELL = "olmoh_train_s8192"
CONFIG = "olmo-hybrid-7b"
NEW_METRICS = ("gdn_ms", "delta_rule_ms", "delta_rule_roofline")
LINEAR, FULL = "linear_attention", "full_attention"
REDUCED = {"num_hidden_layers": 4, "layer_types": [LINEAR] * 3 + [FULL],
           "vocab_size": 12544}
SOURCE = "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
#: the catalog's ``config`` of the model (model-configs guide)
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": ([LINEAR] * 3 + [FULL]) * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


def test_the_cell_resolves_and_reports_its_metrics():
    cell = manifest.cell(CELL)
    assert cell["chips"] == 1 and cell["runner"] == "olmo_hybrid_train"
    assert cell["reference"] == "olmo_hybrid"
    assert cell["traffic"]["seq_len"] == 8192 and cell["traffic"]["batch"] == 1
    assert cell["traffic"]["steps_per_chunk"] == 1
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_METRICS) <= reported
    assert {"device_step_ms", "pallas_ms", "mfu_pct", "place_batch_ms",
            "state_place_s", "model_init_s", "opt_init_s", "loader_wait_ms",
            "device_idle_pct", "compile_cache_misses", "attn_ms",
            "flash_attn_roofline", "kernel_trace_s", "kernel_trace_sites",
            "step_trace_s", "step_lower_s", "step_backend_s", "init_programs",
            "init_compile_s"} <= reported
    assert not {m for m in reported if m.startswith(
        ("ssd_", "ssm_", "moe_", "latent_moe_", "mla_", "mhc_"))}
    assert {m["name"] for m in cell["end_to_end"]} == {"train_step_ms", "setup_s"}
    entries = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in NEW_METRICS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "train_step_ms"
        assert entries[name]["layer"] == "linear-attention layers"
        assert entries[name]["source"] == "device_trace"
    assert entries["delta_rule_roofline"]["unit"] == "%"
    cells = manifest.load()["workloads"]
    # an invariant, not today's contents: later PRs append their cells
    assert len(cells) >= 6 and cells[5]["name"] == CELL
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    assert manifest.validate() == []


def test_the_configuration_holds_every_published_key():
    config = manifest.cell(CELL)["config"]
    assert SOURCE in config["source"] and "olmo_hybrid" in config["source"]
    assert config["reduced"] == list(REDUCED)
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
    for key, value in REDUCED.items():
        assert key in config["reduced_how"]
        assert config["published"][key] == PUBLISHED[key] != value
    assert config["layer_types"] == PUBLISHED["layer_types"][:4]
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # no width among the cuts
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]


def test_the_configuration_states_its_deployment_and_assumptions():
    config = manifest.cell(CELL)["config"]
    dep = config["deployment"]
    for words in ("8 pipeline stages", "one period", "divided by rows",
                  "chip 0", "No layer is divided", "all 30 heads"):
        assert words in dep["stands_for"], words
    assert (dep["dtype"], dep["remat"], dep["flash"]) == ("bf16", True, True)
    assert dep["delta_rule_chunk"] == 64 and dep["learning_rate"] == 3e-4
    entry = next(c for c in manifest.load()["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE and entry["reduced"] == config["reduced"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    for topic in ("block", "attention", "gdn", "gdn_init", "sequence",
                  "weights", "optimizer"):
        assert config["assumed"][topic]
    assert "no rotary embedding" in config["assumed"]["attention"]
    assert "reordered norm" in config["assumed"]["block"]
    assert "no bias" in config["assumed"]["gdn"]


def test_the_reference_imports_nothing_of_the_program():
    text = (ROOT / "benchmark/reference/olmo_hybrid.py").read_text()
    assert "tpu_sandbox" not in text.replace(
        "``tpu_sandbox/models/olmo_hybrid.py``", "")
    assert "import flax" not in text and "pallas_call" not in text
    assert "solve_triangular" not in text and "cumsum" not in text
    assert "lax.scan(step" in text          # the recurrence, token by token
    assert ref.TOLERANCE and all(v > 0 for v in ref.TOLERANCE.values())
    assert ref.TOLERANCE["fp32_rel"] <= 1e-5


# --- the runner at a tiny size ---

@functools.cache
def tiny_config():
    from tests.test_olmo_hybrid_model import TINY

    return {k: v for k, v in TINY.items() if k != "deployment"}


@functools.cache
def tiny_run():
    from test_benchmark_runners import drive, tiny_cell

    cell = tiny_cell(
        CELL, config=tiny_config(),
        deployment={"dtype": "fp32", "remat": False, "delta_rule_chunk": 8,
                    "reference_head_block": 2, "reference_scan_segment": 8,
                    "reference_token_block": 16},
        traffic={"batch": 2, "seq_len": 32, "steps_per_chunk": 1})
    return drive(cell, seconds=1.5)


def test_runner_tiny():
    obs = tiny_run()
    # 64 tokens a step: whether a noisy loss fell is not this test's subject
    assert [p for p in obs.problems if "did not lower the loss" not in p
            and "no Pallas attention kernel" not in p] == []
    assert obs.attempted >= 2 and obs.failed == 0
    assert obs.end_to_end["train_step_ms"] > 0
    dev = obs.notes["reference_deviation"]
    assert dev["logit_rms_rel"] < 1e-4 and dev["loss_abs"] < 1e-4
    grads = {k: v for k, v in dev.items() if k.startswith("grad_rel:")}
    assert len(grads) == 11
    assert {k.split("/", 1)[1] for k in grads} == {
        "gdn/A_log", "gdn/dt_bias", "gdn/b/kernel", "gdn/conv_kernel",
        "gdn/q/kernel", "gdn/v/kernel", "gdn/norm_scale", "attn/q/kernel",
        "attn/k/kernel", "mlp/down/kernel", "embedding"}
    assert max(grads.values()) < 1e-3, grads
    fp32 = {k: v for k, v in dev.items() if k.startswith("fp32_rel:")}
    assert set(fp32) == {"fp32_rel:beta", "fp32_rel:log_decay",
                         "fp32_rel:decay", "fp32_rel:inverse"}
    assert max(fp32.values()) < 1e-5, fp32
    for fact in ("flops_per_step", "attn_flops_per_step",
                 "delta_rule_flops_per_step", "delta_rule_bytes_per_step"):
        assert obs.facts[fact] > 0


@pytest.mark.parametrize("pattern", [
    r"/gdn/", r"/gdn/in_proj/(q|k|v|g|b|a)/", r"/gdn/conv", r"/gdn/gates",
    r"/gdn/delta_rule", r"/gdn/norm", r"/gdn/out_proj", r"/gdn/post_norm",
    r"/attn/(q|k|v|o)/", r"/attn/(q|k)_norm", r"/mlp/(gate|up|down)/",
    r"lm_head", r"(^|/)optimizer(/|$)", r"loss"])
def test_the_compiled_step_carries_the_scopes_the_readers_match(pattern):
    import re

    obs = tiny_run()
    (program, scopes), = obs.scopes.items()
    assert program == "jit_step"
    assert any(re.search(pattern, s) for s in scopes.values())


def test_the_rule_is_recorded_as_a_kernel_site_is():
    """``trace:kernel`` spans and the static counter, readable without a
    chip: three linear layers, forward of the step and of the check's
    forward program."""
    from tpu_sandbox.obs import get_registry

    tiny_run()
    snap = get_registry().snapshot()
    cfg = tiny_config()
    labels = {"impl": "jnp", "heads": cfg["linear_num_key_heads"],
              "key_dim": cfg["linear_key_head_dim"],
              "value_dim": cfg["linear_value_head_dim"], "chunk": 8,
              "tokens": 64, "inverse": "block_doubling"}
    assert get_registry().counter("delta_rule.chunk_choice",
                                  labels=labels).value >= 3
    sites = {k: h["count"] for k, h in snap["histograms"].items()
             if k.startswith("trace.kernel_s{kernel=delta_rule,")}
    assert any("under=compile:lower_step" in k for k in sites), sites
    assert sum(sites.values()) >= 3


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_read_nothing_from_an_untraced_run_or_a_program_without_them(name):
    reader = manifest.module("layer_metrics", name)
    assert reader.read(tiny_run()) is None          # no trace was taken
    # the parent's program: no such fact, no such scope; nothing raised
    bare = observe.Observations(cell={"chips": 1}, seed=0, seconds=1.0,
                                traced=False, device_kind="TPU v5 lite")
    assert reader.read(bare) is None and bare.problems == []
    # a traced run of a program without the mixer: no fact, so no scope is
    # looked for and no problem is written
    other = traced(with_facts=False)
    assert reader.read(other) is None and other.problems == []


def test_the_runner_refuses_a_program_without_the_model(monkeypatch):
    import lm_train

    runner = manifest.module("runners", "olmo_hybrid_train")
    monkeypatch.setattr(lm_train, "CONFIG_MODELS", {"xing4": None})
    with pytest.raises(SystemExit, match="builds no olmo_hybrid model"):
        runner.build(manifest.cell(CELL), 0, [])


# --- the readers on a hand-written trace ---

HLO = '''HloModule jit_step, is_scheduled=true

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(OlmoHybridLM)/block0/gdn/in_proj/q/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(OlmoHybridLM)/block0/gdn/delta_rule/...ij,...jv->...iv/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%f, metadata={op_name="jit(step)/transpose(jvp(OlmoHybridLM))/block0/checkpoint/gdn/delta_rule/while/body/dot_general"}
  %fusion.4 = f32[8]{0} fusion(%fusion.3), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(OlmoHybridLM)/block0/gdn/norm/checkpoint/mul"}
  %flash.5 = f32[8]{0} custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(OlmoHybridLM)/block3/attn/pallas_call"}
  %fusion.6 = f32[8]{0} fusion(%flash.5), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(OlmoHybridLM)/block3/attn/o/dot_general"}
  ROOT %fusion.7 = f32[8]{0} fusion(%fusion.6), kind=kLoop, calls=%f, metadata={op_name="jit(step)/optimizer/add"}
}
'''
#: one chip, two steps; ns per op
DURATIONS = [("fusion.1", 100), ("fusion.2", 300), ("fusion.3", 100),
             ("fusion.4", 60), ("flash.5", 200), ("fusion.6", 40),
             ("fusion.7", 10)]


@functools.cache
def _reduced():
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
    return tr.planes_of(ProfileData.from_text_proto(text))


def traced(with_facts=True):
    obs = observe.Observations(cell={"chips": 1}, seed=0, seconds=1.0,
                               traced=True, device_kind="TPU v5 lite")
    obs.note_program(HLO)
    obs.trace = tr.reduce(_reduced(), scopes=obs.scopes)
    obs.attempted = 2
    if with_facts:
        obs.facts.update(
            delta_rule_flops_per_step=197e12 * 10e-9,    # 5 % of 200 ns
            delta_rule_bytes_per_step=819e9 * 50e-9,     # 25 %: binds
            attn_flops_per_step=197e12 * 25e-9)          # 25 % of 100 ns
    return obs


@pytest.mark.parametrize("name,want", [
    ("gdn_ms", 280e-6), ("delta_rule_ms", 200e-6),
    ("delta_rule_roofline", 25.0), ("attn_ms", 120e-6),
    ("flash_attn_roofline", 25.0)])
def test_readers_on_a_hand_written_trace(name, want):
    obs = traced()
    got = manifest.module("layer_metrics", name).read(obs)
    assert got == pytest.approx(want)
    assert obs.problems == []


# --- the counts ---

def test_counts_against_hand_values():
    counts = olmo_hybrid_counts
    config = manifest.cell(CELL)["config"]
    assert counts.layer_counts(config["layer_types"]) == {LINEAR: 3, FULL: 1}
    # 30 heads x 8192^2 / 2 products of unit width, x 7 x 128, one layer
    attn = counts.causal_attention_train_flops(1, 30, 8192, 128, 128, 1)
    assert attn == 2 * 30 * 8192 ** 2 / 2 * 7 * 128
    # a token a head: decay 1, S k 2, the write 2, S q 2, on a 192 x 96 state
    rule = counts.delta_rule_flops(8192, 30, 96, 192, 3)
    assert rule == 7 * 96 * 192 * 30 * 8192 * 3 * 3
    # q, k 96 and v, o 192 wide in bf16, g and beta float32, a head a token
    moved = counts.delta_rule_bytes(8192, 30, 96, 192, 3)
    assert moved == 3 * 30 * ((2 * 96 + 2 * 192) * 2 + 8) * 8192 * 3
    assert 2.5e9 < moved < 2.6e9
    assert moved / 819e9 > rule / 197e12            # the bytes bind
    linear = 3840 * (2 * 2880 + 2 * 5760 + 2 * 30) + 5760 * 3840
    per_token = (3 * linear + 4 * 3840 * 3840 + 4 * 3 * 3840 * 11008
                 + 3840 * 12544)
    total = counts.train_flops(config, 1, 8192)
    assert total == pytest.approx(6.0 * per_token * 8192 + attn + rule)
    assert 45.0e12 < total < 45.6e12
    # the matrices above are the model's parameters less the embedding, the
    # taps, the norms and the decay's scalars
    assert per_token == 928_862_196 - 12544 * 3840 - 3 * (46080 + 60 + 192) \
        - 8 * 3840 - 2 * 3840 - 3840


def test_the_precision_sweep_reads_the_cell_through_its_comparison():
    """``sweeps/olmo_hybrid_precision.py`` (needs the chip): the float8
    reading of ``TOLERANCE`` is made by the runner's own hooks and the
    reference's own ``compare``."""
    text = (ROOT / "benchmark/sweeps/olmo_hybrid_precision.py").read_text()
    for words in (f'CELL = "{CELL}"', "float8_e4m3fn", "reference.compare(",
                  "runner.reference_hooks(", "runner.gradients("):
        assert words in text, words
    assert json.loads((ROOT / "BENCHMARK.json").read_text())["run_seconds"] == 10
