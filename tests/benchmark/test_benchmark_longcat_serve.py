"""The serving cell ``longcat_serve_decode_replay`` (PR 45) without a chip:
its traffic mix, the runner driven tiny on the CPU through everything
``run.py`` does after its look for a chip, the planted faults and the three
controls that have to come out as not correct, the new readers, the counts
behind ``decode_mfu_pct``, ``latent_ctx_roofline`` and
``serve_moe_experts_roofline``, and the cell's entries in the manifest.
Counts and correctness only: no CPU time stands for a chip's."""

import gc
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import longcat_serve_counts as counts  # noqa: E402
from benchmark.lib import gpt2_serve_counts, manifest, peaks, traffic  # noqa: E402
from benchmark.lib.observe import Observations  # noqa: E402
from test_benchmark_harness import assert_benchmark_invariants  # noqa: E402
from test_benchmark_runners import tiny_cell  # noqa: E402

sys.path.insert(0, str(ROOT / "benchmark" / "sweeps"))
import longcat_serve_precision as sweep  # noqa: E402

pytestmark = pytest.mark.usefixtures("light_compile")

CELL = "longcat_serve_decode_replay"
SPEC = json.loads(
    (ROOT / "benchmark/traffic/decode_replay_s128_chat.json").read_text())
CONFIG = json.loads(
    (ROOT / "benchmark/configs/longcat-flash-omni.json").read_text())
SCOPE_READERS = ("mla_decode_ms", "latent_ctx_roofline", "serve_moe_ms",
                 "serve_moe_experts_ms", "serve_moe_router_ms",
                 "serve_moe_experts_roofline", "dense_mlp_ms",
                 "longcat_outside_model_ms")
COUNTER_READERS = ("serve_moe_pad_pct", "serve_moe_rows_dropped",
                   "zero_choice_pct")
NEW_READERS = SCOPE_READERS + COUNTER_READERS
SERVE_READERS = ("decode_device_ms", "batch_occupancy_pct",
                 "serve_device_idle_pct", "preemptions", "gather_ctx_ms",
                 "write_kv_ms", "decode_call_ms", "sample_ms",
                 "decode_step_p50_ms", "decode_mfu_pct")


# -- the traffic mix ---------------------------------------------------------

def test_the_mix_is_the_issues():
    a = traffic.decode_replay(SPEC, 2 ** 31 + 5, 16384)
    b = traffic.decode_replay(SPEC, 6, 16384)
    lens = sorted(len(s.prompt) for s in a)
    assert len(a) == SPEC["sessions"] == 128
    assert (lens[0], lens[-1]) == (363, 6144) and lens[-3:] == [6144] * 3
    assert 1780 <= lens[64] <= 1810                       # median 1792
    assert sum(lens) == 270_278
    assert {s.max_new_tokens for s in a} == {1024}
    assert lens == sorted(len(s.prompt) for s in b)       # one set of lengths
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    assert len({s.prompt[:16] for s in a}) == 128         # nothing shared
    assert all(0 < t < 16384 for s in a for t in s.prompt[:8])
    assert SPEC["prompt_len"] == {"dist": "lognormal", "median": 1792,
                                  "sigma": 0.6, "min": 256, "max": 6144}


# -- the runner, tiny --------------------------------------------------------

def replay_cell(**deployment) -> dict:
    """The cell at the size ``sweeps/longcat_serve_precision.py --tiny``
    runs."""
    return tiny_cell(CELL, config=sweep.TINY["config"],
                     deployment={**sweep.TINY["deployment"], **deployment},
                     traffic=sweep.TINY["traffic"])


def drive(cell: dict, *, seconds: float = 60.0, seed: int = 2 ** 31 + 7,
          before_window=None) -> Observations:
    """What ``run.main`` does once it has found its chip."""
    obs = Observations(cell=cell, seed=seed, seconds=seconds, traced=False,
                       device_kind="TPU v5 lite")
    runner = manifest.module("runners", cell["runner"])
    session = runner.setup(obs)
    if before_window:
        before_window(session)
    obs.in_window = True
    runner.measure(obs, session, seconds)
    obs.in_window = False
    runner.finish(obs, session)
    runner.verify(obs, session)
    obs.end_to_end = runner.end_to_end(obs)
    obs.session = session
    return obs


@pytest.fixture(scope="module")
def sound() -> Observations:
    return drive(replay_cell())


def test_replay_runner_fills_every_slot_and_measures_full_steps(sound):
    obs = sound
    assert obs.problems == []
    assert obs.attempted == 4 and obs.failed == 0
    # as ``lm_serve_replay``: 1 token from the prefill, 1 + warmup_steps
    # from set-up's steps, one step short of the first retirement
    assert obs.notes["steps"] == 16 - 1 - (1 + 2) - 1 == 11
    assert obs.series["occupancy_pct"] == [100.0] * 11
    assert obs.facts["preemptions"] == 0.0
    assert obs.end_to_end["decode_step_ms"] == pytest.approx(
        1e3 * obs.facts["window_s"] / 11)
    assert {"session_prefill_s", "warmup_s", "init_s", "trace_lower_s",
            "compile_s", "after_window_check_s", "parameters",
            "decode_flops_per_step", "decode_bytes_per_step",
            "latent_ctx_flops_per_step", "latent_ctx_bytes_per_step",
            "moe_experts_bytes_per_step", "moe_experts_flops_per_step",
            "serve_moe_pad_pct", "serve_moe_rows_dropped",
            "zero_choice_pct"} <= set(obs.facts)
    # the window's one program, by the name the trace gives it, and the
    # scopes the readers look for
    assert set(obs.scopes) == {"jit_serve_decode"}
    scopes = list(obs.scopes["jit_serve_decode"].values())
    for wanted in ("/LongcatFlashLM/", "/mla0/gather_ctx/", "/mla1/write_kv/",
                   "/mla0/absorb/", "/mla1/unabsorb/", "/mlp0/gate/",
                   "/mlp1/down/", "/moe/router/", "/moe/experts/",
                   "/moe/zero/"):
        assert any(wanted in s for s in scopes), wanted
    # the reference saw the longest, the shortest and one more session
    seen = obs.notes["reference_sessions"]
    lens = dict(zip((f"s{i}" for i in range(4)), obs.notes["prompt_lens"]))
    assert len(seen) == 3 and {lens[r] for r in seen} >= {
        min(lens.values()), max(lens.values())}
    # the window's 15 a session and the one of the call dispatched ahead of
    # the step that never came, settled before the reference looks
    assert obs.notes["compared_tokens"] == 3 * 16
    compared = obs.notes["compared"]
    assert set(compared) == {"chosen_gap_rel", "chosen_logprob_mean_abs"}
    for pair in compared.values():
        assert pair["value"] < 1e-4 < pair["limit"]


def test_the_shares_counters_are_read_as_a_difference_over_the_window(sound):
    """The device's own count of the calls between the two readings (each
    waits for the call the engine holds ahead, so the window's eleven steps
    are eleven calls), every layer's counted."""
    moved = sound.notes["share_counters"]
    calls = moved["steps"]
    assert calls == 2 * 11                  # 2 layers x 11 steps
    assert moved["rows_dropped"] == 0
    assert moved["real_choices"] + moved["zero_choices"] == calls * 4 * 3
    assert (moved["buffer_rows"], moved["row_tile"]) == (16, 16)
    facts = sound.facts
    assert facts["serve_moe_rows_dropped"] == 0.0
    assert facts["zero_choice_pct"] == pytest.approx(
        100.0 * moved["zero_choices"] / (calls * 12))
    assert 15.0 < facts["zero_choice_pct"] < 55.0     # 4 of 12 outputs
    assert facts["serve_moe_pad_pct"] == pytest.approx(
        100.0 * (1 - moved["rows_held"] / calls / 16))
    # what the step needed follows the rows the router gave
    rows = moved["rows_held"] / calls
    config = sound.cell["config"]
    assert facts["moe_experts_flops_per_step"] == counts.experts_flops(
        config, rows)


def test_the_window_runs_on_a_settled_heap(sound):
    assert gc.get_freeze_count() == 0
    assert sound.session.heap_watch not in gc.callbacks


def test_the_registry_holds_the_latent_pools_gauge(sound):
    from tpu_sandbox.obs import get_registry

    snap = get_registry().snapshot()
    # 2 layers x 2 sub-layers of [65, 4, 128] float32: 40 values in 128 lanes
    assert snap["gauges"]["serve.latent_bytes"] == 4 * 65 * 4 * 128 * 4
    assert any(k.startswith("mla.cache_layout") and "pad_lanes=88" in k
               for k in snap["counters"])


def test_an_empty_slot_is_a_problem():
    obs = drive(replay_cell(max_batch=5))   # four sessions, five slots
    assert any("4 of 4 sessions hold one of 5 slots" in p for p in obs.problems)
    assert any("under full occupancy" in p for p in obs.problems)


def a_row_buffer_too_small(monkeypatch):
    """A share whose buffer keeps one row a decode step: the rest are
    dropped (a prompt's share has no buffer to starve)."""
    from tpu_sandbox.models import longcat_flash

    real = longcat_flash.expert_share

    def starved(cfg, tokens, name, whole_sequence=False):
        import dataclasses

        share = real(cfg, tokens, None, whole_sequence)
        return dataclasses.replace(share, row_tile=1, local_rows=1, name=name)

    monkeypatch.setattr(longcat_flash, "expert_share", starved)


def positions_from_zero(monkeypatch):
    """Decode rotates every new token as position 0."""
    from tpu_sandbox.serve import decode

    real = decode._decode_slots

    def wrong(cache_cfg, lengths, block_tables):
        pos, dest = real(cache_cfg, lengths, block_tables)
        return pos * 0, dest

    monkeypatch.setattr(decode, "_decode_slots", wrong)


def zero_the_pages(session):
    """Prefill's latent rows never reach decode."""
    import jax

    eng = session.eng
    eng.settle()
    eng.k_pages = type(eng.k_pages)(jax.tree.map(lambda a: a * 0,
                                                 tuple(eng.k_pages)))


@pytest.mark.parametrize("fault", ["rows_dropped", "positions_from_zero",
                                   "latent_not_handed_over"])
def test_a_planted_fault_comes_out_not_correct(fault, monkeypatch):
    """The cell's rehearsal with a new mechanism broken underneath."""
    before_window = None
    if fault == "rows_dropped":
        a_row_buffer_too_small(monkeypatch)
    elif fault == "positions_from_zero":
        positions_from_zero(monkeypatch)
    else:
        before_window = zero_the_pages
    obs = drive(replay_cell(), before_window=before_window)
    assert obs.failed == 0          # every session gained its tokens ...
    assert obs.problems, obs.notes.get("compared")     # ... the wrong ones
    if fault == "rows_dropped":
        assert any("dropped" in p for p in obs.problems)
        assert obs.facts["serve_moe_rows_dropped"] > 0
    else:
        assert {k for k, pair in obs.notes["compared"].items()
                if pair["value"] > pair["limit"]}


@pytest.mark.parametrize("seed", [2 ** 31 + 7, 6, 7])
def test_the_controls_come_out_not_correct(seed):
    """The reference with a fault in the program's place over what a sound
    run served (``sweeps/longcat_serve_precision.py``, at the size its
    ``--tiny`` runs), each through the cell's own comparison,
    ``compare_served`` with the cell's limits: the latent cached in float8
    -- one precision below the configuration's bfloat16 -- and either
    ``mla_scale_*`` factor left out come out not correct, here as on the
    chip, where the float32 program that served the tokens reads nothing."""
    cell = replay_cell()
    obs = drive(cell, seed=seed)
    assert obs.problems == []
    assert obs.notes["reference_deviation"]["chosen_logprob_mean_abs"] < 1e-5
    runner = manifest.module("runners", cell["runner"])
    reference = manifest.module("reference", cell["reference"])
    config = {**cell["config"], "deployment": cell["deployment"]}
    tree = reference.from_program_tree(obs.session.params, config)
    for name, fault in sweep.controls().items():
        dev, bad, _ = sweep.control(
            reference, runner, tree, obs.session.batch, config, 16, **fault)
        assert bad, (name, dev)


# -- the comparison ----------------------------------------------------------

def test_compare_served_reads_only_the_rows_that_count():
    from benchmark.reference import longcat_flash

    gap = np.array([[0.01, 0.02, 9.0], [0.03, 9.0, 9.0]])
    logp = np.array([[-1.0, -2.0, -50.0], [-3.0, -50.0, -50.0]])
    dev, bad = longcat_flash.compare_served(gap, logp, [2, 1], [-1.5, -3.0])
    assert dev["chosen_gap_rel"] == pytest.approx(0.03) and bad == []
    assert dev["chosen_logprob_mean_abs"] == pytest.approx(0.0)
    # a mean over the sequences: one of two 0.01 away reads 0.005
    dev, bad = longcat_flash.compare_served(gap, logp, [2, 1], [-1.51, -3.0])
    assert dev["chosen_logprob_mean_abs"] == pytest.approx(0.005) and bad
    _, bad = longcat_flash.compare_served(gap, logp, [3, 1], [-1.5, -3.0])
    assert len(bad) == 2
    assert set(longcat_flash.TOLERANCE) == {"chosen_gap_rel",
                                            "chosen_logprob_mean_abs"}


def test_the_reference_imports_nothing_of_the_program():
    text = (ROOT / "benchmark/reference/longcat_flash.py").read_text()
    assert "import tpu_sandbox" not in text
    assert "from tpu_sandbox" not in text
    assert "default_matmul_precision(\"highest\")" in text


# -- the readers -------------------------------------------------------------

@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_reads_nothing_without_its_source(name):
    obs = Observations(cell={"config": CONFIG}, seed=0, seconds=1.0,
                       traced=False, device_kind="TPU v5 lite")
    assert manifest.module("layer_metrics", name).read(obs) is None
    assert obs.problems == []


CONTEXTS = [2400.0] * 128


@pytest.mark.parametrize("name,want", [
    ("mla_decode_ms", 4.0 + 6.0 + 1.0), ("serve_moe_ms", 8.0 + 0.5 + 0.5),
    ("serve_moe_experts_ms", 8.0), ("serve_moe_router_ms", 0.5),
    ("dense_mlp_ms", 7.0), ("longcat_outside_model_ms", 0.25),
    # the larger of 2.83 GB over 819 GB/s and 0.342 TFLOP over 197 TFLOP/s,
    # over the 6 ms under gather_ctx
    ("latent_ctx_roofline", 100.0 * max(
        counts.latent_ctx_bytes(CONFIG, CONTEXTS) / 819e9,
        counts.latent_ctx_flops(CONFIG, CONTEXTS) / 197e12) / 6e-3),
    # 4.83 GB of held experts and 32 rows in and out over 8 ms
    ("serve_moe_experts_roofline",
     100.0 * counts.experts_bytes(CONFIG, 32.0) / 819e9 / 8e-3)])
def test_new_reader_reads_a_number_with_its_source(name, want, sound):
    """A hand-made reduced trace: one chip, 10 steps of the decode program
    -- a sub-layer's query path, the read of its cached rows, its output
    projection, a dense MLP, the share's router, experts and zero term, a
    copy the compiler added."""
    obs = Observations(cell=sound.cell, seed=0, seconds=1.0, traced=True,
                       device_kind="TPU v5 lite")
    layer = "jit(serve_decode)/LongcatFlashLM/block2/"
    obs.scopes = {"jit_serve_decode": {
        "fusion.1": layer + "mla0/q_b/dot_general",
        "fusion.2": layer + "mla1/gather_ctx/pallas_call",
        "fusion.3": layer + "mla1/o/dot_general",
        "fusion.4": layer + "mlp1/down/dot_general",
        "fusion.5": layer + "moe/router/dot_general",
        "fusion.6": layer + "moe/experts/pallas_call",
        "fusion.7": layer + "moe/zero/mul"}}
    obs.trace = {"devices": [{"by_program": {"jit_serve_decode": {
        "fusion.1": [40_000_000, 80], "fusion.2": [60_000_000, 80],
        "fusion.3": [10_000_000, 80], "fusion.4": [70_000_000, 80],
        "fusion.5": [5_000_000, 40], "fusion.6": [80_000_000, 120],
        "fusion.7": [5_000_000, 40], "copy.9": [2_500_000, 10]}}}]}
    obs.attempted = 128
    obs.facts.update(
        window_steps=10.0,
        latent_ctx_bytes_per_step=counts.latent_ctx_bytes(CONFIG, CONTEXTS),
        latent_ctx_flops_per_step=counts.latent_ctx_flops(CONFIG, CONTEXTS),
        moe_experts_bytes_per_step=counts.experts_bytes(CONFIG, 32.0),
        moe_experts_flops_per_step=counts.experts_flops(CONFIG, 32.0))
    assert manifest.module("layer_metrics", name).read(obs) == \
        pytest.approx(want)
    assert obs.problems == []


@pytest.mark.parametrize("name", COUNTER_READERS)
def test_a_counter_reader_gives_its_fact(name):
    obs = Observations(cell={}, seed=0, seconds=1.0, traced=False,
                       device_kind="TPU v5 lite")
    obs.facts[name] = 12.5
    assert manifest.module("layer_metrics", name).read(obs) == 12.5


# -- the counts --------------------------------------------------------------

def test_counts_of_the_published_sizes():
    """The issue's arithmetic, number for number."""
    assert counts.mla_matmul_params(CONFIG) + 1536 + 512 == 90_572_800
    assert counts.mlp_params(CONFIG) == 226_492_416
    assert counts.layer_parameters(CONFIG) == 638_874_368
    assert counts.expert_params(CONFIG) == 37_748_736
    assert counts.parameters(CONFIG) == 5_172_749_312       # 10.35 GB bf16
    assert (counts.router_width(CONFIG), counts.held_experts(CONFIG),
            counts.latent_dim(CONFIG)) == (768, 16, 576)
    assert counts.mean_held_rows(CONFIG, 128) == 32.0       # 2 an expert
    mid = [2406.0] * 128                                    # 308 k live tokens
    nbytes = counts.decode_step_bytes(CONFIG, mid)
    flops = counts.decode_step_flops(CONFIG, mid)
    assert 12.9e9 < nbytes < 13.1e9 and 1.02e12 < flops < 1.04e12
    assert 2.83e9 < counts.latent_ctx_bytes(CONFIG, mid) < 2.85e9
    assert 0.34e12 < counts.latent_ctx_flops(CONFIG, mid) < 0.35e12
    assert 4.83e9 < counts.experts_bytes(CONFIG, 32.0) < 4.84e9


def test_decode_counts_against_a_hand_count():
    cfg = {"num_layers": 2, "hidden_size": 8, "ffn_hidden_size": 16,
           "expert_ffn_hidden_size": 4, "num_attention_heads": 2,
           "q_lora_rank": 4, "kv_lora_rank": 6, "qk_nope_head_dim": 3,
           "qk_rope_head_dim": 2, "v_head_dim": 3, "n_routed_experts": 2,
           "zero_expert_num": 3, "moe_topk": 2, "vocab_size": 10,
           "deployment": {"routed_experts_total": 5, "held": [0, 1]}}
    # q_a 8x4, q_b 4x(2x5), kv_a 8x(6+2), kv_b 6x(2x6), o (2x3)x8
    mla = 32 + 40 + 64 + 72 + 48
    assert counts.mla_matmul_params(cfg) == mla
    dense = 2 * mla + 2 * 3 * 8 * 16
    router, expert = 8 * 8, 3 * 8 * 4
    assert counts.router_width(cfg) == 8
    assert counts.parameters(cfg) == 2 * (
        dense + router + 8 + 2 * (4 + 6) + 4 * 8 + 2 * expert) + 2 * 80 + 8
    # two sessions, contexts 3 and 5, 1.5 rows held a layer
    core = 2 * 2 * (2.0 * 2 * (8 + 6)) * (3 + 5)
    assert counts.latent_ctx_flops(cfg, [3, 5]) == core
    assert counts.decode_step_flops(cfg, [3, 5], 1.5) == \
        2 * 2.0 * (2 * (dense + router) + 80) + 2.0 * 2 * 1.5 * expert + core
    rows_bytes = 2 * 2 * 8 * 2 * (3 + 5)
    assert counts.latent_ctx_bytes(cfg, [3, 5]) == rows_bytes
    experts = 2 * (2 * expert * 2 + 1.5 * 2 * 8 * 2)
    assert counts.experts_bytes(cfg, 1.5) == experts
    assert counts.decode_step_bytes(cfg, [3, 5], 1.5) == \
        2 * (dense * 2 + router * 4) + 80 * 2 + experts + rows_bytes \
        + 2 * (2 * 2 * 8 * 2) + 2 * (8 * 2 + 4 * 10)
    # the even router's mean where no rows are given: 2 x 2 x 2 / 8
    assert counts.mean_held_rows(cfg, 2) == 1.0


def test_decode_mfu_is_100_at_the_rooflines_own_time():
    contexts = [len(s.prompt) + 300
                for s in traffic.decode_replay(SPEC, 1, 16384)]
    flops = counts.decode_step_flops(CONFIG, contexts)
    nbytes = counts.decode_step_bytes(CONFIG, contexts)
    peak = peaks.peak("TPU v5 lite")
    least = gpt2_serve_counts.roofline_s(flops, nbytes, peak)
    assert least == nbytes / peak["hbm_bytes_per_s"]        # bytes bind
    assert 15.5e-3 < least < 16.3e-3                        # the issue's 16 ms
    obs = Observations(cell={}, seed=0, seconds=1.0, traced=False,
                       device_kind="TPU v5 lite")
    obs.facts.update(decode_flops_per_step=flops, decode_bytes_per_step=nbytes,
                     window_steps=10.0, window_s=10.0 * least)
    read = manifest.module("layer_metrics", "decode_mfu_pct").read
    assert read(obs) == pytest.approx(100.0)


# -- the manifest ------------------------------------------------------------

def test_the_cell_is_in_the_manifest_as_the_issue_sets_it():
    assert manifest.validate() == []
    assert_benchmark_invariants(ROOT)
    m = manifest.load()
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == [
        "convnet3000_dp4_bs5"]
    assert len(m["workloads"]) >= 9
    cell = manifest.cell(CELL)
    assert (cell["config_name"], cell["chips"], cell["runner"],
            cell["reference"]) == ("longcat-flash-omni", 1,
                                   "longcat_serve_replay", "longcat_flash")
    dep = cell["deployment"]
    assert (dep["dtype"], dep["param_dtype"], dep["cache_dtype"]) == (
        "bf16", "bf16", "bf16")
    assert (dep["max_batch"], dep["block_size"], dep["max_blocks_per_seq"]) \
        == (128, 16, 448)
    assert (dep["chips_sharing_a_layer"], dep["routed_experts_total"],
            dep["held"]) == (32, 512, list(range(16)))
    # every session can hold its prompt + 1024 positions beside the null
    # block
    lens = [len(s.prompt) for s in traffic.decode_replay(SPEC, 3, 16384)]
    need = sum(-(-(n + 1024) // 16) for n in lens) + 1
    assert need == 25_145 <= dep["num_blocks"] < need + 16
    assert dep["block_size"] * dep["max_blocks_per_seq"] == 6144 + 1024
    assert dep["prefill_buckets"] == [512, 1024, 2048, 4096, 6144]
    assert dep["reference_sessions"] >= 16
    assert cell["traffic"] == SPEC
    assert (SPEC["sessions"], SPEC["max_new_tokens"], SPEC["warmup_steps"]) \
        == (128, 1024, 2)
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert CELL in e2e["decode_step_ms"]["workloads"]
    assert e2e["decode_step_ms"]["bound"] == 0.02
    assert {x["name"] for x in cell["end_to_end"]} == {"decode_step_ms",
                                                       "setup_s"}
    mine = {x["name"]: x for x in cell["per_layer"]}
    assert set(mine) == {"init_s", "trace_lower_s", "compile_s",
                         *SERVE_READERS, *NEW_READERS}
    for name in NEW_READERS:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "decode_step_ms"
        assert mine[name]["source"] == (
            "program_counter" if name in COUNTER_READERS else "device_trace")
    # the other families' lists stay theirs
    per_layer = {x["name"]: x for x in m["per_layer"]}
    for name in ("mamba_mixer_ms", "jamba_outside_model_ms",
                 "decode_outside_model_ms", "moe_ms", "latent_moe_ms",
                 "mla_ms"):
        assert CELL not in per_layer[name]["workloads"]
    why = next(w["why"] for w in m["workloads"] if w["name"] == CELL)
    assert "1/32" in why and len(why) <= 200


def test_jambas_manifest_test_but_for_the_pinned_list():
    """``test_benchmark_jamba_serve.py``'s manifest test (PR 41) pins
    ``decode_step_ms``'s cells to exactly two with ``==``. A third serving
    cell has to append its name to that list and a file under
    ``tests/benchmark/`` is a ``benchmark`` PR's to edit, so that test is
    ``xfail`` (strict, ``tests/conftest.py``). Here its own body runs as it
    stands in its file, every assertion in its order and with its module's
    constants, with that one line -- still there, once -- turned into a
    prefix: the marker stands for one line and no more."""
    import inspect

    import test_benchmark_jamba_serve as pinned

    line = '["workloads"] == [GPT2_CELL, CELL]'
    name = "test_the_cell_is_in_the_manifest_as_the_issue_sets_it"
    source = inspect.getsource(getattr(pinned, name))
    assert source.count(line) == 1
    scope = dict(vars(pinned))
    exec(source.replace(line, '["workloads"][:2] == [GPT2_CELL, CELL]'),
         scope)
    scope[name]()


def test_the_accepted_serving_cells_lists_stay_theirs():
    """Beyond what the pinned test held: this cell's readers are not
    Jamba's, and the serve readers' lists begin with the accepted cells."""
    per_layer = {x["name"]: x for x in manifest.load()["per_layer"]}
    accepted = ["gpt2m_serve_decode_replay", "jamba2_serve_decode_replay"]
    for name in NEW_READERS:
        assert per_layer[name]["workloads"] == [CELL]
    for name in SERVE_READERS:
        assert per_layer[name]["workloads"][:2] == accepted


def test_the_configuration_is_the_catalogs_row_but_for_three_keys():
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "longcat-flash-omni")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/meituan-longcat/"
                               "LongCat-Flash-Omni/blob/main/config.json")
    assert entry["source"] in CONFIG["source"]
    # `manifest.validate()` holds a cell's `why` to 200 characters and not a
    # configuration's; the driver holds both (it refused this entry at 209)
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    row = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    cut = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}
    assert {k: CONFIG[k] for k in row} == {**row, **cut}
    assert {k: CONFIG["published"][k] for k in cut} == {k: row[k] for k in cut}
    dep = CONFIG["deployment"]
    assert dep["chips_sharing_a_layer"] == 32 and dep["pipeline_stages"] == 7
    assert "32 chips share each layer" in dep["stands_for"]
    assert {"norm_topk_prob", "router_bias", "hidden_act", "head",
            "rope_pairing", "e_score_correction_bias", "weights", "sampling",
            "precision"} <= set(CONFIG["assumed"])
    # the floors a model_config PR keeps to
    assert CONFIG["num_layers"] >= 4 and CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= row["vocab_size"]
