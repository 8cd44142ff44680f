"""The serving cell ``jamba2_serve_decode_replay`` (PR 41) without a chip:
its traffic mix, the runner driven tiny on the CPU through everything
``run.py`` does after its look for a chip, the planted faults and the two
controls that have to come out as not correct, the new readers, the counts
behind ``decode_mfu_pct`` and ``mamba_state_roofline``, and the cell's
entries in the manifest. Counts and correctness only: no CPU time stands
for a chip's."""

import gc
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import jamba_serve_counts as counts  # noqa: E402
from benchmark.lib import gpt2_serve_counts, manifest, peaks, traffic  # noqa: E402
from benchmark.lib.observe import Observations  # noqa: E402
from test_benchmark_harness import assert_benchmark_invariants  # noqa: E402
from test_benchmark_runners import tiny_cell  # noqa: E402

sys.path.insert(0, str(ROOT / "benchmark" / "sweeps"))
import jamba_serve_precision as sweep  # noqa: E402

pytestmark = pytest.mark.usefixtures("light_compile")

CELL = "jamba2_serve_decode_replay"
GPT2_CELL = "gpt2m_serve_decode_replay"
SPEC = json.loads(
    (ROOT / "benchmark/traffic/decode_replay_s128_reason.json").read_text())
CONFIG = json.loads(
    (ROOT / "benchmark/configs/ai21-jamba2-3b.json").read_text())
NEW_READERS = ("mamba_mixer_ms", "mamba_state_ms", "mamba_state_roofline",
               "jamba_outside_model_ms")
#: contexts long enough for a state's rounding to add up: what the two
#: checks of the state's precision run at
LONG = {"deployment": {"max_blocks_per_seq": 256, "num_blocks": 1025,
                       "prefill_buckets": [256, 512, 768, 1024],
                       "scan_chunk": 16, "reference_pad": 64},
        "traffic": {"prompt_len": {"dist": "uniform", "min": 700,
                                   "max": 1000}}}


# -- the traffic mix ---------------------------------------------------------

def test_the_mix_is_the_issues():
    a = traffic.decode_replay(SPEC, 2 ** 31 + 5, 65536)
    b = traffic.decode_replay(SPEC, 6, 65536)
    lens = sorted(len(s.prompt) for s in a)
    assert len(a) == SPEC["sessions"] == 128
    assert (lens[0], lens[-1]) == (256, 3072)
    assert 1000 <= lens[64] <= 1050                       # median 1024
    assert 145_000 < sum(lens) < 160_000                  # about 150 k tokens
    assert {s.max_new_tokens for s in a} == {2048}
    assert lens == sorted(len(s.prompt) for s in b)       # one set of lengths
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    assert len({s.prompt[:16] for s in a}) == 128         # nothing shared
    assert all(0 < t < 65536 for s in a for t in s.prompt[:8])


# -- the runner, tiny --------------------------------------------------------

def replay_cell(*, long: bool = False, **deployment) -> dict:
    """The cell at the size ``sweeps/jamba_serve_precision.py --tiny`` runs."""
    extra = LONG if long else {"deployment": {}, "traffic": {}}
    return tiny_cell(CELL, config=sweep.TINY["config"],
                     deployment={**sweep.TINY["deployment"],
                                 **extra["deployment"], **deployment},
                     traffic={**sweep.TINY["traffic"], **extra["traffic"]})


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
            "mamba_state_bytes_per_step"} <= set(obs.facts)
    # the window's one program, by the name the trace gives it, and the
    # scopes the readers look for
    assert set(obs.scopes) == {"jit_serve_decode"}
    scopes = list(obs.scopes["jit_serve_decode"].values())
    for wanted in ("/JambaLM/", "/mamba/ssm_step/", "/mamba/conv/",
                   "/attn/gather_ctx/", "/attn/write_kv/"):
        assert any(wanted in s for s in scopes), wanted
    # the state's in-place write stands under the scope that names the work
    assert any(s.endswith("/mamba/ssm_step/dynamic_update_slice")
               for s in scopes)
    # the reference saw the longest, the shortest and one more session
    assert sorted(obs.notes["prompt_lens"]) == [9, 18, 27, 36]
    seen = obs.notes["reference_sessions"]
    lens = dict(zip((f"s{i}" for i in range(4)), obs.notes["prompt_lens"]))
    assert len(seen) == 3 and {lens[r] for r in seen} >= {9, 36}
    # the window's 15 a session and the one of the call dispatched ahead of
    # the step that never came, settled before the reference looks
    assert obs.notes["compared_tokens"] == 3 * 16
    compared = obs.notes["compared"]
    assert set(compared) == {"chosen_gap_rel", "chosen_logprob_abs",
                             "slow_state_rel"}
    for pair in compared.values():
        assert pair["value"] < 1e-4 < pair["limit"]


def test_the_window_runs_on_a_settled_heap(sound):
    """Set-up ends with what it left on the heap out of the collector's
    sight; a full collection inside a window is noted; ``verify`` undoes
    both (the fixture's run is over: nothing is frozen, nothing watches)."""
    runner = manifest.module("runners", "jamba_serve_replay")
    assert gc.get_freeze_count() == 0
    assert sound.session.heap_watch not in gc.callbacks
    obs = Observations(cell=sound.cell, seed=1, seconds=1.0, traced=False,
                       device_kind="TPU v5 lite")
    watch = runner.settle_heap(obs)
    try:
        assert gc.get_freeze_count() > 10_000 and watch in gc.callbacks
        gc.collect()                         # outside a window: not noted
        obs.in_window = True
        gc.collect(1)                        # a young generation: not noted
        gc.collect()
        obs.in_window = False
        (took,) = obs.notes["full_collections_ms"]
        assert 0.0 < took < 1e3
    finally:
        gc.callbacks.remove(watch)
        gc.unfreeze()


def test_the_registry_holds_the_states_gauge_and_counters(sound):
    from tpu_sandbox.obs import get_registry

    snap = get_registry().snapshot()
    assert snap["gauges"]["serve.state_bytes"] == 4 * 4 * (
        16 * 128 * 4 + 3 * 128 * 4)
    assert snap["counters"]["serve.state_resets"] >= 4
    assert "serve.prefix_reuse_declined" in snap["counters"] or \
        sound.session.eng.cache.stats["prefix_reuse_declined"] == 0


def test_an_empty_slot_is_a_problem():
    obs = drive(replay_cell(max_batch=5))   # four sessions, five slots
    assert any("4 of 4 sessions hold one of 5 slots" in p for p in obs.problems)
    assert any("under full occupancy" in p for p in obs.problems)


def zero_the_state(session):
    """Prefill's state never reaches decode: every slot starts empty."""
    import jax

    eng = session.eng
    eng.state = jax.tree.map(lambda a: a * 0, eng.state)


def padding_moves_the_state(monkeypatch):
    """The bucket's padding behind a prompt keeps a time step."""
    import jax.numpy as jnp

    from tpu_sandbox.models import jamba

    scan = jamba.selective_scan

    def unmasked(x, dt, *rest, **kw):
        return scan(x, jnp.where(dt == 0.0, 0.05, dt), *rest, **kw)

    monkeypatch.setattr(jamba, "selective_scan", unmasked)


@pytest.mark.parametrize("fault", ["state_not_handed_over",
                                   "padding_moves_the_state",
                                   "bfloat16_state"])
def test_a_planted_fault_comes_out_not_correct(fault, monkeypatch):
    """The cell's rehearsal with the new mechanism broken underneath."""
    before_window, cell = None, replay_cell()
    if fault == "state_not_handed_over":
        before_window = zero_the_state
    elif fault == "padding_moves_the_state":
        padding_moves_the_state(monkeypatch)
    else:
        cell = replay_cell(long=True, state_dtype="bf16")
    obs = drive(cell, before_window=before_window)
    assert obs.failed == 0          # every session gained its tokens ...
    assert obs.problems, obs.notes["compared"]     # ... the wrong ones
    broken = {k for k, pair in obs.notes["compared"].items()
              if pair["value"] > pair["limit"]}
    assert broken
    if fault == "bfloat16_state":
        # through the logits a narrower state cannot be told from the
        # rounding a bfloat16 deployment has anyway: the state itself tells
        assert broken == {"slow_state_rel"}
    else:
        assert "slow_state_rel" in broken


def test_the_long_rehearsal_is_sound_with_a_float32_state():
    obs = drive(replay_cell(long=True))
    assert obs.problems == []
    assert obs.notes["compared"]["slow_state_rel"]["value"] < 1e-4


@pytest.mark.parametrize("seed", [2 ** 31 + 7, 6, 7])
def test_both_controls_come_out_not_correct(seed):
    """The reference one precision below the configuration's, put in the
    program's place over what a sound run served
    (``sweeps/jamba_serve_precision.py``, at a size a test can hold):
    float8 products, and a bfloat16 state."""
    cell = replay_cell(long=True)
    obs = drive(cell, seed=seed)
    assert obs.problems == []
    runner = manifest.module("runners", cell["runner"])
    reference = manifest.module("reference", cell["reference"])
    tree = reference.from_program_tree(obs.session.params, cell["config"])
    for name, precision in sweep.controls().items():
        dev, bad = sweep.control(reference, runner, tree, obs.session.batch,
                                 cell["config"], 64, **precision)
        assert bad, (name, dev)
    assert any("slow_state_rel" in text for text in bad)   # the state's


# -- the comparison ----------------------------------------------------------

def test_compare_served_reads_only_the_rows_that_count():
    from benchmark.reference import jamba

    gap = np.array([[0.01, 0.02, 9.0], [0.03, 9.0, 9.0]])
    logp = np.array([[-1.0, -2.0, -50.0], [-3.0, -50.0, -50.0]])
    state = np.array([[1.0, -2.0, 2.0], [3.0, 0.0, 4.0]])
    dev, bad = jamba.compare_served(gap, logp, [2, 1], [-1.5, -3.0], state,
                                    state * 1.001)
    assert dev["chosen_gap_rel"] == pytest.approx(0.03) and bad == []
    assert dev["chosen_logprob_abs"] == pytest.approx(0.0)
    assert dev["slow_state_rel"] == pytest.approx(1e-3)
    _, bad = jamba.compare_served(gap, logp, [3, 1], [-1.5, -3.0], state,
                                  state * 1.1)
    assert len(bad) == 3


def test_the_slow_states_are_the_smallest_steps_of_the_slowest_state():
    from benchmark.reference import jamba

    bias = np.array([0.5, -3.0, 0.1, -6.0, -1.0, 2.0, -4.0, 0.0], np.float32)
    tree = {"layers": [("attn", "block0", 0), ("mamba", "blocks1_2", 1)],
            "params": {"blocks1_2": {"mamba": {
                "dt_bias": np.stack([np.zeros(8, np.float32), bias])}}}}
    state = np.arange(2 * 16 * 8, dtype=np.float32).reshape(2, 16, 8)
    got = jamba.slow_states(tree, state)
    assert got.shape == (2, 2)
    assert np.array_equal(got, state[:, 0, [3, 6]])     # bias -6 and -4


# -- the readers -------------------------------------------------------------

@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_reads_nothing_without_its_source(name):
    obs = Observations(cell={"config": CONFIG}, seed=0, seconds=1.0,
                       traced=False, device_kind="TPU v5 lite")
    assert manifest.module("layer_metrics", name).read(obs) is None
    assert obs.problems == []


@pytest.mark.parametrize("name,want", [
    ("mamba_mixer_ms", 9.0), ("mamba_state_ms", 5.0),
    ("jamba_outside_model_ms", 0.5),
    # 2.352 GB needed over 5 ms at 819 GB/s
    ("mamba_state_roofline", 100.0 * 2351644672 / 819e9 / 5e-3)])
def test_new_reader_reads_a_number_with_its_source(name, want, sound):
    """A hand-made reduced trace: one chip, 10 steps of the decode program
    -- a mixer's projection, the state's read and its in-place write under
    ``ssm_step``, an MLP outside the mixer, a copy the compiler added."""
    obs = Observations(cell=sound.cell, seed=0, seconds=1.0, traced=True,
                       device_kind="TPU v5 lite")
    layer = "jit(serve_decode)/JambaLM/while/body/closed_call/blocks8_20/"
    obs.scopes = {"jit_serve_decode": {
        "fusion.1": layer + "mamba/in_proj/dot_general",
        "fusion.2": layer + "mamba/ssm_step/reduce_sum",
        "fusion.3": layer + "mamba/ssm_step/dynamic_update_slice",
        "fusion.4": layer + "mlp/down/dot_general"}}
    obs.trace = {"devices": [{"by_program": {"jit_serve_decode": {
        "fusion.1": [40_000_000, 260], "fusion.2": [20_000_000, 260],
        "fusion.3": [30_000_000, 260], "fusion.4": [70_000_000, 260],
        "copy.9": [5_000_000, 10]}}}]}
    obs.attempted = 128
    obs.facts.update(window_steps=10.0,
                     mamba_state_bytes_per_step=counts.state_update_bytes(
                         CONFIG, 128))
    assert manifest.module("layer_metrics", name).read(obs) == \
        pytest.approx(want)
    assert obs.problems == []


# -- the counts --------------------------------------------------------------

def test_counts_of_the_published_sizes():
    assert counts.layer_counts(CONFIG) == (26, 2)
    assert counts.parameters(CONFIG) == 3_029_337_472
    assert counts.slot_state_bytes(CONFIG) == 9_318_400
    # weights once, 128 slots' state read and written, logits: the issue's
    # 8.65 GB and 0.78 TFLOP at about 1300 tokens a session
    nbytes = counts.decode_step_bytes(CONFIG, [1300] * 128)
    flops = counts.decode_step_flops(CONFIG, [1300] * 128)
    assert 8.6e9 < nbytes < 8.7e9 and 0.77e12 < flops < 0.79e12
    assert counts.state_update_bytes(CONFIG, 128) == 128 * 26 * (
        2 * 16 * 5120 * 4 + 5120 * 2 + 5120 * 4 + 2 * 16 * 2 + 5120 * 4)


def test_decode_counts_against_a_hand_count():
    cfg = {"num_hidden_layers": 3, "attn_layer_period": 3,
           "attn_layer_offset": 1, "hidden_size": 8, "intermediate_size": 16,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "mamba_expand": 2, "mamba_d_state": 4, "mamba_d_conv": 4,
           "mamba_dt_rank": 2, "vocab_size": 10}
    # a Mamba mixer: in 8x32, x 16x(2+8), dt 2x16, out 16x8; attention: q, o
    # 8x8 each, k, v 8x4 each; an MLP 3x8x16; the head 8x10
    mamba, attn, mlp = 256 + 160 + 32 + 128, 128 + 64, 384
    assert counts.matmul_params(cfg) == 2 * mamba + attn + 3 * mlp + 80
    matmul = counts.matmul_params(cfg)
    # two sessions, contexts 3 and 5: one attention layer, 4 x c x 8; the
    # scans 7 x 2 layers x 4 x 16 a session
    assert counts.decode_step_flops(cfg, [3, 5]) == \
        2 * 2 * matmul + 4 * 8 * (3 + 5) + 2 * 7 * 2 * 4 * 16
    # weights once; a slot's state 2 layers x (4x16x4 + 3x16x2), read and
    # written; keys and values at ONE head of 4: 2 x c x 4 x 2 bytes; the
    # new token's 2 x 4 x 2; an embedding row and 10 float32 logits
    slot = 2 * (4 * 16 * 4 + 3 * 16 * 2)
    assert counts.slot_state_bytes(cfg) == slot
    assert counts.decode_step_bytes(cfg, [3, 5]) == \
        matmul * 2 + 2 * 2 * slot + 2 * 4 * 2 * (3 + 5) + 2 * (2 * 4 * 2) \
        + 2 * (8 * 2 + 4 * 10)


def test_decode_mfu_is_100_at_the_rooflines_own_time():
    contexts = [len(s.prompt) + 70
                for s in traffic.decode_replay(SPEC, 1, 65536)]
    flops = counts.decode_step_flops(CONFIG, contexts)
    nbytes = counts.decode_step_bytes(CONFIG, contexts)
    peak = peaks.peak("TPU v5 lite")
    least = gpt2_serve_counts.roofline_s(flops, nbytes, peak)
    assert least == nbytes / peak["hbm_bytes_per_s"]        # bytes bind
    assert 10.4e-3 < least < 10.7e-3                        # the 10.6 ms
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
    cell = manifest.cell(CELL)
    assert (cell["config_name"], cell["chips"], cell["runner"],
            cell["reference"]) == ("ai21-jamba2-3b", 1, "jamba_serve_replay",
                                   "jamba")
    dep = cell["deployment"]
    assert (dep["dtype"], dep["param_dtype"], dep["cache_dtype"],
            dep["state_dtype"]) == ("bf16", "bf16", "bf16", "float32")
    assert (dep["max_batch"], dep["block_size"], dep["max_blocks_per_seq"]) \
        == (128, 16, 320)
    # every session can hold its 3072 + 2048 positions beside the null block
    assert dep["num_blocks"] == 128 * 320 + 1 == 40_961
    assert dep["block_size"] * dep["max_blocks_per_seq"] == 3072 + 2048
    assert dep["prefill_buckets"] == [512, 1024, 2048, 3072]
    assert dep["reference_sessions"] >= 16
    assert cell["traffic"] == SPEC
    assert (SPEC["sessions"], SPEC["max_new_tokens"], SPEC["warmup_steps"]) \
        == (128, 2048, 2)
    assert SPEC["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                  "sigma": 0.6, "min": 256, "max": 3072}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert e2e["decode_step_ms"]["workloads"] == [GPT2_CELL, CELL]
    assert e2e["decode_step_ms"]["bound"] == 0.02
    assert {x["name"] for x in cell["end_to_end"]} == {"decode_step_ms",
                                                       "setup_s"}
    mine = {x["name"]: x for x in cell["per_layer"]}
    assert set(mine) == {
        "init_s", "trace_lower_s", "compile_s", "decode_device_ms",
        "batch_occupancy_pct", "serve_device_idle_pct", "preemptions",
        "gather_ctx_ms", "write_kv_ms", "decode_call_ms", "sample_ms",
        "decode_step_p50_ms", "decode_mfu_pct", *NEW_READERS}
    for name in NEW_READERS:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "decode_step_ms"
        assert mine[name]["source"] == "device_trace"
    # the training cells' lists stay the training cells'
    per_layer = {x["name"]: x for x in m["per_layer"]}
    for name in ("ssm_ms", "ssd_ms", "ssd_roofline", "mixer_conv_ms",
                 "decode_outside_model_ms"):
        assert CELL not in per_layer[name]["workloads"]


def test_the_configuration_is_the_catalogs_row_uncut():
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "ai21-jamba2-3b")
    assert entry["reduced"] == [] == CONFIG["reduced"]
    assert entry["source"] == ("https://huggingface.co/ai21labs/"
                               "AI21-Jamba2-3B/blob/main/config.json")
    assert entry["source"] in CONFIG["source"] and len(CONFIG["source"]) <= 200
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
        "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
        "num_hidden_layers": 28, "num_key_value_heads": 1,
        "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
        "sliding_window": None, "tie_word_embeddings": True,
        "use_mamba_kernels": True, "vocab_size": 65536}
    assert {k: CONFIG[k] for k in published} == published
    assert {"A_log", "D", "dt_bias", "weights", "precision", "sampling"} \
        <= set(CONFIG["assumed"])
