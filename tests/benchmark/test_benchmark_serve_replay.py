"""The serving cell ``gpt2m_serve_decode_replay`` (PR 40) without a chip:
the ``decode_replay`` traffic kind, the runner driven tiny on the CPU
through everything ``run.py`` does after its look for a chip (set-up,
window, ``finish``, the reference after the window), the faults and the
control that have to come out as not correct, the new readers, and the
counts behind ``decode_mfu_pct``. Counts and correctness only: no CPU time
stands for a chip's."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import gpt2_serve_counts, manifest, peaks, traffic  # noqa: E402
from benchmark.lib.observe import Observations  # noqa: E402
from test_benchmark_harness import (assert_benchmark_invariants,  # noqa: E402
                                    generate)
from test_benchmark_runners import TINY_GPT2, tiny_cell  # noqa: E402

sys.path.insert(0, str(ROOT / "benchmark" / "sweeps"))
import gpt2_serve_precision as sweep  # noqa: E402

pytestmark = pytest.mark.usefixtures("light_compile")

CELL = "gpt2m_serve_decode_replay"
SPEC = json.loads((ROOT / "benchmark/traffic/decode_replay_s64.json").read_text())
NEW_READERS = ("gather_ctx_ms", "write_kv_ms", "decode_outside_model_ms",
               "decode_call_ms", "sample_ms", "decode_step_p50_ms",
               "decode_mfu_pct")
BY_SCOPE = {"gather_ctx_ms": 2.0, "write_kv_ms": 0.1,
            "decode_outside_model_ms": 30.0}
#: ``traffic.digest`` of every mix the accepted cells use (and of the kept
#: chat mix), seed 7, as the parent commit generates them: the new kind
#: stands beside the old ones and moves none of their draws
ACCEPTED_MIXES = {
    "chat_poisson": "8e554eba8cc96f58d1d7e20ffe8f460f34eed6570febf1b354e8f186efd90f47",
    "lm_b1_s8192": "889a6ee9b7d49cf24f12f6e2d07c363adecf32bdbe8db67be2bc5a855b99b8f4",
    "lm_b2_s4096": "54cbc38aea7e3299d43ad405df4fe0530a8b214f8a33a3448c69f60e972918be",
    "lm_b8_s1024": "eff49c97ed08162508871cc6a4fa8a68dcbb8a83c0268c6aa31ecdc7c01d0950",
    "mnist_bs5": "9dd2e299d7236f219b55341aff43a107f2a978cca1cc40f00af8008bb601a058",
    "mnist_bs5_dp": "0872263fff207b84c6264e22fc9976f51b226c1b509327b01a82369d28e60bd0",
}


# -- the traffic kind --------------------------------------------------------

@pytest.mark.parametrize("seed", [7, 2 ** 31 + 12345])
def test_decode_replay_is_a_function_of_spec_and_seed(seed):
    a = traffic.decode_replay(SPEC, seed, 50257)
    assert traffic.digest(a) == traffic.digest(
        traffic.decode_replay(dict(SPEC), seed, 50257))
    assert traffic.digest(a) != traffic.digest(
        traffic.decode_replay(SPEC, seed + 1, 50257))


def test_decode_replay_honours_its_parameters():
    a = traffic.decode_replay(SPEC, 3, 50257)
    b = traffic.decode_replay(SPEC, 4, 50257)
    assert len(a) == SPEC["sessions"] == 64
    lens = [len(s.prompt) for s in a]
    assert SPEC["prompt_len"]["min"] <= min(lens) and max(lens) == 768
    assert 480 <= sorted(lens)[32] <= 540                 # median 512
    assert {s.max_new_tokens for s in a} == {256}
    assert max(lens) + 256 <= 1024                        # none can retire late
    assert len({s.rid for s in a}) == 64
    assert len({s.prompt[:16] for s in a}) == 64          # nothing shared
    assert all(0 < t < 50257 for s in a for t in s.prompt[:8])
    # every seed gets the same set of lengths, dealt in another order
    assert sorted(lens) == sorted(len(s.prompt) for s in b)
    assert lens != [len(s.prompt) for s in b]
    short = dict(SPEC, sessions=5,
                 prompt_len={"dist": "uniform", "min": 3, "max": 9})
    drawn = [len(s.prompt) for s in traffic.decode_replay(short, 1, 11)]
    assert len(drawn) == 5 and all(3 <= n <= 9 for n in drawn)


@pytest.mark.parametrize("mix,want", sorted(ACCEPTED_MIXES.items()))
def test_the_accepted_mixes_draw_bit_for_bit_as_before(mix, want):
    spec = json.loads((ROOT / "benchmark/traffic" / f"{mix}.json").read_text())
    assert traffic.digest(generate(spec, 7)) == want


# -- the runner, tiny --------------------------------------------------------

def replay_cell(**deployment) -> dict:
    """The cell at the size ``sweeps/gpt2_serve_precision.py --tiny`` runs."""
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
    # 1 token from the prefill, 1 + warmup_steps from set-up's steps; the
    # window stops one step short of the first retirement (16 tokens)
    assert obs.notes["steps"] == 16 - 1 - (1 + 2) - 1 == 11
    assert obs.series["occupancy_pct"] == [100.0] * 11
    assert len(obs.series["decode_step_s"]) == 11
    assert obs.facts["preemptions"] == 0.0
    assert obs.end_to_end["decode_step_ms"] > 0
    assert obs.end_to_end["decode_step_ms"] == pytest.approx(
        1e3 * obs.facts["window_s"] / 11)
    assert {"session_prefill_s", "warmup_s", "init_s", "trace_lower_s",
            "compile_s", "after_window_check_s"} <= set(obs.facts)
    # the window's one program, by the name the trace gives it
    assert set(obs.scopes) == {"jit_serve_decode"}
    scopes = obs.scopes["jit_serve_decode"].values()
    assert any("/attn/gather_ctx/" in s for s in scopes)
    assert any("/attn/write_kv/" in s for s in scopes)
    # the reference saw every session and every served token
    assert obs.notes["compared_tokens"] == 4 * 15
    compared = obs.notes["compared"]
    assert set(compared) == {"chosen_gap_rel", "chosen_logprob_abs"}
    assert compared["chosen_gap_rel"]["value"] < 1e-4
    assert compared["chosen_logprob_abs"]["value"] < 1e-4
    assert sorted(obs.notes["prompt_lens"]) == [9, 18, 27, 36]


def test_a_window_shorter_than_six_steps_is_a_problem():
    obs = drive(replay_cell(), seconds=0.0)
    assert obs.notes["steps"] == 0
    assert any("fewer than 6" in p for p in obs.problems)
    assert obs.end_to_end["decode_step_ms"] is None


def test_an_empty_slot_is_a_problem():
    cell = replay_cell(max_batch=5)   # four sessions, five slots
    obs = drive(cell)
    assert any("4 of 4 sessions hold one of 5 slots" in p for p in obs.problems)
    assert any("under full occupancy" in p for p in obs.problems)
    assert obs.series["occupancy_pct"][0] == 80.0


def test_a_pool_too_small_preempts_and_is_a_problem():
    # 90 prompt tokens take 24 blocks of 4; 26 allocatable ones run out
    # while the sessions decode, so the engine preempts
    obs = drive(replay_cell(num_blocks=27))
    assert obs.facts["preemptions"] >= 1
    assert any("preemptions" in p for p in obs.problems)
    assert obs.failed >= 1


FAULTS = ["token_altered", "slot_skipped"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_under_the_timed_path_comes_out_not_correct(fault):
    """The rest of a run with the engine broken underneath."""
    def plant(session):
        eng = session.eng
        if fault == "token_altered":     # where the token is produced
            pick = eng._pick_token

            def altered(slot, row):
                token = pick(slot, row)
                return (token + 1) % len(row) if slot.request.rid == "s2" \
                    else token
            eng._pick_token = altered
        else:                            # a slot left out of the decode
            decode = eng._decode_active

            def skipping():
                kept, eng.slots[1] = eng.slots[1], None
                decode()
                eng.slots[1] = kept
            eng._decode_active = skipping

    obs = drive(replay_cell(), before_window=plant)
    assert obs.problems
    if fault == "token_altered":
        assert obs.failed == 0           # every session gained its tokens
        assert any("chosen_gap_rel" in p for p in obs.problems)
        gap = obs.notes["compared"]["chosen_gap_rel"]
        assert gap["value"] > gap["limit"] == 0.2
    else:
        assert obs.failed == 1
        assert any("under full occupancy" in p for p in obs.problems)


#: the smallest size at which the float8 reference breaks a limit on every
#: seed tried (at TINY_GPT2's two layers of 32 it stays inside both on one)
CONTROL_GPT2 = {"n_layer": 4, "n_embd": 64, "n_head": 4, "n_inner": 128,
                "vocab_size": 211, "n_positions": 64}


@pytest.mark.parametrize("seed", [2 ** 31 + 7, 6, 7])
def test_the_float8_control_comes_out_not_correct(seed):
    """The reference one precision below bf16, put in the program's place
    over what a sound run served (``sweeps/gpt2_serve_precision.py``, at a
    size a test can hold): not correct, on three seeds."""
    cell = replay_cell()
    cell["config"].update(CONTROL_GPT2)
    obs = drive(cell, seed=seed)
    assert obs.problems == []
    runner = manifest.module("runners", cell["runner"])
    reference = manifest.module("reference", cell["reference"])
    tree = reference.from_program_tree(obs.session.params,
                                       cell["config"]["n_layer"])
    dev, bad = sweep.control(reference, runner, tree, obs.session.batch,
                             cell["config"])
    assert bad, dev
    assert max(dev[k] / reference.TOLERANCE[k] for k in dev) > 1.0


# -- the comparison ----------------------------------------------------------

def test_compare_served_reads_only_the_rows_that_count():
    from benchmark.reference import gpt2

    gap = np.array([[0.01, 0.02, 9.0], [0.03, 9.0, 9.0]])
    logp = np.array([[-1.0, -2.0, -50.0], [-3.0, -50.0, -50.0]])
    dev, bad = gpt2.compare_served(gap, logp, [2, 1], [-1.5, -3.0])
    assert dev["chosen_gap_rel"] == pytest.approx(0.03) and bad == []
    assert dev["chosen_logprob_abs"] == pytest.approx(0.0)
    _, bad = gpt2.compare_served(gap, logp, [3, 1], [-1.5, -3.0])
    assert len(bad) == 2


# -- the readers -------------------------------------------------------------

@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_reads_nothing_without_its_source(name):
    obs = Observations(cell={"config": TINY_GPT2}, seed=0, seconds=1.0,
                       traced=False, device_kind="TPU v5 lite")
    assert manifest.module("layer_metrics", name).read(obs) is None
    assert obs.problems == []


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_reads_a_number_with_its_source(name, sound):
    read = manifest.module("layer_metrics", name).read
    if name in BY_SCOPE:
        # a hand-made reduced trace: one chip, 11 steps of the decode
        # program -- its two scoped operations, a copy of an argument (its
        # op_name is the argument's) and one the compiler added (none)
        obs = Observations(cell=sound.cell, seed=0, seconds=1.0, traced=True)
        model = "jit(serve_decode)/TransformerLM/block0/attn/"
        obs.scopes = {"jit_serve_decode": {
            "gather.1": model + "gather_ctx/gather",
            "scatter.1": model + "write_kv/scatter", "copy.1": "k_pages"}}
        obs.trace = {"devices": [{"by_program": {"jit_serve_decode": {
            "gather.1": [22_000_000, 22], "scatter.1": [1_100_000, 11],
            "copy.1": [110_000_000, 11],
            "fusion.1.remat_uncompressed": [220_000_000, 11]}}}]}
        obs.attempted = 4
        obs.facts["window_steps"] = 11.0
        assert read(obs) == pytest.approx(BY_SCOPE[name])
        assert obs.problems == []
    else:
        value = read(sound)
        assert value is not None and value > 0
        if name == "decode_mfu_pct":
            assert value <= 100.0


# -- the counts --------------------------------------------------------------

def test_decode_counts_against_a_hand_count():
    cfg = {"n_layer": 2, "n_embd": 8, "n_inner": 16, "vocab_size": 10}
    # a layer: qkv 8x24 + out 8x8 = 256, mlp 2 x 8x16 = 256; head 8x10
    assert gpt2_serve_counts.matmul_params(cfg) == 2 * 512 + 80 == 1104
    # two sessions, contexts 3 and 5: attention 2 layers x 4 x c x 8
    assert gpt2_serve_counts.decode_step_flops(cfg, [3, 5]) == \
        2 * 2 * 1104 + 2 * 4 * 8 * (3 + 5)
    # bf16 weights once; keys and values 2 layers x 2 x c x 8 x 2 bytes;
    # the new token's 2 x 2 x 8 x 2 a session; embeddings 2 rows of 8 bf16
    # and 10 float32 logits a session
    assert gpt2_serve_counts.decode_step_bytes(cfg, [3, 5]) == \
        1104 * 2 + 2 * 2 * 8 * 2 * (3 + 5) + 2 * (2 * 2 * 8 * 2) \
        + 2 * (2 * 8 * 2 + 4 * 10)


def test_decode_mfu_is_100_at_the_rooflines_own_time():
    cfg = manifest.cell(CELL)["config"]
    contexts = [len(s.prompt) + 8 for s in traffic.decode_replay(SPEC, 1, 50257)]
    flops = gpt2_serve_counts.decode_step_flops(cfg, contexts)
    nbytes = gpt2_serve_counts.decode_step_bytes(cfg, contexts)
    peak = peaks.peak("TPU v5 lite")
    least = gpt2_serve_counts.roofline_s(flops, nbytes, peak)
    assert least == nbytes / peak["hbm_bytes_per_s"]        # bytes bind
    assert 4.0e9 < nbytes < 4.3e9 and 4.5e10 < flops < 5.2e10
    obs = Observations(cell={}, seed=0, seconds=1.0, traced=False,
                       device_kind="TPU v5 lite")
    obs.facts.update(decode_flops_per_step=flops, decode_bytes_per_step=nbytes,
                     window_steps=10.0, window_s=10.0 * least)
    read = manifest.module("layer_metrics", "decode_mfu_pct").read
    assert read(obs) == pytest.approx(100.0)
    obs.facts["window_s"] = 10.0 * 1.07     # a step as slow as today's
    assert 0.4 < read(obs) < 0.55


# -- the manifest ------------------------------------------------------------

def test_the_cell_is_in_the_manifest_as_the_issue_sets_it():
    assert manifest.validate() == []
    assert_benchmark_invariants(ROOT)
    m = manifest.load()
    assert len(m["workloads"]) >= 7
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == [
        "convnet3000_dp4_bs5"]
    cell = manifest.cell(CELL)
    assert (cell["config_name"], cell["chips"], cell["runner"]) == (
        "gpt2-medium", 1, "lm_serve_replay")
    dep = cell["deployment"]
    assert (dep["max_batch"], dep["block_size"], dep["max_blocks_per_seq"]) \
        == (64, 16, 64)
    # every session can hold the model's 1024 positions beside the null block
    assert dep["num_blocks"] - 1 == 64 * 64
    assert dep["prefill_buckets"] == [128, 256, 512, 768]
    assert cell["traffic"] == SPEC
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert CELL in e2e["decode_step_ms"]["workloads"]
    assert 0.01 <= e2e["decode_step_ms"]["bound"] <= 0.1
    assert {x["name"] for x in cell["end_to_end"]} == {"decode_step_ms",
                                                       "setup_s"}
    mine = {x["name"]: x for x in cell["per_layer"]}
    assert set(mine) == {"init_s", "trace_lower_s", "compile_s",
                         "decode_device_ms", "batch_occupancy_pct",
                         "serve_device_idle_pct", "preemptions", *NEW_READERS}
    for name in set(mine) - {"init_s", "trace_lower_s", "compile_s"}:
        assert mine[name]["moves"] == "decode_step_ms", name
