"""Each runner kind driven at a tiny size through its functions, on the
CPU: the control flow, the checks against the plain float32 references and
the metric readers. The numbers these runs produce are counts and
correctness only; a time from the CPU is never a device metric."""

import copy
import functools
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import manifest  # noqa: E402
from benchmark.lib.observe import Observations  # noqa: E402


def tiny_cell(name: str, *, config=None, deployment=None, traffic=None,
              chips=None, root=manifest.ROOT) -> dict:
    cell = copy.deepcopy(manifest.cell(name, root))
    cell["config"].update(config or {})
    cell["deployment"].update(deployment or {})
    cell["traffic"].update(traffic or {})
    if chips:
        cell["chips"] = chips
    return cell


def drive(cell: dict, seconds: float, seed: int = 3) -> Observations:
    obs = Observations(cell=cell, seed=seed, seconds=seconds, traced=False,
                       device_kind="cpu")
    runner = manifest.module("runners", cell["runner"])
    session = runner.setup(obs)
    obs.in_window = True
    runner.measure(obs, session, seconds)
    obs.in_window = False
    runner.finish(obs, session)
    obs.end_to_end = runner.end_to_end(obs)
    return obs


TINY_GPT2 = {"n_layer": 2, "n_embd": 32, "n_head": 4, "n_inner": 64,
             "vocab_size": 97, "n_positions": 64}


CONVNET_CELLS = [("convnet3000_1chip_bs5", 1), ("convnet3000_dp4_bs5", 4)]


@functools.cache
def tiny_convnet(name: str, chips: int) -> Observations:
    cell = tiny_cell(
        name, chips=chips,
        # plan "auto" is the interpretable NHWC plan on the CPU; the s2dt
        # kernels are what the chip runs
        config={"kernel_scopes": {}},
        deployment={"image_size": 32, "plan": "auto"},
        traffic={"batch_per_chip": 2, "n_images": 64, "steps_per_chunk": 2})
    return drive(cell, seconds=1.5)


@functools.cache
def tiny_lm() -> Observations:
    cell = tiny_cell(
        "gpt2m_train_s1024", config=TINY_GPT2,
        deployment={"flash": False, "dtype": "fp32", "remat": False},
        traffic={"batch": 2, "seq_len": 16, "steps_per_chunk": 2})
    return drive(cell, seconds=1.5)


@pytest.mark.parametrize("name,chips", CONVNET_CELLS)
def test_convnet_train_runner_tiny(name, chips):
    obs = tiny_convnet(name, chips)
    assert obs.problems == []
    assert obs.attempted >= 2 and obs.failed == 0
    assert obs.end_to_end["train_step_ms"] > 0
    assert set(obs.notes["reference_deviation"]) == {
        "logit_rel", "loss_abs", "fc_grad_rel"}
    if chips > 1:
        assert obs.notes["dp_identity_max_abs"] <= 2.0 ** -6
    for metric in ("init_s", "trace_lower_s", "compile_s", "loader_wait_ms"):
        assert manifest.module("layer_metrics", metric).read(obs) is not None
    # no trace was taken: trace readers return nothing, they do not guess
    for metric in ("device_step_ms", "pallas_ms", "device_idle_pct",
                   "head_ms", "optimizer_ms"):
        assert manifest.module("layer_metrics", metric).read(obs) is None
    assert obs.problems == []


def test_lm_train_runner_tiny():
    obs = tiny_lm()
    # 32 tokens a step: one tiny batch's loss is noise, and how many steps
    # fit the window depends on the box, so the rule that training lowers
    # the loss is tested on its own below, not here
    assert [p for p in obs.problems if "did not lower the loss" not in p] == []
    assert obs.attempted >= 2 and obs.failed == 0
    assert obs.notes["reference_deviation"]["logit_rms_rel"] < 1e-4
    assert obs.notes["reference_deviation"]["loss_abs"] < 1e-4
    # 3.5 x (2 x 2 x B x H x S^2 x D / 2) x layers, B 2, H 4, S 16, D 8, 2 layers
    assert obs.facts["attn_flops_per_step"] == 3.5 * 2 * 2 * 2 * 4 * 256 * 8 / 2 * 2
    for metric in ("attn_ms", "flash_attn_roofline", "optimizer_ms"):
        assert manifest.module("layer_metrics", metric).read(obs) is None


#: the scopes the scope readers look for, in the step each runner compiles
#: and notes: flax's module paths and the train step's named scopes
STEP_SCOPES = (
    [(tiny_convnet, cell, pattern) for cell in CONVNET_CELLS
     for pattern in (r"/fc(/|$)", r"(^|/)optimizer(/|$)", r"loss",
                     r"/conv2(/|$)")]
    + [(tiny_lm, (), pattern)
       for pattern in (r"/attn/", r"(^|/)optimizer(/|$)", r"loss",
                       r"/block1/mlp/")])


@pytest.mark.parametrize("driven,args,pattern", STEP_SCOPES)
def test_the_compiled_step_carries_the_scopes_the_readers_match(
        driven, args, pattern):
    obs = driven(*args)
    assert len(obs.scopes) == 1  # one program runs in the window
    (program, scopes), = obs.scopes.items()
    assert program.startswith("jit_")
    assert any(re.search(pattern, s) for s in scopes.values())
    # the Pallas kernels the runner noted are among them (none on the CPU,
    # where kernels are interpreted; the chip's are in the hand-written text
    # of test_benchmark_trace_reduce)
    assert obs.op_scopes.items() <= scopes.items()


def test_lm_train_holds_training_to_a_falling_loss():
    runner = manifest.module("runners", "lm_train")

    class Closed:  # a window whose losses are already in the notes
        def close(self):
            pass

    def problems(first, last, steps):
        obs = Observations(cell={}, seed=0, seconds=1.0, traced=False)
        obs.notes.update(first_loss=first, last_loss=last, steps=steps)
        runner.finish(obs, runner.Session(None, None, None, None, Closed()))
        return obs.problems

    assert problems(11.31, 11.08, 20) == []          # as on the chip
    assert "did not lower the loss" in problems(11.31, 11.31, 20)[0]
    assert "did not lower the loss" in problems(11.31, float("nan"), 20)[0]
    assert problems(11.31, 11.32, 3) == []           # too short to judge


@pytest.fixture(scope="module")
def serving_root(tmp_path_factory):
    """No cell of BENCHMARK.json serves yet: the kept serving cell's files,
    with its entries added in a copy of the tree (test_benchmark_harness)."""
    from test_benchmark_harness import add_serving_cell, copy_benchmark

    root = copy_benchmark(tmp_path_factory.mktemp("serving"))
    add_serving_cell(root)
    return root


def test_lm_serve_runner_tiny(serving_root):
    cell = tiny_cell(
        "gpt2m_serve_chat", root=serving_root, config=TINY_GPT2,
        deployment={"dtype": "fp32", "cache_dtype": "fp32", "max_batch": 4,
                    "block_size": 4, "max_blocks_per_seq": 16,
                    "num_blocks": 80, "prefill_buckets": [8, 16, 32],
                    "check_prompt_len": 11, "check_decode_steps": 3},
        traffic={"rate_per_s": 5.0, "warmup_s": 0.2,
                 "prompt_len": {"dist": "uniform", "min": 3, "max": 30},
                 "output_len": {"dist": "uniform", "min": 2, "max": 8}})
    # a request is failed if it has no first token a tenth of the window
    # after its end: a window long enough that a busy test box (several
    # pytest workers) cannot starve the last request
    obs = drive(cell, seconds=3.0)
    assert obs.problems == []
    dev = obs.notes["reference_deviation"]
    assert dev["chosen_logprob_abs"] < 1e-4 and dev["chosen_gap_rel"] < 1e-4
    assert obs.attempted > 5 and obs.failed == 0
    # both programs are noted by the names the trace gives them; the three
    # prefill buckets share one, and what they scope differently is marked
    assert set(obs.scopes) == {"jit_serve_prefill", "jit_serve_decode"}
    decode = obs.scopes["jit_serve_decode"].values()
    assert any("/attn/gather_ctx/" in s for s in decode)
    assert any("/attn/write_kv/" in s for s in decode)
    e2e = obs.end_to_end
    assert e2e["serve_tok_per_s"] > 0 and e2e["itl_p99_ms"] > 0
    assert e2e["ttft_p90_ms"] > 0 and obs.notes["backlog"] == 0
    for metric in cell["per_layer"]:
        value = manifest.module("layer_metrics", metric["name"]).read(obs)
        # no trace was taken: trace readers return nothing
        assert (value is None) == (metric["source"] == "device_trace"), metric


@pytest.mark.parametrize("wrong", ["tokens", "logprob"])
def test_serving_comparison_fails_a_wrong_system(wrong):
    import numpy as np

    from benchmark.reference import gpt2

    rng = np.random.default_rng(0)
    ref = rng.standard_normal((9, 50257))
    tokens = ref.argmax(axis=-1)
    peak = ref.max(axis=-1)
    logp = (peak - peak - np.log(np.exp(ref - peak[:, None]).sum(-1))).mean()
    assert gpt2.compare_chosen_tokens(ref, tokens, logp)[1] == []
    if wrong == "tokens":  # a system that looked at another row
        _, bad = gpt2.compare_chosen_tokens(ref, np.roll(tokens, 1), logp)
        assert any("chosen_gap_rel" in b for b in bad)
    else:                  # a system whose logits are a tenth too sharp
        _, bad = gpt2.compare_chosen_tokens(ref, tokens, logp + 0.4)
        assert any("chosen_logprob_abs" in b for b in bad)
