"""``serve/`` with a recurrent model (``JambaLM``, tiny, float32, on the
CPU): the slot state beside the pages. Prefill through each bucket and then
decode steps through the engine against the plain reference's full forward
(logits, not tokens); rows that do not decode keep their state bit for bit;
a slot retired and refilled, a request preempted and replayed, and sessions
sharing a batch give what each gives alone in a fresh engine; prefix reuse
is declined and counted; a decode call dispatched ahead of the host's
reading gives, step for step, what one that waits for it gives. The engine's scheduling is tests/test_serve.py's."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import jamba as ref  # noqa: E402
from test_jamba_model import TINY, tiny_config  # noqa: E402
from tests.helpers import counters, label  # noqa: E402
from tpu_sandbox.models.jamba import JambaConfig, JambaLM  # noqa: E402
from tpu_sandbox.obs import get_registry  # noqa: E402
from tpu_sandbox.serve import (CacheConfig, ContinuousEngine,  # noqa: E402
                               PagedKVCache, Request, ServeConfig)
from tpu_sandbox.serve import decode as serve_decode  # noqa: E402
from tpu_sandbox.serve.decode import build_decode_step  # noqa: E402

BUCKETS = (8, 16, 32)
CACHE = CacheConfig(num_blocks=65, block_size=4, max_blocks_per_seq=16)
SMALL_POOL = CacheConfig(num_blocks=11, block_size=4, max_blocks_per_seq=16)


@pytest.fixture(scope="module")
def served():
    cfg = tiny_config()
    params = jax.jit(JambaLM(cfg).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    steps = {cache: build_decode_step(cfg, cache, max_batch=3,
                                      buckets=BUCKETS)
             for cache in (CACHE, SMALL_POOL)}
    return cfg, params, steps, ref.from_program_tree(params, TINY)


def engine(served, cache=CACHE, ahead: bool = True,
           **config) -> ContinuousEngine:
    """``ahead=False``: an engine that reads a step's picks before it
    dispatches the next call, as one whose programs pick nothing does."""
    cfg, params, steps, _ = served
    scfg = ServeConfig(model=cfg, cache=cache, max_batch=3, buckets=BUCKETS,
                       **config)
    eng = ContinuousEngine(params, scfg, step=steps[cache])
    if not ahead:
        eng._decode_ahead = lambda picks, ver: None
    eng.rows = {}       # rid -> the logits every token was chosen from
    run, prefill = eng._run, eng._prefill

    def noting(request, alloc, slot_idx):
        eng.admitting = request.rid
        return prefill(request, alloc, slot_idx)

    def spy(program, params, *args):
        logits, picks = run(program, params, *args)
        got = np.asarray(logits)
        if got.ndim == 1:                                    # a prefill
            eng.rows.setdefault(eng.admitting, []).append(got)
        else:                                                # a decode step
            lengths = np.asarray(args[1])
            for i, slot in enumerate(eng.slots):
                if slot is not None and lengths[i] > 0:
                    eng.rows.setdefault(slot.request.rid, []).append(got[i])
        return logits, picks

    eng._run, eng._prefill = spy, noting
    return eng


def prompt(n: int, seed: int = 0) -> list[int]:
    return np.random.default_rng([seed, n]).integers(1, 256, n).tolist()


def serve(eng, requests: dict, new: int = 6, **sampling) -> dict:
    for rid, p in requests.items():
        eng.submit(Request(rid=rid, prompt=list(p), max_new_tokens=new,
                           **sampling))
    eng.run_until_idle()
    return {rid: (eng.results[rid].tokens, np.stack(eng.rows[rid]))
            for rid in requests}


@pytest.mark.parametrize("plen", [
    2,           # shorter than the convolution's window
    8, 16, 32,   # a prompt that ends at its bucket's end
    5, 13, 27,   # one that ends inside it
])
def test_prefill_then_decode_is_the_references_full_forward(served, plen):
    """Prefill through a padded bucket hands its last state over; every
    later token goes through the slot state and the pages. The logits of
    every served position against one forward that has neither."""
    tree = served[3]
    p = prompt(plen)
    tokens, rows = serve(engine(served), {"r": p}, new=7)["r"]
    want = np.asarray(ref.forward(tree, np.asarray([p + tokens[:-1]]),
                                  TINY))[0, plen - 1:]
    assert rows.shape == want.shape == (7, 256)
    np.testing.assert_allclose(rows, want, rtol=1e-4, atol=1e-4)
    # the program's own pick is the host's argmax of the same logits
    assert tokens == [int(r.argmax()) for r in rows]


def test_a_greedy_request_is_picked_by_the_program_a_sampled_one_on_the_host(
        served):
    """The Jamba programs give every row's greedy pick, so a greedy request
    never brings its logits to ``_pick_token``; a sampled request still
    does, draws the same tokens on a replay, and both keep the
    log-probability of what they chose."""
    p = prompt(11, seed=4)
    calls = []
    logprobs = {}

    def watched(eng):
        pick, retire = eng._pick_token, eng._retire

        def counting(slot, row):
            calls.append(slot.request.rid)
            return pick(slot, row)

        def keeping(i):
            slot = eng.slots[i]
            logprobs[slot.request.rid] = slot.logprob_sum, eng.rows[
                slot.request.rid], list(slot.generated)
            return retire(i)

        eng._pick_token, eng._retire = counting, keeping
        return eng

    greedy = serve(watched(engine(served)), {"g": p})["g"]
    assert calls == []
    sampled = serve(watched(engine(served)), {"s": p}, temperature=0.8,
                    seed=3)["s"]
    assert calls == ["s"] * 6
    again = serve(engine(served), {"s": p}, temperature=0.8, seed=3)["s"]
    assert sampled[0] == again[0] != greedy[0]
    for rid in ("g", "s"):
        total, rows, tokens = logprobs[rid]
        rows = np.stack(rows).astype(np.float64)
        want = sum(r[t] - np.log(np.exp(r - r.max()).sum()) - r.max()
                   for r, t in zip(rows, tokens))
        assert total == pytest.approx(want, abs=1e-4)


def test_sessions_in_one_batch_give_what_each_gives_alone(served):
    prompts = {"a": prompt(8), "b": prompt(13), "c": prompt(3)}
    together = serve(engine(served), prompts)
    for rid, p in prompts.items():
        alone = serve(engine(served), {rid: p})[rid]
        assert together[rid][0] == alone[0]
        np.testing.assert_allclose(together[rid][1], alone[1], rtol=1e-5,
                                   atol=1e-5)


def test_a_slot_retired_and_refilled_starts_from_the_new_prompt(served):
    """Five requests through three slots: the fourth and fifth take a slot
    whose state an earlier sequence left behind."""
    prompts = {f"r{i}": prompt(4 + 3 * i, seed=1) for i in range(5)}
    eng = engine(served)
    resets = get_registry().counter("serve.state_resets")
    before = resets.value
    got = serve(eng, prompts)
    assert resets.value - before == 5
    for rid, p in prompts.items():
        alone = serve(engine(served), {rid: p})[rid]
        assert got[rid][0] == alone[0]
        np.testing.assert_allclose(got[rid][1], alone[1], rtol=1e-5, atol=1e-5)


def test_a_preempted_request_replays_from_its_prompt_on_a_reset_state(served):
    """Ten allocatable blocks of 4: three sequences outgrow them, the newest
    is evicted and replays; its tokens are those of a fresh engine."""
    prompts = {f"p{i}": prompt(9 + i, seed=2) for i in range(3)}
    eng = engine(served, SMALL_POOL)
    got = serve(eng, prompts, new=8)
    assert sum(r.preemptions for r in eng.results.values()) >= 1
    for rid, p in prompts.items():
        alone = serve(engine(served), {rid: p}, new=8)[rid]
        assert got[rid][0] == alone[0]
        # the rows of the last life are the last eight
        np.testing.assert_allclose(got[rid][1][-8:], alone[1], rtol=1e-5,
                                   atol=1e-5)


def test_rows_that_do_not_decode_keep_their_state_bit_for_bit(served):
    """Empty slots and the rows of another weight version ride every call
    with ``lengths == 0``."""
    cfg, params, steps, _ = served
    eng = engine(served)
    eng.submit(Request(rid="x", prompt=prompt(6), max_new_tokens=4))
    eng.step()                                    # slot 0 prefilled, decoded
    marked = jax.tree.map(lambda a: a.at[...].add(0.5), eng.state)
    before = jax.tree.map(np.array, marked)
    eng.state = marked
    eng.step()
    after = jax.tree.map(np.array, eng.state)
    for name, slot_axis in (("ssm", 1), ("conv", 2)):
        for old, new in zip(before[name], after[name]):
            old, new = (np.moveaxis(t, slot_axis, 0) for t in (old, new))
            assert not np.array_equal(old[0], new[0])      # it decoded
            assert np.array_equal(old[1:], new[1:])        # they did not
    eng.run_until_idle()


def test_prefix_reuse_is_declined_beside_recurrent_state(served):
    shared = prompt(12, seed=3)
    eng = engine(served)
    declined = get_registry().counter("serve.prefix_reuse_declined")
    before = declined.value
    got = serve(eng, {"first": shared + [7, 8], "second": shared + [9]})
    assert declined.value - before == 1
    assert eng.cache.stats["prefix_reuse_declined"] == 1
    assert eng.cache.stats["prefix_hits"] == 0
    assert eng.cache.resident_prefix_digest() == []
    assert eng.cache.free_blocks == CACHE.num_blocks - 1   # nothing pinned
    alone = serve(engine(served), {"second": shared + [9]})["second"]
    assert got["second"][0] == alone[0]
    # a model of pages only still shares
    pages_only = PagedKVCache(CACHE)
    a = pages_only.alloc(shared + [7, 8], 0)
    pages_only.commit_prefix(a)
    assert pages_only.alloc(shared + [9], 0).n_shared == 3


def test_the_engine_holds_two_kinds_of_state(served):
    cfg, _, steps, _ = served
    eng = engine(served)
    step = steps[CACHE]
    assert step.recurrent and eng.recurrent
    # the pages are tests/test_serve.py's, either family; beside them:
    *_, state = step.buffers
    assert [s.shape for s in state["ssm"]] == [
        (1, 3, 16, 128), (2, 3, 16, 128), (1, 3, 16, 128)]
    held = sum(x.nbytes for x in jax.tree.leaves(eng.state))
    assert get_registry().gauge("serve.state_bytes").value == held
    assert held == 3 * 4 * (16 * 128 * 4 + 3 * 128 * 4)


# --- the decode program's attention: the kernel or the jnp form, by shape ---

# one key/value head of 128 for two query heads, blocks of 32 float32 rows:
# the shape rule gives these pages the kernel; five allocatable blocks for
# three rows of up to two
WIDE = {**TINY, "hidden_size": 256, "num_attention_heads": 2}
WIDE_POOL = CacheConfig(num_blocks=6, block_size=32, max_blocks_per_seq=2)


@pytest.fixture(scope="module")
def branches():
    """``served``'s tuple for ``WIDE`` over ``WIDE_POOL`` twice: as the rule
    builds it (the kernel, interpreted here, one page a compute step so a
    full row takes two) and with a rule that declines every shape (the
    ``jnp`` form), with the ``paged_attn.kernel_choice`` counts of each."""
    cfg = JambaConfig.from_dict(WIDE, dtype=jnp.float32,
                                param_dtype=jnp.float32, scan_chunk=4)
    params = jax.jit(JambaLM(cfg).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]

    built = {}
    for branch, knob, value in (
            ("pallas", "_STEP_TOKENS", 32),
            ("jnp", "pages_per_step", lambda *shape: None)):
        before = counters("paged_attn.kernel_choice")
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(serve_decode, knob, value)
            step = build_decode_step(cfg, WIDE_POOL, max_batch=3,
                                     buckets=BUCKETS)
        counted = {label(key, "impl"): n for key, n in counters(
            "paged_attn.kernel_choice", since=before).items()}
        built[branch] = (cfg, params, {WIDE_POOL: step}, None), counted
    return built


def test_the_shape_rule_gives_wide_pages_the_kernel(branches):
    """One count an attention layer, of the form the rule took."""
    for branch, (_, counted) in branches.items():
        assert counted == {branch: 2}


def test_the_kernel_branch_serves_what_the_jnp_branch_serves(branches):
    """Three rows that outgrow the pool together: the kernel's tokens are
    the ``jnp`` form's, the logits every token was chosen from agree to
    float32 rounding, and a request preempted and replayed returns what it
    returns alone."""
    prompts = {f"p{i}": prompt(20 + 3 * i, seed=5) for i in range(3)}
    eng = engine(branches["pallas"][0], WIDE_POOL)
    got = serve(eng, prompts, new=30)
    assert sum(r.preemptions for r in eng.results.values()) >= 1
    want = serve(engine(branches["jnp"][0], WIDE_POOL), prompts, new=30)
    for rid, p in prompts.items():
        assert got[rid][0] == want[rid][0]
        np.testing.assert_allclose(got[rid][1][-30:], want[rid][1][-30:],
                                   rtol=1e-4, atol=1e-4)
        alone = serve(engine(branches["pallas"][0], WIDE_POOL), {rid: p},
                      new=30)[rid]
        assert got[rid][0] == alone[0]


@pytest.mark.parametrize("branch", ["pallas", "jnp"])
def test_a_decode_program_keeps_the_scopes_the_benchmark_reads(branches,
                                                               branch):
    """``gather_ctx`` and ``write_kv`` inside both attention layers of the
    compiled decode program, on either branch (``benchmark/lib/readers.py::
    _scope_sum`` reads a program without one as incorrect)."""
    import re

    cfg, _, steps, _ = branches[branch][0]
    names = set(re.findall(r'op_name="([^"]+)"',
                           steps[WIDE_POOL].decode.as_text()))
    for i, kind in enumerate(cfg.layer_kinds):
        for scope in ("gather_ctx", "write_kv") if kind == "attn" else ():
            assert any(re.search(rf"/block{i}/attn/{scope}(/|$)", name)
                       for name in names), (i, scope)


def life(served, script, cache=CACHE, steps=60, **config):
    """An engine driven through ``script`` (step -> requests submitted in
    front of it): rid -> tokens generated so far, after every step; what
    each request ended with (tokens, the sum of their log-probabilities);
    the engine; how many calls were dispatched ahead."""
    eng = engine(served, cache, **config)
    ended, ahead = {}, []
    retire, dispatch = eng._retire, eng._decode_ahead

    def keeping(i):
        slot = eng.slots[i]
        ended[slot.request.rid] = list(slot.generated), slot.logprob_sum
        return retire(i)

    def counting(picks, ver):
        out = dispatch(picks, ver)
        ahead.append(out is not None)
        return out

    eng._retire, eng._decode_ahead = keeping, counting
    trace = []
    for n in range(steps):
        for request in script.get(n, ()):
            eng.submit(request)
        if eng.idle and n > max(script):
            break
        eng.step()
        trace.append({s.request.rid: len(s.generated) for s in eng.slots
                      if s is not None})
    assert eng.idle
    return trace, ended, eng, sum(ahead)


def requests(lengths, new, seed=5, **sampling):
    return [Request(rid=f"q{n}", prompt=prompt(n, seed), max_new_tokens=new,
                    **sampling) for n in lengths]


def eos_of(served) -> int:
    """A token the first request of ``requests([9, 4, 14], 9)`` generates
    in the middle of its answer."""
    _, ended, _, _ = life(served, {0: requests([9, 4, 14], 9)},
                          ahead=False)
    return ended["q9"][0][3]


LIVES = {
    # name: (script, cache, config, the least calls dispatched ahead)
    "three sessions": (lambda s: {0: requests([9, 4, 14], 9)}, CACHE, {}, 6),
    "five requests through three slots": (
        lambda s: {0: requests([5, 8, 11, 3, 17], 6)}, CACHE, {}, 4),
    "arrivals between steps": (
        lambda s: {0: requests([7], 12), 3: requests([10], 5),
                   4: requests([2], 9), 9: requests([13], 4)}, CACHE, {}, 4),
    "a sequence ends on eos_token": (
        lambda s: {0: requests([9, 4, 14], 9), 2: requests([6], 5)}, CACHE,
        {"eos_token": eos_of}, 2),
    "a sampled request beside greedy ones": (
        lambda s: {0: requests([9, 4], 8)
                   + requests([12], 3, temperature=0.7, seed=2)}, CACHE, {},
        2),
}


@pytest.mark.parametrize("name", list(LIVES))
def test_a_call_dispatched_ahead_changes_no_token_and_no_step(served, name):
    """The next step's call goes to the device before this step's picks
    are read (``engine._decode_ahead``): every request ends with the tokens
    and the log-probabilities of an engine that waits, and gains them in
    the same steps."""
    script, cache, config, least = LIVES[name]
    config = {k: v(served) if callable(v) else v for k, v in config.items()}
    want = life(served, script(served), cache, ahead=False, **config)
    got = life(served, script(served), cache, **config)
    assert want[3] == 0 and got[3] >= least
    assert got[0] == want[0]
    assert got[1].keys() == want[1].keys()
    for rid, (tokens, logprob) in want[1].items():
        assert got[1][rid][0] == tokens
        assert got[1][rid][1] == pytest.approx(logprob, abs=1e-5)
    if "eos_token" in config:   # it did end there, and a row was dropped
        assert min(len(t) for t, _ in got[1].values()) < 5


def test_a_call_dispatched_ahead_under_block_pressure(served):
    """Where the pool cannot give the next step's block nothing is
    dispatched ahead and the step preempts as it always did; the tokens
    are a fresh engine's."""
    script = {0: requests([9, 10, 11], 8, seed=2)}
    want = life(served, script, SMALL_POOL, ahead=False)
    got = life(served, script, SMALL_POOL)
    assert got[3] >= 1
    assert sum(r.preemptions for r in got[2].results.values()) >= 1
    assert {r: t for r, (t, _) in got[1].items()} \
        == {r: t for r, (t, _) in want[1].items()}


def test_settle_resolves_the_call_dispatched_ahead(served):
    """After ``settle`` tokens and device state agree as after a step of an
    engine that dispatches nothing ahead: every token but a sequence's
    last has passed through the state."""
    ahead, plain = engine(served), engine(served, ahead=False)
    for eng in ahead, plain:
        for request in requests([9, 4], 12):
            eng.submit(request)
    for _ in range(3):
        ahead.step()
    for _ in range(4):
        plain.step()
    assert ahead._ahead is not None and plain._ahead is None
    held = {s.request.rid: list(s.generated) for s in ahead.slots if s}
    ahead.settle()
    assert ahead._ahead is None
    now = {s.request.rid: list(s.generated) for s in ahead.slots if s}
    assert now == {s.request.rid: list(s.generated) for s in plain.slots if s}
    assert all(now[rid][:-1] == held[rid] for rid in held)
    for a, b in zip(jax.tree.leaves(ahead.state),
                    jax.tree.leaves(plain.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ahead.settle()                                  # nothing left: no-op
    assert {s.request.rid: list(s.generated) for s in ahead.slots if s} == now
    # and the engine goes on from there as the other does
    for eng in ahead, plain:
        eng.run_until_idle()
    assert {r: v.tokens for r, v in ahead.results.items()} \
        == {r: v.tokens for r, v in plain.results.items()}
    # what is drained takes the call dispatched ahead with it
    eng = engine(served)
    eng.submit(requests([9], 12)[0])
    eng.step()
    assert eng._ahead is not None
    assert [r.rid for r in eng.drain_to_requests()] == ["q9"]
    assert eng._ahead is None and eng.idle
