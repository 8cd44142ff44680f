"""Serving stack, fast: paged KV allocator units, prefix sharing, the
decode-vs-forward parity contract, the page store both model families
share (a buffer an attention layer, updated in place, read from the
compiled programs' text), engine-vs-reference greedy
outputs (continuous AND static, including under preemption pressure),
and the in-process replica protocol (drain/requeue, cross-worker
completion, lease-expiry scavenge).

The parity reference is the one-shot ``TransformerLM`` forward evaluated
at the cache's ``max_context`` padding — the same k-axis length the
decode softmax reduces over; the two agree to a few ulps, not to the bit
(they are different compiled programs), which is enough for the same
greedy tokens at every size tested here.

The replica gang under real HostAgents (kill a replica mid-load, lose
nothing) runs slow in test_serve_integration.py.
"""

import math
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import test_serve_jamba as lives
from tests.helpers import StubStep, counters, label, ulps_apart
from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
from tpu_sandbox.serve import (
    CacheConfig,
    ContinuousEngine,
    PagedKVCache,
    Request,
    ServeConfig,
    StaticEngine,
)
from tpu_sandbox.serve import decode as serve_decode
from tpu_sandbox.serve.decode import (Pages, _gather, build_decode_step,
                                      init_buffers, lower_step)
from tpu_sandbox.serve.engine import _token_logprob

MCFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                         d_ff=64, max_len=128, dtype=jnp.float32)
CCFG = CacheConfig(num_blocks=24, block_size=4, max_blocks_per_seq=8)
MAX_CTX = CCFG.max_context  # 32


@pytest.fixture(scope="module")
def model():
    return TransformerLM(MCFG)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]


@pytest.fixture(scope="module")
def step(params):
    """One compiled step set shared by every fp32 test in the module."""
    return build_decode_step(MCFG, CCFG, max_batch=3, buckets=(8, 16))


@pytest.fixture(scope="module")
def fwd32(model, params):
    """One-shot forward at max_context padding — THE parity reference."""
    return jax.jit(lambda toks: model.apply({"params": params}, toks))


@pytest.fixture(scope="module")
def greedy(fwd32):
    """Greedy continuation via the padded one-shot forward. One compiled
    shape total, and the logits the serve decode path computes, to a few
    ulps — this IS the unfaulted reference output."""
    def _greedy(prompt, max_new):
        toks = list(prompt)
        out = []
        for _ in range(max_new):
            padded = np.zeros((1, MAX_CTX), np.int32)
            padded[0, :len(toks)] = toks
            logits = np.asarray(fwd32(jnp.asarray(padded)))[0, len(toks) - 1]
            t = int(logits.argmax())
            out.append(t)
            toks.append(t)
        return out
    return _greedy


def _scfg(**over):
    base = dict(model=MCFG, cache=CCFG, max_batch=3, buckets=(8, 16))
    base.update(over)
    return ServeConfig(**base)


# -- paged allocator units (no jax) ----------------------------------------


def test_cache_blocks_needed_and_admission():
    cache = PagedKVCache(CCFG)
    assert cache.blocks_needed([1] * 4, 0) == 1
    assert cache.blocks_needed([1] * 4, 1) == 2
    assert cache.blocks_needed([1] * 5, 11) == 4
    # 23 usable blocks (block 0 is the null block): a 24-block ask is out
    assert cache.alloc(list(range(5)), 0) is not None
    big = CacheConfig(num_blocks=4, block_size=4, max_blocks_per_seq=8)
    tight = PagedKVCache(big)
    assert tight.alloc([1] * 12, 0) is not None  # 3 blocks: exactly fits
    assert tight.alloc([2] * 4, 0) is None       # nothing left


def test_cache_free_list_reuse_and_grow():
    cfg = CacheConfig(num_blocks=6, block_size=4, max_blocks_per_seq=4)
    cache = PagedKVCache(cfg)
    a = cache.alloc([1, 2, 3, 4, 5], 0)          # 2 blocks
    b = cache.alloc([9, 8, 7], 0)                # 1 block
    assert len(a.block_ids) == 2 and len(b.block_ids) == 1
    assert cache.grow(a)                          # free 2 -> a takes one
    assert len(a.block_ids) == 3
    assert cache.grow(b)                          # b takes the last one
    cache.free(a, cache_prefix=False)
    c = cache.alloc([4] * 10, 0)                  # reuses a's freed blocks
    assert c is not None and len(c.block_ids) == 3
    cache.free(b, cache_prefix=False)
    cache.free(c, cache_prefix=False)
    assert cache.alloc([5] * 16, 0) is not None   # 4 blocks: pool healthy


def test_cache_prefix_sharing_refcounts_and_eviction():
    cfg = CacheConfig(num_blocks=6, block_size=4, max_blocks_per_seq=4)
    cache = PagedKVCache(cfg)                     # 5 usable blocks
    prompt = [7, 7, 7, 7, 5, 5, 5, 5, 9]          # two full blocks + tail
    a = cache.alloc(prompt, 0)
    assert a.n_shared == 0
    cache.commit_prefix(a)
    b = cache.alloc(prompt, 0)                    # full blocks shared
    assert b.n_shared == 2
    assert b.block_ids[:2] == a.block_ids[:2]
    assert cache.stats["prefix_hits"] == 1
    assert cache.stats["prefix_blocks_reused"] == 2
    cache.free(a)
    cache.free(b)
    # freed-with-prefix blocks stay cached (2) leaving 3 plainly free; a
    # 4-block ask only fits by evicting from the prefix cache
    c = cache.alloc([1] * 16, 0)
    assert c is not None
    assert cache.stats["evicted_cache_blocks"] >= 1


# -- parity with the one-shot forward ---------------------------------------


@pytest.mark.parametrize("fault", [None, "row_one_off"])
def test_decode_matches_padded_forward_to_rounding_fp32(
        params, step, fwd32, fault):
    """Prefill + 24 decode steps, every step's logits the one-shot forward's
    at max_context padding (fp32, CPU) to rounding: the prefill, the decode
    step and the forward are three compiled programs, and XLA:CPU does not
    promise them one order of summation. Measured over six seeds of weights
    and prompt at this size: at most 11 ulps of the logits' largest entry
    (prefill 3.5); held to 32. A step that reads its cache one row off
    (the planted fault: one step is given its length less one) is millions
    apart."""
    cache = PagedKVCache(CCFG)
    kp, vp = init_buffers(step)
    prompt = [5, 17, 3, 42, 9]

    def ref_logits(seq):
        padded = np.zeros((1, MAX_CTX), np.int32)
        padded[0, :len(seq)] = seq
        return np.asarray(fwd32(jnp.asarray(padded)))[0, len(seq) - 1]

    alloc = cache.alloc(prompt, 0)
    toks = np.zeros((1, 8), np.int32)
    toks[0, :len(prompt)] = prompt
    dest = cache.dest_indices(alloc, 8).astype(np.int32)
    cur, _, kp, vp = step.prefill[8](
        params, kp, vp, jnp.asarray(toks), jnp.asarray(dest),
        jnp.asarray(len(prompt) - 1, jnp.int32))
    alloc.length = len(prompt)
    cur = np.asarray(cur)
    seq = list(prompt)
    apart = [ulps_apart(cur, ref_logits(seq))]

    for i in range(24):
        token = int(cur.argmax())
        seq.append(token)
        if alloc.length % CCFG.block_size == 0 \
                and alloc.length // CCFG.block_size >= len(alloc.block_ids):
            assert cache.grow(alloc)
        tokens = np.zeros((3, 1), np.int32)
        lengths = np.zeros((3,), np.int32)
        tables = np.zeros((3, CCFG.max_blocks_per_seq), np.int32)
        tokens[0, 0] = token
        lengths[0] = len(seq) - (fault is not None and i == 10)
        tables[0] = cache.block_table(alloc)
        cur, _, kp, vp = step.decode(
            params, kp, vp, jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(tables))
        cur = np.asarray(cur)[0]
        alloc.length = len(seq)
        apart.append(ulps_apart(cur, ref_logits(seq)))
    cache.free(alloc, cache_prefix=False)
    if fault:
        assert max(apart[:11]) <= 32 and max(apart[11:]) > 1e5, apart
    else:
        assert max(apart) <= 32, apart


def test_decode_bf16_cache_stays_close(params, fwd32):
    """With a bf16 KV cache the bitwise contract relaxes to tolerance —
    the cache quantization is the only difference (params stay fp32)."""
    step16 = build_decode_step(MCFG, CCFG, max_batch=2, buckets=(8,),
                               cache_dtype=jnp.bfloat16)
    cache = PagedKVCache(CCFG)
    kp, vp = init_buffers(step16)
    prompt = [11, 2, 33, 4]
    alloc = cache.alloc(prompt, 0)
    toks = np.zeros((1, 8), np.int32)
    toks[0, :len(prompt)] = prompt
    dest = cache.dest_indices(alloc, 8).astype(np.int32)
    cur, _, kp, vp = step16.prefill[8](
        params, kp, vp, jnp.asarray(toks), jnp.asarray(dest),
        jnp.asarray(len(prompt) - 1, jnp.int32))
    alloc.length = len(prompt)
    seq = list(prompt)
    for _ in range(12):
        token = int(np.asarray(cur).argmax())
        seq.append(token)
        if alloc.length % CCFG.block_size == 0 \
                and alloc.length // CCFG.block_size >= len(alloc.block_ids):
            assert cache.grow(alloc)
        tokens = np.zeros((2, 1), np.int32)
        lengths = np.zeros((2,), np.int32)
        tables = np.zeros((2, CCFG.max_blocks_per_seq), np.int32)
        tokens[0, 0] = token
        lengths[0] = len(seq)
        tables[0] = cache.block_table(alloc)
        cur, _, kp, vp = step16.decode(
            params, kp, vp, jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(tables))
        cur = np.asarray(cur)[0]
        alloc.length = len(seq)
        padded = np.zeros((1, MAX_CTX), np.int32)
        padded[0, :len(seq)] = seq
        ref = np.asarray(fwd32(jnp.asarray(padded)))[0, len(seq) - 1]
        np.testing.assert_allclose(cur, ref, rtol=0.05, atol=0.05)
    cache.free(alloc, cache_prefix=False)


# -- the page store, either family -----------------------------------------

POOL = CacheConfig(num_blocks=513, block_size=4, max_blocks_per_seq=8)
POOL_BUCKETS = (8, 16)


@pytest.fixture(scope="module", params=["transformer", "jamba"])
def family(request):
    """A compiled step set of each serving family over one pool geometry
    (bfloat16 pages), and what its page buffers should look like: attention
    layers, key/value heads, head size."""
    if request.param == "transformer":
        cfg, model, layers, heads, head_dim = (
            MCFG, TransformerLM(MCFG), MCFG.n_layers, MCFG.n_heads,
            MCFG.d_model // MCFG.n_heads)
    else:
        from test_jamba_model import tiny_config
        from tpu_sandbox.models.jamba import JambaLM

        cfg = tiny_config()
        model, layers, heads, head_dim = (
            JambaLM(cfg), cfg.layer_kinds.count("attn"),
            cfg.num_key_value_heads, cfg.head_dim)
    weights = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    step = build_decode_step(cfg, POOL, max_batch=3, buckets=POOL_BUCKETS,
                             cache_dtype=jnp.bfloat16)
    scfg = ServeConfig(model=cfg, cache=POOL, max_batch=3,
                       buckets=POOL_BUCKETS, cache_dtype=jnp.bfloat16)
    return dict(step=step, scfg=scfg, layers=layers, heads=heads,
                head_dim=head_dim, weights=jax.tree.leaves(weights))


def test_buffers_are_one_leaf_an_attention_layer(family):
    """``DecodeStep.buffers``: K and V each a ``Pages`` of one buffer an
    attention layer, a token's key/value heads side by side in the minor
    dimension, in the cache's type; a recurrent family's slot state comes
    after them."""
    step, layers = family["step"], family["layers"]
    k_pages, v_pages, *state = step.buffers
    assert len(state) == int(step.recurrent)
    want = (POOL.num_blocks, POOL.block_size,
            family["heads"] * family["head_dim"])
    for pages in k_pages, v_pages:
        assert isinstance(pages, Pages) and len(pages) == layers
        assert [p.shape for p in pages] == [want] * layers
        assert {p.dtype for p in pages} == {jnp.dtype(jnp.bfloat16)}
    # what init_buffers gives is what the programs take: zeros, finite
    held = init_buffers(step)
    assert jax.tree.structure(held) == jax.tree.structure(step.buffers)
    assert not any(np.asarray(x, np.float32).any()
                   for x in jax.tree.leaves(held[:2]))


@pytest.mark.parametrize("program", ["decode", *POOL_BUCKETS])
def test_programs_update_every_page_leaf_in_place(family, program):
    """Read from the compiled program's text: every page leaf that goes in
    is aliased to the page leaf that comes out (the donation is honoured:
    no second copy of a layer's pages), and no instruction gives a value as
    large as the K or V pool (a stacked ``[layers, ...]`` buffer threaded
    through the layers was one, and the chip's compiler copied it twice a
    layer: PERF.md section 6, PR 42)."""
    from tools.aot_serve_step import count_page_copies

    step, layers = family["step"], family["layers"]
    compiled = step.decode if program == "decode" else step.prefill[program]
    text = compiled.as_text()
    aliased = {int(param): int(out) for out, param in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
        text.split("entry_computation_layout")[0])}
    first_in = len(family["weights"])          # the weights come first
    first_out = 1 + int(step.picks)            # the logits, the picks
    for leaf in range(2 * layers):
        assert aliased.get(first_in + leaf) == first_out + leaf, (
            leaf, aliased)
    layer = math.prod(step.buffers[0][0].shape)
    # the fixture's pool is larger than any weight: a value of its size
    # could only be the pool
    assert layers * layer > max(w.size for w in family["weights"])
    found = count_page_copies(text, layer, layers * layer)
    assert found["pool_sized"] == 0, found
    assert found["remat_compressed"] == found["remat_uncompressed"] == 0


def test_the_engines_pages_answer_delete_and_flatten_to_their_leaves(family):
    """What the benchmark's runners do with them after a window."""
    eng = ContinuousEngine(None, family["scfg"], step=family["step"])
    leaves = jax.tree.leaves((eng.k_pages, eng.v_pages))
    assert len(leaves) == 2 * family["layers"]
    assert all(isinstance(x, jax.Array) for x in leaves)
    eng.k_pages.delete()
    eng.v_pages.delete()
    assert all(x.is_deleted() for x in leaves)


def test_a_handoff_round_trips_the_same_rows(family):
    """Blocks leave a replica as ``[L, n, block, H, D]`` whatever the
    layout of a layer's buffer (``serve/disagg.py``) and arrive at another
    replica's block ids bit for bit, where that replica's programs read
    them; no other block is touched."""
    step, layers = family["step"], family["layers"]
    heads, head_dim = family["heads"], family["head_dim"]
    rng = np.random.default_rng(0)
    pages = Pages(jnp.asarray(rng.standard_normal(s.shape), s.dtype)
                  for s in step.buffers[0])
    ids, home = jnp.asarray([7, 3, 200]), jnp.asarray([1, 2, 5])
    rows = pages.blocks(ids, heads)
    assert rows.shape == (layers, 3, POOL.block_size, heads, head_dim)
    assert rows.dtype == jnp.bfloat16
    # off a host wire: numpy, as pack_views stages it
    wire = np.asarray(rows.astype(jnp.float32))
    there = init_buffers(step)[0].with_blocks(home, wire)
    assert isinstance(there, Pages)
    back = there.blocks(home, heads)
    np.testing.assert_array_equal(np.asarray(back.astype(jnp.float32)), wire)
    for layer in range(layers):
        # what a decode step's gather reads at those blocks
        ctx = _gather(there[layer], home[None], heads)[0]
        np.testing.assert_array_equal(
            np.asarray(ctx.astype(jnp.float32)).reshape(wire[layer].shape),
            wire[layer])
        rest = np.delete(np.asarray(there[layer].astype(jnp.float32)),
                         np.asarray(home), axis=0)
        assert not rest.any()


# -- engines vs reference ---------------------------------------------------


def _requests(rng, n, *, lo=3, hi=13, new_lo=4, new_hi=10):
    out = []
    for i in range(n):
        prompt = [int(t) for t in rng.integers(1, 64,
                                               size=int(rng.integers(lo, hi)))]
        out.append(Request(rid=f"r{i}", prompt=prompt,
                           max_new_tokens=int(rng.integers(new_lo, new_hi))))
    return out


def test_continuous_and_static_match_reference(params, step, greedy):
    rng = np.random.default_rng(1)
    reqs = _requests(rng, 8)
    want = {r.rid: greedy(r.prompt, r.max_new_tokens) for r in reqs}
    for engine_cls in (ContinuousEngine, StaticEngine):
        eng = engine_cls(params, _scfg(), step=step)
        for r in reqs:
            eng.submit(Request(rid=r.rid, prompt=list(r.prompt),
                               max_new_tokens=r.max_new_tokens))
        eng.run_until_idle()
        got = {rid: res.tokens for rid, res in eng.results.items()}
        assert got == want, engine_cls.__name__
        assert all(res.ttft >= 0 for res in eng.results.values())


def test_prefix_sharing_preserves_outputs(params, step, greedy):
    """Duplicate prompts share prefix blocks (observable in stats) and the
    outputs stay identical to the reference — sharing is invisible."""
    rng = np.random.default_rng(2)
    prompt = [int(t) for t in rng.integers(1, 64, size=9)]
    eng = ContinuousEngine(params, _scfg(), step=step)
    eng.submit(Request(rid="a", prompt=list(prompt), max_new_tokens=6))
    eng.run_until_idle()
    eng.submit(Request(rid="b", prompt=list(prompt), max_new_tokens=6))
    eng.run_until_idle()
    assert eng.cache.stats["prefix_hits"] >= 1
    want = greedy(prompt, 6)
    assert eng.results["a"].tokens == want
    assert eng.results["b"].tokens == want


def test_preemption_under_block_pressure_replays_identically(params, step,
                                                             greedy):
    """A cache too small for the admitted set forces preempt-to-requeue
    across block-table eviction and re-admission; greedy replay makes the
    final outputs identical to the unpressured reference anyway."""
    rng = np.random.default_rng(3)
    # three DISTINCT 12-token prompts (distinct so prefix sharing can't
    # collapse their block usage), each decoding to the 32-token context
    # ceiling: all three slots march in lockstep toward 8 blocks apiece,
    # and 3 x 8 = 24 > 23 usable blocks guarantees one grow() fails
    reqs = [Request(rid=f"r{i}",
                    prompt=[int(t) for t in rng.integers(1, 64, size=12)],
                    max_new_tokens=20)
            for i in range(3)]
    eng = ContinuousEngine(params, _scfg(), step=step)
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    assert sum(res.preemptions for res in eng.results.values()) >= 1, \
        "pressure case produced no preemption; shrink the pool"
    for r in reqs:
        assert eng.results[r.rid].tokens == greedy(r.prompt,
                                                   r.max_new_tokens), r.rid


# -- the greedy pick on the device, the next call dispatched ahead ----------

# tests/test_serve_jamba.py has the bodies, written against a ``served``
# tuple of any family (configuration, weights, a step set a pool, -): here
# they run over ``TransformerLM``, whose allocator shares prefixes beside
# the call ahead where a recurrent family's declines to
LMCFG = TransformerConfig(vocab_size=256, d_model=32, n_heads=4, n_layers=2,
                          d_ff=64, max_len=64, dtype=jnp.float32)
AHEAD = "engine.decode_ahead"


@pytest.fixture(scope="module")
def served():
    weights = [TransformerLM(LMCFG).init(
        jax.random.key(seed), jnp.zeros((1, 8), jnp.int32))["params"]
        for seed in (0, 1)]
    steps = {cache: build_decode_step(LMCFG, cache, max_batch=3,
                                      buckets=lives.BUCKETS)
             for cache in (lives.CACHE, lives.SMALL_POOL)}
    return LMCFG, weights[0], steps, weights[1]


def outcomes(since: dict) -> dict:
    """``engine.decode_ahead``'s gains by outcome."""
    return {label(key, "outcome"): n
            for key, n in counters(AHEAD, since=since).items()}


def test_a_greedy_request_is_picked_by_the_program_a_sampled_one_on_the_host(
        served):
    """A greedy request never brings a row of logits to ``_pick_token``; a
    sampled one does for every token. Either way the token is what the same
    program's logits give and its log-probability the float64 one of those
    logits (the program's: ``max - logsumexp`` in float32)."""
    p = lives.prompt(11, seed=4)
    for rid, sampling, picked_on_host in (
            ("g", {}, 0), ("s", {"temperature": 0.8, "seed": 3}, 6)):
        eng = lives.engine(served)
        pick, emit = eng._pick_token, eng._emit_token
        on_host, chosen = [], []

        def counting(slot, row):
            on_host.append(slot.request.rid)
            return pick(slot, row)

        def keeping(slot, token):
            chosen.append((token, slot.logprob_sum))
            return emit(slot, token)

        eng._pick_token, eng._emit_token = counting, keeping
        tokens, rows = lives.serve(eng, {rid: p}, **sampling)[rid]
        assert on_host == [rid] * picked_on_host
        assert tokens == [t for t, _ in chosen] and len(tokens) == 6
        if not sampling:
            assert tokens == [int(r.argmax()) for r in rows]
        sums = [0.0] + [total for _, total in chosen]
        for row, token, before, after in zip(rows, tokens, sums, sums[1:]):
            assert after - before == pytest.approx(
                _token_logprob(row, token), abs=1e-5)     # the float64 one
    again = lives.serve(lives.engine(served), {"s": p}, temperature=0.8,
                        seed=3)["s"]
    assert again[0] == tokens           # a replay draws the same tokens


@pytest.mark.parametrize("name", list(lives.LIVES))
def test_a_call_dispatched_ahead_changes_no_token_and_no_step(served, name):
    lives.test_a_call_dispatched_ahead_changes_no_token_and_no_step(
        served, name)


def test_a_call_dispatched_ahead_under_block_pressure(served):
    since = counters(AHEAD)
    lives.test_a_call_dispatched_ahead_under_block_pressure(served)
    assert outcomes(since)["no_blocks"] >= 1


def test_settle_resolves_the_call_dispatched_ahead(served):
    lives.test_settle_resolves_the_call_dispatched_ahead(served)
    # and the pages are those of an engine that waited for every step
    # (but the null block's: an empty row writes there whatever token it
    # was given)
    ahead, plain = lives.engine(served), lives.engine(served, ahead=False)
    for eng in ahead, plain:
        for request in lives.requests([9, 4], 12):
            eng.submit(request)
    for _ in range(3):
        ahead.step()
        plain.step()
    plain.step()
    ahead.settle()
    for a, b in zip(jax.tree.leaves((ahead.k_pages, ahead.v_pages)),
                    jax.tree.leaves((plain.k_pages, plain.v_pages))):
        np.testing.assert_array_equal(np.asarray(a)[1:], np.asarray(b)[1:])
    for eng in ahead, plain:
        eng.drain_to_requests()


def test_requests_that_share_a_prefix_decode_ahead(served):
    """What this family has and a recurrent one declines: the second
    request reuses the first one's full prompt blocks, both ride the calls
    dispatched ahead, and each is served what it is served alone."""
    shared = lives.prompt(12, seed=3)
    script = {0: [Request(rid="first", prompt=shared + [7, 8],
                          max_new_tokens=9),
                  Request(rid="second", prompt=shared + [9],
                          max_new_tokens=7)]}
    _, ended, eng, ahead = lives.life(served, script)
    assert eng.cache.stats["prefix_hits"] == 1
    assert eng.cache.stats["prefix_blocks_reused"] == 3
    assert ahead >= 6
    for request in script[0]:
        _, alone, _, _ = lives.life(served, {0: [Request(
            rid=request.rid, prompt=list(request.prompt),
            max_new_tokens=request.max_new_tokens)]}, ahead=False)
        assert ended[request.rid][0] == alone[request.rid][0]
        assert ended[request.rid][1] == pytest.approx(alone[request.rid][1],
                                                      abs=1e-5)


def test_a_row_dropped_at_eos_token_has_its_slot_refilled(served):
    """A sequence that ends on ``eos_token`` is known to have ended only
    after the next call went out with its row: the row is dropped, the
    request that waited takes the slot with a call of its own (counted
    ``admitted``), and is served what it is served alone."""
    eos = lives.eos_of(served)
    eng = lives.engine(served, eos_token=eos)
    for request in lives.requests([9, 4, 14, 6], 9):
        eng.submit(request)
    since, dropped = counters(AHEAD), []
    while not eng.idle:
        flying = dict(eng._ahead.slots) if eng._ahead else {}
        eng.step()
        dropped += [(i, s.request.rid) for i, s in flying.items()
                    if eng.slots[i] is not s]
        if dropped and "q6" not in eng.results:   # refilled the step it left
            assert eng.slots[dropped[0][0]].request.rid == "q6"
    assert dropped[0][1] == "q9"
    assert eng.results["q9"].tokens[-1] == eos
    assert len(eng.results["q9"].tokens) < 9
    assert outcomes(since)["admitted"] >= 1
    _, alone, _, _ = lives.life(served, {0: lives.requests([6], 9)},
                                ahead=False, eos_token=eos)
    assert eng.results["q6"].tokens == alone["q6"][0]


def test_a_swap_between_two_steps_declines_the_call_ahead(served):
    """Slots on two weight versions take a call a version, and nothing is
    dispatched ahead of such a step (``versions``); every request finishes
    on the weights it pinned at admission."""
    _, old, _, new = served
    eng = lives.engine(served)
    before_swap, after_swap = lives.requests([9, 4], 10), \
        lives.requests([7], 6)
    for request in before_swap:
        eng.submit(request)
    eng.step()
    eng.step()
    assert eng._ahead is not None       # in flight over the swap
    eng.swap_params(new, version=1)
    eng.submit(after_swap[0])
    since = counters(AHEAD)
    eng.step()
    assert outcomes(since) == {"versions": 1} and eng._ahead is None
    eng.run_until_idle()
    assert outcomes(since)["dispatched"] >= 1       # q7 alone, once they left
    assert {rid: r.ver for rid, r in eng.results.items()} \
        == {"q9": 0, "q4": 0, "q7": 1}
    for weights, version, asked in ((old, 0, before_swap),
                                    (new, 1, after_swap)):
        for request in asked:
            alone = lives.engine((LMCFG, weights, served[2], None))
            alone.submit(Request(rid=request.rid, prompt=request.prompt,
                                 max_new_tokens=request.max_new_tokens))
            alone.run_until_idle()
            assert eng.results[request.rid].tokens \
                == alone.results[request.rid].tokens, (request.rid, version)


@pytest.mark.parametrize("batch, want", [
    ("greedy", {"dispatched": 6, "no_rows": 1}),
    ("one member samples", {"sampled": 6, "no_rows": 1}),
    ("a stub step", {"no_picks": 7}),
])
def test_every_step_that_decodes_counts_what_came_of_the_call_ahead(
        served, batch, want):
    """``engine.decode_ahead{outcome}``: one count a step that decoded
    (seven: the prefills gave the first of eight tokens; in the last every
    slot is on its last token, which a stub step never comes to ask)."""
    if batch == "a stub step":
        eng = ContinuousEngine(None, _scfg(), step=StubStep())
    else:
        eng = lives.engine(served)
    sampling = {"temperature": 0.7, "seed": 2} \
        if batch == "one member samples" else {}
    for request in lives.requests([9, 4], 8) \
            + lives.requests([12], 8, **sampling):
        request.prompt = [t % 64 for t in request.prompt]   # the stub's
        eng.submit(request)
    since = counters(AHEAD)
    eng.run_until_idle()
    assert outcomes(since) == want and eng.steps == 7


# -- the decode programs' attention: the kernel or the jnp form, by shape ----

# a geometry whose page row is two 128-lane tiles and whose block is two
# float32 sublane tiles (16 KB a page): the rule gives it the kernel
KMCFG = TransformerConfig(vocab_size=64, d_model=256, n_heads=4, n_layers=2,
                          d_ff=256, max_len=64, dtype=jnp.float32)
KCCFG = CacheConfig(num_blocks=10, block_size=16, max_blocks_per_seq=4)


CHOICE = "paged_attn.kernel_choice"


@pytest.fixture(scope="module")
def branches():
    """``KMCFG``'s weights and its step set twice over ``KCCFG``: as the
    rule builds it (the kernel, interpreted here; two pages a compute step,
    so a row that fills its table takes two) and with a rule that declines
    every shape (the ``jnp`` form), and what each build counted."""
    params = TransformerLM(KMCFG).init(
        jax.random.key(1), jnp.zeros((1, 8), jnp.int32))["params"]
    built = {}
    for branch, knob, value in (
            ("pallas", "_STEP_TOKENS", 32),
            ("jnp", "pages_per_step", lambda *shape: None)):
        before = counters(CHOICE)
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(serve_decode, knob, value)
            step = build_decode_step(KMCFG, KCCFG, max_batch=3,
                                     buckets=(32,))
        built[branch] = step, counters(CHOICE, since=before)
    return params, built


def test_each_branch_counts_its_choice_a_layer(branches):
    """One count a traced site (a layer) through ``kernel_site``, static
    labels: the form, the heads, the page geometry, the batch."""
    for branch, (_, counted) in branches[1].items():
        (key, n), = counted.items()
        assert n == KMCFG.n_layers
        assert label(key, "impl") == branch
        assert label(key, "pages_per_step") == (
            "2" if branch == "pallas" else "0")
        # a full step copies a page of K and of V and waits once a buffer
        assert (label(key, "copies_per_step"),
                label(key, "waits_per_step")) == (
                    ("4", "2") if branch == "pallas" else ("0", "0"))
        assert (label(key, "kv_heads"), label(key, "group"),
                label(key, "head_dim"), label(key, "block_size"),
                label(key, "max_blocks"), label(key, "batch")) == (
                    "4", "1", "64", "16", "4", "3")


def test_the_kernel_branch_serves_what_the_jnp_branch_serves(branches):
    """Through the engine, three rows that outgrow the pool together (nine
    allocatable blocks for twelve): the kernel's greedy tokens are the
    ``jnp`` form's, the chosen tokens' log-probabilities agree to float32
    rounding, and the request that was preempted and replayed returns what
    it returns alone in a fresh engine."""
    params, built = branches
    scfg = ServeConfig(model=KMCFG, cache=KCCFG, max_batch=3, buckets=(32,))
    rng = np.random.default_rng(8)
    prompts = {f"r{i}": [int(t) for t in rng.integers(1, 64, size=18 + i)]
               for i in range(3)}

    def serve(branch, rids):
        eng = ContinuousEngine(params, scfg, step=built[branch][0])
        logprobs, retire = {}, eng._retire

        def keeping(i):
            slot = eng.slots[i]
            logprobs[slot.request.rid] = slot.logprob_sum
            return retire(i)

        eng._retire = keeping
        for rid in rids:
            eng.submit(Request(rid=rid, prompt=list(prompts[rid]),
                               max_new_tokens=64 - len(prompts[rid])))
        eng.run_until_idle()
        return ({rid: eng.results[rid].tokens for rid in rids}, logprobs,
                sum(r.preemptions for r in eng.results.values()))

    tokens, logprobs, preempted = serve("pallas", list(prompts))
    assert preempted >= 1, "no preemption; shrink the pool"
    want, want_logprobs, _ = serve("jnp", list(prompts))
    assert tokens == want
    for rid in prompts:
        assert logprobs[rid] == pytest.approx(want_logprobs[rid], abs=1e-3)
        assert serve("pallas", [rid])[0][rid] == tokens[rid]


@pytest.mark.parametrize("branch", ["pallas", "jnp"])
def test_a_decode_program_keeps_the_scopes_the_benchmark_reads(branches,
                                                               branch):
    """``gather_ctx`` and ``write_kv`` inside every ``block{i}/attn`` of the
    compiled decode program, on either branch: the benchmark's readers sum
    the device time under both, and a program without one is read as
    incorrect (``benchmark/lib/readers.py::_scope_sum``)."""
    names = set(re.findall(r'op_name="([^"]+)"',
                           branches[1][branch][0].decode.as_text()))
    for i in range(KMCFG.n_layers):
        for scope in ("gather_ctx", "write_kv"):
            assert any(re.search(rf"/block{i}/attn/{scope}(/|$)", name)
                       for name in names), (i, scope)


@pytest.mark.parametrize("width, block, dtype, pages", [
    (1024, 16, jnp.bfloat16, 16),    # gpt2-medium's pages: 32 KB each
    (128, 16, jnp.bfloat16, 64),     # Jamba's 4 KB: 1024 positions a step
    (256, 16, jnp.float32, 32),      # KCCFG's 16 KB (its rows have 4)
    (2048, 32, jnp.bfloat16, 4),     # 128 KB a page
    (32, 4, jnp.float32, None),      # MCFG's: no lane multiple
    (1024, 4, jnp.bfloat16, None),   # a block under the type's sublane tile
    (1024, 8, jnp.bfloat16, None),
    (1024, 8, jnp.float32, 16),
])
def test_the_shape_rule_reads_the_pages_alone(width, block, dtype, pages):
    """``pages_per_step``: from a row's width, a block's positions and the
    cache's type, nothing else."""
    assert serve_decode.pages_per_step(width, block, dtype, 64) == (
        pages if pages is None else min(pages, 64))
    assert serve_decode.pages_per_step(width, block, dtype, 4) == (
        pages if pages is None else min(pages, 4))


@pytest.mark.parametrize("d_model, heads, cache_dtype, impl", [
    (256, 4, jnp.float32, "pallas"), (256, 4, jnp.bfloat16, "pallas"),
    (256, 8, jnp.float32, "pallas"),     # heads of 32 side by side
    (192, 3, jnp.float32, "jnp"),        # 192 lanes
])
def test_a_lowered_decode_program_takes_the_branch_its_pages_give(
        d_model, heads, cache_dtype, impl):
    """Read from ``paged_attn.kernel_choice`` after tracing alone (no
    compile): the model's name, a flag or the environment play no part."""
    cfg = TransformerConfig(vocab_size=64, d_model=d_model, n_heads=heads,
                            n_layers=3, d_ff=64, max_len=64,
                            dtype=jnp.float32)
    before = counters(CHOICE)
    lower_step(cfg, KCCFG, max_batch=2, cache_dtype=cache_dtype)[2](None)
    (key, n), = counters(CHOICE, since=before).items()
    assert (label(key, "impl"), n) == (impl, 3)


# -- replica protocol (in-process) -----------------------------------------


def _submit_all(kv, reqs):
    from tpu_sandbox.serve import replica as R

    for r in reqs:
        R.submit_request(kv, r.rid, r.prompt, r.max_new_tokens)
    R.announce_total(kv, len(reqs))


def test_replica_drain_requeues_and_peer_finishes(params, step, greedy):
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.serve import replica as R

    server = KVServer()
    kv = KVClient(port=server.port)
    try:
        rng = np.random.default_rng(4)
        reqs = _requests(rng, 6)
        _submit_all(kv, reqs)
        w1 = R.ReplicaWorker(kv, ContinuousEngine(params, _scfg(),
                                                  step=step),
                             tag="w1", lease_ttl=0.5)
        for _ in range(3):
            w1.tick()
        assert w1.stats.claimed >= 1
        w1.request_drain()           # the SIGTERM path
        w1.tick()
        assert w1.stats.requeued + w1.stats.completed >= w1.stats.claimed
        w2 = R.ReplicaWorker(kv, ContinuousEngine(params, _scfg(),
                                                  step=step),
                             tag="w2", lease_ttl=0.5)
        w2.run(timeout=60)
        for r in reqs:
            res = R.read_result(kv, r.rid, timeout=5)
            assert res["tokens"] == greedy(r.prompt, r.max_new_tokens), r.rid
    finally:
        kv.close()
        server.stop()


def test_replica_scavenge_rescues_orphaned_claims(params, step, greedy):
    """A claimant that vanishes without draining (SIGKILL) leaves claims
    whose leases expire; a peer's scavenge pass requeues them exactly once
    and the job still completes with reference outputs."""
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.serve import replica as R

    server = KVServer()
    kv = KVClient(port=server.port)
    try:
        rng = np.random.default_rng(5)
        reqs = _requests(rng, 4)
        _submit_all(kv, reqs)
        dead = R.ReplicaWorker(kv, ContinuousEngine(params, _scfg(),
                                                    step=step),
                               tag="dead", lease_ttl=0.3)
        dead.tick()                  # claims + leases, then goes silent
        assert dead.stats.claimed >= 1
        dead.engine.drain_to_requests()  # drop its work on the floor
        time.sleep(0.5)              # leases expire unheartbeaten
        w = R.ReplicaWorker(kv, ContinuousEngine(params, _scfg(),
                                                 step=step),
                            tag="rescuer", lease_ttl=0.5,
                            scavenge_interval=0.1)
        w.run(timeout=60)
        assert w.stats.scavenged >= 1
        for r in reqs:
            res = R.read_result(kv, r.rid, timeout=5)
            assert res["tokens"] == greedy(r.prompt, r.max_new_tokens), r.rid
    finally:
        kv.close()
        server.stop()


def test_sampled_decode_interrupted_mid_decode_replays_bitwise(params, step):
    """Replay-exact sampling through a kill: a temperature/top-k request is
    claimed, decoded partway, then its worker drains (the SIGTERM path) and
    a peer re-executes it from scratch — the final tokens are bitwise
    identical to an uninterrupted run, because each sampled step draws from
    ``fold_in(key(seed), step_index)``, not from mutable sampler state."""
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.serve import replica as R

    rng = np.random.default_rng(6)
    prompt = [int(t) for t in rng.integers(1, 64, size=9)]
    kwargs = dict(max_new_tokens=12, temperature=3.0, top_k=8, seed=7)

    # the uninterrupted reference run, and proof the sampler is live
    ref = ContinuousEngine(params, _scfg(), step=step)
    ref.submit(Request(rid="ref", prompt=list(prompt), **kwargs))
    ref.run_until_idle()
    want = ref.results["ref"].tokens
    greedy_eng = ContinuousEngine(params, _scfg(), step=step)
    greedy_eng.submit(Request(rid="g", prompt=list(prompt),
                              max_new_tokens=12))
    greedy_eng.run_until_idle()
    assert want != greedy_eng.results["g"].tokens, \
        "temperature-3.0 sampling reproduced greedy — sampler not engaged"

    server = KVServer()
    kv = KVClient(port=server.port)
    try:
        R.submit_request(kv, "s", prompt, 12, temperature=3.0, top_k=8,
                         seed=7)
        R.announce_total(kv, 1)
        w1 = R.ReplicaWorker(kv, ContinuousEngine(params, _scfg(),
                                                  step=step),
                             tag="w1", lease_ttl=0.5)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            w1.tick()
            slots = [s for s in w1.engine.slots
                     if s is not None and s.request.rid == "s"]
            if slots and len(slots[0].generated) >= 3:
                break
        assert slots and 3 <= len(slots[0].generated) < 12, \
            "no mid-decode window"
        w1.request_drain()
        w1.tick()
        assert w1.stats.requeued == 1
        w2 = R.ReplicaWorker(kv, ContinuousEngine(params, _scfg(),
                                                  step=step),
                             tag="w2", lease_ttl=0.5)
        w2.run(timeout=60)
        assert R.read_result(kv, "s", timeout=5)["tokens"] == want
    finally:
        kv.close()
        server.stop()
