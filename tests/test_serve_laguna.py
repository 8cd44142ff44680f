"""``serve/`` with the fourth family (``LagunaLM``, tiny, float32, on the
CPU): pages by the layer's kind, two tables a call, a ring of window blocks a
sequence. Prefill through each bucket and then decode steps through the
engine against the plain reference's one full forward (logits, not tokens),
with prompts shorter and longer than the window and contexts that cross the
window and a block boundary while decoding; sessions in one batch, a slot
refilled, a request preempted and replayed; the allocator's two pools; the
paged kernel with a window, interpreted, against the ``jnp`` form; the flash
kernel's band against an explicit mask. The engine's scheduling is
tests/test_serve.py's."""

import functools
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import laguna as ref  # noqa: E402
from test_laguna_model import (TINY, engine_params,  # noqa: E402
                               init_variables, tiny_config)
from tests.helpers import counters, label  # noqa: E402
from tpu_sandbox.models import laguna  # noqa: E402
from tpu_sandbox.obs import get_registry  # noqa: E402
from tpu_sandbox.ops.pallas_attention import flash_attention  # noqa: E402
from tpu_sandbox.ops.pallas_paged_attention import paged_attention  # noqa: E402
from tpu_sandbox.serve import (CacheConfig, ContinuousEngine,  # noqa: E402
                               Request, ServeConfig)
from tpu_sandbox.serve import decode as serve_decode  # noqa: E402
from tpu_sandbox.serve.cache import PagedKVCache  # noqa: E402
from tpu_sandbox.serve.decode import build_decode_step  # noqa: E402

pytestmark = pytest.mark.usefixtures("light_compile")

BUCKETS = (8, 16, 32)
# window 8 in blocks of 4: a ring of 3 blocks a sequence
CACHE = CacheConfig(num_blocks=65, block_size=4, max_blocks_per_seq=16,
                    window=8, window_blocks=13)
SMALL_POOL = CacheConfig(num_blocks=11, block_size=4, max_blocks_per_seq=16,
                         window=8, window_blocks=13)


@pytest.fixture(scope="module")
def served():
    cfg = tiny_config()
    params = engine_params(jax.jit(lambda k: init_variables(cfg, k))(
        jax.random.key(0)))
    steps = {cache: build_decode_step(cfg, cache, max_batch=3,
                                      buckets=BUCKETS)
             for cache in (CACHE, SMALL_POOL)}
    return cfg, params, steps, ref.from_program_tree(params, TINY)


@pytest.fixture(autouse=True)
def small_query_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 4)


def engine(served, cache=CACHE, ahead: bool = True) -> ContinuousEngine:
    cfg, params, steps, _ = served
    scfg = ServeConfig(model=cfg, cache=cache, max_batch=3, buckets=BUCKETS)
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
    return np.random.default_rng([seed, n]).integers(1, 96, n).tolist()


def serve(eng, requests: dict, new: int = 6) -> dict:
    for rid, p in requests.items():
        eng.submit(Request(rid=rid, prompt=list(p), max_new_tokens=new))
    eng.run_until_idle()
    return {rid: (eng.results[rid].tokens, np.stack(eng.rows[rid]))
            for rid in requests}


def reference_rows(tree, tokens, first: int):
    padded = np.zeros((1, len(tokens) + -len(tokens) % 4), np.int32)
    padded[0, :len(tokens)] = tokens
    return np.asarray(ref.forward(tree, padded, TINY))[0, first:len(tokens)]


@pytest.mark.parametrize("plen", [
    1, 5,          # inside the first window, which decoding then crosses
    8, 16, 32,     # a prompt that ends at its bucket's end
    13, 27,        # longer than the window, ending inside a block
])
def test_prefill_then_decode_is_the_references_full_forward(served, plen):
    """Prefill through a padded bucket stores every position's keys and
    values on the full layer and the last window's on the window layers,
    through the ring; every later token is written and read through both
    tables. 11 served tokens cross three block boundaries, and from a short
    prompt the window's edge. The logits of every served position against
    one forward that has neither cache."""
    tree = served[3]
    p = prompt(plen)
    tokens, rows = serve(engine(served), {"r": p}, new=11)["r"]
    want = reference_rows(tree, p + tokens[:-1], plen - 1)
    assert rows.shape == want.shape == (11, 96)
    np.testing.assert_allclose(rows, want, rtol=3e-4, atol=3e-4)
    # the program's own pick is the host's argmax of the same logits
    assert tokens == [int(r.argmax()) for r in rows]


def test_sessions_in_one_batch_give_what_each_gives_alone(served):
    prompts = {"a": prompt(8), "b": prompt(13), "c": prompt(3)}
    together = serve(engine(served), prompts, new=9)
    for rid, p in prompts.items():
        alone = serve(engine(served), {rid: p}, new=9)[rid]
        assert together[rid][0] == alone[0]
        np.testing.assert_allclose(together[rid][1], alone[1], rtol=1e-5,
                                   atol=1e-5)


def test_a_slot_retired_and_refilled_starts_from_the_new_prompt(served):
    """Five requests through three slots: the fourth and fifth take a slot,
    and blocks of both kinds, an earlier sequence left behind."""
    prompts = {f"r{i}": prompt(4 + 5 * i, seed=1) for i in range(5)}
    eng = engine(served)
    got = serve(eng, prompts)
    for rid, p in prompts.items():
        alone = serve(engine(served), {rid: p})[rid]
        assert got[rid][0] == alone[0]
        np.testing.assert_allclose(got[rid][1], alone[1], rtol=1e-5, atol=1e-5)
    # every block of both kinds is back
    assert eng.cache.free_blocks == 64 and eng.cache.free_window_blocks == 12


def test_a_preempted_request_replays_from_its_prompt(served):
    """Ten allocatable full blocks of 4: three sequences outgrow them, the
    newest is evicted -- its ring returned with its full blocks -- and
    replays; its tokens are those of a fresh engine."""
    prompts = {f"p{i}": prompt(9 + i, seed=2) for i in range(3)}
    eng = engine(served, SMALL_POOL)
    got = serve(eng, prompts, new=8)
    assert sum(r.preemptions for r in eng.results.values()) >= 1
    assert eng.cache.free_blocks == 10 and eng.cache.free_window_blocks == 12
    for rid, p in prompts.items():
        alone = serve(engine(served), {rid: p}, new=8)[rid]
        assert got[rid][0] == alone[0]
        np.testing.assert_allclose(got[rid][1][-8:], alone[1], rtol=1e-5,
                                   atol=1e-5)


def test_a_shared_prefix_is_declined_beside_window_layers(served):
    """The window layers' rows behind a shared prefix's end are gone: the
    second request prefills from its start, and the decline is counted."""
    shared = prompt(12, seed=3)
    eng = engine(served)
    before = counters("serve.prefix_reuse_declined")
    for rid, tail in (("first", [7, 8]), ("second", [9])):
        eng.submit(Request(rid=rid, prompt=shared + tail, max_new_tokens=6))
    eng.run_until_idle()
    assert eng.cache.stats["prefix_hits"] == 0
    assert eng.cache.stats["prefix_reuse_declined"] == 1
    assert sum(counters("serve.prefix_reuse_declined",
                        since=before).values()) == 1
    alone = serve(engine(served), {"second": shared + [9]})["second"]
    assert eng.results["second"].tokens == alone[0]


def test_the_engine_holds_pages_by_kind_and_the_shares_counters(served):
    cfg, _, steps, _ = served
    step = steps[CACHE]
    assert step.picks and not step.recurrent and step.next_tokens is not None
    k_pages, v_pages, counters_ = step.buffers
    assert [p.shape for p in k_pages] == [p.shape for p in v_pages] == [
        (65, 4, 32)] + 3 * [(13, 4, 32)]
    assert set(counters_) == {"block1", "block2", "block3"}
    eng = engine(served)
    recycled = counters("cache.window_blocks_recycled")
    serve(eng, {"x": prompt(6)}, new=20)
    for layer in jax.tree.map(int, eng.state).values():
        assert layer["steps"] >= 20 and layer["rows_dropped"] == 0
    # 26 positions are 7 blocks: the ring's 3, then 4 that overwrote one
    assert sum(counters("cache.window_blocks_recycled",
                        since=recycled).values()) == 4
    assert eng.cache.stats["window_blocks_seq_max"] == 3
    gauge = get_registry().gauge
    assert gauge("cache.blocks", labels={"kind": "window",
                                         "state": "free"}).value == 12
    assert gauge("cache.blocks", labels={"kind": "full",
                                         "state": "held"}).value == 0


@pytest.mark.parametrize("name", ["together", "staggered"])
def test_a_call_dispatched_ahead_changes_no_token_and_no_step(served, name):
    prompts = {"a": prompt(8, seed=5), "b": prompt(13, seed=5),
               "c": prompt(3, seed=5)}
    new = 9 if name == "together" else 5
    ahead, waits = engine(served), engine(served, ahead=False)
    got, want = serve(ahead, prompts, new=new), serve(waits, prompts, new=new)
    assert ahead.steps == waits.steps
    for rid in prompts:
        assert got[rid][0] == want[rid][0]
        np.testing.assert_allclose(got[rid][1], want[rid][1], rtol=1e-5,
                                   atol=1e-5)


def test_the_decode_program_keeps_the_scopes_the_benchmark_reads(served):
    names = set(re.findall(r'op_name="([^"]+)"',
                           served[2][CACHE].decode.as_text()))
    kinds = served[0].layer_kinds
    for i, kind in enumerate(kinds):
        scopes = [f"attn/gather_ctx/{kind}", "attn/write_kv", "attn/qkv",
                  "attn/rope", "attn/attn_gate", "attn/o"]
        scopes += ["mlp0/gate", "mlp0/down"] if i == 0 else [
            "moe/router", "moe/dispatch", "moe/experts", "moe/combine",
            "moe/shared"]
        for scope in scopes:
            assert any(re.search(rf"/LagunaLM/block{i}/{scope}(/|$)", n)
                       for n in names), (i, scope)
    assert any("/LagunaLM/lm_head" in n for n in names)


def test_the_caches_window_must_be_the_models(served):
    with pytest.raises(ValueError, match="window"):
        serve_decode.lower_step(
            served[0], CacheConfig(num_blocks=9, block_size=4),
            max_batch=2, cache_dtype=jnp.float32)


# --- the allocator: two kinds of block ---

def pools(cache: PagedKVCache) -> tuple[int, int]:
    return cache.free_blocks, cache.free_window_blocks


def test_a_sequence_grown_to_ten_windows_never_owns_more_than_the_ring():
    cache = PagedKVCache(CACHE)
    assert CACHE.ring_blocks == 3
    seq = cache.alloc(list(range(1, 4)), 0)
    assert (len(seq.block_ids), len(seq.window_ids)) == (1, 1)
    seen = set(seq.window_ids)
    cfg = CacheConfig(num_blocks=65, block_size=4, max_blocks_per_seq=32,
                      window=8, window_blocks=13)
    cache = PagedKVCache(cfg)
    seq = cache.alloc(list(range(1, 4)), 0)
    for _ in range(20):      # 21 blocks: 84 positions, ten windows and more
        assert cache.grow(seq)
        assert len(seq.window_ids) <= 3
        seen |= set(seq.window_ids)
    assert (len(seq.block_ids), len(seq.window_ids)) == (21, 3)
    assert pools(cache) == (64 - 21, 12 - 3)
    # position p is in entry (p // 4) % 3 of the ring, whatever its block
    table = cache.window_table(seq)
    assert table.tolist() == seq.window_ids
    cache.free(seq)
    assert pools(cache) == (64, 12)


def test_a_long_prompt_stores_its_last_window_through_the_ring():
    cache = PagedKVCache(CACHE)
    seq = cache.alloc(list(range(1, 28)), 0)          # 27 positions
    assert (len(seq.block_ids), len(seq.window_ids)) == (7, 3)
    dest = cache.window_dest_indices(seq, 32, 27)
    # the window is 19..26; its first block starts at 16
    assert not dest[:16].any() and not dest[27:].any()
    for p in range(16, 27):
        assert dest[p] == seq.window_ids[p // 4 % 3] * 4 + p % 4
    assert len(set(dest[16:27])) == 11
    full = cache.dest_indices(seq, 32)
    for p in range(28):
        assert full[p] == seq.block_ids[p // 4] * 4 + p % 4
    # a prompt inside the first window stores all of itself
    short = cache.alloc([1, 2, 3, 4, 5], 0)
    dest = cache.window_dest_indices(short, 8, 5)
    assert [d // 4 for d in dest[:5]] == [short.window_ids[0]] * 4 + [
        short.window_ids[1]]


def test_can_admit_and_alloc_refuse_when_either_pool_is_short():
    # room for two rings, and full blocks for many sequences
    cfg = CacheConfig(num_blocks=65, block_size=4, max_blocks_per_seq=16,
                      window=8, window_blocks=7)
    cache = PagedKVCache(cfg)
    long = list(range(1, 20))
    a, b = cache.alloc(long, 0), cache.alloc(long, 0)
    assert cache.free_window_blocks == 0 and cache.free_blocks > 10
    assert not cache.can_admit(long, 0) and cache.alloc(long, 0) is None
    assert cache.free_blocks == 64 - 10          # a refusal takes nothing
    cache.free(a)
    assert cache.can_admit(long, 0)
    # the other way round: full blocks short, rings to spare
    cfg = CacheConfig(num_blocks=6, block_size=4, max_blocks_per_seq=16,
                      window=8, window_blocks=13)
    cache = PagedKVCache(cfg)
    assert cache.alloc(long, 0) is not None      # 5 full blocks, 3 of a ring
    assert not cache.can_admit([1, 2], 0) and cache.alloc([1, 2], 0) is None
    assert cache.free_window_blocks == 9
    del b


def test_grow_takes_both_kinds_or_neither():
    cfg = CacheConfig(num_blocks=65, block_size=4, max_blocks_per_seq=16,
                      window=8, window_blocks=3)
    cache = PagedKVCache(cfg)
    seq = cache.alloc([1, 2, 3], 0)
    assert cache.grow(seq) and pools(cache) == (62, 0)
    assert not cache.grow(seq)                   # no window block for the ring
    assert pools(cache) == (62, 0) and len(seq.block_ids) == 2
    cache.free(seq)
    assert pools(cache) == (64, 2)


def test_a_seeded_run_of_alloc_grow_free_loses_no_block():
    rng = np.random.default_rng(7)
    cfg = CacheConfig(num_blocks=41, block_size=4, max_blocks_per_seq=12,
                      window=8, window_blocks=10)
    cache = PagedKVCache(cfg)
    live = []
    for _ in range(400):
        move = rng.integers(3)
        if move == 0 or not live:
            seq = cache.alloc(rng.integers(1, 90, rng.integers(1, 30)).tolist(),
                              0)
            if seq is not None:
                live.append(seq)
        elif move == 1:
            cache.grow(live[rng.integers(len(live))])
        else:
            cache.free(live.pop(rng.integers(len(live))),
                       cache_prefix=bool(rng.integers(2)))
        owned = [b for s in live for b in s.block_ids]
        rings = [b for s in live for b in s.window_ids]
        assert len(set(owned)) == len(owned) and 0 not in owned
        assert len(set(rings)) == len(rings) and 0 not in rings
        assert all(len(s.window_ids) == min(len(s.block_ids), 3)
                   for s in live)
        assert len(owned) + cache.free_blocks == 40
        assert len(rings) + cache.free_window_blocks == 9
    for seq in live:
        cache.free(seq)
    assert pools(cache) == (40, 9)


def test_a_cache_without_window_layers_has_one_pool():
    cache = PagedKVCache(CacheConfig(num_blocks=9, block_size=4))
    seq = cache.alloc([1, 2, 3, 4, 5], 0)
    assert seq.window_ids == [] and cache.free_window_blocks == 0
    assert cache.grow(seq) and seq.window_ids == []
    with pytest.raises(ValueError, match="window_blocks"):
        PagedKVCache(CacheConfig(num_blocks=9, block_size=4, window=8))


# --- the paged kernel with a window, interpreted, against the jnp form ---

BLOCK, WINDOW, HKV, HD = 16, 40, 2, 128
RING = -(-WINDOW // BLOCK) + 1
LENGTHS = {"empty": 0, "one": 1, "under": 23, "at": WINDOW,
           "over": WINDOW + 1, "off_block": 101, "many_rings": 170}


def ring_case(group: int, lengths, dtype=jnp.float32, seed=0):
    """Every row's positions written in order through its ring, as a
    sequence's are: a later block of positions overwrites an earlier."""
    rng = np.random.default_rng(seed)
    rows = len(lengths)
    q = jnp.asarray(rng.standard_normal((rows, HKV * group, HD)), dtype)
    pages = rng.standard_normal((2, rows * RING + 1, BLOCK, HKV * HD))
    tables = (rng.permutation(rows * RING).reshape(rows, RING) + 1
              ).astype(np.int32)
    return (q, jnp.asarray(pages[0], dtype), jnp.asarray(pages[1], dtype),
            tables, np.asarray(lengths, np.int32))


@functools.partial(jax.jit, static_argnames=("pages_per_step",))
def window_kernel(q, k_pages, v_pages, tables, lengths, pages_per_step=2):
    return paged_attention(q, k_pages, v_pages, tables, lengths,
                           pages_per_step=pages_per_step, window=WINDOW)


@jax.jit
def window_jnp(q, k_pages, v_pages, tables, lengths):
    with jax.default_matmul_precision("highest"):
        return serve_decode._attend_jnp(
            q.astype(jnp.float32), k_pages.astype(jnp.float32),
            v_pages.astype(jnp.float32), tables, lengths, HKV, WINDOW)


@pytest.mark.parametrize("length", LENGTHS)
@pytest.mark.parametrize("group", [6, 8])
def test_window_kernel_matches_the_jnp_form(group, length):
    """Row 1 at the length under test between two others; groups of 6 (the
    full layers' share of query heads a key/value head would be, here under
    a window all the same) and 8."""
    q, k, v, tables, lengths = ring_case(group, [57, LENGTHS[length], 100])
    got = np.asarray(window_kernel(q, k, v, tables, lengths))
    want = np.asarray(window_jnp(q, k, v, tables, lengths))
    assert got.shape == (3, HKV * group, HD) and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    if lengths[1] == 0:
        assert not got[1].any()


def test_the_jnp_form_reads_the_window_alone():
    """The ``jnp`` form against attention over the rows the window holds,
    taken out of the ring by hand."""
    q, k, v, tables, lengths = ring_case(3, [101])
    got = np.asarray(window_jnp(q, k, v, tables, lengths))[0]
    at = np.arange(101 - WINDOW, 101)
    rows = tables[0, at // BLOCK % RING] * BLOCK + at % BLOCK
    keys = np.asarray(k).reshape(-1, HKV, HD)[rows]
    values = np.asarray(v).reshape(-1, HKV, HD)[rows]
    s = np.einsum("hgd,khd->hgk", np.asarray(q[0]).reshape(HKV, 3, HD),
                  keys) / np.sqrt(HD)
    w = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hgk,khd->hgd", w / w.sum(-1, keepdims=True), values)
    np.testing.assert_allclose(got, want.reshape(HKV * 3, HD), rtol=2e-5,
                               atol=2e-5)


def test_window_kernel_reads_nothing_outside_the_window():
    """NaN in every block the window has left, in the rows behind a length
    and in the null block."""
    q, k, v, tables, lengths = ring_case(6, [170, 0, 23])
    held = np.zeros(k.shape[:2], bool)
    for table, n in zip(tables, lengths):
        for at in range(max(0, n - WINDOW) // BLOCK * BLOCK, n):
            held[table[at // BLOCK % RING], at % BLOCK] = True
    mask = jnp.asarray(held)[:, :, None]
    got = np.asarray(window_kernel(q, jnp.where(mask, k, jnp.nan),
                                   jnp.where(mask, v, jnp.nan), tables,
                                   lengths))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got, np.asarray(window_kernel(q, k, v, tables, lengths)))


def test_a_ring_too_short_for_the_window_is_refused():
    q, k, v, tables, lengths = ring_case(6, [5])
    with pytest.raises(ValueError, match="ring"):
        paged_attention(q, k, v, tables[:, :2], lengths, pages_per_step=2,
                        window=WINDOW)


def test_the_cells_pages_take_the_kernel_on_both_kinds():
    """8 heads of 128 in bfloat16 blocks of 16: sixteen 32 KB pages a step,
    over a table of 2112 blocks or a ring of 33."""
    assert serve_decode.pages_per_step(1024, 16, jnp.bfloat16, 2112) == 16
    assert serve_decode.pages_per_step(1024, 16, jnp.bfloat16, 33) == 16
    cfg = CacheConfig(num_blocks=9, block_size=16, max_blocks_per_seq=4,
                      window=512, window_blocks=5)
    assert cfg.ring_blocks == 33
    k_pages, v_pages = serve_decode.page_shapes(
        cfg, 4, 8, 128, jnp.bfloat16, kinds=("full", "window", "window",
                                              "window"))
    assert [p.shape[0] for p in k_pages] == [9, 5, 5, 5]
    assert {p.shape[1:] for p in v_pages} == {(16, 1024)}


# --- the flash kernel's band against an explicit mask ---

@pytest.mark.parametrize("s,d,window,block", [
    (384, 128, 100, 128),    # packed form; a window that is no tile multiple
    (512, 128, 130, 128),    # a band that crosses two key tiles a query tile
    (300, 16, 37, 128),      # padded form: S no lane multiple, D 16
    (256, 128, 300, 128),    # a window longer than the sequence: causal
])
def test_the_flash_band_is_the_explicit_mask(s, d, window, block):
    keys = jax.random.split(jax.random.key(s), 3)
    q, k, v = (jax.random.normal(key, (1, s, 2, d), jnp.float32)
               for key in keys)
    before = counters("attn.tile_choice")
    got = jax.jit(functools.partial(
        flash_attention, window=window, block_q=block, block_k=block))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = laguna.banded_attention(q, k, v, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    (key, _), = counters("attn.tile_choice", since=before).items()
    assert label(key, "window") == str(window)
    # tiles wholly behind the band are skipped, not masked
    tiles = (s + -s % block) // block
    causal = 2 * tiles * (tiles + 1) // 2
    assert int(label(key, "steps_with_work")) <= causal
    if window + block < s:
        assert int(label(key, "steps_with_work")) < causal


def test_a_band_is_causal_and_forward_only():
    q = jnp.ones((1, 128, 1, 128))
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=False, window=8)
