"""``serve/`` with the third family (``LongcatFlashLM``, tiny, float32, on
the CPU): a latent page buffer an attention sub-layer, rotary positions from
``lengths``, the expert shares' counters beside the pages. Prefill in the
expanded form through each bucket and then decode steps in the absorbed form
through the engine against the plain reference's one full forward (logits,
not tokens); a slot retired and refilled, a request preempted and replayed,
and sessions sharing a batch give what each gives alone; a shared prefix is
reused over latent pages; the latent Pallas kernel, interpreted, against the
``jnp`` form; a call dispatched ahead changes no token. The engine's
scheduling is tests/test_serve.py's."""

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

from benchmark.reference import longcat_flash as ref  # noqa: E402
from test_longcat_flash_model import (TINY, engine_params,  # noqa: E402
                                      init_variables,
                                      tiny_config)
from tests.helpers import counters, label  # noqa: E402
from tpu_sandbox.models import longcat_flash as lf  # noqa: E402
from tpu_sandbox.obs import get_registry  # noqa: E402
from tpu_sandbox.ops.pallas_paged_attention import paged_attention  # noqa: E402
from tpu_sandbox.serve import (CacheConfig, ContinuousEngine,  # noqa: E402
                               Request, ServeConfig)
from tpu_sandbox.serve import decode as serve_decode  # noqa: E402
from tpu_sandbox.serve.decode import build_decode_step, lower_step  # noqa: E402

pytestmark = pytest.mark.usefixtures("light_compile")

BUCKETS = (8, 16, 32)
CACHE = CacheConfig(num_blocks=65, block_size=4, max_blocks_per_seq=16)
SMALL_POOL = CacheConfig(num_blocks=11, block_size=4, max_blocks_per_seq=16)


@pytest.fixture(scope="module")
def served():
    cfg = tiny_config()
    params = engine_params(jax.jit(lambda k: init_variables(cfg, k))(
        jax.random.key(0)))
    steps = {cache: build_decode_step(cfg, cache, max_batch=3,
                                      buckets=BUCKETS)
             for cache in (CACHE, SMALL_POOL)}
    return cfg, params, steps, ref.from_program_tree(params, TINY)


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


@pytest.mark.parametrize("plen", [
    1, 8, 16, 32,    # a prompt that ends at its bucket's end
    5, 13, 27,       # one that ends inside it
])
def test_prefill_then_decode_is_the_references_full_forward(served, plen):
    """Prefill in the expanded form through a padded bucket stores the
    latent rows; every later token attends in the absorbed form through the
    pages at its rotary position. The logits of every served position
    against one forward that has neither."""
    tree = served[3]
    p = prompt(plen)
    tokens, rows = serve(engine(served), {"r": p}, new=7)["r"]
    want = np.asarray(ref.forward(tree, np.asarray([p + tokens[:-1]]),
                                  TINY))[0, plen - 1:]
    assert rows.shape == want.shape == (7, 96)
    np.testing.assert_allclose(rows, want, rtol=3e-4, atol=3e-4)
    # the program's own pick is the host's argmax of the same logits
    assert tokens == [int(r.argmax()) for r in rows]


def test_sessions_in_one_batch_give_what_each_gives_alone(served):
    prompts = {"a": prompt(8), "b": prompt(13), "c": prompt(3)}
    together = serve(engine(served), prompts)
    for rid, p in prompts.items():
        alone = serve(engine(served), {rid: p})[rid]
        assert together[rid][0] == alone[0]
        np.testing.assert_allclose(together[rid][1], alone[1], rtol=1e-5,
                                   atol=1e-5)


def test_a_slot_retired_and_refilled_starts_from_the_new_prompt(served):
    """Five requests through three slots: the fourth and fifth take a slot,
    and blocks, an earlier sequence left behind."""
    prompts = {f"r{i}": prompt(4 + 3 * i, seed=1) for i in range(5)}
    got = serve(engine(served), prompts)
    for rid, p in prompts.items():
        alone = serve(engine(served), {rid: p})[rid]
        assert got[rid][0] == alone[0]
        np.testing.assert_allclose(got[rid][1], alone[1], rtol=1e-5, atol=1e-5)


def test_a_preempted_request_replays_from_its_prompt(served):
    """Ten allocatable blocks of 4: three sequences outgrow them, the newest
    is evicted and replays; its tokens are those of a fresh engine."""
    prompts = {f"p{i}": prompt(9 + i, seed=2) for i in range(3)}
    eng = engine(served, SMALL_POOL)
    got = serve(eng, prompts, new=8)
    assert sum(r.preemptions for r in eng.results.values()) >= 1
    for rid, p in prompts.items():
        alone = serve(engine(served), {rid: p}, new=8)[rid]
        assert got[rid][0] == alone[0]
        np.testing.assert_allclose(got[rid][1][-8:], alone[1], rtol=1e-5,
                                   atol=1e-5)


def test_a_shared_prefix_is_reused_over_latent_pages(served):
    """No recurrent state beside these pages: the second request takes the
    first one's leading blocks, and gets what it gets alone."""
    shared = prompt(12, seed=3)
    eng = engine(served)
    got = serve(eng, {"first": shared + [7, 8], "second": shared + [9]})
    assert eng.cache.stats["prefix_hits"] >= 1
    assert not eng.recurrent
    alone = serve(engine(served), {"second": shared + [9]})["second"]
    assert got["second"][0] == alone[0]
    np.testing.assert_allclose(got["second"][1], alone[1], rtol=1e-5,
                               atol=1e-5)


def test_the_engine_holds_latent_pages_and_the_shares_counters(served):
    cfg, _, steps, _ = served
    eng = engine(served)
    step = steps[CACHE]
    assert step.picks and not step.recurrent and step.next_tokens is not None
    k_pages, v_pages, counters_ = step.buffers
    # a buffer a sub-layer, one row a position padded to whole lane tiles,
    # no V pages
    assert len(k_pages) == 2 * cfg.num_layers and len(v_pages) == 0
    assert {p.shape for p in k_pages} == {(65, 4, 128)}
    assert cfg.latent_dim == 40
    assert set(counters_) == {"block0", "block1"}
    held = sum(x.nbytes for x in eng.k_pages)
    assert get_registry().gauge("serve.latent_bytes").value == held \
        == 4 * 65 * 4 * 128 * 4
    serve(eng, {"x": prompt(6)}, new=4)
    stats = jax.tree.map(int, eng.state)
    for layer in stats.values():
        # one prefill and three decode calls (the fourth token's call ran
        # ahead as an empty row's), every row counted, none dropped
        assert layer["steps"] >= 4 and layer["rows_dropped"] == 0
        assert layer["zero_choices"] + layer["real_choices"] == \
            3 * (8 + 3 * (layer["steps"] - 1))


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
    for i in range(2):
        for scope in ("mla0/gather_ctx", "mla1/write_kv", "mla0/absorb",
                      "mla1/unabsorb", "mla0/rope", "mla0/q_a", "mla1/kv_a",
                      "mla0/o", "mlp0/gate", "mlp1/down", "moe/router",
                      "moe/dispatch", "moe/experts", "moe/combine",
                      "moe/zero"):
            assert any(re.search(rf"/LongcatFlashLM/block{i}/{scope}(/|$)", n)
                       for n in names), (i, scope)
    assert any("/LongcatFlashLM/lm_head" in n for n in names)


def test_a_family_is_picked_by_the_configurations_type():
    class Other:
        pass

    with pytest.raises(TypeError, match="no serving family for Other"):
        lower_step(Other(), CACHE, max_batch=2, cache_dtype=jnp.float32)


# --- the latent kernel, interpreted, against the jnp form ---

BLOCK, MAX_BLOCKS, ROWS = 16, 8, 3
NUM_BLOCKS = 2 * ROWS * MAX_BLOCKS + 1
LANES, QK, V, HEADS = 256, 160, 128, 8   # a row of 160 values in 256 lanes
TYPES = {"float32": (jnp.float32, 2e-5), "bfloat16": (jnp.bfloat16, 2e-2)}
LENGTHS = {"empty": 0, "one": 1, "block": BLOCK, "block_and_one": BLOCK + 1,
           "two_steps": 4 * BLOCK, "max_context": BLOCK * MAX_BLOCKS}


def latent_case(dtype, lengths, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((len(lengths), HEADS, QK)), dtype)
    pages = rng.standard_normal((NUM_BLOCKS, BLOCK, LANES))
    pages[..., QK:] = 0.0                        # the padding lanes
    tables = (rng.permutation(NUM_BLOCKS - 1)[:len(lengths) * MAX_BLOCKS]
              .reshape(len(lengths), MAX_BLOCKS) + 1).astype(np.int32)
    return q, jnp.asarray(pages, dtype), tables, np.asarray(lengths, np.int32)


@functools.partial(jax.jit, static_argnames=("pages_per_step",))
def latent_kernel(q, pages, tables, lengths, pages_per_step=2):
    return paged_attention(q, pages, None, tables, lengths,
                           pages_per_step=pages_per_step, scale=0.11, v_dim=V)


@jax.jit
def latent_jnp(q, pages, tables, lengths):
    with jax.default_matmul_precision("highest"):
        rows = pages[tables].reshape(tables.shape[0], -1, LANES)[..., :QK]
        return lf.absorbed_attention(
            q.astype(jnp.float32), rows.astype(jnp.float32), lengths,
            v_dim=V, scale=0.11)


@pytest.mark.parametrize("length", LENGTHS)
@pytest.mark.parametrize("dtype", TYPES)
def test_latent_kernel_matches_the_jnp_form(dtype, length):
    """Keys a row shared by all the query heads, values its first ``v_dim``
    lanes: row 1 at the length under test between two others; a row of
    length 0 reads nothing and gives zeros."""
    kind, tol = TYPES[dtype]
    q, pages, tables, lengths = latent_case(kind, [37, LENGTHS[length], 100])
    got = np.asarray(latent_kernel(q, pages, tables, lengths), np.float32)
    want = np.asarray(latent_jnp(q, pages, tables, lengths))
    assert got.shape == (3, HEADS, V) and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    if lengths[1] == 0:
        assert not got[1].any()


def test_latent_kernel_never_reads_behind_a_length():
    """NaN wherever no row's length reaches, the null block included."""
    q, pages, tables, lengths = latent_case(jnp.float32, [5, 0, 49])
    held = np.zeros(pages.shape[:2], bool)
    for table, n in zip(tables, lengths):
        for at in range(n):
            held[table[at // BLOCK], at % BLOCK] = True
    poisoned = jnp.where(jnp.asarray(held)[:, :, None], pages, jnp.nan)
    got = np.asarray(latent_kernel(q, poisoned, tables, lengths))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got, np.asarray(latent_kernel(q, pages, tables, lengths)))


def test_latent_kernel_needs_the_values_width():
    q, pages, tables, lengths = latent_case(jnp.float32, [5])
    with pytest.raises(ValueError, match="v_dim"):
        paged_attention(q, pages, None, tables, lengths, pages_per_step=2)


# a latent row of 104 + 24 = 128 values exactly fills the lanes, blocks of 8
# float32 rows: the shape rule gives these pages the kernel
WIDE = {**TINY, "kv_lora_rank": 104, "qk_rope_head_dim": 24}
WIDE_POOL = CacheConfig(num_blocks=12, block_size=8, max_blocks_per_seq=4)
CHOICE = "paged_attn.kernel_choice"


@pytest.fixture(scope="module")
def branches():
    cfg = lf.LongcatFlashConfig.from_dict(WIDE, dtype=jnp.float32,
                                          param_dtype=jnp.float32)
    params = engine_params(jax.jit(lambda k: init_variables(cfg, k))(
        jax.random.key(1)))
    built = {}
    for branch, knob, value in (
            ("pallas", "_STEP_TOKENS", 16),
            ("jnp", "pages_per_step", lambda *shape: None)):
        before = counters(CHOICE)
        layout = counters("mla.cache_layout")
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(serve_decode, knob, value)
            step = build_decode_step(cfg, WIDE_POOL, max_batch=3,
                                     buckets=(16,))
        built[branch] = (step, counters(CHOICE, since=before),
                         counters("mla.cache_layout", since=layout))
    return cfg, params, built


def test_each_branch_counts_its_choice_and_the_caches_layout(branches):
    cfg, _, built = branches
    for branch, (_, chosen, layout) in built.items():
        (key, n), = chosen.items()
        assert n == 2 * cfg.num_layers
        assert (label(key, "impl"), label(key, "kv_heads"),
                label(key, "group"), label(key, "qk_dim"),
                label(key, "v_dim"), label(key, "head_dim")) == (
                    branch, "1", "4", "128", "104", "128")
        assert label(key, "pages_per_step") == (
            "2" if branch == "pallas" else "0")
        # a full step of the one latent buffer: a copy a page, one wait
        assert (label(key, "copies_per_step"),
                label(key, "waits_per_step")) == (
                    ("2", "1") if branch == "pallas" else ("0", "0"))
        (key, n), = layout.items()
        assert n == 2 * cfg.num_layers
        assert (label(key, "latent"), label(key, "rope"), label(key, "lanes"),
                label(key, "pad_lanes"), label(key, "block_size")) == (
                    "104", "24", "128", "0", "8")


def test_the_kernel_branch_serves_what_the_jnp_branch_serves(branches):
    cfg, params, built = branches
    scfg = ServeConfig(model=cfg, cache=WIDE_POOL, max_batch=3, buckets=(16,))
    prompts = {f"r{i}": prompt(9 + 2 * i, seed=8) for i in range(3)}

    def run(branch):
        eng = ContinuousEngine(params, scfg, step=built[branch][0])
        for rid, p in prompts.items():
            eng.submit(Request(rid=rid, prompt=list(p), max_new_tokens=10))
        eng.run_until_idle()
        return {rid: eng.results[rid].tokens for rid in prompts}

    assert run("pallas") == run("jnp")


def test_the_cells_latent_row_takes_the_kernel_at_24_pages_a_step():
    """576 values in 640 lanes, blocks of 16 bfloat16 rows: 25 pages fit a
    step's bytes, 24 make whole lane tiles of positions."""
    assert serve_decode.pages_per_step(640, 16, jnp.bfloat16, 448) == 24
    k_pages, v_pages = serve_decode.page_shapes(
        CacheConfig(num_blocks=9, block_size=16, max_blocks_per_seq=4), 8, 1,
        576, jnp.bfloat16, latent=True)
    assert len(k_pages) == 8 and len(v_pages) == 0
    assert k_pages[0].shape == (9, 16, 640)
