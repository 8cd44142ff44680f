"""The paged-attention kernel (``ops/pallas_paged_attention.py``), interpreted
on the CPU, against the decode programs' ``jnp`` form (``serve/decode.py::
_attend_jnp``: ``_gather`` and the products, here in float32 at ``highest``)
over random block tables: three cells' page shapes (16 heads of 64 side by
side, one query head each; one head of 128 for twenty query heads; a latent
row of 640 lanes for 64 query heads, the values its first 512 lanes),
float32 and bfloat16 pages, the lengths that break kernels, pages full of
NaN wherever no length reaches, and rows moved to other slots and other
blocks.

A compute step whose pages are all live (``PAGES`` of them) is waited for
once and its copies are issued from straight-line code; a row's last,
partial step keeps the walk page by page. ``STEPS`` are the lengths where
the two meet, ``RINGS`` a window's ring that wraps inside a full step; each
is held to the ``jnp`` form, to the poison and to the placement.

The calls are jitted (a shape compiles once; lengths and tables are data).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_sandbox.models.longcat_flash import absorbed_attention
from tpu_sandbox.ops.pallas_paged_attention import paged_attention
from tpu_sandbox.serve.decode import _attend_jnp

BLOCK, MAX_BLOCKS, PAGES, ROWS = 16, 8, 2, 3
MAX_CTX = BLOCK * MAX_BLOCKS                      # 128: four compute steps
NUM_BLOCKS = 2 * ROWS * MAX_BLOCKS + 1            # room to move every row
# (key/value heads, query heads a key/value head, head size)
SHAPES = {"16x64_group1": (16, 1, 64), "1x128_group20": (1, 20, 128),
          "2x64_group4": (2, 4, 64), "latent640_h64": (1, 64, 640)}
# a latent row: the queries' width, the values' lanes, the caller's scale
LATENT = {"latent640_h64": (576, 512, 192 ** -0.5)}
CELLS = ["16x64_group1", "1x128_group20", "latent640_h64"]
TYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# what a kernel tends to get wrong: nothing, one position, a block to its
# last position, one position into the next, whole compute steps, the lot
LENGTHS = {"empty": 0, "one": 1, "block": BLOCK, "block_and_one": BLOCK + 1,
           "two_steps": 2 * PAGES * BLOCK, "max_context": MAX_CTX}
TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}
STEP = PAGES * BLOCK                              # 32 positions a full step
# rows of lengths where a full step (one wait, straight-line copies) meets
# the partial one (page by page), an empty slot or the next row
STEPS = {
    "full_steps": [3 * STEP, STEP, 2 * STEP, 4 * STEP],
    "one_page": [BLOCK, 5, BLOCK, BLOCK - 1],
    "full_steps_and_one": [2 * STEP + 1, STEP + 1, 3 * STEP + 1, 1],
    "empty_between_live": [2 * STEP, 0, 0, STEP + BLOCK, 0, 3 * STEP],
    "full_then_one_page": [STEP + BLOCK, 3 * STEP + BLOCK, STEP + 3],
}


@functools.partial(jax.jit, static_argnames=("pages", "window"))
def kernel(q, k_pages, v_pages, tables, lengths, pages=PAGES, window=None):
    if v_pages is None:
        d, v_dim, scale = LATENT["latent640_h64"]
        return paged_attention(q, k_pages, None, tables, lengths,
                               pages_per_step=pages, scale=scale, v_dim=v_dim)
    return paged_attention(q, k_pages, v_pages, tables, lengths,
                           pages_per_step=pages, window=window)


@functools.partial(jax.jit, static_argnames=("hkv", "window"))
def reference(q, k_pages, v_pages, tables, lengths, hkv, window=None):
    with jax.default_matmul_precision("highest"):
        if v_pages is None:
            d, v_dim, scale = LATENT["latent640_h64"]
            rows = k_pages.astype(jnp.float32)[tables].reshape(
                tables.shape[0], -1, k_pages.shape[-1])[..., :d]
            return absorbed_attention(q.astype(jnp.float32), rows, lengths,
                                      v_dim=v_dim, scale=scale)
        return _attend_jnp(
            q.astype(jnp.float32), k_pages.astype(jnp.float32),
            v_pages.astype(jnp.float32), tables, lengths, hkv, window)


def make(shape: str, dtype: str, lengths, seed: int = 0,
         max_blocks: int = MAX_BLOCKS):
    """Queries, pages of random content and a random table for rows of
    ``lengths``: every row's blocks are its own, none the null block 0."""
    hkv, group, hd = SHAPES[shape]
    rng = np.random.default_rng(seed)
    width = hkv * hd
    num_blocks = 2 * len(lengths) * max_blocks + 1

    def draw(*dims):
        return jnp.asarray(rng.standard_normal(dims), TYPES[dtype])

    tables = (rng.permutation(num_blocks - 1)[:len(lengths) * max_blocks]
              .reshape(len(lengths), max_blocks) + 1).astype(np.int32)
    if shape in LATENT:     # one buffer, the queries narrower than its rows
        return (draw(len(lengths), group, LATENT[shape][0]),
                draw(num_blocks, BLOCK, width), None, tables,
                np.asarray(lengths, np.int32))
    return (draw(len(lengths), hkv * group, hd),
            draw(num_blocks, BLOCK, width), draw(num_blocks, BLOCK, width),
            tables, np.asarray(lengths, np.int32))


def poisoned(pages, tables, lengths):
    """``pages`` with NaN wherever no row's length reaches: every block no
    table names within its row's length, and the tail of each last block."""
    if pages is None:
        return None
    held = np.zeros(pages.shape[:2], bool)
    for table, n in zip(tables, lengths):
        for at in range(n):
            held[table[at // BLOCK], at % BLOCK] = True
    return jnp.where(jnp.asarray(held)[:, :, None], pages, jnp.nan)


def moved(q, k, v, tables, lengths, seed=7):
    """The same rows in another order, every block of the pool at a new
    place (the null block stays): the operands, and the order."""
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(lengths))
    to = np.concatenate([[0], rng.permutation(k.shape[0] - 1) + 1])
    back = jnp.asarray(np.argsort(to))
    k2, v2 = (None if p is None else jnp.asarray(p)[back] for p in (k, v))
    return (q[jnp.asarray(order)], k2, v2, to[tables][order],
            lengths[order]), order


@pytest.mark.parametrize("length", LENGTHS)
@pytest.mark.parametrize("dtype", TYPES)
@pytest.mark.parametrize("shape", CELLS)
def test_kernel_matches_the_jnp_form(shape, dtype, length):
    """Row 1 at the length under test between two rows of other lengths; a
    row of length 0 reads nothing and gives zeros."""
    q, k, v, tables, lengths = make(shape, dtype, [37, LENGTHS[length], 100])
    got = np.asarray(kernel(q, k, v, tables, lengths), np.float32)
    want = np.asarray(reference(q, k, v, tables, lengths,
                                hkv=SHAPES[shape][0]))
    assert got.dtype == np.float32 and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=TOLERANCE[dtype],
                               atol=TOLERANCE[dtype])
    if lengths[1] == 0:
        assert not got[1].any()


def test_kernel_groups_query_heads_of_several_key_value_heads():
    """Two key/value heads of four query heads each: the general form of
    the block-diagonal query (neither cell's)."""
    q, k, v, tables, lengths = make("2x64_group4", "float32", [5, 77, 128])
    np.testing.assert_allclose(
        np.asarray(kernel(q, k, v, tables, lengths)),
        np.asarray(reference(q, k, v, tables, lengths, hkv=2)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pages", [1, 4, 8])
def test_kernel_at_other_pages_a_step(pages):
    """One page a step, and a step as long as a row can be: the same
    answer to rounding (the chunking is the online softmax's only freedom)."""
    q, k, v, tables, lengths = make("16x64_group1", "float32", [128, 0, 49])
    np.testing.assert_allclose(
        np.asarray(kernel(q, k, v, tables, lengths, pages=pages)),
        np.asarray(reference(q, k, v, tables, lengths, hkv=16)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", TYPES)
@pytest.mark.parametrize("shape", CELLS)
def test_poison_behind_the_lengths_never_reaches_the_output(shape, dtype):
    """Every block no table names within a length, and every tail behind a
    length, full of NaN: the output is finite and the same bits as over
    clean pages."""
    q, k, v, tables, lengths = make(shape, dtype, [1, 0, 53, 128, 96])
    clean = np.asarray(kernel(q, k, v, tables, lengths), np.float32)
    k_bad, v_bad = (poisoned(p, tables, lengths) for p in (k, v))
    assert np.isnan(np.asarray(k_bad, np.float32)).mean() > 0.5
    dirty = np.asarray(kernel(q, k_bad, v_bad, tables, lengths), np.float32)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)


@pytest.mark.parametrize("dtype", TYPES)
@pytest.mark.parametrize("shape", CELLS)
def test_a_row_gives_the_same_bits_wherever_it_is_placed(shape, dtype):
    """The same rows in other batch slots, beside other lengths (an empty
    slot between them), from other physical blocks: bit for bit the same
    outputs — replay's "same program, same bits"."""
    lengths = [128, 45, 17, 1, 80]
    case = make(shape, dtype, lengths)
    first = np.asarray(kernel(*case), np.float32)
    elsewhere, order = moved(*case)
    again = np.asarray(kernel(*elsewhere), np.float32)
    np.testing.assert_array_equal(again, first[order])


@pytest.mark.parametrize("steps", STEPS)
@pytest.mark.parametrize("shape", ["16x64_group1", "latent640_h64"])
def test_full_and_partial_steps_match_the_jnp_form(shape, steps):
    """Where a step waited for once meets one waited for page by page."""
    q, k, v, tables, lengths = make(shape, "float32", STEPS[steps], seed=3)
    got = np.asarray(kernel(q, k, v, tables, lengths))
    want = np.asarray(reference(q, k, v, tables, lengths,
                                hkv=SHAPES[shape][0]))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert not got[lengths == 0].any()


@pytest.mark.parametrize("steps", STEPS)
@pytest.mark.parametrize("shape", ["16x64_group1", "latent640_h64"])
def test_full_and_partial_steps_keep_the_guarantees(shape, steps):
    """Poison behind the lengths, and the same bits wherever a row is
    placed, on the lengths where the two kinds of step meet."""
    case = make(shape, "bfloat16", STEPS[steps], seed=4)
    q, k, v, tables, lengths = case
    clean = np.asarray(kernel(*case), np.float32)
    dirty = np.asarray(kernel(q, poisoned(k, tables, lengths),
                              poisoned(v, tables, lengths), tables, lengths),
                       np.float32)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)
    elsewhere, order = moved(*case)
    np.testing.assert_array_equal(
        np.asarray(kernel(*elsewhere), np.float32), clean[order])


@pytest.mark.parametrize("shape", ["2x64_group4", "latent640_h64"])
def test_a_step_of_several_lane_tiles_issues_its_copies_in_shares(shape):
    """Sixteen pages a step are two lane tiles of positions: the next
    step's copies go out a share before the wait and a share inside the
    score product. Rows of four full steps, of two and a partial one, of
    none, of one position past two steps: the ``jnp`` form's answer, the
    poison, the placement."""
    lengths = [1024, 700, 0, 513, 256]
    case = make(shape, "float32", lengths, seed=5, max_blocks=64)
    q, k, v, tables, lengths = case
    got = np.asarray(kernel(*case, pages=16))
    want = np.asarray(reference(*case, hkv=SHAPES[shape][0]))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    dirty = np.asarray(kernel(q, poisoned(k, tables, lengths),
                              poisoned(v, tables, lengths), tables, lengths,
                              pages=16))
    np.testing.assert_array_equal(dirty, got)
    elsewhere, order = moved(*case)
    np.testing.assert_array_equal(
        np.asarray(kernel(*elsewhere, pages=16)), got[order])


def test_an_entry_past_the_pool_is_clamped_as_the_gather_clamps_it():
    """The kernel is built without the DMA's bounds checks; a table entry
    past the pool reads the pool's last block, which is what the ``jnp``
    form's gather makes of it."""
    q, k, v, tables, lengths = make("16x64_group1", "float32", [70, 128, 40])
    tables = tables.copy()
    tables[0, 1], tables[1, 5] = k.shape[0] + 3, 2 ** 30
    np.testing.assert_allclose(
        np.asarray(kernel(q, k, v, tables, lengths)),
        np.asarray(reference(q, k, v, tables, lengths, hkv=16)),
        rtol=2e-5, atol=2e-5)


WINDOW, RING = 48, 5                  # ceil(48 / 16) + 1 blocks, and one more
# a ring that wraps inside a full step (blocks 3, 4 | 5, 0 | 1 of a row of
# 101), at a step's edge (4, 0), nowhere, and a row shorter than the window
RINGS = {"inside_a_full_step": [101, 7, 117], "at_the_edge": [96, 0, 85],
         "not_yet": [WINDOW, 64, 33], "many_rings": [170, 333, 251]}


def ring_case(lengths, dtype=jnp.float32, seed=0):
    """Two heads of 64, three query heads each, every row a ring of ``RING``
    blocks of its own."""
    rng = np.random.default_rng(seed)
    rows = len(lengths)
    pages = rng.standard_normal((2, rows * RING + 1, BLOCK, 128))
    tables = (rng.permutation(rows * RING).reshape(rows, RING) + 1
              ).astype(np.int32)
    return (jnp.asarray(rng.standard_normal((rows, 6, 64)), dtype),
            jnp.asarray(pages[0], dtype), jnp.asarray(pages[1], dtype),
            tables, np.asarray(lengths, np.int32))


def ring_poisoned(pages, tables, lengths):
    """NaN in every row of a ring the window has left or no length has
    reached."""
    held = np.zeros(pages.shape[:2], bool)
    for table, n in zip(tables, lengths):
        for at in range(max(0, n - WINDOW) // BLOCK * BLOCK, n):
            held[table[at // BLOCK % RING], at % BLOCK] = True
    return jnp.where(jnp.asarray(held)[:, :, None], pages, jnp.nan)


@pytest.mark.parametrize("ring", RINGS)
def test_a_ring_that_wraps_matches_the_jnp_form(ring):
    q, k, v, tables, lengths = ring_case(RINGS[ring])
    got = np.asarray(kernel(q, k, v, tables, lengths, window=WINDOW))
    want = np.asarray(reference(q, k, v, tables, lengths, hkv=2,
                                window=WINDOW))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert not got[lengths == 0].any()


@pytest.mark.parametrize("ring", RINGS)
def test_a_ring_that_wraps_keeps_the_guarantees(ring):
    """No block before the window is read, nothing behind a length, and the
    same bits from other slots and other blocks."""
    case = ring_case(RINGS[ring], jnp.bfloat16, seed=1)
    q, k, v, tables, lengths = case
    clean = np.asarray(kernel(*case, window=WINDOW), np.float32)
    dirty = np.asarray(kernel(q, ring_poisoned(k, tables, lengths),
                              ring_poisoned(v, tables, lengths), tables,
                              lengths, window=WINDOW), np.float32)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)
    elsewhere, order = moved(*case)
    np.testing.assert_array_equal(
        np.asarray(kernel(*elsewhere, window=WINDOW), np.float32),
        clean[order])
