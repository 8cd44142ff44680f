"""The paged-attention kernel (``ops/pallas_paged_attention.py``), interpreted
on the CPU, against the decode programs' ``jnp`` form (``serve/decode.py::
_attend_jnp``: ``_gather`` and the products, here in float32 at ``highest``)
over random block tables: both cells' head shapes (16 heads of 64 side by
side, one query head each; one head of 128 for twenty query heads), float32
and bfloat16 pages, the lengths that break kernels, pages full of NaN
wherever no length reaches, and rows moved to other slots and other blocks.

The calls are jitted (a shape compiles once; lengths and tables are data).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_sandbox.ops.pallas_paged_attention import paged_attention
from tpu_sandbox.serve.decode import _attend_jnp

BLOCK, MAX_BLOCKS, PAGES, ROWS = 16, 8, 2, 3
MAX_CTX = BLOCK * MAX_BLOCKS                      # 128: four compute steps
NUM_BLOCKS = 2 * ROWS * MAX_BLOCKS + 1            # room to move every row
# (key/value heads, query heads a key/value head, head size)
SHAPES = {"16x64_group1": (16, 1, 64), "1x128_group20": (1, 20, 128),
          "2x64_group4": (2, 4, 64)}
TYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# what a kernel tends to get wrong: nothing, one position, a block to its
# last position, one position into the next, whole compute steps, the lot
LENGTHS = {"empty": 0, "one": 1, "block": BLOCK, "block_and_one": BLOCK + 1,
           "two_steps": 2 * PAGES * BLOCK, "max_context": MAX_CTX}
TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}


@functools.partial(jax.jit, static_argnames=("pages",))
def kernel(q, k_pages, v_pages, tables, lengths, pages=PAGES):
    return paged_attention(q, k_pages, v_pages, tables, lengths,
                           pages_per_step=pages)


@functools.partial(jax.jit, static_argnames=("hkv",))
def reference(q, k_pages, v_pages, tables, lengths, hkv):
    with jax.default_matmul_precision("highest"):
        return _attend_jnp(
            q.astype(jnp.float32), k_pages.astype(jnp.float32),
            v_pages.astype(jnp.float32), tables, lengths, hkv)


def make(shape: str, dtype: str, lengths, seed: int = 0):
    """Queries, pages of random content and a random table for rows of
    ``lengths``: every row's blocks are its own, none the null block 0."""
    hkv, group, hd = SHAPES[shape]
    rng = np.random.default_rng(seed)
    width = hkv * hd

    def draw(*dims):
        return jnp.asarray(rng.standard_normal(dims), TYPES[dtype])

    tables = (rng.permutation(NUM_BLOCKS - 1)[:len(lengths) * MAX_BLOCKS]
              .reshape(len(lengths), MAX_BLOCKS) + 1).astype(np.int32)
    return (draw(len(lengths), hkv * group, hd),
            draw(NUM_BLOCKS, BLOCK, width), draw(NUM_BLOCKS, BLOCK, width),
            tables, np.asarray(lengths, np.int32))


def poisoned(pages, tables, lengths):
    """``pages`` with NaN wherever no row's length reaches: every block no
    table names within its row's length, and the tail of each last block."""
    held = np.zeros(pages.shape[:2], bool)
    for table, n in zip(tables, lengths):
        for at in range(n):
            held[table[at // BLOCK], at % BLOCK] = True
    return jnp.where(jnp.asarray(held)[:, :, None], pages, jnp.nan)


@pytest.mark.parametrize("length", LENGTHS)
@pytest.mark.parametrize("dtype", TYPES)
@pytest.mark.parametrize("shape", ["16x64_group1", "1x128_group20"])
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
@pytest.mark.parametrize("shape", ["16x64_group1", "1x128_group20"])
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
@pytest.mark.parametrize("shape", ["16x64_group1", "1x128_group20"])
def test_a_row_gives_the_same_bits_wherever_it_is_placed(shape, dtype):
    """The same rows in other batch slots, beside other lengths (an empty
    slot between them), from other physical blocks: bit for bit the same
    outputs — replay's "same program, same bits"."""
    lengths = [128, 45, 17, 1, 80]
    q, k, v, tables, lengths = make(shape, dtype, lengths)
    first = np.asarray(kernel(q, k, v, tables, lengths), np.float32)

    rng = np.random.default_rng(7)
    order = rng.permutation(len(lengths))
    # every block of the pool to a new place; the null block stays
    moved = np.concatenate([[0], rng.permutation(NUM_BLOCKS - 1) + 1])
    back = np.argsort(moved)
    k2, v2 = (jnp.asarray(p)[jnp.asarray(back)] for p in (k, v))
    again = np.asarray(kernel(
        q[jnp.asarray(order)], k2, v2, moved[tables][order], lengths[order]),
        np.float32)
    np.testing.assert_array_equal(again, first[order])
