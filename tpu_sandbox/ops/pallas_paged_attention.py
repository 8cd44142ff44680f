"""Pallas TPU kernel: one new token's attention over a paged key/value
cache, read where it lies.

``paged_attention(q [B, Hq, D], k_pages, v_pages [num_blocks, block_size,
Hkv * D], block_tables [B, max_blocks], lengths [B])`` is every decode row's
attention output ``[B, Hq, D]`` over the first ``lengths[b]`` positions of
the blocks its table names. The pages are the cache's own buffers
(``serve/decode.py::page_shapes``), taken as they are: the kernel copies a
row's pages out of HBM itself, ``pages_per_step`` of them a compute step
into one of two VMEM buffers (``make_async_copy``; the block table and the
lengths are scalar-prefetch operands), and starts the next step's copies,
the next live row's first among them, before it needs them. A row reads
``ceil(lengths[b] / block_size)`` blocks and no more; a row with ``lengths
== 0`` reads nothing and gives zeros.

**A step's bookkeeping** (PERF.md section 6, PR 51: a copy cost the core
22-33 ns of issue and wait, in series with the products). A step whose
pages are all live -- every step of a row but its last -- is waited for
**once a buffer**: a DMA semaphore counts bytes, so one wait on a descriptor
of the whole buffer stands for its ``pages_per_step`` copies. Its copies
are ``pages_per_step`` descriptors in **straight-line code**, each table
entry read once. Where the step after a full step is full too they go out
in shares, one a lane tile of the step's positions: the first *before* the
step's wait, so that the copies never run dry where the transfer binds
(pages of 32 KB), the others *inside* the step's score product, one after
each lane tile of positions, where the scheduler lays the scalar work
beside the MXU's (pages of 4 and 20 KB, where the core binds). The row's
last, partial step keeps a loop of its live pages, for its copies and for
its waits. Which form a step takes follows ``lengths`` alone. A table's
entry is clamped to the pool as XLA's gather clamps an index, and the kernel
is built without the DMA's own bounds checks (two ``shalt.err`` chains a
descriptor: two thirds of a copy's scalar work); a copy's destination is a
page of a buffer by construction.

A page row holds ``Hkv`` heads of ``D`` side by side in its lanes, and the
heads are split on the tile in VMEM, never in HBM: the row's queries become
a block-diagonal matrix ``[G * Hkv, Hkv * D]`` (row ``g * Hkv + h`` holds
query head ``h * G + g`` in head ``h``'s lanes and zeros beside it), one
product with the chunk's keys ``[T, Hkv * D]`` gives every head's scores
``[G * Hkv, T]``, one product of the weights with the values gives ``[G *
Hkv, Hkv * D]``, of which each row keeps its own head's lanes. With one
key/value head (``Hkv == 1``) the matrix is the group's queries themselves.
The products' operands are in the wider of the query's and the cache's
types (bf16 on the MXU where both are), scores, running maximum, sum and
output accumulate in float32 across a row's chunks (online softmax), scale
``1 / sqrt(D)``.

**A latent cache** (``v_pages`` None, ``v_dim``; ``serve/decode.py``'s
third family): the pages hold one row a position, shared by all the query
heads -- ``[c | k_pe | padding]`` of MLA's absorbed form -- and the values
are **the first ``v_dim`` lanes of the same row**. One buffer is copied, the
scores read the row whole (the queries carry zeros over the padding), the
second product reads its first ``v_dim`` lanes in VMEM, and the output is
``[B, Hq, v_dim]``. The scale is the caller's (``1 / sqrt(192)`` where the
row is 576 wide).

What lies behind ``lengths[b]`` never reaches the output, whatever it is:
those scores are replaced before the maximum, and the values' rows there
are zeroed in VMEM before the second product (a weight of 0 times a NaN is
a NaN). A row's result is a function of its own query, table and length:
the chunking follows the length alone, so a row gives the same bits in any
slot, beside any batch, from any physical blocks.

**A window** (``window``; ``serve/decode.py``'s fourth family): a row
attends to its last ``min(lengths[b], window)`` positions alone, and its
table is a **ring** (``serve/cache.py``): the block that holds position
``p`` is entry ``(p // block_size) % max_blocks`` of the row's table. The
walk starts at the block that holds ``max(0, lengths[b] - window)`` and
copies no block before it, so a window layer's bytes follow the window, not
the sequence; the positions of that first block that lie before the window
are the sequence's own older rows, and their scores are replaced like those
behind the length.

Interpreted off-TPU, like ops.pallas_attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_sandbox.ops.pallas_common import (
    LANE,
    NEG,
    default_interpret,
    kernel_site,
    traced_once,
)

_F32 = jnp.float32


def _kernel(tables_ref, lengths_ref, q_ref, k_hbm, *rest, pages: int,
            max_blocks: int, hkv: int, group: int, precision, scale: float,
            v_dim: int | None, window: int | None = None,
            probe: str | None = None):
    # ``probe`` is ``tools/paged_attn_race.py``'s: "copies" leaves the
    # products out, "products" the copies, "poison" fills a buffer with NaN
    # before its copies start (a wait that returns early lets one through)
    # a latent cache has no V pages: the values are lanes of the K row
    if v_dim is None:
        v_hbm, o_ref, k_buf, v_buf, sems, state = rest
        copied = ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1))
    else:
        v_buf = None
        o_ref, k_buf, sems, state = rest
        copied = ((k_hbm, k_buf, 0),)
    b, rows = pl.program_id(0), pl.num_programs(0)
    _, bs, width = k_hbm.shape
    hd = width // hkv
    tokens = pages * bs
    length = lengths_ref[b]
    copying = probe != "products"

    def walk(row):
        """The first block of ``row``'s walk (the one that holds the first
        position of its window) and the blocks it covers."""
        reach = pl.cdiv(lengths_ref[row], bs)
        if window is None:
            return 0, reach
        first = jnp.maximum(lengths_ref[row] - window, 0) // bs
        return first, reach - first

    first, blocks = walk(b)
    span = length - first * bs      # positions from the first block's start
    n_chunks = pl.cdiv(blocks, pages)

    def in_table(entry):
        """A step's first block as an entry of the row's table (a ring's:
        one division a step, none a page)."""
        return entry if window is None else entry % max_blocks

    def start_page(row, entry, j, slot):
        """Start the copies, K's and V's, of the block ``j`` entries after
        ``entry`` of ``row``'s table to page ``j`` of buffer ``slot``: the
        one read of the table."""
        entry = entry + j
        if window is not None:      # round the ring: j < max_blocks
            entry = jnp.where(entry >= max_blocks, entry - max_blocks, entry)
        # (clamped as XLA's own gather clamps an index: the kernel is built
        # without the DMA's bounds checks, two thirds of a copy's scalar work)
        block = jnp.minimum(tables_ref[row * max_blocks + entry],
                            k_hbm.shape[0] - 1)
        at = pl.ds(j * bs if isinstance(j, int)
                   else pl.multiple_of(j * bs, bs), bs)
        for hbm, buf, sem in copied:
            pltpu.make_async_copy(
                hbm.at[block], buf.at[slot, at], sems.at[sem, slot]).start()

    def poison(slot):
        if probe == "poison":
            for _, buf, _ in copied:
                buf[slot] = jnp.full(buf.shape[1:], jnp.nan, buf.dtype)

    # a full step's copies go out in shares, one a lane tile of positions
    shares = tokens // LANE if tokens % LANE == 0 else 1

    def start_full(row, entry, slot, share=None):
        """A step whose pages are all live, from block ``entry`` on:
        ``pages`` copies in straight-line code (``share``: that share of
        them alone), for the scheduler to lay beside whatever stands next
        to them."""
        if not share:       # the whole step, or its first share
            poison(slot)
        entry = in_table(entry)
        size = -(-pages // shares)
        for j in (range(pages) if share is None else
                  range(share * size, min((share + 1) * size, pages))):
            start_page(row, entry, j, slot)

    def start(row, chunk, slot):
        """Start the copies of ``row``'s ``chunk``: those its length
        reaches."""
        if not copying:
            return
        first, blocks = walk(row)
        live = jnp.minimum(blocks - chunk * pages, pages)
        entry = first + chunk * pages

        @pl.when(live == pages)
        def _():
            start_full(row, entry, slot)

        @pl.when(live < pages)
        def _():
            poison(slot)
            at = in_table(entry)
            jax.lax.fori_loop(
                0, live, lambda j, c: start_page(row, at, j, slot) or c, 0)

    def wait(slot, live=None):
        """Wait for buffer ``slot``'s copies: once a buffer where its pages
        are all live (a DMA semaphore counts bytes, and a descriptor of the
        whole buffer stands for ``pages`` copies' worth), once a page where
        ``live`` of them are."""
        if not copying:
            return
        for hbm, buf, sem in copied:
            if live is None:
                pltpu.make_async_copy(
                    buf.at[slot], buf.at[slot], sems.at[sem, slot]).wait()
            else:
                page = pltpu.make_async_copy(
                    hbm.at[0], buf.at[slot, pl.ds(0, bs)], sems.at[sem, slot])
                jax.lax.fori_loop(0, live, lambda j, c: page.wait() or c, 0)

    # state[0]: the buffer the next chunk to compute lies in; state[1]:
    # whether its copies are under way (started by the row before)
    @pl.when(b == 0)
    def _():
        state[0] = 0
        state[1] = 0
        if not copying:
            for _, buf, _ in copied:
                buf[...] = jnp.zeros_like(buf)

    slot0 = state[0]

    @pl.when(jnp.logical_and(n_chunks > 0, state[1] == 0))
    def _():
        start(b, 0, slot0)

    # the queries, block-diagonal over the key/value heads' lanes
    qg = q_ref[0]                                           # [G, Hkv * D]
    if hkv == 1:
        own = None
        qbd = qg
    else:
        shape = (hkv, width)
        own = (jax.lax.broadcasted_iota(jnp.int32, shape, 1) // hd
               == jax.lax.broadcasted_iota(jnp.int32, shape, 0))
        # (selected as float32: a mask of 32-bit lanes over a packed type
        # is a relayout Mosaic refuses)
        qbd = jnp.concatenate([
            jnp.where(own, jnp.broadcast_to(qg[g:g + 1].astype(_F32), shape),
                      0.0)
            for g in range(group)], axis=0).astype(qg.dtype)  # [G * Hkv, W]
    heads = group * hkv
    out_width = width if v_dim is None else v_dim
    # the buffer the values are read from, and zeroed behind the length in
    values = k_buf if v_dim is not None else v_buf

    def attend(i, slot, carry, ends: bool, between=None):
        """Chunk ``i``'s products on buffer ``slot`` into the running
        maximum, sum and output. ``ends``: the chunk may hold the row's
        last position (what lies behind it is replaced). ``between(c)``
        is called after lane tile ``c`` of positions of the score product:
        the place of the next step's copies."""
        parts = 1 if between is None else shares
        if probe == "copies":
            for c in range(parts) if between is not None else ():
                between(c)
            return carry
        m, l, acc = carry
        left = span - i * tokens            # positions of this chunk in use
        if ends:
            @pl.when(left < tokens)
            def _():
                held = (jax.lax.broadcasted_iota(jnp.int32, (tokens, 1), 0)
                        < left)
                values[slot] = jnp.where(held, values[slot], 0)

        size = tokens // parts
        scores = []
        for c in range(parts):
            k_c = k_buf[slot, c * size:(c + 1) * size].astype(qbd.dtype)
            scores.append(jax.lax.dot_general(
                qbd, k_c, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=_F32) * scale)
            if between is not None:
                between(c)
        s = scores[0] if parts == 1 else jnp.concatenate(scores, axis=1)
        v = (k_buf[slot, :, :v_dim] if v_dim is not None
             else v_buf[slot]).astype(qbd.dtype)
        at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = at < left if ends else None
        if window is not None:   # the first block's rows before the window
            old = at >= length - window - first * bs - i * tokens
            seen = old if seen is None else jnp.logical_and(seen, old)
        if seen is not None:
            s = jnp.where(seen, s, NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=_F32)
        return m_new, l, acc

    # the steps whose own pages and the next step's are all live: one wait,
    # the next step's copies and the products in one straight line
    n_full = blocks // pages
    n_inner = jnp.maximum(n_full - 1, 0)

    def inner(i, carry):
        slot = (slot0 + i) % 2

        def start_share(share):
            if copying and share < shares:
                start_full(b, first + (i + 1) * pages, 1 - slot, share)

        # the first share before the wait, so that the copies never run
        # dry where the transfer binds; the others inside the products
        start_share(0)
        wait(slot)
        return attend(i, slot, carry, ends=False,
                      between=lambda c: start_share(c + 1))

    def outer(i, carry):
        """The row's last full step and the partial one behind it: what
        follows is a step of any kind, or the next live row's first."""
        slot = (slot0 + i) % 2
        last = i + 1 == n_chunks

        @pl.when(jnp.logical_not(last))
        def _():
            start(b, i + 1, 1 - slot)

        @pl.when(last)
        def _():
            nxt = jax.lax.while_loop(
                lambda r: jnp.logical_and(
                    r < rows, lengths_ref[jnp.minimum(r, rows - 1)] == 0),
                lambda r: r + 1, b + 1)

            @pl.when(nxt < rows)
            def _():
                start(nxt, 0, 1 - slot)

            state[0] = 1 - slot
            state[1] = (nxt < rows).astype(jnp.int32)

        live = blocks - i * pages

        @pl.when(live >= pages)
        def _():
            wait(slot)

        @pl.when(live < pages)
        def _():
            wait(slot, live)

        return attend(i, slot, carry, ends=True)

    carry = (jnp.full((heads, 1), NEG, _F32), jnp.zeros((heads, 1), _F32),
             jnp.zeros((heads, out_width), _F32))
    carry = jax.lax.fori_loop(0, n_inner, inner, carry)
    m, l, acc = jax.lax.fori_loop(n_inner, n_chunks, outer, carry)
    out = acc / jnp.maximum(l, 1e-30)
    if own is not None:       # each row's own head's lanes, heads side by side
        out = jnp.concatenate([
            jnp.where(own, out[g * hkv:(g + 1) * hkv], 0).sum(
                axis=0, keepdims=True)
            for g in range(group)], axis=0)                 # [G, W]
    o_ref[0] = out.astype(o_ref.dtype)


@traced_once
def _paged_attn(q, k_pages, v_pages, block_tables, lengths, *,
                pages_per_step, interpret, scale=None, v_dim=None,
                window=None, probe=None):
    bsz, hq, hd = q.shape
    _, bs, width = k_pages.shape
    latent = v_pages is None
    # a latent row is every query head's: one "head" as wide as the row
    hkv = 1 if latent else width // hd
    group = hq // hkv
    dtype = jnp.promote_types(q.dtype, k_pages.dtype)
    if latent:  # zeros over the row's padding lanes
        qg = jnp.pad(q, ((0, 0), (0, 0), (0, width - hd))).astype(dtype)
    else:
        # a key/value head's group of query heads in rows, the heads in
        # lanes
        qg = (q.reshape(bsz, hkv, group, hd).swapaxes(1, 2)
              .reshape(bsz, group, width).astype(dtype))
    out_width = v_dim if latent else width
    tokens = pages_per_step * bs
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kv_buffer = pltpu.VMEM((2, tokens, width), k_pages.dtype)
    with kernel_site("paged_attn"):
        out = pl.pallas_call(
            functools.partial(
                _kernel, pages=pages_per_step,
                max_blocks=block_tables.shape[1], hkv=hkv, group=group,
                precision=(jax.lax.Precision.HIGHEST if dtype == _F32
                           else None),
                scale=(hd ** -0.5 if scale is None else scale),
                v_dim=v_dim if latent else None, window=window,
                probe=probe),
            out_shape=jax.ShapeDtypeStruct((bsz, group, out_width), q.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(bsz,),
                in_specs=[
                    pl.BlockSpec((1, group, width),
                                 lambda b, tables, lens: (b, 0, 0)),
                    hbm, *(() if latent else (hbm,))],
                out_specs=pl.BlockSpec((1, group, out_width),
                                       lambda b, tables, lens: (b, 0, 0)),
                scratch_shapes=[
                    kv_buffer, *(() if latent else (kv_buffer,)),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.SMEM((2,), jnp.int32)]),
            # a row hands the next one its first chunk's copies
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                disable_bounds_checks=True),
            interpret=interpret,
        )(block_tables.reshape(-1).astype(jnp.int32),
          lengths.astype(jnp.int32), qg, k_pages,
          *(() if latent else (v_pages,)))
    if latent:
        return out
    return (out.reshape(bsz, group, hkv, hd).swapaxes(1, 2)
            .reshape(bsz, hq, hd))


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    pages_per_step: int, interpret: bool | None = None,
                    scale: float | None = None, v_dim: int | None = None,
                    window: int | None = None):
    """The attention output ``[B, Hq, D]`` (``q``'s type) of ``q [B, Hq,
    D]`` over each row's first ``lengths[b]`` cached positions: ``k_pages``,
    ``v_pages`` ``[num_blocks, block_size, Hkv * D]`` through
    ``block_tables [B, max_blocks]``, ``pages_per_step`` pages a compute
    step (module docstring). The width ``Hkv * D`` is a multiple of the 128
    lanes and ``block_size`` of the cache type's sublane tile
    (``serve/decode.py::pages_per_step`` holds the rule). ``scale``: ``1 /
    sqrt(D)`` where None.

    ``v_pages`` None: a latent cache. ``q [B, Hq, d]`` with ``d`` at most
    the pages' width, every query head against the same row, the values the
    row's first ``v_dim`` lanes: ``[B, Hq, v_dim]``.

    ``window``: each row over its last ``min(lengths[b], window)`` positions
    alone, ``block_tables`` a ring (module docstring) of at least
    ``ceil(window / block_size) + 1`` blocks a row."""
    if v_pages is None and not v_dim:
        raise ValueError("a latent cache's values need their width (v_dim)")
    if window is not None and block_tables.shape[1] \
            < -(-window // k_pages.shape[1]) + 1:
        raise ValueError(f"a ring of {block_tables.shape[1]} blocks of "
                         f"{k_pages.shape[1]} cannot hold a window of {window}")
    return _paged_attn(q, k_pages, v_pages, block_tables, lengths,
                       pages_per_step=pages_per_step,
                       interpret=default_interpret(interpret), scale=scale,
                       v_dim=v_dim, window=window)
