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
the next live row's first among them, before it waits for its own. A row
reads ``ceil(lengths[b] / block_size)`` blocks and no more; a row with
``lengths == 0`` reads nothing and gives zeros.

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
    NEG,
    default_interpret,
    kernel_site,
    traced_once,
)

_F32 = jnp.float32


def _kernel(tables_ref, lengths_ref, q_ref, k_hbm, *rest, pages: int,
            max_blocks: int, hkv: int, group: int, precision, scale: float,
            v_dim: int | None, window: int | None = None):
    # a latent cache has no V pages: the values are lanes of the K row
    if v_dim is None:
        v_hbm, o_ref, k_buf, v_buf, sems, state = rest
    else:
        v_hbm = v_buf = None
        o_ref, k_buf, sems, state = rest
    b, rows = pl.program_id(0), pl.num_programs(0)
    _, bs, width = k_hbm.shape
    hd = width // hkv
    tokens = pages * bs
    length = lengths_ref[b]

    def base(row):
        """The first block of a windowed ``row``'s walk: the one that holds
        the first position of its window."""
        return jnp.maximum(lengths_ref[row] - window, 0) // bs

    # the positions the walk covers, from its first block's start
    span = length if window is None else length - base(b) * bs
    n_chunks = pl.cdiv(span, tokens)

    def copies(row, chunk, slot, act):
        """``act`` on the copy of every page of ``row``'s ``chunk`` that its
        length reaches, K's and V's, into buffer ``slot``."""
        first = chunk * pages
        if window is not None:
            first = base(row) + first
        live = jnp.minimum(pl.cdiv(lengths_ref[row], bs) - first, pages)

        def page(j, carry):
            if window is None:
                block = tables_ref[row * max_blocks + first + j]
            else:   # the table is a ring
                block = tables_ref[row * max_blocks
                                   + (first + j) % max_blocks]
            at = pl.ds(pl.multiple_of(j * bs, bs), bs)
            act(pltpu.make_async_copy(
                k_hbm.at[block], k_buf.at[slot, at], sems.at[0, slot]))
            if v_hbm is not None:
                act(pltpu.make_async_copy(
                    v_hbm.at[block], v_buf.at[slot, at], sems.at[1, slot]))
            return carry

        jax.lax.fori_loop(0, live, page, 0)

    def start(row, chunk, slot):
        copies(row, chunk, slot, lambda copy: copy.start())

    # state[0]: the buffer the next chunk to compute lies in; state[1]:
    # whether its copies are under way (started by the row before)
    @pl.when(b == 0)
    def _():
        state[0] = 0
        state[1] = 0

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

    def chunk(i, carry):
        m, l, acc = carry
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

        copies(b, i, slot, lambda copy: copy.wait())
        left = span - i * tokens            # positions of this chunk in use

        @pl.when(left < tokens)
        def _():
            held = jax.lax.broadcasted_iota(jnp.int32, (tokens, 1), 0) < left
            values[slot] = jnp.where(held, values[slot], 0)

        k = k_buf[slot].astype(qbd.dtype)                   # [T, W]
        v = (k[:, :v_dim] if v_dim is not None
             else v_buf[slot].astype(qbd.dtype))
        s = jax.lax.dot_general(
            qbd, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=_F32) * scale            # [heads, T]
        at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = at < left
        if window is not None:   # the first block's rows before the window
            seen = jnp.logical_and(
                seen, at >= length - window - base(b) * bs - i * tokens)
        s = jnp.where(seen, s, NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=_F32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, n_chunks, chunk,
        (jnp.full((heads, 1), NEG, _F32), jnp.zeros((heads, 1), _F32),
         jnp.zeros((heads, out_width), _F32)))
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
                window=None):
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
                v_dim=v_dim if latent else None, window=window),
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
                dimension_semantics=("arbitrary",)),
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
