"""Pallas TPU kernel: flash attention (online-softmax, O(seq) memory).

The reference repo has no attention anywhere (SURVEY §2.2: ring attention /
CP "ABSENT" — its model is a CNN), but this framework treats long-context as
first-class, and attention is the one transformer op where XLA's default
lowering materializes the [S, S] score matrix in HBM. This kernel never
does: the forward pass streams K/V blocks through VMEM with the online
softmax recurrence, so peak memory is O(block_q · block_k) per core instead
of O(S²), and the matmuls stay on the MXU in the input dtype with fp32
accumulation.

Shapes and grid. The kernels read their operands in one of two forms,
chosen from what the call can see (``_heads_per_block``):
- packed: q, k, v ``[B, S, H, D]`` are read as ``[B, S, H·D]`` — the
  projection's own array — in column blocks 128 lanes wide that hold a
  whole number of heads: two at D 64, four at D 32, one where D is a lane
  multiple (a block is then D wide). No transpose, no pad and no slice
  surrounds the calls, forward or backward; and no copy either where the
  caller's projection holds the heads side by side in its last dimension
  (``models/transformer.py::HeadsDense``: the two reshapes cancel), since
  a ``[..., H, 64]`` array of its own has no unpadded 64-minor layout on a
  TPU and the compiler would move it into one the kernel reads. The rule:
  self-attention (q, k, v of one shape), S a lane multiple that every
  explicit block divides, D a lane multiple or a divisor of 128 whose
  group divides H. A head's products contract over its own lanes only: the
  operand that enters each contraction has the lanes of the block's other
  heads zeroed (exact), and a head writes only its own lanes of a result
  block its group shares;
- padded: everything else (q.k and v of different head sizes: latent
  attention's 192 / 128; an S that is no lane multiple; explicit blocks
  whose lcm pads S; the ring's entry points, whose S and Sk differ). The
  operands are made ``[B, H, S, D]`` with S zero-padded to the lane tile
  (to the blocks, where given) and D, Dv each to its 128-lane multiple
  (``_to_bhsd``, the single home of that convention), and both are undone
  on the way out.
In both, the softmax scale is an argument, and
- the grid is (B, H / G, S/block_q, G, S/block_k): one head a step, the G
  heads of a packed block one after the other on the block the pipeline
  already holds, kv innermost ("arbitrary" — it carries the softmax
  state); m/l/acc live in VMEM scratch across kv steps and the output +
  logsumexp are written on the last kv step. One kernel body serves every
  head of a group (the head's lanes are picked by its place in the group,
  a runtime index), because every ``pallas_call`` site traces its body
  anew;
- the row statistics ride lane-broadcast blocks ``[block_q, 128]``: a
  head's logsumexp over a whole block of ``[B, H, S, 128]`` (padded), or
  over its own lanes of a block of ``[B, S, (H/G)·128]`` (packed: half the
  bytes at D 64); the packed backward takes rowsum(do ⊙ out) on the blocks
  it holds, where the padded form's caller computes it (the ring reuses it
  across hops).

The tiles are chosen from the shape, not a constant (``choose_tiles``): a
grid step costs the chip about half a microsecond whatever it holds, so
each of the three kernels takes the largest lane-multiple divisors of the
padded lengths, up to 1024 on a side, that fit a VMEM budget reckoned from
the blocks, scratch and float32 tile temporaries it holds (S 1024: one
tile a head; S 4096: 4 x 4, ten of them with work). ``block_q=`` /
``block_k=`` given explicitly are kept (tests, the ring). Each traced call
counts its choice into the registry (``attn.tile_choice``: the tiles, the
steps, ``layout`` and ``heads_per_block``). Under causality, on a grid of
more than one tile a side, no step is spent above the diagonal: the
products are skipped (a runtime predicate on the offsets) and the index
maps, which see the offsets as scalar prefetch, stand still on the last
block with work, so the pipeline fetches nothing; and only the tiles the
diagonal crosses or that hold padded keys build the mask, interior tiles
are plain products (a variant no tile of the grid needs is not built at
all: at S 1024 each kernel is one body, as it was before there were
variants). A grid of one tile a head (S 1024) computes the masked half of
that tile: raced on the chip, it still beat four tiles of 512
(``_TILE_CAP``).

Backward is the standard flash backward recomputation — no O(S²) residual is
saved, only (q, k, v, out, lse). ``out`` and ``lse``, which only the forward
kernel can make, carry checkpoint names (``FLASH_RESIDUALS``): a ``remat``
whose policy keeps those names (``save_flash_residuals``: every block of the
four LM models, and ``TransformerLM``'s ``"dots"``) holds the two arrays
from its forward pass and runs the forward kernel once a layer; a
policy-less ``remat`` (``TransformerLM``'s ``"full"``, the pipeline's tick)
runs it again in its backward pass, and outside a ``remat`` the names mean
nothing. The backward runs as two Pallas kernels (VERDICT
r01 weak #4: the first version scanned kv blocks in jnp, holding
[S, block_k] score slabs): a dk/dv kernel with q blocks innermost and a dq
kernel with kv blocks innermost, both accumulating in VMEM scratch with the
[block_q, block_k] probability tile recomputed from the saved logsumexp.
Like the forward, both feed the MXU the input dtype: all seven products
take their operands as they came (bfloat16 stays bfloat16, float32 stays
float32), ``p`` and ``ds`` are rounded to that dtype just before the
product they enter, and the softmax arithmetic and the accumulators are
float32. Peak memory is O(block²) per core in both passes. The jnp scan
version is kept as ``_blockwise_bwd`` — the reference implementation the
kernels are tested against.

Falls back to interpret mode off-TPU automatically, like ops.pallas_ce.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from tpu_sandbox.ops.pallas_common import (
    LANE as _LANE,
    NEG as _NEG,
    default_interpret,
    kernel_site,
    round_up as _round_up,
)


def _to_bhsd(x, s_target: int, d_target: int):
    """[B, S, H, D] -> [B, H, s_target, d_target]: the kernel layout
    (heads to dim 1, sequence zero-padded to the block multiple, head dim
    to the lane tile). Single home for the padding convention — forward,
    lse-forward and backward all go through here."""
    x = jnp.moveaxis(x, 2, 1)
    return jnp.pad(
        x,
        ((0, 0), (0, 0), (0, s_target - x.shape[2]),
         (0, d_target - x.shape[3])),
    )


def _from_bhsd(x, s: int, d: int):
    """Inverse of _to_bhsd: slice off padding, heads back to dim 2."""
    return jnp.moveaxis(x[:, :, :s, :d], 1, 2)


class _Form(NamedTuple):
    """How a call's operands lie in HBM, read from their shapes.

    Padded (``packed`` False): q, k ``[B, H, S, d]`` and v ``[B, H, S, dv]``
    as ``_to_bhsd`` makes them, one head a block. Packed: q, k, v
    ``[B, S, H·D]``, the projection's own array, read in column blocks
    ``d == dv`` lanes wide that hold ``group`` whole heads (two at D 64, one
    at D 128). ``b, h, s, sk`` are the batch, the heads and the lengths of
    queries and keys."""
    packed: bool
    group: int
    b: int
    h: int
    s: int
    sk: int
    d: int
    dv: int


def _heads_per_block(q, k, v, block_q, block_k) -> int | None:
    """The heads a 128-lane column block of ``[B, S, H·D]`` holds whole, or
    None where the call's shapes keep it on the padded form: q, k, v
    ``[B, S, H, D]`` of one shape (self-attention, one head size), ``S`` a
    lane multiple that every explicit block divides, and ``D`` a lane
    multiple (one head a block) or a divisor of the lane tile whose group
    divides ``H``."""
    _, s, h, d = q.shape
    if not (q.shape == k.shape == v.shape
            and _pad_len(s, block_q, block_k) == s):
        return None
    if d % _LANE == 0:
        return 1
    group = _LANE // d
    return group if group * d == _LANE and h % group == 0 else None


def _form(q, k, v, heads: int | None) -> _Form:
    """``heads`` None: the padded form's ``[B, H, S, D]`` operands; the head
    count of packed ``[B, S, H·D]`` ones otherwise."""
    if heads is None:
        b, h, s, d = q.shape
        return _Form(False, 1, b, h, s, k.shape[2], d, v.shape[3])
    b, s, hd = q.shape
    d = hd // heads
    group = max(1, _LANE // d)
    return _Form(True, group, b, heads, s, s, group * d, group * d)


_KERNELS = ("fwd", "dkv", "dq")
# What one grid step may hold in VMEM (v5e: 128 MiB on the core, of which
# Mosaic scopes 16 MiB to a kernel unless told otherwise). The tile rule
# keeps its reckoning under the budget; a call whose reckoning passes half
# the default scope is given the budget as its ``vmem_limit_bytes``.
_VMEM_BUDGET = 32 * 2**20
_VMEM_DEFAULT_SCOPE = 16 * 2**20
# float32 [block_q, block_k] temporaries reckoned a step: the chip's
# compiler strip-mines the elementwise chain between the products and was
# found (by bisecting ``vmem_limit_bytes`` on compiles for a v5e) to need
# about one such tile over the blocks and the scratch; two are reckoned
_TILE_TEMPS = 2
# No tile side grows past this. Raced on a v5e at S 1024 (64 / 64) and
# S 4096 (192 / 128): a grid step costs about half a microsecond whatever
# it holds, so every kernel gained up to 1024 on both sides (at S 1024 the
# single tile beat four 512s although it computes the masked half), and
# 2048 gained nothing more (PERF.md §6, PR 28)
_TILE_CAP = 1024


def _vmem_bytes(kernel: str, block_q: int, block_k: int, d: int, dv: int,
                itemsize: int) -> int:
    """Bytes of VMEM one grid step of ``kernel`` holds at these tiles:
    double-buffered operand and result blocks (results reckoned at float32,
    which the ring's partials are), the float32 scratch accumulators and
    the float32 ``[block_q, block_k]`` temporaries."""
    row = _LANE * 4                       # a lane-broadcast row statistic
    q_in, kv_in = block_q * d * itemsize, block_k * (d + dv) * itemsize
    if kernel == "fwd":
        blocks = q_in + kv_in + block_q * (dv * 4 + row)
        scratch = block_q * (2 * row + dv * 4)
    else:
        out = (block_k * (d + dv) if kernel == "dkv" else block_q * d) * 4
        blocks = q_in + kv_in + block_q * (dv * itemsize + 2 * row) + out
        scratch = out
    return 2 * blocks + scratch + _TILE_TEMPS * block_q * block_k * 4


def _divisors(n: int, cap: int) -> list[int]:
    """Multiples of the lane tile that divide ``n``, largest first, none
    above ``cap``."""
    return [t for t in range(min(n, cap), 0, -_LANE) if n % t == 0]


def choose_tiles(kernel: str, s: int, sk: int, d: int, dv: int,
                 itemsize: int, *, block_q: int | None = None,
                 block_k: int | None = None,
                 budget: int = _VMEM_BUDGET) -> tuple[int, int]:
    """(block_q, block_k) for one of the three kernels, from what the call
    can see: the padded lengths ``s`` (queries) and ``sk`` (keys), both
    lane multiples, the padded head sizes, the operands' itemsize. The
    largest lane-multiple divisors of the lengths, none above
    ``_TILE_CAP``, whose ``_vmem_bytes`` for this kernel fits ``budget``,
    the key tile giving way first. A side given explicitly is kept as
    given."""
    if kernel not in _KERNELS:
        raise ValueError(f"kernel must be one of {_KERNELS}, got {kernel!r}")
    qs = [block_q] if block_q else _divisors(s, _TILE_CAP)
    ks = [block_k] if block_k else _divisors(sk, _TILE_CAP)
    for bq in qs:
        for bk in ks:
            if _vmem_bytes(kernel, bq, bk, d, dv, itemsize) <= budget:
                return bq, bk
    return qs[-1], ks[-1]


def _pad_len(s: int, *blocks: int | None) -> int:
    """``s`` rounded up to what every explicit block divides (the lane
    tile when none is given: the rule then picks divisors of that)."""
    unit = math.lcm(_LANE, *(b for b in blocks if b))
    return _round_up(max(s, unit), unit)


def _tile_flags(i, j, q_off, kv_off, *, causal, block_q, block_k, kv_len,
                window=None):
    """``(interior, masked)`` for the tile of q block ``i`` and kv block
    ``j`` at GLOBAL offsets ``q_off`` / ``kv_off``: interior tiles lie
    wholly under the diagonal and hold no padded key (plain products);
    masked ones are crossed by the diagonal or hold padded keys; a tile
    that is neither lies above the diagonal and has no work. Python ints in,
    python bools out (the census of a grid, at trace time); traced scalars
    in, traced bools out (the kernel's own predicates, so the ring's
    rotating source is covered).

    ``window`` (causal only): a band. Query ``q`` sees the keys ``q -
    window < k <= q``: a tile wholly behind every query's window has no
    work either, and one that the band's lower edge crosses is masked."""
    k_end = (j + 1) * block_k
    unpadded, padded = k_end <= kv_len, k_end > kv_len
    if not causal:
        return unpadded, padded
    q_lo, k_hi = q_off + i * block_q, kv_off + k_end - 1
    run = k_hi - block_k + 1 <= q_lo + block_q - 1
    if window is None:
        return run & (k_hi <= q_lo) & unpadded, run & ((k_hi > q_lo) | padded)
    # the last query's window starts at q_hi - window + 1
    edge = q_lo + block_q - 1 - window
    run = run & (k_hi > q_lo - window)
    inside, crossed = k_hi - block_k + 1 > edge, k_hi - block_k + 1 <= edge
    return (run & (k_hi <= q_lo) & inside & unpadded,
            run & ((k_hi > q_lo) | crossed | padded))


def _tile_census(nq, nk, q_offset, kv_offset, **tile):
    """How many tiles of the (nq, nk) grid are ``(interior, masked)``; None
    when an offset is traced (the ring's), since only the run knows."""
    if not (isinstance(q_offset, int) and isinstance(kv_offset, int)):
        return None
    flags = [_tile_flags(i, j, q_offset, kv_offset, **tile)
             for i in range(nq) for j in range(nk)]
    return sum(f[0] for f in flags), sum(f[1] for f in flags)


def _plan(kernel, form, block_q, block_k, causal, kv_len, q_offset,
          kv_offset, window=None):
    """The static half of a call: its grid ``(nq, nk)``, the keywords its
    kernel takes, and the counter in the always-on registry of which form
    and tiles it was built with and how many of its grid steps have work,
    which the call site counts once (``kernel_site``). ``d`` / ``dv`` are
    the width a head has in its block: the padded one on the padded form,
    the head's own on the packed form."""
    from tpu_sandbox.obs import get_registry

    nq, nk = form.s // block_q, form.sk // block_k
    tile = dict(causal=causal, block_q=block_q, block_k=block_k,
                kv_len=kv_len, window=window)
    census = _tile_census(nq, nk, q_offset, kv_offset, **tile)
    heads = form.b * form.h
    choice = get_registry().counter("attn.tile_choice", labels={
        "kernel": kernel, "block_q": block_q, "block_k": block_k,
        "s": form.s, "d": form.d // form.group, "dv": form.dv // form.group,
        "steps": heads * nq * nk,
        "steps_with_work": ("traced" if census is None
                            else heads * sum(census)),
        "layout": "packed" if form.packed else "padded",
        "heads_per_block": form.group,
    } | ({} if window is None else {"window": window}))
    return nq, nk, dict(tile, census=census, sk=form.sk, form=form), choice


def _on_tile(i, j, q_off, kv_off, step, *, census, sk, **tile):
    """Run ``step(valid)`` for this grid step's tile in the variant it
    needs — ``valid`` None on interior tiles (no iota, no select), the
    bool mask on masked ones — and not at all above the diagonal. A
    variant no tile of the grid needs is not built (``census``, where the
    offsets were static): every ``pallas_call`` site traces its kernel
    anew, so a body more is paid at each of them."""
    block_q, block_k, kv_len = tile["block_q"], tile["block_k"], tile["kv_len"]

    def valid():
        """[block_q, block_k] bool: the key is no padding (where the keys
        were padded at all) and, under causality, not after the query."""
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        ok = k_pos < kv_len if kv_len < sk else None
        if tile["causal"]:
            q_pos = q_off + i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            below = q_pos >= kv_off + k_pos
            if tile.get("window") is not None:
                below = jnp.logical_and(
                    below, q_pos - tile["window"] < kv_off + k_pos)
            ok = below if ok is None else jnp.logical_and(ok, below)
        return ok

    flags = _tile_flags(i, j, q_off, kv_off, **tile)
    # offsets traced: the counts are unknown, but for the masked variant
    # where nothing can mask (no causality, no padded key)
    maskable = tile["causal"] or kv_len < sk
    for count, flag, mask in zip(census or (None, None if maskable else 0),
                                 flags, (None, valid)):
        if count != 0:
            pl.when(flag)(functools.partial(step, mask))


def _head_lanes(g, group: int, shape):
    """Bool ``shape``: the lanes of a block that belong to head ``g`` of
    the ``group`` its lanes hold; None where a block is one head's (nothing
    to select). A lane outside them enters a contraction as zero, which is
    exact, and is not written by this head."""
    if group == 1:
        return None
    d = shape[-1] // group
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (lane >= g * d) & (lane < (g + 1) * d)


def _own(x, lanes):
    """``x`` with the lanes of the block's other heads zeroed."""
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _row_stat(ref, lanes):
    """[block_q, 1]: the head's row statistic out of a block that carries
    it lane-broadcast: over all 128 lanes, or over the head's own."""
    if lanes is None:
        return ref[...][:, :1]
    return jnp.max(jnp.where(lanes, ref[...], -jnp.inf), axis=-1,
                   keepdims=True)


def _write_own(ref, x, lanes):
    """Write the head's lanes of a result block its group shares: the
    block stays in VMEM over the group's consecutive steps, each head
    filling its own lanes, and goes back to HBM when its index moves."""
    ref[...] = x if lanes is None else jnp.where(lanes, x, ref[...])


def _fwd_kernel(q_off_ref, kv_off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale: float, form: _Form, **tile):
    i, g, j = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    lanes = _head_lanes(g, form.group, q_ref.shape)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _step(valid):
        q = _own(q_ref[...], lanes)          # [block_q, d]
        k = k_ref[...]                       # [block_k, d]
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                            # [block_q, block_k] fp32
        if valid is not None:
            s = jnp.where(valid(), s, _NEG)

        m_prev = m_scr[:, :1]                # [block_q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)               # [block_q, block_k]
        alpha = jnp.exp(m_prev - m_new)      # [block_q, 1]
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    _on_tile(i, j, q_off_ref[0], kv_off_ref[0], _step, **tile)

    @pl.when(j == pl.num_programs(4) - 1)
    def _emit():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        _write_own(o_ref, (acc_scr[:] / l).astype(o_ref.dtype), lanes)
        # lane-broadcast row stats: Mosaic requires the last two block dims
        # to be (8k, 128m)-aligned, so lse is carried as [block_q, LANE]
        # (the official TPU flash kernel's MIN_BLOCK_SIZE convention): a
        # head's value over the whole block, or over its own lanes of a
        # block its group shares
        _write_own(lse_ref, jnp.broadcast_to(
            m_scr[:, :1] + jnp.log(l), lse_ref.shape
        ).astype(jnp.float32), lanes)


def _last_kv_block(i, q_off_ref, kv_off_ref, block_q, block_k, nk):
    """The last kv block with work for q block ``i`` under causality (0
    where none has)."""
    reach = q_off_ref[0] + (i + 1) * block_q - 1 - kv_off_ref[0]
    return jnp.minimum(jax.lax.div(jnp.maximum(reach, 0), block_k), nk - 1)


def _first_q_block(j, q_off_ref, kv_off_ref, block_q, block_k, nq):
    """The first q block with work for kv block ``j`` under causality."""
    ahead = kv_off_ref[0] + j * block_k - q_off_ref[0]
    return jnp.minimum(jax.lax.div(jnp.maximum(ahead, 0), block_q), nq - 1)


def _first_kv_block(i, q_off_ref, kv_off_ref, block_q, block_k, window):
    """The first kv block that the window of q block ``i``'s first query
    reaches."""
    behind = q_off_ref[0] + i * block_q - window + 1 - kv_off_ref[0]
    return jax.lax.div(jnp.maximum(behind, 0), block_k)


def _block_specs(packed, causal, block_q, block_k, nq, nk, inner,
                 window=None):
    """``(q_side, kv_side)``: BlockSpec factories ``f(width)`` for blocks
    ``[block, width]`` over a grid (B, H / G, outer, G, inner) whose
    innermost dimension walks the kv blocks (``inner == "kv"``: forward,
    dq) or the q blocks (``"q"``: dk/dv), and whose fourth walks the G
    heads of a group (1 on the padded form). A block lies at ``(b, h, block,
    0)`` of a padded ``[B, H, S, width]`` array and at ``(b, block, h)`` of
    a packed ``[B, S, (H / G)·width]`` one: the group's index, the same for
    each of its heads, so the pipeline fetches a group's block once. The
    outer side's block is the grid's own. The inner side's is clamped,
    under causality, to the blocks that have work: on the steps above the
    diagonal the index stands still, the pipeline sees the block it already
    holds and fetches nothing. The offsets arrive as scalar prefetch, so
    that holds for the ring's traced ones too. Under a ``window`` (the
    forward pass alone) the kv index stands still likewise on the first
    block a q block's window reaches, through the blocks behind it."""
    def outer(x, y, q_off, kv_off):
        return x

    def inner_kv(i, j, q_off, kv_off):
        if causal:
            j = jnp.minimum(j, _last_kv_block(i, q_off, kv_off, block_q,
                                              block_k, nk))
            if window is not None:
                j = jnp.maximum(j, _first_kv_block(i, q_off, kv_off, block_q,
                                                   block_k, window))
        return j

    def inner_q(j, i, q_off, kv_off):
        if causal:
            i = jnp.maximum(i, _first_q_block(j, q_off, kv_off, block_q,
                                              block_k, nq))
        return i

    def side(rows, block):
        if packed:
            return lambda width: pl.BlockSpec(
                (None, rows, width),
                lambda b, h, x, g, y, *offs: (b, block(x, y, *offs), h))
        return lambda width: pl.BlockSpec(
            (None, None, rows, width),
            lambda b, h, x, g, y, *offs: (b, h, block(x, y, *offs), 0))

    q_block, kv_block = ((outer, inner_kv) if inner == "kv"
                         else (inner_q, outer))
    return side(block_q, q_block), side(block_k, kv_block)


def _compiler_params(kernel, block_q, block_k, d, dv, itemsize):
    from jax.experimental.pallas import tpu as pltpu

    need = _vmem_bytes(kernel, block_q, block_k, d, dv, itemsize)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary",
                             "arbitrary"),
        vmem_limit_bytes=(_VMEM_BUDGET if need > _VMEM_DEFAULT_SCOPE // 2
                          else None),
    )


def _offsets(q_offset, kv_offset):
    return [jnp.asarray(x, jnp.int32).reshape(1) for x in (q_offset, kv_offset)]


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, kv_len,
               q_offset=0, kv_offset=0, out_dtype=None, heads=None,
               window=None):
    """q,k [B,H,S,D], v [B,H,S,Dv] (S a multiple of the blocks, D and Dv
    lane-aligned; ``kv_len`` is the true pre-padding length) ->
    (out [B,H,S,Dv], lse [B,H,S]); with ``heads`` given, q, k, v packed
    [B,S,H·D] -> (out [B,S,H·D], lse as the kernel wrote it: a head's value
    over its lanes of [B,S,(H/G)·128], which the packed backward reads as
    it lies). A block left ``None`` is the tile rule's (``choose_tiles``).

    ``q_offset``/``kv_offset`` are *global* positions of the first local
    query/key (python ints or traced scalars — ring attention passes the
    rotating source offset); the causal block skip stays active either way
    because the kernel predicates on the runtime offsets. ``out_dtype``
    defaults to q's dtype; partial-attention callers pass fp32 so the
    cross-block merge never sees a rounded partial. ``window``: the
    band of ``_tile_flags``.
    """
    from jax.experimental.pallas import tpu as pltpu

    interpret = default_interpret(interpret)
    form = _form(q, k, v, heads)
    d, dv = form.d, form.dv
    block_q, block_k = choose_tiles("fwd", form.s, form.sk, d, dv,
                                    q.dtype.itemsize, block_q=block_q,
                                    block_k=block_k)
    nq, nk, tile, choice = _plan("fwd", form, block_q, block_k, causal,
                                 kv_len, q_offset, kv_offset, window)
    kernel = functools.partial(_fwd_kernel, scale=scale, **tile)
    q_side, kv_side = _block_specs(form.packed, causal, block_q, block_k, nq,
                                   nk, "kv", window)
    with kernel_site("flash_fwd", choice):
        out, lse = pl.pallas_call(
            kernel,
            out_shape=(
                jax.ShapeDtypeStruct((*q.shape[:-1], v.shape[-1]),
                                     out_dtype or q.dtype),
                # lse: a lane tile for every block of q's last dimension
                jax.ShapeDtypeStruct(
                    (*q.shape[:-1], q.shape[-1] // d * _LANE), jnp.float32),
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(form.b, form.h // form.group, nq, form.group, nk),
                in_specs=[q_side(d), kv_side(d), kv_side(dv)],
                out_specs=(q_side(dv), q_side(_LANE)),
                scratch_shapes=[
                    pltpu.VMEM((block_q, _LANE), jnp.float32),  # max m
                    pltpu.VMEM((block_q, _LANE), jnp.float32),  # sum l
                    pltpu.VMEM((block_q, dv), jnp.float32),     # output
                ],
            ),
            compiler_params=_compiler_params("fwd", block_q, block_k, d, dv,
                                             q.dtype.itemsize),
            interpret=interpret,
        )(*_offsets(q_offset, kv_offset), q, k, v)
    return out, lse if form.packed else lse[..., 0]


def _delta_rows(form, lanes, do_ref, ref):
    """[block_q, 1]: the head's delta = rowsum(do ⊙ out). The padded form's
    caller computed it, once (the ring reuses it across hops), and ``ref``
    carries it lane-broadcast; the packed form hands the kernels ``out``
    itself, a block like ``do``'s, and the sum over the head's lanes is
    taken on the blocks the step holds (float32, as the caller's)."""
    if not form.packed:
        return _row_stat(ref, lanes)
    prod = do_ref[...].astype(jnp.float32) * ref[...].astype(jnp.float32)
    return jnp.sum(_own(prod, lanes), axis=-1, keepdims=True)


def _bwd_tile(valid, lanes, q_ref, k_ref, v_ref, do_ref, lse_ref, delta,
              scale):
    """What both backward kernels recompute on a tile: the operands, each
    in the dtype the MXU is fed (the inputs' own: bfloat16 stays bfloat16,
    float32 stays float32), and the float32 ``p`` and ``ds`` tiles.
    p = exp(s - lse); ds = p ⊙ (do·vᵀ - delta) · scale. ``q`` and ``do``
    come back with the lanes of the block's other heads zeroed (``lanes``),
    so each of the four products they enter is this head's alone."""
    f32 = jnp.float32
    dt = jnp.result_type(q_ref.dtype, k_ref.dtype, v_ref.dtype, do_ref.dtype)
    q, k = _own(q_ref[...], lanes).astype(dt), k_ref[...].astype(dt)
    v, do = v_ref[...].astype(dt), _own(do_ref[...], lanes).astype(dt)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=f32
    ) * scale                                         # [bq, bk]
    p = jnp.exp(s - _row_stat(lse_ref, lanes))
    if valid is not None:
        p = jnp.where(valid(), p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=f32
    )
    ds = p * (dp - delta) * scale
    return q, k, do, p, ds


def _bwd_dkv_kernel(q_off_ref, kv_off_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale: float, form: _Form, **tile):
    """dk/dv: grid (B, H/G, kv blocks, G, q blocks), q innermost
    (accumulates); a group's G heads add into one pair of accumulators,
    each into its own lanes (its ``q`` and ``do`` are zero elsewhere).

    Standard flash backward with saved lse: dv += pᵀ·do; dk += dsᵀ·q, with
    ``p`` and ``ds`` rounded to the operands' dtype just before the product
    they enter (as the forward rounds ``p``) and float32 accumulators.
    Peak memory is the [block_q, block_k] tile + two [block_k, d] scratch
    accumulators — O(block), the VERDICT r01 weak #4 fix (the jnp scan
    backward held [S, block_k] score slabs per step).
    """
    j, g, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    lanes = _head_lanes(g, form.group, q_ref.shape)

    @pl.when((g == 0) & (i == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _step(valid):
        delta = _delta_rows(form, lanes, do_ref, delta_ref)
        q, _, do, p, ds = _bwd_tile(valid, lanes, q_ref, k_ref, v_ref, do_ref,
                                    lse_ref, delta, scale)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # q block entirely before the kv block -> nothing flows
    _on_tile(i, j, q_off_ref[0], kv_off_ref[0], _step, **tile)

    @pl.when((g == form.group - 1) & (i == pl.num_programs(4) - 1))
    def _emit():
        dk_ref[...] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_off_ref, kv_off_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_scr,
                   *, scale: float, form: _Form, **tile):
    """dq: grid (B, H/G, q blocks, G, kv blocks), kv innermost
    (accumulates). dq += ds·k with the same p/ds tiles as the dk/dv kernel;
    a head writes its own lanes of the group's block."""
    i, g, j = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    lanes = _head_lanes(g, form.group, q_ref.shape)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _step(valid):
        delta = _delta_rows(form, lanes, do_ref, delta_ref)
        _, k, _, _, ds = _bwd_tile(valid, lanes, q_ref, k_ref, v_ref, do_ref,
                                   lse_ref, delta, scale)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _on_tile(i, j, q_off_ref[0], kv_off_ref[0], _step, **tile)

    @pl.when(j == pl.num_programs(4) - 1)
    def _emit():
        _write_own(dq_ref, dq_scr[:].astype(dq_ref.dtype), lanes)


def _flash_bwd(q, k, v, delta, lse, g, scale, causal, block_q, block_k,
               interpret, kv_len, q_offset=0, kv_offset=0, out_dtype=None,
               heads=None):
    """Pallas backward: (dq, dk, dv), peak memory O(block) per core.

    q,k [B,H,S,D], v,g [B,H,S,Dv] (block-padded, lane-aligned), lse [B,H,S] fp32,
    delta = rowsum(g ⊙ out) [B,H,S] precomputed by the caller (once — ring
    callers reuse it across hops). ``out_dtype`` overrides the gradient
    dtype (ring callers pass fp32 so per-hop partials accumulate unrounded).
    Blocks left ``None`` are the tile rule's, each kernel its own. With
    ``heads`` given, q, k, v, g are packed [B,S,H·D], lse lies as the packed
    forward wrote it and ``delta`` is the forward's ``out``, packed like g
    (``_delta_rows``).
    """
    from jax.experimental.pallas import tpu as pltpu

    interpret = default_interpret(interpret)
    form = _form(q, k, v, heads)
    d, dv = form.d, form.dv
    itemsize = q.dtype.itemsize
    if not form.packed:
        # row stats enter lane-broadcast ([B,H,S] -> [B,H,S,LANE]) for the
        # same Mosaic block-alignment reason the forward emits lse that way
        lse = jnp.broadcast_to(lse[..., None], (*lse.shape, _LANE))
        delta = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANE))
    offs = _offsets(q_offset, kv_offset)

    def call(name, kernel, inner, like, widths):
        """One backward kernel over a grid whose innermost dimension walks
        the ``inner`` side; it emits a gradient for each array of ``like``
        (which lie on the other side, in blocks ``widths`` wide), through a
        float32 scratch each."""
        bq, bk = choose_tiles(name, form.s, form.sk, d, dv, itemsize,
                              block_q=block_q, block_k=block_k)
        nq, nk, tile, choice = _plan(name, form, bq, bk, causal, kv_len,
                                     q_offset, kv_offset)
        q_side, kv_side = _block_specs(form.packed, causal, bq, bk, nq, nk,
                                       inner)
        out_side, rows = (kv_side, bk) if inner == "q" else (q_side, bq)
        groups = form.h // form.group
        with kernel_site("flash_" + name, choice):
            return pl.pallas_call(
                functools.partial(kernel, scale=scale, **tile),
                out_shape=[jax.ShapeDtypeStruct(x.shape, out_dtype or x.dtype)
                           for x in like],
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=2,
                    grid=((form.b, groups, nk, form.group, nq) if inner == "q"
                          else (form.b, groups, nq, form.group, nk)),
                    in_specs=[q_side(d), kv_side(d), kv_side(dv), q_side(dv),
                              q_side(_LANE),
                              q_side(dv if form.packed else _LANE)],
                    out_specs=[out_side(w) for w in widths],
                    scratch_shapes=[pltpu.VMEM((rows, w), jnp.float32)
                                    for w in widths],
                ),
                compiler_params=_compiler_params(name, bq, bk, d, dv,
                                                 itemsize),
                interpret=interpret,
            )(*offs, q, k, v, g, lse, delta)

    dk, dv_ = call("dkv", _bwd_dkv_kernel, "q", (k, v), (d, dv))
    (dq,) = call("dq", _bwd_dq_kernel, "kv", (q,), (d,))
    return dq, dk, dv_


def _blockwise_bwd(q, k, v, out, lse, g, scale, causal, block_k, kv_len):
    """Flash backward: scan over kv blocks, O(S·block_k) live memory.

    Standard formulas with saved lse: p = exp(q·kᵀ·scale − lse);
    D = rowsum(g ⊙ out); dS = p ⊙ (g·vᵀ − D); dq = dS·k·scale;
    dk = dSᵀ·q·scale; dv = pᵀ·g.  All per (batch, head) via vmap.
    """
    s_len = q.shape[2]
    n_blocks = s_len // block_k
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    q_pos = jnp.arange(s_len)

    def per_head(q1, k1, v1, lse1, g1, delta1):
        # q1,k1,v1,g1 [S, D]; lse1, delta1 [S]
        qf = q1.astype(jnp.float32)
        gf = g1.astype(jnp.float32)

        def body(dq_acc, jb):
            ks = jax.lax.dynamic_slice_in_dim(k1, jb * block_k, block_k, 0)
            vs = jax.lax.dynamic_slice_in_dim(v1, jb * block_k, block_k, 0)
            ksf = ks.astype(jnp.float32)
            s_blk = (qf @ ksf.T) * scale                   # [S, block_k]
            k_pos = jb * block_k + jnp.arange(block_k)
            mask = (k_pos < kv_len)[None, :]
            if causal:
                mask = jnp.logical_and(mask, q_pos[:, None] >= k_pos[None, :])
            s_blk = jnp.where(mask, s_blk, _NEG)
            p = jnp.exp(s_blk - lse1[:, None])             # [S, block_k]
            dv = p.T @ gf                                  # [block_k, D]
            dp = gf @ vs.astype(jnp.float32).T             # [S, block_k]
            ds = p * (dp - delta1[:, None])                # [S, block_k]
            dq_acc = dq_acc + (ds @ ksf) * scale
            dk = (ds.T @ qf) * scale                       # [block_k, D]
            return dq_acc, (dk, dv)

        dq, (dks, dvs) = jax.lax.scan(
            body, jnp.zeros(q1.shape, jnp.float32), jnp.arange(n_blocks)
        )
        dk = dks.reshape(s_len, -1)
        dv = dvs.reshape(s_len, -1)
        return dq.astype(q1.dtype), dk.astype(k1.dtype), dv.astype(v1.dtype)

    f = jax.vmap(jax.vmap(per_head))
    return f(q, k, v, lse, g, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_core(q, k, v, scale, causal, block_q, block_k, interpret, kv_len,
                heads):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                        kv_len, heads=heads)
    return out


#: The checkpoint names of what only the forward kernel can make: its
#: output and its logsumexp, as the differentiated call keeps them.
FLASH_RESIDUALS = ("flash_out", "flash_lse")


def save_flash_residuals(also=None):
    """The ``remat`` policy that keeps ``FLASH_RESIDUALS`` (and what the
    policy ``also`` keeps): the backward pass of a block under it reads
    the forward kernel's two results where a policy-less ``remat`` runs
    the kernel a second time. q, k and v carry no name: they are
    projections' results, recomputed (or kept by ``also``) like any other."""
    names = jax.checkpoint_policies.save_only_these_names(*FLASH_RESIDUALS)
    if also is None:
        return names
    return jax.checkpoint_policies.save_from_both_policies(also, names)


def remat_saving(remat_cls, model: str, names=FLASH_RESIDUALS):
    """``remat_cls`` -- a block's class as ``model`` has put it under
    ``nn.remat`` -- as a callable that builds it and counts
    ``remat.saved{model, names}`` in the always-on registry, once a block
    built: ``names`` are the checkpoint names that ``remat`` keeps."""
    from tpu_sandbox.obs import get_registry

    saved = get_registry().counter("remat.saved", labels={
        "model": model, "names": "+".join(names) or "none"})

    def build(*args, **kwargs):
        saved.inc()
        return remat_cls(*args, **kwargs)

    return build


def _core_fwd(q, k, v, scale, causal, block_q, block_k, interpret, kv_len,
              heads):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                          kv_len, heads=heads)
    out, lse = map(checkpoint_name, (out, lse), FLASH_RESIDUALS)
    return out, (q, k, v, out, lse)


def _core_bwd(scale, causal, block_q, block_k, interpret, kv_len, heads, res,
              g):
    q, k, v, out, lse = res
    delta = out if heads else jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    return _flash_bwd(q, k, v, delta, lse, g, scale, causal, block_q, block_k,
                      interpret, kv_len, heads=heads)


_flash_core.defvjp(_core_fwd, _core_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    window: int | None = None,
) -> jnp.ndarray:
    """Flash attention over q, k [B, S, H, D] and v [B, S, H, Dv] (the
    layout used by models.transformer.SelfAttention and
    ops.attention.causal_attention, which this matches numerically —
    tested) -> [B, S, H, Dv]. ``scale`` multiplies q.k before the softmax;
    None is ``D ** -0.5``.

    Where the shapes allow (``_heads_per_block``: one shape for q, k, v,
    S a lane multiple, D 64 / 32 / a lane multiple) the kernels read and
    write ``[B, S, H·D]``, a reshape of what came. Otherwise: pads S up to
    the lane tile (to the blocks, where they are given) and D, Dv each up
    to the 128-lane tile (zero-padded keys are masked inside the kernel;
    zero-padded q/k lanes add nothing to a score; zero-padded value lanes
    produce zero output lanes, sliced off). ``block_q`` / ``block_k`` left
    ``None`` are chosen per kernel from the shapes (``choose_tiles``);
    given, they hold for all three kernels.

    ``window`` (with ``causal``): query ``i`` attends to the keys ``i -
    window < j <= i``, a band; key tiles wholly behind it are skipped, not
    masked. **Forward only** (a served prompt's window layers): the two
    backward kernels know no band yet.
    """
    b, s, h, d = q.shape
    dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    static = (float(scale), causal, block_q, block_k, interpret, s)
    if window is None:
        core = _flash_core
    elif not causal or window < 1:
        raise ValueError(f"a window of {window} keys, causal {causal}")
    else:
        def core(q, k, v, *static):
            *static, heads = static
            return _flash_fwd(q, k, v, *static, heads=heads, window=window)[0]
    if _heads_per_block(q, k, v, block_q, block_k):
        out = core(*(x.reshape(b, s, h * d) for x in (q, k, v)), *static, h)
        return out.reshape(b, s, h, d)
    sp = _pad_len(s, block_q, block_k)
    dp, dvp = _round_up(d, _LANE), _round_up(dv, _LANE)
    out = core(
        _to_bhsd(q, sp, dp), _to_bhsd(k, sp, dp), _to_bhsd(v, sp, dvp),
        *static, None,
    )
    return _from_bhsd(out, s, dv)


def flash_attention_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset=0,
    kv_offset=0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Forward-only flash attention returning (out [B,S,H,D], lse [B,S,H]).

    The partial-attention building block for ring attention: offsets give
    queries/keys their global positions, and the logsumexp output lets the
    caller merge partials from different K/V blocks exactly
    (parallel/flash_ring.py). NOT differentiable on its own — the ring
    defines the custom VJP at its own level.
    """
    b, s, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / float(d) ** 0.5
    sp, skp = _pad_len(s, block_q), _pad_len(sk, block_k)
    dp = _round_up(d, _LANE)
    # padded q rows also run; their garbage rows are sliced off below, and
    # the grid only needs square-compatible blocks, not equal q/kv lengths.
    # fp32 partials: the caller's logsumexp merge must not see bf16 rounding
    out, lse = _flash_fwd(
        _to_bhsd(q, sp, dp), _to_bhsd(k, skp, dp), _to_bhsd(v, skp, dp),
        scale, causal, block_q, block_k, interpret, sk, q_offset=q_offset,
        kv_offset=kv_offset, out_dtype=jnp.float32,
    )
    return (
        _from_bhsd(out, s, d),
        jnp.moveaxis(lse[:, :, :s], 1, 2),  # [B, S, H]
    )


def make_flash_bwd_lse(
    q, out, g, lse, *,
    causal: bool = True,
    q_offset=0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
):
    """Partial-attention backward factory, [B, S, H, D] layout: pads the
    loop-invariant q-side tensors and computes delta = rowsum(g ⊙ out)
    ONCE, returning ``fn(k_blk, v_blk, kv_offset) -> (dq, dk, dv)`` for the
    per-hop calls of flash-ring's backward (parallel/flash_ring.py) — only
    the rotating K/V blocks are padded per hop. Gradients come back fp32 so
    ring callers can accumulate hops unrounded. ``lse`` [B, S, H] is the
    FINAL (merged) logsumexp.
    """
    b, s, hh, d = q.shape
    scale = 1.0 / float(d) ** 0.5
    sp = _pad_len(s, block_q)
    dp = _round_up(d, _LANE)
    qp, outp, gp = (_to_bhsd(x, sp, dp) for x in (q, out, g))
    # padded q rows: zero q/g rows give p = exp(0 - 0) = 1 but ds = dv = 0
    # through the zero cotangent, so padding lse with 0 is safe
    lse_p = jnp.pad(jnp.moveaxis(lse, 2, 1), ((0, 0), (0, 0), (0, sp - s)))
    delta = jnp.sum(gp.astype(jnp.float32) * outp.astype(jnp.float32), -1)

    def partial_bwd(k_blk, v_blk, kv_offset):
        sk = k_blk.shape[1]
        skp = _pad_len(sk, block_k)
        dq, dk, dv = _flash_bwd(
            qp, _to_bhsd(k_blk, skp, dp), _to_bhsd(v_blk, skp, dp), delta,
            lse_p, gp, scale, causal, block_q, block_k, interpret, sk,
            q_offset=q_offset, kv_offset=kv_offset, out_dtype=jnp.float32,
        )
        return _from_bhsd(dq, s, d), _from_bhsd(dk, sk, d), _from_bhsd(dv, sk, d)

    return partial_bwd


def flash_attention_fn(
    *, causal: bool = True, scale: float | None = None,
    block_q: int | None = None, block_k: int | None = None,
    interpret: bool | None = None,
):
    """An ``attention_fn`` drop-in for models.transformer.TransformerLM and
    for models.xing4's latent attention (which passes its own ``scale``)."""

    def fn(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, interpret=interpret,
        )

    return fn
