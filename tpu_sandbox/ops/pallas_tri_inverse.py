"""Pallas TPU kernels: the delta rule's triangular inverse and its cotangent.

``T = (I + A)^-1`` of every chunk's strictly lower ``A [n, n]`` is what the
chunked gated delta rule (``ops/delta_rule.py``) solves its chunk with, and
``dA = -T^T dT T^T`` is its cotangent. Written in ``jnp`` (``delta_rule.
_doubling``: the fallback and the tests' oracle) the inverse is ``log2 n``
doubling levels of two batched float32 products each, and every product
reads two and writes one ``[chunks, n, n]`` float32 array in HBM, whose
``n``-wide rows are padded to 128 lanes there: 26 such passes a layer and
step at the cell's ``[1, 30, 128, 64, 64]``. Here a block of chunks is held
in VMEM, ``A`` is read once and ``T`` written once, and nothing in between
leaves the chip:

- ``tri_inverse_fwd``: ``a [..., c, n, n]`` float32 -> ``T`` of the same
  shape; what lies on or above the diagonal of ``a`` is not read;
- ``tri_inverse_bwd``: ``(T, dT)`` -> ``dA``, 0 on and above the diagonal.

*The inverse on the tile* (``inverse_on_tile``, a function of a value ``[g,
n, n]`` that a later kernel of the whole rule can call) is block forward
substitution in two parts, both float32 throughout. Inside diagonal blocks
of ``_ROWS`` rows it runs on the VPU a column at a time (``_substituted``):
row ``s`` of ``T`` is final once the columns before it are done, and every
row under it in the block then loses ``a[r, s]`` times it. A diagonal block
of ``T`` has nothing outside its own columns, so the ``n / _ROWS`` blocks of
a chunk stand side by side in the lanes of one pair of sublane tiles and one
substitution solves them all: a step is a lane gather that spreads a column
of ``a`` over its block's lanes (the XLU: 23 a chunk where a tile at a time
took 92 and bound the kernel), a sublane broadcast of the row, a multiply
and a subtract. From there up the blocks double (``_doubled``): the inverse
of ``[[T11, 0], [A21, T22]]`` keeps its diagonal blocks and gains ``-T22 A21
T11``, so a level moves only the rows of the odd blocks (whole sublane
tiles: picked by slicing, not by a mask) with two products on the MXU. A
float32 product there is the six bf16 products ``Precision.HIGHEST`` is
made of; they are written out (``_split3``, ``_mm6``) because the pieces of
``a`` serve every level and a level's operands are half the rows, where
``HIGHEST`` splits both whole operands of each product anew and spent more
VPU instructions on that than the MXU on the products (PERF.md section 6,
PR 47, with the forms that were tried and dropped). No operand is rounded
to bf16, nothing grows as in the product form, and the result is held to
``solve_triangular`` in float64 like ``_doubling``
(``tests/test_pallas_tri_inverse.py``). The cotangent's two products
(``cotangent_on_tile``) take whole operands once each and stay at
``HIGHEST``.

*Tiles.* The grid walks the last leading axis (a head's chunks) in blocks of
``choose_tile`` chunks, every other leading axis one step an index, all
``"parallel"``; inside a step a loop takes ``_GROUP`` chunks at a time
(``_BWD_GROUP`` backward, or the largest divisor of the block under it), so
that several chains of dependent steps are in flight. **The leading axes
are not flattened**: a ``[1, 30, 128, 64, 64]`` operand reshaped to ``[3840,
64, 64]`` in front of the call is a bitcast, but XLA then lays ``T`` out
transposed for the four products that read it in the backward pass and
copies 126 MB behind every recomputed forward kernel (PERF.md section 6,
PR 47).

A shape the kernels do not take (``n`` no power of two from ``_MIN_N`` to
``_MAX_N``, no leading axis, or chunks that no multiple of a group divides)
goes to ``jnp`` by that shape alone; ``delta_rule`` counts which ran, once a
site (``delta_rule.inverse_choice``). The calls are jitted functions, traced
and lowered once a shape and not once a site.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_sandbox.ops.pallas_common import divisors, kernel_site, traced_once

_F32 = jnp.float32
_BF16 = jnp.bfloat16
_HIGHEST = jax.lax.Precision.HIGHEST
# rows of a float32 sublane tile
_SUB = 8
# VMEM a kernel may take (Mosaic scopes 16 MiB unless told otherwise): the
# backward kernel's three blocks, double-buffered, are 12 MiB, and a loop
# step spills some of its values
_VMEM_LIMIT = 24 * 2**20
# The chunks a kernel takes: from two sublane tiles (a chunk of 8 is one
# vreg an eighth full, and the tiny models of the CPU tests keep to `jnp`;
# no less than `_ROWS`) to a lane tile
_MIN_N, _MAX_N = 16, 128
# Chunks of 64 a VMEM block holds (2 MB lane-padded; more of smaller
# chunks, fewer of larger), the rows of a diagonal block solved by
# substitution, and the chunks a loop step works on, forward and backward.
# Raced alone on a v5e at [1, 30, 128, 64, 64], ms a pass of 3840 chunks
# (PERF.md section 6, PR 47): 16 rows 1.42 (8 rows 1.55, 32 rows 1.80); 4
# chunks a step forward 1.42 (2 chunks 1.79, 1 chunk 2.82: the chains of
# dependent steps need company more than their values need registers); 8
# backward 0.88 (4 chunks 0.99, 2 chunks 1.20); blocks of 16 to 64 chunks
# alike
_TILE_CAP = 64
_ROWS = 16
_GROUP = 4
_BWD_GROUP = 8


def tile_masks(n: int, rows: int):
    """What ``inverse_on_tile`` and ``cotangent_on_tile`` pick entries of a
    ``[g, n, n]`` value with, made once a kernel body (outside its loop):
    ``lower``, the entries below the diagonal, and for every doubling level
    from blocks of ``rows`` up, ``A21`` of every pair of diagonal blocks
    where it stands."""
    row = jax.lax.broadcasted_iota(jnp.int32, (1, n, n), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n, n), 2)
    levels, shift = [], rows.bit_length() - 1
    while 1 << shift < n:
        r, c = row >> shift, col >> shift
        levels.append((r == c + 1) & ((r & 1) == 1))
        shift += 1
    return row > col, tuple(levels)


def _substituted(a, rows: int):
    """The inverse of every ``rows x rows`` diagonal block of ``I + a``, 0
    elsewhere, ``a [g, n, n]`` strictly lower: the blocks of a chunk side by
    side in the lanes of ``rows / 8`` sublane tiles, one forward substitution
    for all of them, a column at a time; a chunk at a time, since Mosaic
    gathers in two dimensions."""
    g, n, _ = a.shape
    per, blocks = rows // _SUB, n // rows
    lane = jax.lax.broadcasted_iota(jnp.int32, (_SUB, n), 1)
    r = jax.lax.broadcasted_iota(jnp.int32, (_SUB, n), 0)
    shift = rows.bit_length() - 1
    block = lane >> shift               # the diagonal block a lane belongs to
    first = block << shift              # and that block's first lane
    out = []
    for c in range(g):
        tiles = [a[c, lo:lo + _SUB] for lo in range(0, n, _SUB)]
        packed, t = [], []
        for u in range(per):
            d = tiles[(blocks - 1) * per + u]
            for b in range(blocks - 2, -1, -1):
                d = jnp.where(block == b, tiles[b * per + u], d)
            packed.append(d)
            t.append(jnp.where(lane - first == r + u * _SUB, 1.0, 0.0)
                     .astype(_F32))
        for s in range(rows - 1):       # the last row has none under it
            su = s // _SUB
            row_s = t[su][s % _SUB:s % _SUB + 1]
            for u in range(su, per):
                col = jnp.take_along_axis(packed[u], first + s, axis=1)
                t[u] = t[u] - col * row_s
        out.append(jnp.concatenate(
            [jnp.where(block == b, t[u], 0.0)
             for b in range(blocks) for u in range(per)], axis=0))
    return jnp.stack(out)


def _split3(x):
    """float32 ``x`` as three bf16 pieces with ``x0 + x1 + x2 == x`` to
    float32's last bit: what ``HIGHEST`` makes of an operand."""
    x0 = x.astype(_BF16)
    rest = x - x0.astype(_F32)
    x1 = rest.astype(_BF16)
    return x0, x1, (rest - x1.astype(_F32)).astype(_BF16)


def _mm6(xs, ys):
    """The six bf16 products ``HIGHEST`` makes of two float32 operands
    given in their pieces, float32 accumulation, the small terms first."""
    def dot(x, y):
        return jnp.einsum("gij,gjk->gik", x, y, preferred_element_type=_F32)

    small = dot(xs[0], ys[2]) + dot(xs[1], ys[1]) + dot(xs[2], ys[0])
    return (small + (dot(xs[0], ys[1]) + dot(xs[1], ys[0]))) + dot(xs[0], ys[0])


def _doubled(a, inv, levels, rows: int):
    """The doubling levels from diagonal blocks of ``rows`` up: a level
    moves the rows of the odd blocks alone (``-T22 A21 T11`` stands in
    them); the pieces of ``a`` are made once."""
    n = a.shape[-1]
    pieces = _split3(a)
    size = rows
    for below in levels:
        tiles = [inv[:, lo:lo + size] for lo in range(0, n, size)]
        odd = jnp.concatenate(tiles[1::2], axis=1)
        block = _mm6(_split3(odd), [jnp.where(below, p, 0) for p in pieces])
        moved = odd - _mm6(_split3(block), _split3(inv))
        tiles[1::2] = [moved[:, lo:lo + size]
                       for lo in range(0, n // 2, size)]
        inv = jnp.concatenate(tiles, axis=1)
        size *= 2
    return inv


def inverse_on_tile(a, masks):
    """``(I + a)^-1`` of ``a [g, n, n]`` float32, a value on the tile; what
    lies on or above the diagonal is not read. ``masks = tile_masks(n,
    rows)`` with ``rows`` the diagonal blocks solved by substitution."""
    lower, levels = masks
    n = a.shape[-1]
    rows = n >> len(levels)
    a = jnp.where(lower, a, 0.0)
    return _doubled(a, _substituted(a, rows), levels, rows)


def cotangent_on_tile(t, dt, masks):
    """``-T^T dT T^T`` below the diagonal, 0 elsewhere."""
    left = jnp.einsum("gji,gjk->gik", t, dt, precision=_HIGHEST,
                      preferred_element_type=_F32)
    da = jnp.einsum("gik,glk->gil", left, t, precision=_HIGHEST,
                    preferred_element_type=_F32)
    return jnp.where(masks[0], -da, 0.0)


def _by_group(ref, group: int, body):
    """``body(at)`` for every ``group`` matrices of the block, in turn."""
    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * group, group), group))
        return carry

    jax.lax.fori_loop(0, ref.shape[0] // group, step, None)


def _fwd_kernel(a_ref, t_ref, *, group, rows):
    masks = tile_masks(a_ref.shape[-1], rows)

    def one(at):
        t_ref[at] = inverse_on_tile(a_ref[at], masks)

    _by_group(a_ref, group, one)


def _bwd_kernel(t_ref, dt_ref, da_ref, *, group):
    masks = tile_masks(t_ref.shape[-1], t_ref.shape[-1])

    def one(at):
        da_ref[at] = cotangent_on_tile(t_ref[at], dt_ref[at], masks)

    _by_group(t_ref, group, one)


def _call(kernel, name, cap, *operands, tile, interpret):
    """``kernel(*refs, group=...)`` over blocks of ``tile`` matrices along
    the last leading axis of ``operands [..., c, n, n]``, the leading axes
    as they are (the module's docstring), at most ``cap`` of a block's
    matrices a loop step."""
    *lead, c, n, _ = operands[0].shape
    spec = pl.BlockSpec((*[None] * len(lead), tile, n, n),
                        lambda *ids: (*ids, 0, 0))
    grid = (*lead, c // tile)
    return pl.pallas_call(
        functools.partial(kernel, group=divisors(tile, 1, cap)[0]),
        grid=grid, in_specs=[spec] * len(operands), out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(operands[0].shape, _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=name, interpret=interpret)(*operands)


def choose_tile(shape) -> int | None:
    """The matrices of a VMEM block, along the last leading axis of
    ``shape [..., c, n, n]``: the largest even divisor of ``c`` under the
    cap; None where the kernels do not take the shape."""
    if len(shape) < 3:
        return None
    c, n = shape[-3], shape[-1]
    if n & (n - 1) or not _MIN_N <= n <= _MAX_N:
        return None
    fits = divisors(c, 2, _TILE_CAP * 64 // n)
    return fits[0] if fits else None


@traced_once
def tri_inverse_fwd(a, *, tile, interpret):
    with kernel_site("tri_inverse_fwd"):
        return _call(functools.partial(_fwd_kernel, rows=_ROWS),
                     "tri_inverse_fwd", _GROUP, a, tile=tile,
                     interpret=interpret)


@traced_once
def tri_inverse_bwd(t, dt, *, tile, interpret):
    with kernel_site("tri_inverse_bwd"):
        return _call(_bwd_kernel, "tri_inverse_bwd", _BWD_GROUP, t, dt,
                     tile=tile, interpret=interpret)
