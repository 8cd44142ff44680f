"""Pallas TPU kernels: a mixer's short causal convolution with its activation.

``y[t] = silu(bias + sum_i taps[i] * x[t - (K - 1 - i)])``, depthwise over
the channels of ``x [B, S, C]``, is what stands between the input
projection and the recurrence of a Mamba-2 mixer (``models/nemotron_h.py``)
and of a Gated DeltaNet (``models/olmo_hybrid.py``). Written in ``jnp``
(``causal_conv`` below: the plain definition, the fallback and the tests'
oracle) XLA pads a float32 copy of ``x``, keeps float32 arrays of
``[S, C]`` between its fusions and, backward, writes one such array a tap
for the taps' gradient to read back. Here the operand is read once in its
dtype where it lies, float32 exists on the tile only, and nothing but the
result is written:

- ``short_conv_fwd``: ``x`` in, ``y`` out, in ``x``'s dtype (or in float32
  where the caller's next pass is float32: nothing is rounded between);
- ``short_conv_bwd``: ``x`` and ``dy`` in; the pre-activation again on the
  tile, ``dpre = dy silu'(pre)``, ``dx[t] = sum_i taps[i] dpre[t + (K - 1 -
  i)]`` out, and ``dtaps [K, C]``, ``dbias [C]`` summed in float32 over the
  sequence tiles in a block that stays in VMEM.

*Tiles and halos.* The grid walks ``[B, S, C]`` in tiles of
``tile_tokens x tile_c`` (``choose_tiles``). The K - 1 tokens a tile needs
from the one before come through a second, one-sublane-tile block of ``x``
(the rows in front of the tile; zero in front of the first token), never
from a padded copy in HBM; every step of the forward grid is independent.
The backward grid walks the sequence from its end, so that the K - 1 rows
of ``dpre`` that ``dx`` needs from the following tile are what the previous
step left in a VMEM scratch. Inside a step the tile is staged once as
float32 and a loop walks it a row group and a lane chunk at a time, so that
no more than a few vregs a value are live. The operand may be a slice of the
channels of a wider array (``start``: Nemotron's ``xBC`` inside ``in_proj``'s
result): where the tile's width divides the offset the index map reads it
in place, else it is sliced first.

*Two pairs, by where the array lies.* XLA keeps an array whose last
dimension is no lane multiple with its *tokens* minor (``in_proj``'s
``[8192, 9280]``, Olmo's keys ``[8192, 2880]``: nothing is padded that
way), and a Mosaic call reads row-major only: a pair written for ``[B, S,
C]`` alone makes XLA transpose such an operand, the result, and whatever
else reads the array (measured: Nemotron's scan + 11 ms and norm + 13 ms a
step, more than the kernels won). So there is the same pair once more for
``[B, C, S]`` (the ``_cf`` functions: channels on the sublanes, tokens on
the lanes, the halo a lane tile, the shifts along the lanes), which takes
the transposed view of such an array at no copy, and ``short_conv`` picks
by the array's width. Olmo's values (5760 channels, 45 lane tiles) lie
row-major and take the first pair, which is the faster by half (sublane
shifts are nearly free, lane shifts a rotate and a select): it is kept for
those 10 ms a step alone, and goes when the second is as fast (ROADMAP.md
A3(a2)).

A shape the kernels do not take (a sequence that the rows of one packed
tile, 8 float32 or 16 bf16, do not divide, or a lane tile where the tokens
go on the lanes; channels there that those rows do not divide; more taps
than a sublane tile holds) falls back to ``jnp`` by that shape alone. Every
call site counts what it was built with into the registry
(``conv.kernel_choice``); the calls themselves are jitted functions, traced
and lowered once a shape and not once a site.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_sandbox.ops.pallas_common import (
    LANE as _LANE,
    default_interpret,
    divisors,
    kernel_site,
    traced_once,
)

_F32 = jnp.float32
# rows of a float32 sublane tile: what a tile keeps of the tile before it,
# and the rows a tap or the bias takes in the coefficient blocks
_SUB = 8
# What one grid step may hold in VMEM: its double-buffered blocks and the
# float32 stage (Mosaic scopes 16 MiB to a kernel unless told otherwise)
_VMEM_BUDGET = 16 * 2**20
# No tile grows past these. Raced on a v5e in bf16 at 8192 tokens x 5120
# channels (of 9280), 5760 and 2880 (PERF.md section 6, PR 39): token tiles
# of 256 to 1024 and widths of 512 to 2048, or all of C, read the same to
# 3 %, 128 tokens up to 17 % slower: the loop below binds, not the grid
_TOKEN_CAP = 512
_WIDTH_CAP = 1024
# Rows and lanes the loop inside a step works on at a time: 16 vregs a
# value. Raced from 16 x 512 (0.42 ms forward, 0.76 backward at 5120
# channels: too little work a step to fill the pipeline) to 128 x 128 and
# 64 x 512 (0.36 / 0.70: the backward's sums spill); 64 x 256 reads 0.33 /
# 0.61, a loop unrolled whole 5 % less for five times the compile
_ROW_GROUP = 64
_LANE_CHUNK = 256
# The same for the pair that reads [B, C, S], the tokens on the lanes: the
# tiles' caps, and the channels and tokens of a loop step. Raced on the
# chip at the same shapes (PERF.md section 6, PR 39): the shifts along the
# lanes cost this pair 2.2 to 2.5 times the other's time (0.74 ms forward,
# 1.55 backward at 5120 channels), and what helps is a long run of tokens a
# step: 16 channels x 1024 tokens 1.55 backward, 16 x 512 1.94, 32 x 256
# 2.25, 32 x 128 3.58; token tiles of 2048 against 1024 - 25 % forward,
# channel tiles of 128 to 512 alike
_LANE_TOKEN_CAP = 2048
_CHANNEL_CAP = 256
_CHANNEL_GROUP = 16
_TOKEN_CHUNK = 1024


# --- tiles ---

def _vmem_bytes(tt: int, tc: int, itemsize: int) -> int:
    """Bytes of VMEM a grid step of the backward kernel holds at most (the
    forward one holds a block less): ``x`` and ``dx`` double-buffered, ``dy``
    too (float32 at worst), the float32 stage."""
    return tt * tc * (4 * itemsize + 8 + 4)


def choose_tiles(c: int, start: int, tokens: int, taps: int, itemsize: int,
                 *, budget: int = _VMEM_BUDGET) -> tuple[int, int] | None:
    """``(tile_tokens, tile_c)`` for a sequence of ``tokens`` and ``c``
    channels that begin at channel ``start`` of their array, or None where
    the kernels do not apply (the caller falls back to ``jnp``): tokens that
    the rows of one packed tile (8 float32, 16 bf16) do not divide, taps
    that with the bias do not fit a sublane tile, a width or an offset that
    no lane multiple divides. The width is the largest lane multiple up to
    ``_WIDTH_CAP`` that divides ``c`` and ``start``, the token tile the
    largest divisor of the tokens up to ``_TOKEN_CAP`` whose ``_vmem_bytes``
    fits ``budget``."""
    unit = 32 // itemsize
    tc = next((t for t in divisors(c, _LANE, _WIDTH_CAP) if start % t == 0),
              None)
    if tokens % unit or not 1 <= taps < _SUB or tc is None:
        return None
    for tt in divisors(tokens, unit, _TOKEN_CAP):
        if _vmem_bytes(tt, tc, itemsize) <= budget:
            return tt, tc
    return None


def choose_tiles_cf(c: int, start: int, tokens: int, taps: int,
                    itemsize: int, *, budget: int = _VMEM_BUDGET
                    ) -> tuple[int, int] | None:
    """``choose_tiles`` for an operand ``[B, C, S]``: the tokens fill the
    lanes and the channels the sublanes, so the tokens must be a lane
    multiple, and the channels and their offset a multiple of the rows of
    one packed tile. The channel tile is the largest such divisor of ``c``
    and ``start`` up to ``_CHANNEL_CAP``, the token tile the largest lane
    multiple that divides the tokens, up to ``_LANE_TOKEN_CAP``, whose
    ``_vmem_bytes`` fits ``budget``."""
    unit = 32 // itemsize
    if tokens % _LANE or c % unit or start % unit or not 1 <= taps < _SUB:
        return None
    tc = next(t for t in divisors(c, unit, _CHANNEL_CAP) if start % t == 0)
    for tt in divisors(tokens, _LANE, _LANE_TOKEN_CAP):
        if _vmem_bytes(tt, tc, itemsize) <= budget:
            return tt, tc
    return None


def _choice(impl: str, x, taps, bias, cf, tiles):
    """The counter a call site adds one to: what it was built with."""
    from tpu_sandbox.obs import get_registry

    return get_registry().counter("conv.kernel_choice", labels={
        "impl": impl, "channels": taps.shape[1], "taps": taps.shape[0],
        "bias": int(bias is not None),
        "tokens": x.shape[0] * x.shape[2 if cf else 1],
        "tile_tokens": tiles[0] if tiles else 0})


# --- the kernels ---

def _lane_chunks(tc: int):
    return [slice(lo, min(lo + _LANE_CHUNK, tc))
            for lo in range(0, tc, _LANE_CHUNK)]


def _stage(x_ref, halo_ref, stage, first):
    """The tile as float32 behind the ``_SUB`` rows in front of it (zero
    where ``first``: nothing comes before the first token)."""
    halo = halo_ref[0].astype(_F32)[-_SUB:]
    stage[:_SUB, :] = jnp.where(first, 0.0, halo)
    stage[_SUB:, :] = x_ref[0].astype(_F32)


def _coefficients(coef_ref, lanes, taps: int, bias: bool):
    k = [coef_ref[i:i + 1, lanes] for i in range(taps)]
    return k, (coef_ref[taps:taps + 1, lanes] if bias else None)


def _lead(axis: int) -> int:
    """The tokens a window holds in front of its own: a sublane tile of
    rows, or a lane tile where the tokens lie along the lanes (axis 1)."""
    return _LANE if axis else _SUB


def _shifted(window, i: int, taps: int, n: int, axis: int = 0):
    """What tap ``i`` reads for the ``n`` tokens behind the leading ones of
    ``window``: the tokens ``taps - 1 - i`` earlier."""
    lo = _lead(axis) - (taps - 1 - i)
    return jax.lax.slice_in_dim(window, lo, lo + n, axis=axis)


def _pre_activation(window, k, b, n: int, axis: int = 0):
    """``bias + sum_i taps[i] x[t - (K - 1 - i)]``, the taps left to right
    as ``causal_conv``'s ``sum``."""
    acc = _shifted(window, 0, len(k), n, axis) * k[0]
    for i in range(1, len(k)):
        acc = acc + _shifted(window, i, len(k), n, axis) * k[i]
    return acc if b is None else b + acc


def _input_gradient(dpre, behind, k, n: int, axis: int = 0):
    """``dx[t] = sum_i taps[i] dpre[t + (K - 1 - i)]``: ``behind`` holds the
    first tokens of ``dpre`` that follow these ``n``."""
    ahead = jnp.concatenate([dpre, behind], axis)
    taps = len(k)
    dx = jax.lax.slice_in_dim(ahead, taps - 1, taps - 1 + n, axis=axis) * k[0]
    for i in range(1, taps):
        dx = dx + jax.lax.slice_in_dim(
            ahead, taps - 1 - i, taps - 1 - i + n, axis=axis) * k[i]
    return dx


def _pre_gradient(dy, pre):
    """``dy silu'(pre)``, float32."""
    sig = jax.nn.sigmoid(pre)
    return dy.astype(_F32) * (sig * (1.0 + pre * (1.0 - sig)))


def _fwd_kernel(x_ref, halo_ref, coef_ref, y_ref, stage, *, taps, bias, rows):
    tt, tc = y_ref.shape[1:]
    _stage(x_ref, halo_ref, stage, pl.program_id(2) == 0)
    for lanes in _lane_chunks(tc):
        k, b = _coefficients(coef_ref, lanes, taps, bias)

        def group(g, carry, lanes=lanes, k=k, b=b):
            r = pl.multiple_of(g * rows, rows)
            window = stage[pl.ds(r, rows + _SUB), lanes]
            y = jax.nn.silu(_pre_activation(window, k, b, rows))
            y_ref[0, pl.ds(r, rows), lanes] = y.astype(y_ref.dtype)
            return carry

        jax.lax.fori_loop(0, tt // rows, group, None)


def _fold(a):
    """``a [rows, lanes]`` summed down to one sublane tile of rows."""
    return a.reshape(-1, _SUB, a.shape[1]).sum(0)


def _bwd_kernel(x_ref, halo_ref, dy_ref, coef_ref, dx_ref, dcoef_ref, stage,
                following, *, taps, bias, rows):
    """Grid (channel tiles, batch, sequence tiles from the last to the
    first). ``following`` holds the first rows of ``dpre`` of the tile
    behind this one; ``dcoef_ref [(taps + 1) 8, tile_c]`` the taps' and the
    bias's gradients, eight partial sums each, over every step of a channel
    tile."""
    tt, tc = dx_ref.shape[1:]
    step, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when((pl.program_id(1) == 0) & (step == 0))
    def _():
        dcoef_ref[...] = jnp.zeros_like(dcoef_ref)

    @pl.when(step == 0)
    def _():
        following[...] = jnp.zeros_like(following)

    _stage(x_ref, halo_ref, stage, step == steps - 1)
    groups = tt // rows
    for lanes in _lane_chunks(tc):
        k, b = _coefficients(coef_ref, lanes, taps, bias)
        width = lanes.stop - lanes.start

        def group(j, carry, lanes=lanes, k=k, b=b):
            behind, sums = carry
            r = pl.multiple_of((groups - 1 - j) * rows, rows)
            window = stage[pl.ds(r, rows + _SUB), lanes]
            dpre = _pre_gradient(dy_ref[0, pl.ds(r, rows), lanes],
                                 _pre_activation(window, k, b, rows))
            dx = _input_gradient(dpre, behind, k, rows)
            dx_ref[0, pl.ds(r, rows), lanes] = dx.astype(dx_ref.dtype)
            sums = tuple(
                s + _fold(dpre * _shifted(window, i, taps, rows))
                for i, s in enumerate(sums[:taps])) + tuple(
                s + _fold(dpre) for s in sums[taps:])
            return dpre[:_SUB], sums

        zero = jnp.zeros((_SUB, width), _F32)
        behind, sums = jax.lax.fori_loop(
            0, groups, group, (following[:, lanes], (zero,) * (taps + bias)))
        following[:, lanes] = behind
        for i, s in enumerate(sums):
            at = slice(i * _SUB, (i + 1) * _SUB)
            dcoef_ref[at, lanes] = dcoef_ref[at, lanes] + s


# --- the same pair for an operand [B, C, S]: the tokens on the lanes ---

def _stage_cf(x_ref, halo_ref, stage, first):
    """The tile as float32 behind the lane tile of tokens in front of it."""
    stage[:, :_LANE] = jnp.where(first, 0.0, halo_ref[0].astype(_F32))
    stage[:, _LANE:] = x_ref[0].astype(_F32)


def _coefficients_cf(coef_ref, rows, taps: int, bias: bool):
    coef = coef_ref[rows, :]
    k = [coef[:, i:i + 1] for i in range(taps)]
    return k, (coef[:, taps:taps + 1] if bias else None)


def _token_chunks(tt: int):
    return [(lo, min(_TOKEN_CHUNK, tt - lo))
            for lo in range(0, tt, _TOKEN_CHUNK)]


def _fwd_kernel_cf(x_ref, halo_ref, coef_ref, y_ref, stage, *, taps, bias,
                   rows):
    tc, tt = y_ref.shape[1:]
    _stage_cf(x_ref, halo_ref, stage, pl.program_id(2) == 0)

    def group(g, carry):
        at = pl.ds(pl.multiple_of(g * rows, rows), rows)
        k, b = _coefficients_cf(coef_ref, at, taps, bias)
        for lo, n in _token_chunks(tt):
            window = stage[at, lo:lo + _LANE + n]
            y = jax.nn.silu(_pre_activation(window, k, b, n, 1))
            y_ref[0, at, lo:lo + n] = y.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, tc // rows, group, None)


def _fold_cf(a):
    """``a [rows, lanes]`` summed down to one lane tile of columns."""
    acc = a[:, :_LANE]
    for lo in range(_LANE, a.shape[1], _LANE):
        acc = acc + a[:, lo:lo + _LANE]
    return acc


def _bwd_kernel_cf(x_ref, halo_ref, dy_ref, coef_ref, dx_ref, dcoef_ref,
                   stage, following, *, taps, bias, rows):
    """As ``_bwd_kernel``; ``following [tile_c, 128]`` holds the first lane
    tile of ``dpre`` of the tile behind this one, ``dcoef_ref [tile_c,
    (taps + 1) 128]`` 128 partial sums a tap and a channel."""
    tc, tt = dx_ref.shape[1:]
    step, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when((pl.program_id(1) == 0) & (step == 0))
    def _():
        dcoef_ref[...] = jnp.zeros_like(dcoef_ref)

    @pl.when(step == 0)
    def _():
        following[...] = jnp.zeros_like(following)

    _stage_cf(x_ref, halo_ref, stage, step == steps - 1)

    def group(g, carry):
        at = pl.ds(pl.multiple_of(g * rows, rows), rows)
        k, b = _coefficients_cf(coef_ref, at, taps, bias)
        behind, sums = following[at, :], None
        for lo, n in reversed(_token_chunks(tt)):
            window = stage[at, lo:lo + _LANE + n]
            dpre = _pre_gradient(dy_ref[0, at, lo:lo + n],
                                 _pre_activation(window, k, b, n, 1))
            dx = _input_gradient(dpre, behind, k, n, 1)
            dx_ref[0, at, lo:lo + n] = dx.astype(dx_ref.dtype)
            parts = [_fold_cf(dpre * _shifted(window, i, taps, n, 1))
                     for i in range(taps)] + [_fold_cf(dpre)] * bias
            sums = parts if sums is None else [
                s + part for s, part in zip(sums, parts)]
            behind = dpre[:, :_LANE]
        following[at, :] = behind
        for i, s in enumerate(sums):
            lanes = slice(i * _LANE, (i + 1) * _LANE)
            dcoef_ref[at, lanes] = dcoef_ref[at, lanes] + s
        return carry

    jax.lax.fori_loop(0, tc // rows, group, None)


# --- the calls ---

def _coef_block(taps, bias, cf: bool):
    """``taps [K, C]`` and the bias as the rows of one ``[8, C]`` block, or,
    for an operand ``[B, C, S]``, as the columns of one ``[C, 128]``."""
    rows = [taps] + ([] if bias is None else [bias[None]])
    coef = jnp.concatenate(rows)
    if cf:
        return jnp.pad(coef.T, ((0, 0), (0, _LANE - coef.shape[0])))
    return jnp.pad(coef, ((0, _SUB - coef.shape[0]), (0, 0)))


def _params(semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=_VMEM_BUDGET + 8 * 2**20)


def _plan(x, taps, start, cf):
    """``(tile_tokens, tile_c, unit, rows, c0)`` of a call: the tiles, the
    rows of one packed tile of ``x``, the loop's row group, and the channel
    tile the operand begins at."""
    unit = 32 // x.dtype.itemsize
    tt, tc = (choose_tiles_cf if cf else choose_tiles)(
        taps.shape[1], start, x.shape[2 if cf else 1], taps.shape[0],
        x.dtype.itemsize)
    rows = (math.gcd(tc, max(unit, _CHANNEL_GROUP)) if cf
            else math.gcd(tt, max(unit, _ROW_GROUP)))
    return tt, tc, unit, rows, start // tc


def _specs(x, taps, start, cf, order, backwards):
    """The grid and the block specs of a call over ``x``: the operand's
    tile, the tokens in front of it (one packed tile of rows, or one lane
    tile where the tokens fill the lanes; clamped at the first), a tile of
    a dense ``[B, tokens, C]`` array (``y``, ``dy``, ``dx``) and the
    coefficients' block. ``order`` names the grid's axes out of ``b``
    (batch), ``j`` (channel tile), ``t`` (token tile); ``backwards`` walks
    the token tiles from the last."""
    tt, tc, unit, _, c0 = _plan(x, taps, start, cf)
    tokens = x.shape[2 if cf else 1]
    last, per = tokens // tt - 1, tt // (_LANE if cf else unit)
    sizes = {"b": x.shape[0], "j": taps.shape[1] // tc, "t": tokens // tt}

    def spec(shape, index):
        def index_map(*ids):
            at = dict(zip(order, ids))
            if backwards:
                at["t"] = last - at["t"]
            b, tok, ch = index(at)
            return (b, ch, tok) if cf else (b, tok, ch)
        b, tok, ch = shape
        return pl.BlockSpec((b, ch, tok) if cf else (b, tok, ch), index_map)

    halo = _LANE if cf else unit
    operand = spec((1, tt, tc), lambda at: (at["b"], at["t"], c0 + at["j"]))
    before = spec((1, halo, tc), lambda at: (
        at["b"], jnp.maximum(at["t"] * per - 1, 0), c0 + at["j"]))
    dense = spec((1, tt, tc), lambda at: (at["b"], at["t"], at["j"]))
    j = order.index("j")
    coef = (pl.BlockSpec((tc, _LANE), lambda *ids: (ids[j], 0)) if cf
            else pl.BlockSpec((_SUB, tc), lambda *ids: (0, ids[j])))
    return tuple(sizes[a] for a in order), operand, before, dense, coef


@traced_once
def _fwd(x, taps, bias, *, start, dtype, cf, interpret):
    k, c = taps.shape
    tt, tc, _, rows, _ = _plan(x, taps, start, cf)
    grid, operand, before, dense, coef = _specs(x, taps, start, cf, "bjt",
                                                False)
    shape = (x.shape[0], c, x.shape[2]) if cf else (*x.shape[:2], c)
    stage = (tc, _LANE + tt) if cf else (_SUB + tt, tc)
    with kernel_site("short_conv_fwd"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel_cf if cf else _fwd_kernel, taps=k,
                              bias=bias is not None, rows=rows),
            grid=grid, in_specs=[operand, before, coef], out_specs=dense,
            out_shape=jax.ShapeDtypeStruct(shape, dtype),
            scratch_shapes=[pltpu.VMEM(stage, _F32)],
            compiler_params=_params(("parallel",) * 3),
            interpret=interpret,
        )(x, x, _coef_block(taps, bias, cf))


@traced_once
def _bwd(x, taps, bias, dy, *, start, cf, interpret):
    """``dx`` in ``x``'s dtype and ``dy``'s shape, ``dtaps [K, C]`` and
    ``dbias [C]`` (None without a bias) in float32."""
    k, c = taps.shape
    tt, tc, _, rows, _ = _plan(x, taps, start, cf)
    grid, operand, before, dense, coef = _specs(x, taps, start, cf, "jbt",
                                                True)
    parts = k + (bias is not None)
    if cf:
        stage, carry = (tc, _LANE + tt), (tc, _LANE)
        sums = (c, parts * _LANE)
        dcoef = pl.BlockSpec((tc, parts * _LANE), lambda j, b, t: (j, 0))
    else:
        stage, carry = (_SUB + tt, tc), (_SUB, tc)
        sums = (parts * _SUB, c)
        dcoef = pl.BlockSpec((parts * _SUB, tc), lambda j, b, t: (0, j))
    with kernel_site("short_conv_bwd"):
        dx, dcoef = pl.pallas_call(
            functools.partial(_bwd_kernel_cf if cf else _bwd_kernel, taps=k,
                              bias=bias is not None, rows=rows),
            grid=grid, in_specs=[operand, before, dense, coef],
            out_specs=[dense, dcoef],
            out_shape=[jax.ShapeDtypeStruct(dy.shape, x.dtype),
                       jax.ShapeDtypeStruct(sums, _F32)],
            scratch_shapes=[pltpu.VMEM(stage, _F32), pltpu.VMEM(carry, _F32)],
            compiler_params=_params(("parallel", "arbitrary", "arbitrary")),
            interpret=interpret,
        )(x, x, dy, _coef_block(taps, bias, cf))
    dcoef = (dcoef.reshape(c, parts, _LANE).sum(-1).T if cf
             else dcoef.reshape(parts, _SUB, c).sum(1))
    return dx, dcoef[:k], (None if bias is None else dcoef[k])


# --- the differentiable operation ---

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _short_conv(x, taps, bias, start, dtype, cf, interpret):
    return _short_conv_fwd(x, taps, bias, start, dtype, cf, interpret)[0]


def _site(kernel: str, x, taps, bias, start, cf):
    kernel_site(kernel, _choice("pallas", x, taps, bias, cf,
                                _plan(x, taps, start, cf)))


def _short_conv_fwd(x, taps, bias, start, dtype, cf, interpret):
    _site("short_conv_fwd", x, taps, bias, start, cf)
    y = _fwd(x, taps, bias, start=start, dtype=dtype, cf=cf,
             interpret=interpret)
    return y, (x, taps, bias)


def _short_conv_bwd(start, dtype, cf, interpret, residuals, dy):
    x, taps, bias = residuals
    _site("short_conv_bwd", x, taps, bias, start, cf)
    dx, dtaps, dbias = _bwd(x, taps, bias, dy, start=start, cf=cf,
                            interpret=interpret)
    axis = 1 if cf else 2
    beside = x.shape[axis] - start - taps.shape[1]
    if start or beside:       # the channels beside the operand's: no part
        pad = [(0, 0)] * 3
        pad[axis] = (start, beside)
        dx = jnp.pad(dx, pad)
    return dx, dtaps, dbias


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


def causal_conv(x, kernel, bias):
    """Depthwise causal convolution over ``x [B, S, C]`` with
    ``kernel [K, C]`` (tap K - 1 reads the current token) as K shifted
    multiply-adds in float32, plus ``bias``."""
    k, s = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    return bias + sum(padded[:, i:i + s] * kernel[i] for i in range(k))


def short_conv(x, taps, bias=None, *, start: int = 0, dtype=None,
               interpret: bool | None = None):
    """``silu(causal_conv(x[..., start:start + C], taps, bias))`` in
    ``dtype`` (``x``'s unless given: float32 where a float32 pass follows,
    as the L2 norm of Olmo's keys and queries, so that nothing is rounded
    in between): ``x [B, S, >= start + C]``, ``taps [K, C]`` (tap K - 1
    reads the current token), ``bias [C]`` or None. Through the kernels
    where they apply, else ``causal_conv`` differentiated by JAX.

    Which pair runs follows the array's width: XLA keeps an array whose
    last dimension is no lane multiple with its *tokens* minor, so that
    nothing is padded (``in_proj``'s ``[8192, 9280]``, Olmo's keys ``[8192,
    2880]``), and then the pair that reads ``[B, C, S]`` takes a transposed
    view that costs no copy, where the row-major pair would make XLA
    transpose the operand, the result and, beside them, whatever else reads
    the array (PERF.md section 6, PR 39: + 24 ms on Nemotron's scan and
    norm); a lane-multiple width (Olmo's values ``[8192, 5760]``) lies
    row-major and takes the other."""
    k, c = taps.shape
    dtype = jnp.dtype(dtype or x.dtype)
    cf = x.shape[-1] % _LANE != 0
    aligned = 32 // x.dtype.itemsize if cf else _LANE
    if x.shape[-1] != c and (c % aligned or start % aligned):
        x, start = x[..., start:start + c], 0   # no tile lies in place
        cf = c % _LANE != 0
    rule = choose_tiles_cf if cf else choose_tiles
    if x.dtype not in (jnp.bfloat16, jnp.float32) or rule(
            c, start, x.shape[1], k, x.dtype.itemsize) is None:
        x = x[..., start:start + c]
        kernel_site("short_conv_fwd",
                    _choice("jnp", x, taps, bias, False, None))
        return jax.nn.silu(causal_conv(
            x, taps, 0.0 if bias is None else bias)).astype(dtype)
    y = _short_conv(jnp.swapaxes(x, 1, 2) if cf else x, taps.astype(_F32),
                    None if bias is None else bias.astype(_F32), start,
                    dtype, cf, default_interpret(interpret))
    return jnp.swapaxes(y, 1, 2) if cf else y
