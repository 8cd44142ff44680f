"""Pallas TPU kernels: the stream-wide passes of a hyper-connection (mHC).

A sub-layer's mHC (``models/xing4.py``) touches its ``n`` residual streams
``[n, tokens, C]`` four times: the token-wide sum of squares and the
projection onto ``n*n + 2n`` coefficients, the mixed input ``u``, the new
streams, and all of it again backward. Written in ``jnp`` (``pre_jnp`` /
``post_jnp`` below: the plain definition, the fallback and the tests'
oracle) XLA up-casts the bf16 streams to float32 and walks them in many
unfused passes, and multiplies the 4C-wide token row at
``Precision.HIGHEST``: six bf16 passes of the MXU. Here every stream-wide
pass happens once, in a kernel that keeps the streams in their dtype in
HBM and float32 in registers:

- ``pre_fwd``: one read of the streams gives the sum of squares, the raw
  projection and, from the ``n`` input coefficients it needs (a sigmoid of
  the first ``n`` columns, computed in the kernel as ``pre_jnp`` defines
  it), the mixed input ``u``;
- ``post_fwd``: streams, ``y`` and the coefficients in, the new streams out;
- ``post_bwd``: ``dx[j] = sum_i h_res[i, j] dout[i]``,
  ``dy = sum_i h_post[i] dout[i]`` and the coefficients' per-token
  cotangents (``sum_c dout[i] x[j]``, ``sum_c dout[i] y``) in one pass;
- ``pre_bwd``: reduces ``sum_c du x[i]`` (the input coefficients'
  cotangent), takes it through their sigmoid and the norm by hand (the
  few lines of ``_coefficients``, a row group at a time), writes ``dx``
  (the mix, the norm, the projection, plus the cotangent that came in
  through ``post``) and accumulates ``dphi`` over the token tiles in
  float32.

The rest of the 24-numbers-a-token arithmetic (the output coefficients'
sigmoid, Sinkhorn) stays outside, in ``jnp`` and float32.

*Products.* A product whose one operand is exact in bf16 (the streams,
when they are bf16) splits the other, a float32 array, into three bf16
pieces (``a = a0 + a1 + a2`` to the last bit, float32 accumulation): the
float32 result ``HIGHEST`` gives, in three products for its six. The three
pieces stand side by side in the 128 lanes the narrow operand is padded to
anyway (three slots of ``_SLOT`` columns), so the MXU passes once and the
kernel adds the slots. Where the streams are float32 the product stays at
``HIGHEST``. Where neither operand is exact (``g Phi^T`` in ``pre_bwd``,
contracted over the 24 coefficients) the six products ``HIGHEST`` is made
of stand in six slots along the contraction: two passes. This reads the
dtype the call sees; there is no switch.

*Tiles* (``choose_tiles``) come from the shape: the ``pre`` kernels hold
the whole width of a token tile (their reductions run over it), the
element-wise ``post`` kernels tile the width too; inside a grid step a loop
walks the tile a few rows at a time, so that nothing wider than a row
group is live in float32. A width that is no lane multiple, tokens that no
row unit divides, or more coefficients than a slot holds fall back to the
``jnp`` formulation. Every call site counts what it was built with into
the registry (``mhc.kernel_choice``), as ``attn.tile_choice`` does for
flash. The calls themselves are jitted functions (``_traced_once``): a
model's sub-layers share shapes, so each kernel is traced and lowered once
a program and not once a site.
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
    divisors as _divisors,
    kernel_site,
    traced_once as _traced_once,
)

_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
# lanes a coefficient group takes in the [tokens, 128] side arrays: the
# three bf16 pieces of a projection stand in slots 0, 1, 2
_SLOT = 32
_SS_LANE = _LANE - 1          # where ``pre_fwd`` leaves the sum of squares
# What one grid step may hold in VMEM (v5e: 128 MiB on the core; Mosaic
# scopes 16 MiB to a kernel unless told otherwise)
_VMEM_BUDGET = 48 * 2**20
# No tile grows past these. Raced on a v5e at 4 x 8192 x 3584 in bf16
# (PERF.md §6, PR 30): token tiles of 128 to 512 and width tiles of 512 to
# 896 read the same to 2 % in every kernel, 64 tokens 3-8 % and 32 tokens
# 10-20 % slower; the smaller tile is the smaller program to compile
_TOKEN_CAP = 128
_WIDTH_CAP = 1024


# --- the plain definition: fallback and oracle ---

def _coefficients(raw, ss, alpha_pre, b_pre, *, n, c, eps):
    """``raw [K, T]`` (the streams' token row times Phi) and the token-wide
    sum of squares ``ss [T]`` -> the normalised projection ``[K, T]`` and
    the input coefficients ``h_pre [n, T]``, in float32."""
    inv = jax.lax.rsqrt(ss / (n * c) + eps)
    proj = raw * inv
    return proj, jax.nn.sigmoid(alpha_pre * proj[:n] + b_pre[:, None])


def pre_jnp(streams, phi, alpha_pre, b_pre, *, eps, dtype):
    """``streams [n, ..., C]``, ``phi [n, C, K]`` (the input coefficients'
    columns first) -> the mixed input ``u [..., C]`` in ``dtype``, the
    normalised projection ``[K, ...]`` in float32 (the coefficient index
    leads, tokens fill the lanes), and the streams as they came."""
    n, c = streams.shape[0], streams.shape[-1]
    x = streams.astype(_F32)
    ss = jnp.sum(jnp.square(x), (0, -1))
    raw = jnp.einsum("n...c,nck->k...", x, phi, precision=_HIGHEST)
    lead = ss.shape
    proj, h_pre = _coefficients(raw.reshape(-1, ss.size), ss.reshape(-1),
                                alpha_pre, b_pre, n=n, c=c, eps=eps)
    h_pre = h_pre.reshape(n, *lead)
    u = sum(h_pre[i][..., None] * x[i] for i in range(n))
    return u.astype(dtype), proj.reshape(-1, *lead), streams


def post_jnp(streams, y, h_res, h_post):
    """``X'[i] = sum_j h_res[i, j] X[j] + h_post[i] y`` in float32, in the
    streams' dtype."""
    n = streams.shape[0]
    x, y = streams.astype(_F32), y.astype(_F32)
    return jnp.stack([
        sum(h_res[i, j][..., None] * x[j] for j in range(n))
        + h_post[i][..., None] * y for i in range(n)
    ]).astype(streams.dtype)


# --- tiles ---

def _vmem_bytes(kernel: str, n: int, c: int, tt: int, tc: int,
                itemsize: int) -> int:
    """Bytes of VMEM one grid step of ``kernel`` holds: its double-buffered
    blocks (stream-wide rows of the tile, the [tokens, 128] side arrays),
    Phi (held once: its block never moves) and, in ``pre_bwd``, Phi's
    gradient and the float32 scratch."""
    row, side = tt * tc * itemsize, tt * _LANE * 4
    wide = n * _LANE * c * 4           # Phi^T's six bf16 slots; dPhi^T
    if kernel == "pre_fwd":
        return 2 * ((n + 1) * row + side) + wide * itemsize // 4
    if kernel == "pre_bwd":
        return (2 * ((3 * n + 1) * row + 3 * side + wide) + wide + 2 * side
                + tt * c * 4)
    rows = {"post_fwd": 2 * n + 1, "post_bwd": 3 * n + 2}[kernel]
    return 2 * (rows * row + 2 * side)


def choose_tiles(kernel: str, n: int, c: int, tokens: int, itemsize: int,
                 *, budget: int = _VMEM_BUDGET) -> tuple[int, int] | None:
    """``(tile_tokens, tile_c)`` for one of the kernels, from what the call
    can see, or None where the kernels do not apply (the caller falls back
    to ``jnp``): a width that is no lane multiple, tokens that the rows of
    one packed tile (8 float32, 16 bf16) do not divide, more coefficients
    than a slot of the side arrays holds. The ``pre`` kernels take the whole
    width; the others the largest lane-multiple divisor up to
    ``_WIDTH_CAP``. The token tile is the largest divisor of the tokens up
    to ``_TOKEN_CAP`` whose ``_vmem_bytes`` fits ``budget``."""
    unit = 32 // itemsize
    if c % _LANE or tokens % unit or n * n + 2 * n > _SLOT:
        return None
    whole = kernel in ("pre_fwd", "pre_bwd")
    tc = c if whole else _divisors(c, _LANE, _WIDTH_CAP)[0]
    for tt in _divisors(tokens, unit, _TOKEN_CAP):
        if _vmem_bytes(kernel, n, c, tt, tc, itemsize) <= budget:
            return tt, tc
    return None


def _pieces(x) -> int:
    """The bf16 pieces a float32 factor of the streams ``x`` is split into:
    three where the streams are exact in bf16, one (float32, multiplied at
    ``HIGHEST``) where they are not."""
    return 3 if x.dtype == jnp.bfloat16 else 1


def _count(kernel: str, x):
    """One count in the always-on registry of what a call site of ``kernel``
    over the streams ``x [n, tokens, C]`` is built with: its tiles, and
    ``passes``, the bf16 products its matrix products take (three where a
    factor is exact in bf16, six at ``HIGHEST``). Counted at the site,
    outside the jitted call; the kernel's ``trace:kernel`` span opens inside
    it, once a shape (``kernel_site``)."""
    from tpu_sandbox.obs import get_registry

    n, tokens, c = x.shape
    split = 3 if _pieces(x) == 3 else 6
    passes = {"pre_fwd": split, "pre_bwd": split + 6}.get(kernel, 0)
    tt, tc = (0, 0) if kernel == "fallback" else choose_tiles(
        kernel, n, c, tokens, x.dtype.itemsize)
    kernel_site("mhc_" + kernel, get_registry().counter(
        "mhc.kernel_choice", labels={
            "kernel": kernel, "n": n, "c": c, "tokens": tokens,
            "tile_tokens": tt, "tile_c": tc, "passes": passes}))


def _call(body, *, grid, in_specs, out_specs, out_shape, interpret,
          aliases=None, scratch=()):
    return pl.pallas_call(
        body, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, input_output_aliases=aliases or {},
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            # the token axis carries dPhi in ``pre_bwd``, the width axis the
            # coefficients' cotangents in the two reductions
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=_VMEM_BUDGET + 16 * 2**20),
        interpret=interpret,
    )


# --- small helpers of the kernels ---

def _split3(a):
    """float32 ``a`` as three bf16 pieces with ``a0 + a1 + a2 == a``."""
    a0 = a.astype(jnp.bfloat16)
    r = a - a0.astype(_F32)
    a1 = r.astype(jnp.bfloat16)
    return a0, a1, (r - a1.astype(_F32)).astype(jnp.bfloat16)


def _slots(a, pieces: int):
    """``a [..., K]`` float32 -> ``[..., 128]``: as it is in slot 0
    (``pieces`` 1, float32) or its three bf16 pieces in slots 0, 1, 2."""
    k = a.shape[-1]
    parts = (a,) if pieces == 1 else _split3(a)
    pad = [(0, 0)] * (a.ndim - 1)
    out = jnp.concatenate(
        [jnp.pad(p, pad + [(0, _SLOT - k)]) for p in parts], -1)
    return jnp.pad(out, pad + [(0, _LANE - out.shape[-1])])


def _side(rows):
    """``rows [k, T]`` float32 -> the ``[T, 128]`` side array whose column
    ``j`` is row ``j``: tokens on the sublanes, as the streams have them."""
    return jnp.pad(rows.T, ((0, 0), (0, _LANE - rows.shape[0])))


def _scalars(alpha_pre, n: int, c: int, eps: float):
    """What the ``pre`` kernels read from SMEM: alpha, 1 / (n C), eps."""
    return jnp.stack([alpha_pre.astype(_F32), jnp.asarray(1.0 / (n * c), _F32),
                      jnp.asarray(eps, _F32)])


def _b_row(b_pre):
    return jnp.pad(b_pre.astype(_F32), (0, _LANE - b_pre.size))[None]


def _row_groups(tt: int, rg: int, body):
    """Run ``body(rows)`` over the token tile ``rg`` rows at a time."""
    def step(r, carry):
        body(pl.ds(pl.multiple_of(r * rg, rg), rg))
        return carry

    jax.lax.fori_loop(0, tt // rg, step, None)


def _col(h, j: int):
    return h[:, j:j + 1]


def _mix(h, cols, xs):
    """``sum_k h[:, cols[k]] * xs[k]``, left to right as ``jnp``'s ``sum``."""
    acc = _col(h, cols[0]) * xs[0]
    for k, x in zip(cols[1:], xs[1:]):
        acc = acc + _col(h, k) * x
    return acc


def _place(lane, entries):
    """A ``[rows, 128]`` array holding ``value [rows, 1]`` in lane ``j`` for
    each ``(j, value)``, zero elsewhere."""
    out = jnp.zeros(lane.shape, _F32)
    for j, value in entries:
        out = jnp.where(lane == j, value, out)
    return out


def _lane_iota(rg: int):
    return jax.lax.broadcasted_iota(jnp.int32, (rg, _LANE), 1)


def _rowsum(a):
    return jnp.sum(a, axis=-1, keepdims=True)


# --- pre, forward ---

def _pre_fwd_kernel(scalars_ref, b_ref, x_ref, phi_ref, u_ref, stats_ref, *,
                    n, pieces, rg):
    tt = x_ref.shape[1]
    precision = _HIGHEST if pieces == 1 else None
    acc = None
    for i in range(n):
        part = jnp.dot(x_ref[i], phi_ref[i], preferred_element_type=_F32,
                       precision=precision)
        acc = part if acc is None else acc + part
    if pieces == 3:                  # slot 0 + slot 1 + slot 2 into slot 0
        acc = (acc + pltpu.roll(acc, _LANE - _SLOT, 1)
               + pltpu.roll(acc, _LANE - 2 * _SLOT, 1))
    stats_ref[...] = acc
    alpha, inv_nc, eps = scalars_ref[0], scalars_ref[1], scalars_ref[2]

    def rows(sl):
        xs = [x_ref[i, sl, :].astype(_F32) for i in range(n)]
        ss = _rowsum(xs[0] * xs[0])
        for x in xs[1:]:
            ss = ss + _rowsum(x * x)
        raw = stats_ref[sl, :]
        # ``_coefficients``, on the lanes of the input coefficients
        proj = raw * jax.lax.rsqrt(ss * inv_nc + eps)
        h = jax.nn.sigmoid(alpha * proj + b_ref[...])
        u_ref[sl, :] = _mix(h, range(n), xs).astype(u_ref.dtype)
        lane = _lane_iota(rg)
        stats_ref[sl, :] = jnp.where(
            lane == _SS_LANE, ss, jnp.where(lane < _SLOT, raw, 0.0))

    _row_groups(tt, rg, rows)


@_traced_once
def _pre_fwd(x, phi, alpha_pre, b_pre, *, eps, dtype, interpret):
    """``x [n, T, C]`` -> ``u [T, C]`` and ``stats [T, 128]`` float32 (the
    raw projection in its first K lanes, the sum of squares in the last)."""
    n, tokens, c = x.shape
    itemsize = x.dtype.itemsize
    pieces = _pieces(x)
    tt, _ = choose_tiles("pre_fwd", n, c, tokens, itemsize)
    with kernel_site("mhc_pre_fwd"):
        return _call(
            functools.partial(_pre_fwd_kernel, n=n, pieces=pieces,
                              rg=32 // itemsize),
            grid=(tokens // tt,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, _LANE), lambda t: (0, 0)),
                pl.BlockSpec((n, tt, c), lambda t: (0, t, 0)),
                pl.BlockSpec((n, c, _LANE), lambda t: (0, 0, 0),
                             pipeline_mode=pl.Buffered(1)),
            ],
            out_specs=[pl.BlockSpec((tt, c), lambda t: (t, 0)),
                       pl.BlockSpec((tt, _LANE), lambda t: (t, 0))],
            out_shape=[jax.ShapeDtypeStruct((tokens, c), dtype),
                       jax.ShapeDtypeStruct((tokens, _LANE), _F32)],
            interpret=interpret,
        )(_scalars(alpha_pre, n, c, eps), _b_row(b_pre), x,
          _slots(phi, pieces))


# --- post, forward and backward ---

def _post_fwd_kernel(x_ref, y_ref, h_ref, o_ref, *, n, rg):
    def rows(sl):
        h = h_ref[sl, :]
        xs = [x_ref[j, sl, :].astype(_F32) for j in range(n)]
        y = y_ref[sl, :].astype(_F32)
        for i in range(n):
            o_ref[i, sl, :] = (
                _mix(h, range(i * n, i * n + n), xs)
                + _col(h, n * n + i) * y).astype(o_ref.dtype)

    _row_groups(x_ref.shape[1], rg, rows)


def _post_bwd_kernel(x_ref, y_ref, g_ref, h_ref, dx_ref, dy_ref, dh_ref, *,
                     n, rg):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)

    def rows(sl):
        h = h_ref[sl, :]
        xs = [x_ref[j, sl, :].astype(_F32) for j in range(n)]
        y = y_ref[sl, :].astype(_F32)
        gs = [g_ref[i, sl, :].astype(_F32) for i in range(n)]
        for j in range(n):
            dx_ref[j, sl, :] = _mix(
                h, range(j, n * n, n), gs).astype(dx_ref.dtype)
        dy_ref[sl, :] = _mix(
            h, range(n * n, n * n + n), gs).astype(dy_ref.dtype)
        dots = [(i * n + j, _rowsum(gs[i] * xs[j]))
                for i in range(n) for j in range(n)]
        dots += [(n * n + i, _rowsum(gs[i] * y)) for i in range(n)]
        dh_ref[sl, :] = dh_ref[sl, :] + _place(_lane_iota(rg), dots)

    _row_groups(x_ref.shape[1], rg, rows)


def _post_specs(n, tt, tc):
    wide = pl.BlockSpec((n, tt, tc), lambda t, k: (0, t, k))
    row = pl.BlockSpec((tt, tc), lambda t, k: (t, k))
    side = pl.BlockSpec((tt, _LANE), lambda t, k: (t, 0))
    return wide, row, side


@_traced_once
def _post_fwd(x, y, coef, *, interpret):
    n, tokens, c = x.shape
    itemsize = x.dtype.itemsize
    tt, tc = choose_tiles("post_fwd", n, c, tokens, itemsize)
    wide, row, side = _post_specs(n, tt, tc)
    with kernel_site("mhc_post_fwd"):
        return _call(
            functools.partial(_post_fwd_kernel, n=n, rg=32 // itemsize),
            grid=(tokens // tt, c // tc), in_specs=[wide, row, side],
            out_specs=wide, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interpret,
        )(x, y, coef)


@_traced_once
def _post_bwd(x, y, g, coef, *, interpret):
    n, tokens, c = x.shape
    itemsize = x.dtype.itemsize
    tt, tc = choose_tiles("post_bwd", n, c, tokens, itemsize)
    wide, row, side = _post_specs(n, tt, tc)
    with kernel_site("mhc_post_bwd"):
        return _call(
            functools.partial(_post_bwd_kernel, n=n, rg=32 // itemsize),
            grid=(tokens // tt, c // tc), in_specs=[wide, row, wide, side],
            out_specs=[wide, row, side],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct(y.shape, y.dtype),
                       jax.ShapeDtypeStruct((tokens, _LANE), _F32)],
            interpret=interpret,
        )(x, y, g, coef)


# --- pre, backward ---

def _pre_bwd_kernel(scalars_ref, b_ref, x_ref, du_ref, dxt_ref, stats_ref,
                    dproj_ref, phit_ref, dx_ref, dphit_ref, dz_ref,
                    g_scr, h_scr, prod_scr, *, n, pieces, rg):
    @pl.when(pl.program_id(0) == 0)
    def _():
        dphit_ref[...] = jnp.zeros_like(dphit_ref)

    alpha, inv_nc, eps = scalars_ref[0], scalars_ref[1], scalars_ref[2]

    def coefficients(sl):
        """The input coefficients again, as ``pre_fwd`` made them, and
        their cotangent back to the raw projection ``g`` and to the sum of
        squares: ``_coefficients`` differentiated by hand, a row group."""
        lane = _lane_iota(rg)
        du = du_ref[sl, :].astype(_F32)
        dh = _place(lane, [(i, _rowsum(du * x_ref[i, sl, :].astype(_F32)))
                           for i in range(n)])
        stats = stats_ref[sl, :]
        raw = jnp.where(lane < _SLOT, stats, 0.0)
        inv = jax.lax.rsqrt(stats[:, _SS_LANE:] * inv_nc + eps)
        h = jax.nn.sigmoid(alpha * (raw * inv) + b_ref[...])
        dz = jnp.where(lane < n, dh * h * (1.0 - h), 0.0)
        dproj = dproj_ref[sl, :] + alpha * dz
        dss = _rowsum(dproj * raw) * (-0.5 * inv_nc) * (inv * inv * inv)
        g_scr[sl, :] = dproj * inv
        h_scr[sl, :] = jnp.where(lane == n, 2.0 * dss, h)
        dz_ref[sl, :] = dz

    _row_groups(x_ref.shape[1], rg, coefficients)
    # g Phi^T: neither operand is exact in bf16, so the six products of
    # HIGHEST (g0 p0, g0 p1, g1 p0, g0 p2, g1 p1, g2 p0), side by side along
    # the contraction: six slots of g against six slots of Phi^T's rows
    g = [p.astype(_F32) for p in _split3(g_scr[...])]

    def slots(*pieces_in_slots):
        out = pieces_in_slots[0]
        for k, piece in enumerate(pieces_in_slots[1:], 1):
            out = out + pltpu.roll(piece, k * _SLOT, 1)
        return out.astype(jnp.bfloat16)

    lo, hi = slots(g[0], g[0], g[1], g[0]), slots(g[1], g[2])
    # dPhi^T += g^T x: the streams exact in bf16, g in its three pieces
    gs, precision = ((slots(*g), None) if pieces == 3
                     else (g_scr[...], _HIGHEST))
    for i in range(n):
        dphit_ref[i] = dphit_ref[i] + jax.lax.dot_general(
            gs, x_ref[i], (((0,), (0,)), ((), ())),
            preferred_element_type=_F32, precision=precision)
        prod_scr[...] = (
            jnp.dot(lo, phit_ref[i, :_LANE], preferred_element_type=_F32)
            + jnp.dot(hi, phit_ref[i, _LANE:], preferred_element_type=_F32))

        def rows(sl, i=i):
            h = h_scr[sl, :]
            dx = (dxt_ref[i, sl, :].astype(_F32)
                  + _col(h, i) * du_ref[sl, :].astype(_F32)
                  + _col(h, n) * x_ref[i, sl, :].astype(_F32)
                  + prod_scr[sl, :])
            dx_ref[i, sl, :] = dx.astype(dx_ref.dtype)

        _row_groups(x_ref.shape[1], rg, rows)


def _six_slots(phi):
    """``phi [n, C, K]`` float32 -> ``[n, 256, C]`` bf16: the pieces
    ``p0, p1, p0, p2, p1, p0`` of ``phi^T`` in six slots of rows, which meet
    ``g0, g0, g1, g0, g1, g2`` in ``_pre_bwd_kernel``."""
    p = _split3(jnp.swapaxes(phi, 1, 2))
    rows = [jnp.pad(p[j], ((0, 0), (0, _SLOT - phi.shape[-1]), (0, 0)))
            for j in (0, 1, 0, 2, 1, 0)]
    return jnp.pad(jnp.concatenate(rows, 1),
                   ((0, 0), (0, 2 * _LANE - 6 * _SLOT), (0, 0)))


@_traced_once
def _pre_bwd(x, phi, alpha_pre, b_pre, stats, du, dproj, dxt, *, eps,
             interpret):
    """``dx [n, T, C]``, ``dphi [n, C, K]`` and ``dz [T, n]``, the cotangent
    of what the input coefficients' sigmoid is taken of."""
    n, tokens, c = x.shape
    k = phi.shape[-1]
    itemsize = x.dtype.itemsize
    pieces = _pieces(x)
    tt, _ = choose_tiles("pre_bwd", n, c, tokens, itemsize)
    wide = pl.BlockSpec((n, tt, c), lambda t: (0, t, 0))
    side = pl.BlockSpec((tt, _LANE), lambda t: (t, 0))
    with kernel_site("mhc_pre_bwd"):
        dx, dphit, dz = _call(
            functools.partial(_pre_bwd_kernel, n=n, pieces=pieces,
                              rg=32 // itemsize),
            grid=(tokens // tt,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, _LANE), lambda t: (0, 0)),
                wide, pl.BlockSpec((tt, c), lambda t: (t, 0)), wide, side,
                side,
                pl.BlockSpec((n, 2 * _LANE, c), lambda t: (0, 0, 0),
                             pipeline_mode=pl.Buffered(1)),
            ],
            out_specs=[wide, pl.BlockSpec((n, _LANE, c), lambda t: (0, 0, 0)),
                       side],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((n, _LANE, c), _F32),
                       jax.ShapeDtypeStruct((tokens, _LANE), _F32)],
            interpret=interpret, aliases={4: 0},
            scratch=[pltpu.VMEM((tt, _LANE), _F32),
                     pltpu.VMEM((tt, _LANE), _F32),
                     pltpu.VMEM((tt, c), _F32)],
        )(_scalars(alpha_pre, n, c, eps), _b_row(b_pre), x, du, dxt, stats,
          _side(dproj), _six_slots(phi))
    dphit = dphit[:, :pieces * _SLOT].reshape(n, pieces, _SLOT, c).sum(1)
    return dx, jnp.swapaxes(dphit[:, :k], 1, 2), dz[:, :n]


# --- the differentiable operations ---

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _pre(x, phi, alpha_pre, b_pre, eps, dtype, interpret):
    return _pre_vjp_fwd(x, phi, alpha_pre, b_pre, eps, dtype, interpret)[0]


def _pre_vjp_fwd(x, phi, alpha_pre, b_pre, eps, dtype, interpret):
    n, _, c = x.shape
    _count("pre_fwd", x)
    u, stats = _pre_fwd(x, phi, alpha_pre, b_pre, eps=eps, dtype=dtype,
                        interpret=interpret)
    proj, _ = _coefficients(stats[:, :phi.shape[-1]].T, stats[:, _SS_LANE],
                            alpha_pre, b_pre, n=n, c=c, eps=eps)
    return (u, proj, x), (x, phi, alpha_pre, b_pre, stats, proj[:n])


def _pre_vjp_bwd(eps, dtype, interpret, residuals, cotangents):
    x, phi, alpha_pre, b_pre, stats, proj_in = residuals
    du, dproj, dxt = cotangents
    _count("pre_bwd", x)
    dx, dphi, dz = _pre_bwd(x, phi, alpha_pre, b_pre, stats, du, dproj, dxt,
                            eps=eps, interpret=interpret)
    # h_pre = sigmoid(alpha_pre proj_in + b_pre), and dz is its argument's
    return dx, dphi, jnp.sum(dz.T * proj_in), jnp.sum(dz, 0)


_pre.defvjp(_pre_vjp_fwd, _pre_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _post(x, y, h_res, h_post, interpret):
    return _post_vjp_fwd(x, y, h_res, h_post, interpret)[0]


def _post_vjp_fwd(x, y, h_res, h_post, interpret):
    coef = _side(jnp.concatenate([h_res, h_post]))
    _count("post_fwd", x)
    return _post_fwd(x, y, coef, interpret=interpret), (x, y, coef)


def _post_vjp_bwd(interpret, residuals, g):
    x, y, coef = residuals
    nn = x.shape[0] ** 2
    _count("post_bwd", x)
    dx, dy, dh = _post_bwd(x, y, g, coef, interpret=interpret)
    return dx, dy, dh[:, :nn].T, dh[:, nn:nn + x.shape[0]].T


_post.defvjp(_post_vjp_fwd, _post_vjp_bwd)

def _applies(kernels, streams, *others) -> bool:
    """Whether the kernels take this call: bf16 or float32 streams (and the
    narrow rows beside them in the same dtype) of a shape every one of
    ``kernels`` finds tiles for; else one ``fallback`` is counted."""
    n, c = streams.shape[0], streams.shape[-1]
    tokens = math.prod(streams.shape[1:-1])
    ok = (streams.dtype in (jnp.bfloat16, jnp.float32)
          and all(o == streams.dtype for o in others)
          and all(choose_tiles(k, n, c, tokens, streams.dtype.itemsize)
                  for k in kernels))
    if not ok:
        _count("fallback", jax.ShapeDtypeStruct((n, tokens, c), streams.dtype))
    return ok


def pre(streams, phi, alpha_pre, b_pre, *, eps: float, dtype,
        interpret: bool | None = None):
    """``pre_jnp`` through the kernels where they apply. The streams come
    back as a result so that ``post`` can read that copy: its cotangent
    then enters ``pre``'s backward kernel and is added there, where the
    streams' cotangent is written anyway, not in a pass of its own."""
    if not _applies(("pre_fwd", "pre_bwd"), streams, jnp.dtype(dtype)):
        return pre_jnp(streams, phi, alpha_pre, b_pre, eps=eps, dtype=dtype)
    n, *lead, c = streams.shape
    u, proj, thru = _pre(streams.reshape(n, -1, c), phi.astype(_F32),
                         alpha_pre, b_pre, eps, jnp.dtype(dtype),
                         default_interpret(interpret))
    return (u.reshape(*lead, c), proj.reshape(-1, *lead),
            thru.reshape(streams.shape))


def post(streams, y, h_res, h_post, *, interpret: bool | None = None):
    """``post_jnp`` through the kernels where they apply."""
    if not _applies(("post_fwd", "post_bwd"), streams, y.dtype):
        return post_jnp(streams, y, h_res, h_post)
    n, *lead, c = streams.shape
    out = _post(streams.reshape(n, -1, c), y.reshape(-1, c),
                h_res.reshape(n * n, -1).astype(_F32),
                h_post.reshape(n, -1).astype(_F32),
                default_interpret(interpret))
    return out.reshape(streams.shape)
