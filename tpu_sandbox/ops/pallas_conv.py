"""Pallas TPU 3x3 SAME conv for the space-to-depth ConvNet — fwd, dgrad,
wgrad, ~one HBM pass each.

Why XLA's conv is the wrong tool here (tools/hlo_traffic.py on the
AOT-compiled s2d train step, bs=16): XLA:TPU lowers each conv through a
materialized packed ("im2col") copy of its input, so conv1 fwd alone moves
~16 GB/step — a 2.3 GB (lane-padded) input read, a 4.6 GB packed write,
a 4.6 GB packed read, and the 4.6 GB output write — and the backward pass
repeats the pattern for wgrad. The convolution itself is nine shifted
[pixels, C] x [C, CO] matmuls; no packed copy needs to exist:

- **fwd**: read the input ~once, write the output once. Bias add fused.
- **wgrad**: read input + cotangent ~once each, accumulate all nine
  [C, CO] tap gradients in VMEM scratch across the (sequential) grid,
  dbias fused into the same pass.
- **dgrad** is the same 3x3 SAME conv with spatially-flipped,
  ci/co-transposed weights — it reuses the fwd kernel.

Halo handling: the grid walks row-blocks of ``block_h`` rows. The H-edge
neighbors come in as two extra single-row BlockSpecs whose index maps
CLAMP to the image (rows are re-read, so the input costs (bh+2)/bh ~ 1.2
passes, not 3); at the top/bottom image edge the kernel zero-masks the
halo row, which makes SAME zero-padding exact. The W-direction pad is a
zero-column concatenate inside the kernel. Everything rides the standard
pipelined BlockSpec path — the first cut of this kernel DMA'd
[bh+2, W, C] strips from a ``pl.ANY`` ref instead and died in Mosaic
("slice shape along dimension 2 must be aligned to tiling (8), but is
750"): manual memref slices need 8-aligned extents, pipelined block
delivery does not.

Numerics: accumulation in f32 via preferred_element_type regardless of
the (bf16) activation dtype, bias added in f32, one rounding to the
output dtype — at least as accurate as the lax.conv_general_dilated call
it replaces (tests/test_pallas_conv.py pins equality to the jnp
reference; the s2d model equality tests pin the end-to-end plan).

Used by models/convnet_s2d.py ``_Conv`` when ``ConvNetS2D(fused_conv=
True)`` (the TPU default via ``pick_convnet``, like ``fused_tail``).
Reference being accelerated: the two 5x5 convs of
/root/reference/mnist_onegpu.py:11-31, s2d-scattered to 3x3 (see
models/convnet_s2d.py docstring).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_sandbox.ops.pallas_common import default_interpret, kernel_site


def _pick_block_h(h: int, w: int, c: int, co: int) -> int:
    """Rows per grid block, budgeted against scoped VMEM (16 MB): the
    pipeline double-buffers the bh·W·(C + CO) in/out blocks and the row
    loop keeps ~a [W, 9C] bf16 tap tile plus [W, CO] f32 accumulators
    live. bh=10 at conv1-wgrad's 750-wide 16->256 shape hit 21.9 MB on
    the Mosaic stack (chipless AOT compile); this budget lands it at 3."""
    per_bh = w * (c + co) * 2 * 2            # double-buffered blocks, bf16
    per_row = w * (9 * c + co) * 4           # tap tile + f32 row buffers
    cap = max(1, int(7_000_000 // max(per_bh + per_row, 1)))
    for bh in (10, 8, 6, 5, 4, 3, 2, 1):
        if bh <= cap and h % bh == 0:
            return bh
    return 1


def _shift_w(row, dx: int):
    """row [W, C] -> the dx-tap's view: row shifted by (dx-1) pixels with
    zero columns entering at the W edge (SAME padding, W direction)."""
    if dx == 1:
        return row
    zero = jnp.zeros_like(row[:1])
    if dx == 0:
        return jnp.concatenate([zero, row[:-1]], axis=0)
    return jnp.concatenate([row[1:], zero], axis=0)


def _halo_specs(bh: int, nblk: int, w: int, c: int):
    """Body block + clamped single-row halo blocks above and below."""
    return [
        pl.BlockSpec((1, bh, w, c), lambda n, i: (n, i, 0, 0)),
        pl.BlockSpec((1, 1, w, c),
                     lambda n, i: (n, jnp.maximum(i * bh - 1, 0), 0, 0)),
        pl.BlockSpec((1, 1, w, c),
                     lambda n, i: (n, jnp.minimum(i * bh + bh, nblk * bh - 1),
                                   0, 0)),
    ]


def _row_getter(x_ref, up_ref, dn_ref, bh: int, nblk: int):
    """Row r_in of the (bh+2)-row halo'd strip, r_in in [-1, bh]; the
    out-of-image halo rows read the clamped neighbor block and are
    zero-masked (exact SAME padding at the H edges)."""
    i = pl.program_id(1)

    def get(r_in: int):
        if r_in == -1:
            return jnp.where(i > 0, up_ref[0, 0], 0)
        if r_in == bh:
            return jnp.where(i < nblk - 1, dn_ref[0, 0], 0)
        return x_ref[0, r_in]

    return get


def _tap_tile(get, r: int):
    """The row's im2col tile [W, 9·C], built in VMEM (lane concatenates —
    VPU work, zero HBM cost). Tap order (dy, dx) major then C, matching
    the [9C, CO] flattening of w. One [W, 9C] x [9C, CO] matmul then runs
    the MXU at K = 9C (K=144 for conv1) instead of nine K=C matmuls —
    at C=16, nine separate taps would leave 7/8 of the MXU's contraction
    rows idle and make the kernel compute-bound."""
    return jnp.concatenate(
        [_shift_w(get(r + dy - 1), dx)
         for dy in range(3) for dx in range(3)],
        axis=1,
    )


def _conv_row(get, w_ref, b_ref, r: int):
    acc = jax.lax.dot_general(
        _tap_tile(get, r), w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return acc + b_ref[...].astype(jnp.float32)  # [1, CO] broadcasts over W


def _fwd_kernel(x_ref, up_ref, dn_ref, w_ref, b_ref, y_ref,
                *, bh: int, nblk: int):
    get = _row_getter(x_ref, up_ref, dn_ref, bh, nblk)
    for r in range(bh):
        y_ref[0, r] = _conv_row(get, w_ref, b_ref, r).astype(y_ref.dtype)


def _fwd_stats_kernel(x_ref, up_ref, dn_ref, w_ref, b_ref,
                      y_ref, s_ref, ss_ref, s_scr, ss_scr,
                      *, bh: int, nblk: int):
    """fwd + per-lane sum/sumsq of the ROUNDED output accumulated across
    the sequential grid — the BN-stats pass for free (the unfused chain
    computes batch statistics from the stored activation-dtype y, so the
    sums must see the rounded values too)."""
    n, i = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _init():
        s_scr[:] = jnp.zeros_like(s_scr)
        ss_scr[:] = jnp.zeros_like(ss_scr)

    get = _row_getter(x_ref, up_ref, dn_ref, bh, nblk)
    for r in range(bh):
        y_row = _conv_row(get, w_ref, b_ref, r).astype(y_ref.dtype)
        y_ref[0, r] = y_row
        yf = y_row.astype(jnp.float32)
        s_scr[:] = s_scr[:] + jnp.sum(yf, axis=0, keepdims=True)
        ss_scr[:] = ss_scr[:] + jnp.sum(yf * yf, axis=0, keepdims=True)

    @pl.when(jnp.logical_and(n == pl.num_programs(0) - 1, i == nblk - 1))
    def _emit():
        s_ref[...] = s_scr[:]
        ss_ref[...] = ss_scr[:]


def _wgrad_kernel(x_ref, up_ref, dn_ref, g_ref, dw_ref, db_ref,
                  dw_scr, db_scr, *, bh: int, nblk: int):
    n, i = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    get = _row_getter(x_ref, up_ref, dn_ref, bh, nblk)
    for r in range(bh):
        g_row = g_ref[0, r].astype(jnp.float32)  # [W, CO]
        db_scr[:] = db_scr[:] + jnp.sum(g_row, axis=0, keepdims=True)
        dw_scr[:] = dw_scr[:] + jax.lax.dot_general(
            _tap_tile(get, r), g_row,  # contract W: [9C, CO], K=W on MXU
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jnp.logical_and(n == pl.num_programs(0) - 1, i == nblk - 1))
    def _emit():
        dw_ref[...] = dw_scr[:]
        db_ref[...] = db_scr[:]


def _conv_call(x, w, bias, out_dtype, interpret, stats=False):
    n, h, wd, c = x.shape
    co = w.shape[-1]
    bh = _pick_block_h(h, wd, c, co)
    nblk = h // bh
    if stats:
        kernel = functools.partial(_fwd_stats_kernel, bh=bh, nblk=nblk)
        out_shape = (jax.ShapeDtypeStruct((n, h, wd, co), out_dtype),
                     jax.ShapeDtypeStruct((1, co), jnp.float32),
                     jax.ShapeDtypeStruct((1, co), jnp.float32))
        out_specs = (
            pl.BlockSpec((1, bh, wd, co), lambda n, i: (n, i, 0, 0)),
            pl.BlockSpec((1, co), lambda n, i: (0, 0)),
            pl.BlockSpec((1, co), lambda n, i: (0, 0)),
        )
        scratch = [pltpu.VMEM((1, co), jnp.float32),
                   pltpu.VMEM((1, co), jnp.float32)]
    else:
        kernel = functools.partial(_fwd_kernel, bh=bh, nblk=nblk)
        out_shape = jax.ShapeDtypeStruct((n, h, wd, co), out_dtype)
        out_specs = pl.BlockSpec((1, bh, wd, co), lambda n, i: (n, i, 0, 0))
        scratch = []
    with kernel_site("conv_fwd"):
        return pl.pallas_call(
            kernel,
            out_shape=out_shape,
            grid=(n, nblk),
            in_specs=_halo_specs(bh, nblk, wd, c) + [
                pl.BlockSpec((9 * c, co), lambda n, i: (0, 0)),
                pl.BlockSpec((1, co), lambda n, i: (0, 0)),
            ],
            out_specs=out_specs,
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
            ),
            interpret=default_interpret(interpret),
        )(x, x, x, w.reshape(9 * c, co), bias.reshape(1, co))


def _flip_transpose(w):
    """fwd weights -> dgrad weights: spatial flip + ci/co transpose (the
    transpose of a stride-1 SAME conv is the same conv with these)."""
    return w[::-1, ::-1].transpose(0, 1, 3, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def conv3x3(x, w, bias, interpret=None):
    """3x3 stride-1 SAME conv + bias: x [N,H,W,C], w [3,3,C,CO], bias [CO]
    -> y [N,H,W,CO] in x.dtype, f32 accumulation. Differentiable (custom
    VJP: dgrad reuses the fwd kernel with flipped weights; wgrad+dbias are
    one fused pass)."""
    return _conv_call(x, w, bias, x.dtype, interpret)


def _conv_vjp_fwd(x, w, bias, interpret):
    return _conv_call(x, w, bias, x.dtype, interpret), (x, w)


def _conv_vjp_bwd(interpret, res, g):
    x, w = res
    n, h, wd, c = x.shape
    co = w.shape[-1]
    # dx: unused for conv1 (the image is not differentiated) — the
    # pallas_call is side-effect free, so XLA DCEs it there
    dx = _conv_call(g, _flip_transpose(w), jnp.zeros((c,), g.dtype),
                    x.dtype, interpret)
    bh = _pick_block_h(h, wd, c, co)
    nblk = h // bh
    with kernel_site("conv_wgrad"):
        dw_flat, db = pl.pallas_call(
            functools.partial(_wgrad_kernel, bh=bh, nblk=nblk),
            out_shape=(jax.ShapeDtypeStruct((9 * c, co), jnp.float32),
                       jax.ShapeDtypeStruct((1, co), jnp.float32)),
            grid=(n, nblk),
            in_specs=_halo_specs(bh, nblk, wd, c) + [
                pl.BlockSpec((1, bh, wd, co), lambda n, i: (n, i, 0, 0)),
            ],
            out_specs=(pl.BlockSpec((9 * c, co), lambda n, i: (0, 0)),
                       pl.BlockSpec((1, co), lambda n, i: (0, 0))),
            scratch_shapes=[
                pltpu.VMEM((9 * c, co), jnp.float32),
                pltpu.VMEM((1, co), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
            ),
            interpret=default_interpret(interpret),
        )(x, x, x, g)
    dw = dw_flat.reshape(3, 3, c, co).astype(w.dtype)
    return dx, dw, db[0].astype(w.dtype)


conv3x3.defvjp(_conv_vjp_fwd, _conv_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def conv3x3_stats(x, w, bias, interpret=None):
    """conv3x3 that also returns (sum [1,CO], sumsq [1,CO]) of the rounded
    output in f32 — the BN batch-statistics reductions fused into the conv
    pass, saving the separate full read of y they otherwise cost.

    The stats outputs' cotangents are IGNORED (treated as zero): the
    consumer (ops/pallas_bn_tail.py) accounts for the statistics'
    dependence on y inside its own custom VJP — same contract as its own
    mu/var outputs — so routing them again here would double-count."""
    return _conv_call(x, w, bias, x.dtype, interpret, stats=True)


def _conv_stats_vjp_fwd(x, w, bias, interpret):
    return _conv_call(x, w, bias, x.dtype, interpret, stats=True), (x, w)


def _conv_stats_vjp_bwd(interpret, res, cts):
    return _conv_vjp_bwd(interpret, res, cts[0])


conv3x3_stats.defvjp(_conv_stats_vjp_fwd, _conv_stats_vjp_bwd)


def conv3x3_reference(x, w, bias):
    """The lax.conv call this kernel replaces (models/convnet_s2d.py
    ``_Conv``) — single home for the equality contract."""
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + bias
