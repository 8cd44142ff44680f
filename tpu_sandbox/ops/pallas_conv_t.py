"""Pallas TPU 3x3 SAME conv in TRANSPOSED layout [N, H, C, W] — the
round-3 rework of ops/pallas_conv.py after on-chip micro-benchmarks.

Measured (tools/conv_micro.py, v5e, bs=16): the [N,H,W,C] kernel runs
conv1 fwd at ~27 TF/s and the backward at ~19 TF/s against a ~110 TF/s
MXU ceiling — SLOWER than the XLA conv it replaced (~41 TF/s). Two
pathologies, both layout-induced:

1. **Tap-tile build.** The [W, 9C] im2col tile is assembled by nine
   lane-direction concatenates of [W, C] pieces; at C=16 each piece
   occupies 16 of 128 lanes, so every VPU op wastes 7/8 of the machine
   and the inserts at lane offsets 16k are multi-op shuffles. The build
   costs several times the [W,9C]x[9C,CO] matmul it feeds.
2. **HBM lane padding.** Pallas operands use the default layout (last
   dim on lanes): a [...,W,C] block with C=16 is padded 8x in HBM and
   VMEM, so the kernel also moves ~8x the bytes it thinks it does.

The transposed layout fixes both at once. Activations are [N, H, C, W]:
W=750 rides the 128-lane dim (pad 750->768, 2.4%), C rides sublanes
(C=16 = exactly one bf16 sublane-tile). The im2col tile becomes
tileT [9C, W], built by stacking nine [C, W] row views along SUBLANES —
tile-aligned register placement, no lane shuffles; the dx taps are
single-lane shifts of full-width rows. The matmul is
wt [CO, 9C] x tileT [9C, W] -> y_rowT [CO, W], i.e. the same
K = 9C contraction, now fed at full VPU/lane width.

Interface mirrors pallas_conv (same scattered w [3,3,C,CO], bias [CO],
f32 accumulation, custom VJP with dgrad = fwd kernel on flipped
weights and a fused wgrad+dbias pass; a *_stats variant folds the BN
sum/sumsq over (N,H,W) into the output pass). conv3x3_t_reference
transposes to NHWC, runs the exact lax.conv the NHWC plan uses, and
transposes back — the equality contract for tests/test_pallas_conv_t.py.

Reference being accelerated: the two 5x5 convs of
/root/reference/mnist_onegpu.py:11-31, s2d-scattered to 3x3
(models/convnet_s2d.py docstring).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_sandbox.ops.pallas_common import default_interpret, kernel_site


_VMEM_LIMIT = 100_000_000  # raised from the 16 MB default (v5e: 128 MB)


def _pick_block_h(h: int, w: int, c: int, co: int) -> int:
    """Rows per grid block, budgeted against VMEM (raised to
    ``_VMEM_LIMIT`` via CompilerParams). Bigger blocks matter here:
    measured at bh=3 the kernel pays 2 small halo DMAs per 3 rows and
    ~4000 grid blocks/step; bh=30 cuts both 10x. The fixed per-row cost
    (tap tile + f32 accumulator) does not scale with bh, so it is
    subtracted from the budget rather than multiplied."""
    per_bh = w * (c + co) * 2 * 2             # double-buffered blocks, bf16
    per_row = w * (9 * c + co) * 4            # tap tile + f32 row buffers
    cap = max(1, int((28_000_000 - per_row) // max(per_bh, 1)))
    for bh in (30, 25, 20, 15, 12, 10, 8, 6, 5, 4, 3, 2, 1):
        if bh <= cap and h % bh == 0:
            return bh
    return 1


def _shift_lanes(row, dx: int):
    """row [C, W] -> the dx-tap's view: shifted along lanes (the W
    direction) with a zero column entering at the edge (SAME padding)."""
    if dx == 1:
        return row
    zero = jnp.zeros_like(row[:, :1])
    if dx == 0:
        return jnp.concatenate([zero, row[:, :-1]], axis=1)
    return jnp.concatenate([row[:, 1:], zero], axis=1)


def _halo_specs(bh: int, nblk: int, c: int, w: int):
    """Body block + clamped single-row halo blocks above and below."""
    return [
        pl.BlockSpec((1, bh, c, w), lambda n, i: (n, i, 0, 0)),
        pl.BlockSpec((1, 1, c, w),
                     lambda n, i: (n, jnp.maximum(i * bh - 1, 0), 0, 0)),
        pl.BlockSpec((1, 1, c, w),
                     lambda n, i: (n, jnp.minimum(i * bh + bh, nblk * bh - 1),
                                   0, 0)),
    ]


def _row_getter(x_ref, up_ref, dn_ref, bh: int, nblk: int):
    """Row r_in of the (bh+2)-row halo'd strip as [C, W]; out-of-image
    halo rows read the clamped neighbor block and are zero-masked."""
    i = pl.program_id(1)

    def get(r_in: int):
        if r_in == -1:
            return jnp.where(i > 0, up_ref[0, 0], 0)
        if r_in == bh:
            return jnp.where(i < nblk - 1, dn_ref[0, 0], 0)
        return x_ref[0, r_in]

    return get


def _tap_tile_t(get, r: int):
    """The row's im2col tile TRANSPOSED, [9C, W]: nine [C, W] views
    stacked along sublanes (tap order (dy, dx) major then C — the same
    flattening as w.reshape(9C, CO), so the two kernels share weight
    layout). Sublane concatenation of C-row pieces is tile-aligned
    placement; the lane shifts are single-lane rotates of full-width
    rows — this build is the whole point of the transposed layout."""
    return jnp.concatenate(
        [_shift_lanes(get(r + dy - 1), dx)
         for dy in range(3) for dx in range(3)],
        axis=0,
    )


def _conv_row_t(get, wt_ref, b_ref, r: int):
    acc = jax.lax.dot_general(
        wt_ref[...], _tap_tile_t(get, r),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # [CO, W]
    return acc + b_ref[...].astype(jnp.float32)  # [CO, 1] broadcasts over W


def _fwd_kernel(x_ref, up_ref, dn_ref, wt_ref, b_ref, y_ref,
                *, bh: int, nblk: int):
    get = _row_getter(x_ref, up_ref, dn_ref, bh, nblk)
    for r in range(bh):
        y_ref[0, r] = _conv_row_t(get, wt_ref, b_ref, r).astype(y_ref.dtype)


def _fwd_stats_kernel(x_ref, up_ref, dn_ref, wt_ref, b_ref,
                      y_ref, s_ref, ss_ref, s_scr, ss_scr,
                      *, bh: int, nblk: int):
    """fwd + per-channel sum/sumsq of the ROUNDED output accumulated
    across the sequential grid (channels on sublanes: the reductions run
    over lanes/W and rows)."""
    n, i = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _init():
        s_scr[:] = jnp.zeros_like(s_scr)
        ss_scr[:] = jnp.zeros_like(ss_scr)

    get = _row_getter(x_ref, up_ref, dn_ref, bh, nblk)
    for r in range(bh):
        y_row = _conv_row_t(get, wt_ref, b_ref, r).astype(y_ref.dtype)
        y_ref[0, r] = y_row
        yf = y_row.astype(jnp.float32)
        s_scr[:] = s_scr[:] + jnp.sum(yf, axis=1, keepdims=True)
        ss_scr[:] = ss_scr[:] + jnp.sum(yf * yf, axis=1, keepdims=True)

    @pl.when(jnp.logical_and(n == pl.num_programs(0) - 1, i == nblk - 1))
    def _emit():
        s_ref[...] = s_scr[:]
        ss_ref[...] = ss_scr[:]


def _wgrad_kernel(x_ref, up_ref, dn_ref, g_ref, dw_ref, db_ref,
                  dw_scr, db_scr, *, bh: int, nblk: int, gt: bool):
    """Accumulates the weight gradient and db [CO, 1] in VMEM scratch
    across the sequential grid. The contraction is over W, which sits on
    the LANES of both operands (g_row [CO, W], tile [9C, W]) — not a
    native MXU form, so SOME operand must be restaged per row. Two
    variants (VERDICT r04 next-2, the named wgrad bottleneck):

    - ``gt=False`` (r03 form): ``dot_general(g_row, tile, contract W on
      both)`` -> dwT [CO, 9C]. Mosaic resolves the lane-lane contraction
      itself, transposing the TILE — a per-row relayout of [9C, W]
      (9C = 144/576: ragged, non-128-multiple sublane counts).
    - ``gt=True`` (r05): transpose ``g_row`` explicitly ([CO, W] ->
      [W, CO]; CO = 256/128 — exact lane-tile multiples) and run the
      native [M,K]x[K,N] dot ``tile [9C, W] x gT [W, CO] -> dw [9C,
      CO]``: all three MXU dims >= 128 at production geometry, and the
      per-row transpose moves 4.5x fewer bytes for conv2 (128x768 vs
      576x768) and is 128-aligned for both convs.

    Which wins on hardware is a measured question — tools/conv_micro.py
    races both (rows wgrad[gt] / wgrad[auto])."""
    n, i = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    get = _row_getter(x_ref, up_ref, dn_ref, bh, nblk)
    for r in range(bh):
        g_row = g_ref[0, r]                    # [CO, W]
        db_scr[:] = db_scr[:] + jnp.sum(
            g_row.astype(jnp.float32), axis=1, keepdims=True)
        if gt:
            acc = jax.lax.dot_general(         # [9C, CO], native form
                _tap_tile_t(get, r), g_row.T,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            acc = jax.lax.dot_general(         # [CO, 9C]
                g_row, _tap_tile_t(get, r),
                (((1,), (1,)), ((), ())),      # contract W on both
                preferred_element_type=jnp.float32,
            )
        dw_scr[:] = dw_scr[:] + acc

    @pl.when(jnp.logical_and(n == pl.num_programs(0) - 1, i == nblk - 1))
    def _emit():
        dw_ref[...] = dw_scr[:]
        db_ref[...] = db_scr[:]


def _conv_call(x, w, bias, out_dtype, interpret, stats=False):
    n, h, c, wd = x.shape
    co = w.shape[-1]
    bh = _pick_block_h(h, wd, c, co)
    nblk = h // bh
    wt = w.reshape(9 * c, co).T                # [CO, 9C]
    if stats:
        kernel = functools.partial(_fwd_stats_kernel, bh=bh, nblk=nblk)
        out_shape = (jax.ShapeDtypeStruct((n, h, co, wd), out_dtype),
                     jax.ShapeDtypeStruct((co, 1), jnp.float32),
                     jax.ShapeDtypeStruct((co, 1), jnp.float32))
        out_specs = (
            pl.BlockSpec((1, bh, co, wd), lambda n, i: (n, i, 0, 0)),
            pl.BlockSpec((co, 1), lambda n, i: (0, 0)),
            pl.BlockSpec((co, 1), lambda n, i: (0, 0)),
        )
        scratch = [pltpu.VMEM((co, 1), jnp.float32),
                   pltpu.VMEM((co, 1), jnp.float32)]
    else:
        kernel = functools.partial(_fwd_kernel, bh=bh, nblk=nblk)
        out_shape = jax.ShapeDtypeStruct((n, h, co, wd), out_dtype)
        out_specs = pl.BlockSpec((1, bh, co, wd), lambda n, i: (n, i, 0, 0))
        scratch = []
    with kernel_site("conv_t_fwd"):
        return pl.pallas_call(
            kernel,
            out_shape=out_shape,
            grid=(n, nblk),
            in_specs=_halo_specs(bh, nblk, c, wd) + [
                pl.BlockSpec((co, 9 * c), lambda n, i: (0, 0)),
                pl.BlockSpec((co, 1), lambda n, i: (0, 0)),
            ],
            out_specs=out_specs,
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=default_interpret(interpret),
        )(x, x, x, wt, bias.reshape(co, 1))


def _flip_transpose(w):
    """fwd weights -> dgrad weights: spatial flip + ci/co transpose."""
    return w[::-1, ::-1].transpose(0, 1, 3, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def conv3x3_t(x, w, bias, interpret=None):
    """3x3 stride-1 SAME conv + bias in transposed layout: x [N,H,C,W],
    w [3,3,C,CO], bias [CO] -> y [N,H,CO,W] in x.dtype, f32 accumulation.
    Differentiable (custom VJP: dgrad reuses the fwd kernel with flipped
    weights; wgrad+dbias are one fused pass)."""
    return _conv_call(x, w, bias, x.dtype, interpret)


def _conv_vjp_fwd(x, w, bias, interpret):
    return _conv_call(x, w, bias, x.dtype, interpret), (x, w)


def wgrad_restage(restage: str | None) -> str:
    """Resolve the wgrad restage choice: explicit argument, else the
    TPU_SANDBOX_WGRAD_RESTAGE env (read at TRACE time, same discipline
    as TPU_SANDBOX_NO_SPARSE_CONV1 — models/convnet_s2d_t.py), else the
    r05 default 'gt'."""
    import os

    if restage is None:
        restage = os.environ.get("TPU_SANDBOX_WGRAD_RESTAGE", "gt")
    if restage not in ("gt", "auto"):
        raise ValueError(f"wgrad restage must be 'gt' or 'auto': {restage}")
    return restage


def conv3x3_t_wgrad(x, g, interpret=None, restage=None):
    """The fused wgrad+dbias pass alone: x [N,H,C,W], g [N,H,CO,W] ->
    (dwT [CO, 9C] f32, db [CO, 1] f32). Used by the VJP below and timed
    in isolation by tools/conv_micro.py. ``restage`` picks the per-row
    MXU staging (see _wgrad_kernel): 'gt' transposes g explicitly and
    runs the native dot; 'auto' leaves the lane-lane contraction to
    Mosaic; None resolves via wgrad_restage."""
    gt = wgrad_restage(restage) == "gt"
    n, h, c, wd = x.shape
    co = g.shape[2]
    bh = _pick_block_h(h, wd, c, co)
    nblk = h // bh
    dw_shape = (9 * c, co) if gt else (co, 9 * c)
    with kernel_site("conv_t_wgrad"):
        dw, db = pl.pallas_call(
            functools.partial(_wgrad_kernel, bh=bh, nblk=nblk, gt=gt),
            out_shape=(jax.ShapeDtypeStruct(dw_shape, jnp.float32),
                       jax.ShapeDtypeStruct((co, 1), jnp.float32)),
            grid=(n, nblk),
            in_specs=_halo_specs(bh, nblk, c, wd) + [
                pl.BlockSpec((1, bh, co, wd), lambda n, i: (n, i, 0, 0)),
            ],
            out_specs=(pl.BlockSpec(dw_shape, lambda n, i: (0, 0)),
                       pl.BlockSpec((co, 1), lambda n, i: (0, 0))),
            scratch_shapes=[
                pltpu.VMEM(dw_shape, jnp.float32),
                pltpu.VMEM((co, 1), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=default_interpret(interpret),
        )(x, x, x, g)
    # caller-facing layout is dwT [CO, 9C] either way; the gt variant's
    # [9C, CO] is a one-off [576, 128]-ish XLA transpose per step (and
    # cancels against the VJP's .T below)
    return (dw.T if gt else dw), db


def _conv_vjp_bwd(interpret, res, g):
    x, w = res
    c, co = x.shape[2], w.shape[-1]
    # dx: DCE'd by XLA when unused (conv1: the image is not differentiated)
    dx = _conv_call(g, _flip_transpose(w), jnp.zeros((c,), g.dtype),
                    x.dtype, interpret)
    dwt, db = conv3x3_t_wgrad(x, g, interpret)
    dw = dwt.T.reshape(3, 3, c, co).astype(w.dtype)
    return dx, dw, db[:, 0].astype(w.dtype)


conv3x3_t.defvjp(_conv_vjp_fwd, _conv_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def conv3x3_t_stats(x, w, bias, interpret=None):
    """conv3x3_t that also returns (sum [CO,1], sumsq [CO,1]) of the
    rounded output in f32 — BN batch statistics fused into the conv's
    output pass. The stats outputs' cotangents are IGNORED: the consumer
    accounts for the statistics' dependence on y inside its own custom
    VJP (same contract as pallas_conv.conv3x3_stats)."""
    return _conv_call(x, w, bias, x.dtype, interpret, stats=True)


def _conv_stats_vjp_fwd(x, w, bias, interpret):
    return _conv_call(x, w, bias, x.dtype, interpret, stats=True), (x, w)


def _conv_stats_vjp_bwd(interpret, res, cts):
    return _conv_vjp_bwd(interpret, res, cts[0])


conv3x3_t_stats.defvjp(_conv_stats_vjp_fwd, _conv_stats_vjp_bwd)


def conv3x3_t_reference(x, w, bias):
    """Equality contract: NCHW->NHWC transpose, the exact lax.conv the
    NHWC plan uses (pallas_conv.conv3x3_reference), transpose back."""
    from tpu_sandbox.ops.pallas_conv import conv3x3_reference

    y = conv3x3_reference(x.transpose(0, 1, 3, 2), w, bias)
    return y.transpose(0, 1, 3, 2)
