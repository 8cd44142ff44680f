"""Sparse-tap 5x5 s2d conv for conv1 in TRANSPOSED layout — the round-4
attack on the s2d FLOP inflation (VERDICT r03 next-5).

conv1's scattered 3x3 form (ops/pallas_conv_t.py over
models/convnet_s2d.py::scatter_kernel) executes a [256, 9C=144] x
[144, W] row matmul whose weight is only 25/144 dense: the original
5x5 kernel has 25 taps per (output position, channel) but the block-conv
scatter pads them into 144 K-slots, and the MXU then runs
ceil(256/128) * ceil(144/128) = 4 tile-passes per row.

This kernel contracts the 25 real taps against a UNION tap tile indexed
by (m', j) = (full-res row offset in -2..5, full-res col offset in
-2..5) relative to the output block (m' = a'+ty-2 with a' in 0..3,
ty in 0..4 spans exactly -2..5): T[(m', j), w4] = image[4*h4+m',
4*w4+j]. Every output channel (a', b', co) needs the 25 entries
(m' = a'+ty-2, j = b'+tx-2), all inside the 64 tile rows, so one
[256, 64] x [64, W] matmul computes the whole row:
ceil(256/128) * ceil(64/128) = **2 tile-passes — half the MXU work** —
and K=64 is an exact sublane tile (zero K padding). The tile build is
24 contiguous sublane slices (vs 9 full-block concats). The weight is
built at trace time by scattering the canonical k5 [5, 5, 1, 16] into
[256, 64] (39% dense; MXU cost is shape-, not density-, driven, so
K=64 <= 128 is the whole win).

Executed flops drop from 2*B*H*W*(256*144) to 2*B*H*W*(256*64) per call
(2.25x); MXU passes halve. conv2 is left on the 3x3 kernel: its scatter
is 25/36 = 69% dense (real 16-channel input), so the same trick buys
under 1.3x there.

Interface: conv1_s2d_t(x [N,H4,16,W4], k5 [5,5,1,F1], bias [F1]) ->
y [N, H4, 16*F1, W4]; custom VJP (dx is never needed — conv1's input is
the image — and is returned as zeros for jax to DCE; wgrad accumulates
dW [256, 64] in one fused pass and gathers it back to dk5). A *_stats
variant fuses the BN sum/sumsq like conv3x3_t_stats.

Reference being accelerated: the first 5x5 conv of
/root/reference/mnist_onegpu.py:14-18.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_sandbox.ops.pallas_common import default_interpret, kernel_site
from tpu_sandbox.ops.pallas_conv_t import (
    _VMEM_LIMIT,
    _halo_specs,
    _row_getter,
    _shift_lanes,
)

R = 4          # s2d factor (conv1's)
K5 = 5         # original kernel size
NT = 8 * 8     # union tap tile rows: (m', j) in (-2..5)^2


@functools.lru_cache(maxsize=2)
def _scatter_indices(f1: int):
    """Static index arrays mapping k5[ty, tx, 0, co] into W1[c_out, t]:
    c_out = (a'*4 + b')*f1 + co, t = (a'+ty)*8 + (b'+tx)."""
    a, b, ty, tx, co = np.meshgrid(
        np.arange(R), np.arange(R), np.arange(K5), np.arange(K5),
        np.arange(f1), indexing="ij",
    )
    rows = (a * R + b) * f1 + co
    cols = (a + ty) * 8 + (b + tx)
    return (rows.reshape(-1), cols.reshape(-1),
            ty.reshape(-1), tx.reshape(-1), co.reshape(-1))


def scatter_k5(k5: jnp.ndarray) -> jnp.ndarray:
    """k5 [5,5,1,f1] -> W1 [16*f1, 64] (the union-tile weight)."""
    f1 = k5.shape[-1]
    rows, cols, ty, tx, co = _scatter_indices(f1)
    w1 = jnp.zeros((R * R * f1, NT), k5.dtype)
    return w1.at[rows, cols].set(k5[ty, tx, 0, co])


def gather_dk5(dw1: jnp.ndarray, f1: int) -> jnp.ndarray:
    """Transpose of scatter_k5: dW1 [16*f1, 64] -> dk5 [5,5,1,f1]
    (each k5 tap accumulates its 16 (a', b') occurrences)."""
    rows, cols, ty, tx, co = _scatter_indices(f1)
    dk5 = jnp.zeros((K5, K5, 1, f1), dw1.dtype)
    return dk5.at[ty, tx, 0, co].add(dw1[rows, cols])


def _tap_tile_u(get, r: int):
    """The union tap tile [64, W]: rows (m', j) for m', j in -2..5,
    j-major within m'. Row (m', j) = sublane p*4+q of block row
    r + floor(m'/4) (p = m' mod 4), lane-shifted by floor(j/4). Per m'
    that is three contiguous sublane slices: q=2,3 shifted right (j=-2,
    -1), q=0..3 unshifted (j=0..3), q=0..1 shifted left (j=4..5)."""
    pieces = []
    for mp in range(-2, 6):
        blk = get(r + mp // R)   # {-2,-1}->r-1, {0..3}->r, {4,5}->r+1
        p = mp % R
        s = p * R
        pieces += [
            _shift_lanes(blk[s + 2:s + 4], 0),   # j = -2, -1 (right)
            blk[s:s + 4],                        # j = 0..3
            _shift_lanes(blk[s:s + 2], 2),       # j = 4, 5 (left)
        ]
    return jnp.concatenate(pieces, axis=0)


def _conv_row_u(get, w1_ref, b_ref, r: int):
    acc = jax.lax.dot_general(
        w1_ref[...], _tap_tile_u(get, r),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                            # [256, W]
    return acc + b_ref[...].astype(jnp.float32)


def _fwd_kernel(x_ref, up_ref, dn_ref, w1_ref, b_ref, y_ref,
                *, bh: int, nblk: int):
    get = _row_getter(x_ref, up_ref, dn_ref, bh, nblk)
    for r in range(bh):
        y_ref[0, r] = _conv_row_u(get, w1_ref, b_ref, r).astype(y_ref.dtype)


def _fwd_stats_kernel(x_ref, up_ref, dn_ref, w1_ref, b_ref,
                      y_ref, s_ref, ss_ref, s_scr, ss_scr,
                      *, bh: int, nblk: int):
    n, i = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _init():
        s_scr[:] = jnp.zeros_like(s_scr)
        ss_scr[:] = jnp.zeros_like(ss_scr)

    get = _row_getter(x_ref, up_ref, dn_ref, bh, nblk)
    for r in range(bh):
        y_row = _conv_row_u(get, w1_ref, b_ref, r).astype(y_ref.dtype)
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
    """Weight-gradient + db accumulated across the grid; the union tile
    is rebuilt per row (same build as forward). Same two restage
    variants as pallas_conv_t._wgrad_kernel: ``gt=True`` transposes
    g_row ([CO=256, W] — 128-aligned) and runs the native
    tile [64, W] x gT [W, 256] -> dW [64, 256]; ``gt=False`` leaves the
    lane-lane contraction to Mosaic (which restages the ragged [64, W]
    tile instead)."""
    n, i = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    get = _row_getter(x_ref, up_ref, dn_ref, bh, nblk)
    for r in range(bh):
        g_row = g_ref[0, r]                      # [CO, W]
        db_scr[:] = db_scr[:] + jnp.sum(
            g_row.astype(jnp.float32), axis=1, keepdims=True)
        if gt:
            acc = jax.lax.dot_general(           # [64, CO], native form
                _tap_tile_u(get, r), g_row.T,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            acc = jax.lax.dot_general(           # [CO, 64]
                g_row, _tap_tile_u(get, r),
                (((1,), (1,)), ((), ())),        # contract W on both
                preferred_element_type=jnp.float32,
            )
        dw_scr[:] = dw_scr[:] + acc

    @pl.when(jnp.logical_and(n == pl.num_programs(0) - 1, i == nblk - 1))
    def _emit():
        dw_ref[...] = dw_scr[:]
        db_ref[...] = db_scr[:]


def _pick_block_h(h: int, w: int, co: int) -> int:
    """Rows per grid block (cf. pallas_conv_t._pick_block_h): the fixed
    per-row cost is the [64, W] tile + [CO, W] f32 row accumulator."""
    per_bh = w * (16 + co) * 2 * 2
    per_row = w * (NT + co) * 4
    cap = max(1, int((28_000_000 - per_row) // max(per_bh, 1)))
    for bh in (30, 25, 20, 15, 12, 10, 8, 6, 5, 4, 3, 2, 1):
        if bh <= cap and h % bh == 0:
            return bh
    return 1


def _conv_call(x, w1, bias_g, out_dtype, interpret, stats=False):
    n, h, c, wd = x.shape
    assert c == R * R, (c, "conv1_s2d_t is the r=4, 1-channel-input conv")
    co = w1.shape[0]
    bh = _pick_block_h(h, wd, co)
    nblk = h // bh
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
    with kernel_site("conv5_t_fwd"):
        return pl.pallas_call(
            kernel,
            out_shape=out_shape,
            grid=(n, nblk),
            in_specs=_halo_specs(bh, nblk, c, wd) + [
                pl.BlockSpec((co, NT), lambda n, i: (0, 0)),
                pl.BlockSpec((co, 1), lambda n, i: (0, 0)),
            ],
            out_specs=out_specs,
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=default_interpret(interpret),
        )(x, x, x, w1, bias_g)


def _prep(k5, bias, dtype):
    f1 = k5.shape[-1]
    w1 = scatter_k5(k5.astype(dtype))
    bias_g = jnp.tile(bias.astype(dtype), R * R).reshape(-1, 1)
    return w1, bias_g, f1


@jax.custom_jvp
def _data_only(x):
    """Identity that REFUSES differentiation through its argument.

    conv1_s2d_t returns a ZERO input cotangent by contract — correct for
    the production model, whose conv1 input is the image through the
    fixed linear fused input stage (models/convnet_s2d_t.py), and the
    zeros let jax DCE the dead dx. But composed after any TRAINABLE
    preprocessing that contract would silently zero real gradients
    (VERDICT r04 weak-5). The misuse check must live at the AD-RULE
    level: a wrapper inspecting tracer types is blind across trace
    boundaries (under grad-of-jit / remat / scan the forward runs with
    plain jaxpr tracers and AD happens on the jaxpr afterwards). This
    shim's JVP rule runs wherever AD actually happens, with
    symbolic_zeros=True so a data input presents as SymbolicZero and a
    differentiated input presents as a real tangent — which raises.
    Under jit with no AD the rule never runs and the identity compiles
    away."""
    return x


@functools.partial(_data_only.defjvp, symbolic_zeros=True)
def _data_only_jvp(primals, tangents):
    (x,), (x_dot,) = primals, tangents
    if not isinstance(x_dot, jax.custom_derivatives.SymbolicZero):
        raise ValueError(
            "conv1_s2d_t's input is being differentiated. This kernel "
            "returns a ZERO input cotangent by contract — its input "
            "must be data (the fused input stage output), never a "
            "function of trainable parameters; composing it after "
            "trainable preprocessing would produce silently wrong "
            "gradients. Use the scattered-3x3 conv1 instead "
            "(ConvNetS2DT(sparse_conv1=False) or "
            "ops.pallas_conv_t.conv3x3_t, which propagates a real "
            "input cotangent)."
        )
    return _data_only(x), x_dot


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv1_s2d_t_prim(x, k5, bias, interpret=None):
    w1, bias_g, _ = _prep(k5, bias, x.dtype)
    return _conv_call(x, w1, bias_g, x.dtype, interpret)


def conv1_s2d_t(x, k5, bias, interpret=None):
    """Sparse-tap conv1: x [N,H4,16,W4] (s2d-transposed image),
    k5 [5,5,1,f1] CANONICAL 5x5 weights, bias [f1] ->
    y [N,H4,16*f1,W4] in x.dtype, f32 accumulation. The x cotangent is
    zeros (the image is data; jax DCEs it) — a differentiated x is
    rejected by the AD rule itself, see _data_only."""
    return _conv1_s2d_t_prim(_data_only(x), k5, bias, interpret)


def conv1_s2d_t_wgrad(x, g, interpret=None, restage=None):
    """Fused wgrad+dbias: x [N,H4,16,W4], g [N,H4,CO,W4] ->
    (dW1 [CO, 64] f32, db [CO, 1] f32). ``restage`` as in
    conv3x3_t_wgrad ('gt' native-dot variant is the r05 default)."""
    from tpu_sandbox.ops.pallas_conv_t import wgrad_restage

    gt = wgrad_restage(restage) == "gt"
    n, h, c, wd = x.shape
    co = g.shape[2]
    bh = _pick_block_h(h, wd, co)
    nblk = h // bh
    dw_shape = (NT, co) if gt else (co, NT)
    with kernel_site("conv5_t_wgrad"):
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
    return (dw.T if gt else dw), db


def _vjp_fwd(x, k5, bias, interpret):
    return _conv1_s2d_t_prim(x, k5, bias, interpret), (x, k5, bias)


def _vjp_bwd(interpret, res, g):
    x, k5, bias = res
    f1 = k5.shape[-1]
    dw1, db = conv1_s2d_t_wgrad(x, g, interpret)
    dk5 = gather_dk5(dw1, f1).astype(k5.dtype)
    db_f1 = db[:, 0].reshape(R * R, f1).sum(0).astype(bias.dtype)
    return jnp.zeros_like(x), dk5, db_f1


_conv1_s2d_t_prim.defvjp(_vjp_fwd, _vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv1_s2d_t_stats_prim(x, k5, bias, interpret=None):
    w1, bias_g, _ = _prep(k5, bias, x.dtype)
    return _conv_call(x, w1, bias_g, x.dtype, interpret, stats=True)


def conv1_s2d_t_stats(x, k5, bias, interpret=None):
    """conv1_s2d_t that also returns (sum [CO,1], sumsq [CO,1]) of the
    rounded output — same contract as conv3x3_t_stats (stats cotangents
    ignored; the fused tail's backward accounts for them). Same
    differentiated-input rejection as conv1_s2d_t."""
    return _conv1_s2d_t_stats_prim(_data_only(x), k5, bias, interpret)


def _stats_vjp_fwd(x, k5, bias, interpret):
    out = _conv1_s2d_t_stats_prim(x, k5, bias, interpret)
    return out, (x, k5, bias)


def _stats_vjp_bwd(interpret, res, cts):
    return _vjp_bwd(interpret, res, cts[0])


_conv1_s2d_t_stats_prim.defvjp(_stats_vjp_fwd, _stats_vjp_bwd)


def conv1_s2d_t_reference(x, k5, bias):
    """Equality contract: the existing scattered-3x3 path
    (scatter_kernel + conv3x3_t_reference) on the same operands."""
    from tpu_sandbox.models.convnet_s2d import scatter_kernel
    from tpu_sandbox.ops.pallas_conv_t import conv3x3_t_reference

    wg = scatter_kernel(k5, R)
    reps = wg.shape[-1] // k5.shape[-1]
    return conv3x3_t_reference(x, wg, jnp.tile(bias, reps))
