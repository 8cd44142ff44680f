"""conv1 + BN/ReLU/pool tail as ONE differentiable unit — the r05
backward fusion that deletes conv1's cotangent from HBM.

In the unfused step, bn1's tail backward WRITES the conv1-output
cotangent g [N, H, 256, W] (~4.7 GB bf16 at bs=16 — the single largest
tensor in the step) and conv1's wgrad immediately READS it back; no
other consumer exists because conv1's input cotangent is dead (the
input is the image through the fixed input stage). That HBM round-trip
is ~9.4 GB of the step's traffic for pure plumbing.

This composite keeps the forward exactly as before (the sparse-tap
conv1-with-stats kernel + the fused tail forward, two Pallas calls) and
fuses the BACKWARD: the tail's reduce pass runs unchanged
(ops/pallas_bn_tail_t.py::bwd_reduce — it produces the batch-wide
s1/s2 the row math needs), then ONE kernel recomputes each row's
tail-backward dy IN VMEM (identical math to _bwd_apply_kernel,
including the rounded-relu recompute, exact 0.5/0.5 pool tie splitting,
and the bf16 rounding the HBM tensor would have applied) and feeds it
straight into the sparse conv1 wgrad dot (restaged per
TPU_SANDBOX_WGRAD_RESTAGE like every other wgrad kernel: 'gt' native
form by default, 'auto' to let Mosaic restage the ragged tile).
g never exists in HBM; reads are y1 + pooled-cotangent + x instead of
g + x — the fused backward's traffic is ~12.7 GB vs ~22.1 GB unfused
across the reduce+apply+wgrad trio.

Gradient outputs: dk5 (canonical 5x5), conv bias, dgamma, dbeta; dx is
zeros by the same guarded contract as conv1_s2d_t (the composite's x
input passes through the _data_only AD guard).

Wired in by models/convnet_s2d_t.py when the sparse conv1 and fused
tail are both active; TPU_SANDBOX_NO_FUSED_CONV1_BWD=1 (trace-time, as
the other levers) or ConvNetS2DT(fused_conv1_bwd=False) falls back to
the unfused composition.

Reference chain being fused: the first conv block of
/root/reference/mnist_onegpu.py:14-18 (conv 5x5 + BN + ReLU + pool),
backward pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_sandbox.ops.pallas_bn_tail_t import (
    _forward as _tail_forward,
    _row_dz,
    bwd_reduce,
    bwd_scales,
)
from tpu_sandbox.ops.pallas_common import default_interpret, kernel_site
from tpu_sandbox.ops.pallas_conv5_t import (
    NT,
    R,
    _data_only,
    _tap_tile_u,
    conv1_s2d_t_stats,
    gather_dk5,
)
from tpu_sandbox.ops.pallas_conv_t import (
    _VMEM_LIMIT,
    _halo_specs,
    _row_getter,
    wgrad_restage,
)


def _wgrad_tail_kernel(x_ref, up_ref, dn_ref, y1_ref, gp_ref,
                       a_ref, b_ref, sel_ref, mu_ref, inv_ref,
                       gi_ref, c1_ref, c2_ref,
                       dw_ref, db_ref, dw_scr, db_scr,
                       *, bh: int, nblk: int, co: int, blk: int,
                       gt: bool):
    """Per row: the tail backward's dy (exact _bwd_apply_kernel math,
    rounded to the activation dtype like the HBM tensor would be), then
    the sparse conv1 wgrad dot against the union tap tile. Same two
    restage variants as pallas_conv5_t._wgrad_kernel: ``gt=True``
    transposes dy ([CO, W] — 128-aligned) and runs the native
    tile [NT, W] x dyT [W, CO] -> dW [NT, CO]; ``gt=False`` leaves the
    lane-lane contraction to Mosaic (which restages the ragged [NT, W]
    tile instead)."""
    n, i = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    get = _row_getter(x_ref, up_ref, dn_ref, bh, nblk)
    for r in range(bh):
        dz = _row_dz(y1_ref, a_ref, b_ref, gp_ref, sel_ref, r, co, blk,
                     y1_ref.dtype)
        yf = y1_ref[0, r].astype(jnp.float32)
        t_hat = (yf - mu_ref[...]) * inv_ref[...]
        dy = gi_ref[...] * (dz - c1_ref[...] - t_hat * c2_ref[...])
        g_row = dy.astype(x_ref.dtype)          # the rounding HBM applied
        db_scr[:] = db_scr[:] + jnp.sum(
            g_row.astype(jnp.float32), axis=1, keepdims=True)
        if gt:
            acc = jax.lax.dot_general(           # [NT, CO], native form
                _tap_tile_u(get, r), g_row.T,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            acc = jax.lax.dot_general(           # [CO, NT]
                g_row, _tap_tile_u(get, r),
                (((1,), (1,)), ((), ())),        # contract W on both
                preferred_element_type=jnp.float32,
            )
        dw_scr[:] = dw_scr[:] + acc

    @pl.when(jnp.logical_and(n == pl.num_programs(0) - 1, i == nblk - 1))
    def _emit():
        dw_ref[...] = dw_scr[:]
        db_ref[...] = db_scr[:]


def _pick_block_h_fused(h: int, wd: int, c16: int, cbig: int,
                        cpool: int) -> int:
    """VMEM-budgeted rows per block for the fused kernel: per-row it
    streams the x block THREE times (the x/up/dn halo triple of
    _halo_specs each stages its own double-buffered copy — counting it
    once under-budgets VMEM by 4*wd*c16*bh bytes per block) plus y1 +
    g_pool (all double-buffered bf16), and keeps ~6 [cbig, W] f32
    tail-backward intermediates plus the tap tile and dw scratch live."""
    per_bh = wd * (3 * c16 + cbig + cpool) * 2 * 2
    fixed = wd * cbig * 4 * 6 + wd * NT * 4 + NT * cbig * 4
    cap = max(1, int((28_000_000 - fixed) // max(per_bh, 1)))
    for bh in (15, 10, 6, 5, 3, 2, 1):
        if bh <= cap and h % bh == 0:
            return bh
    return 1


def _fused_wgrad(x, y1, g_pool, a_col, b_col, sel, mu_col, inv_col,
                 gi_col, c1_col, c2_col, co, blk, interpret,
                 restage=None):
    """``restage`` as in conv3x3_t_wgrad ('gt' native-dot variant is the
    r05 default; None resolves TPU_SANDBOX_WGRAD_RESTAGE at trace
    time). Returns dw1 [cbig, NT] regardless of variant — gt stores the
    native [NT, cbig] and transposes on the way out."""
    gt = wgrad_restage(restage) == "gt"
    n, h, c16, wd = x.shape
    assert c16 == R * R, (c16,)
    cbig = y1.shape[2]
    cpool = g_pool.shape[2]
    bh = _pick_block_h_fused(h, wd, c16, cbig, cpool)
    nblk = h // bh
    dw_shape = (NT, cbig) if gt else (cbig, NT)

    def vec():
        return pl.BlockSpec((cbig, 1), lambda n, i: (0, 0))

    with kernel_site("conv1_tail_t_wgrad"):
        dw, db = pl.pallas_call(
            functools.partial(_wgrad_tail_kernel, bh=bh, nblk=nblk,
                              co=co, blk=blk, gt=gt),
            out_shape=(jax.ShapeDtypeStruct(dw_shape, jnp.float32),
                       jax.ShapeDtypeStruct((cbig, 1), jnp.float32)),
            grid=(n, nblk),
            in_specs=_halo_specs(bh, nblk, c16, wd) + [
                pl.BlockSpec((1, bh, cbig, wd), lambda n, i: (n, i, 0, 0)),
                pl.BlockSpec((1, bh, cpool, wd), lambda n, i: (n, i, 0, 0)),
                vec(), vec(),
                pl.BlockSpec(sel.shape, lambda n, i: (0, 0)),
                vec(), vec(), vec(), vec(), vec(),
            ],
            out_specs=(pl.BlockSpec(dw_shape, lambda n, i: (0, 0)),
                       pl.BlockSpec((cbig, 1), lambda n, i: (0, 0))),
            scratch_shapes=[
                pltpu.VMEM(dw_shape, jnp.float32),
                pltpu.VMEM((cbig, 1), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=default_interpret(interpret),
        )(x, x, x, y1, g_pool, a_col, b_col, sel, mu_col, inv_col,
          gi_col, c1_col, c2_col)
    return (dw.T if gt else dw), db


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _conv1_tail_t_prim(x, k5, cbias, gamma, beta, co, blk, eps=1e-5,
                       interpret=None):
    out, mu, var, _, _ = _fwd_impl(x, k5, cbias, gamma, beta, co, blk,
                                   eps, interpret)
    return out, mu, var


def conv1_tail_t(x, k5, cbias, gamma, beta, co, blk, eps=1e-5,
                 interpret=None):
    """x [N,H4,16,W4] (s2d image — DATA ONLY: a differentiated x is
    rejected by the same AD-rule guard as conv1_s2d_t, applied here
    outside the custom_vjp boundary where it can still see the AD
    trace), k5 [5,5,1,co] canonical, cbias [co], gamma/beta [co] ->
    (pooled [N,H4,4*co,W4], mu [co], var [co]). Forward ==
    conv1_s2d_t_stats + fused tail; backward fuses the tail's dy into
    the conv wgrad (module docstring). mu/var cotangents ignored (stats
    update not differentiated — same contract as fused_bn_relu_pool_t)."""
    return _conv1_tail_t_prim(_data_only(x), k5, cbias, gamma, beta,
                              co, blk, eps, interpret)


def _fwd_impl(x, k5, cbias, gamma, beta, co, blk, eps, interpret):
    y1, s, ss = conv1_s2d_t_stats(x, k5, cbias, interpret)
    out, mu, var, (a_col, b_col, inv) = _tail_forward(
        y1, gamma, beta, co, blk, eps, interpret, ysums=(s, ss))
    return out, mu, var, y1, (a_col, b_col, inv)


def _vjp_fwd(x, k5, cbias, gamma, beta, co, blk, eps, interpret):
    out, mu, var, y1, (a_col, b_col, inv) = _fwd_impl(
        x, k5, cbias, gamma, beta, co, blk, eps, interpret)
    return (out, mu, var), (x, k5, cbias, y1, gamma, beta, mu, inv,
                            a_col, b_col)


def _vjp_bwd(co, blk, eps, interpret, res, cts):
    g = cts[0]  # stats cotangents ignored — see docstring
    x, k5, cbias, y1, gamma, beta, mu, inv, a_col, b_col = res
    n, h, c, w = y1.shape
    groups = blk * blk
    s1_co, s2_co, mu_col, inv_col, sel = bwd_reduce(
        y1, g, co, blk, a_col, b_col, mu, inv, interpret)
    m_count = n * h * w * groups
    gi_col, c1_col, c2_col = bwd_scales(s1_co, s2_co, gamma, inv,
                                        groups, m_count)

    dw1, db = _fused_wgrad(x, y1, g, a_col, b_col, sel, mu_col, inv_col,
                           gi_col, c1_col, c2_col, co, blk, interpret)
    f1 = k5.shape[-1]
    dk5 = gather_dk5(dw1, f1).astype(k5.dtype)
    db_f1 = db[:, 0].reshape(R * R, f1).sum(0).astype(cbias.dtype)
    dgamma = s2_co.astype(gamma.dtype)
    dbeta = s1_co.astype(beta.dtype)
    return jnp.zeros_like(x), dk5, db_f1, dgamma, dbeta


_conv1_tail_t_prim.defvjp(_vjp_fwd, _vjp_bwd)


def conv1_tail_t_reference(x, k5, cbias, gamma, beta, co, blk, eps=1e-5,
                           interpret=None):
    """The unfused composition (the exact ops the model runs with
    fused_conv1_bwd=False): equality contract for the tests."""
    from tpu_sandbox.ops.pallas_bn_tail_t import fused_bn_relu_pool_t

    y1, s, ss = conv1_s2d_t_stats(x, k5, cbias, interpret)
    return fused_bn_relu_pool_t(y1, gamma, beta, co, blk, eps, interpret,
                                (s, ss))
