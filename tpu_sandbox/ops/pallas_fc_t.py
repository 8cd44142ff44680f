"""Pallas fc head for the TRANSPOSED plan: the dgrad relayout killer.

The s2dt step's fc is ``einsum('nhcw,hcwk->nk')`` over the ~18M-feature
map (reference mnist_onegpu.py:27-30's LazyLinear at 3000^2). Forward
and weight-grad are fine as XLA dots — they are bandwidth-bound and run
near their byte counts. The INPUT-grad is not: XLA computes
``einsum('nk,hcwk->nhcw')`` with an output layout that puts N off-minor
({3,0,2,1}) and then pays a whole-activation relayout copy to feed the
bn2 backward kernel, which (like every Pallas call) requires the
standard {3,2,1,0} layout — at bs=16 that is fusion.8 + copy.92 in the
AOT dump, ~11 ms of the 59 ms non-kernel residue and ~1.6 GB of HBM
traffic (measured/hlo_cycles_s2dt_b16_r04.json; VERDICT r04 next-3).
A 2D reformulation does not help: the padded TPU tiling of
[N,750,32,750] has pad gaps at W=750->768, so any [N, 18M] view is
itself a relayout.

This kernel computes dy directly in the native layout:
``dy[n,h,c,w] = sum_k g[n,k] * wT[k,h,c,w]`` with K=10 scalars per
output element — a scalar-FMA accumulation on the VPU (the MXU wants
K>=128; at K=10 it would run ~8% occupied). Per grid block it streams
wT [K, bh, C, W] and writes dy [N, bh, C, W] — ~1.2 GB/step total
traffic, no relayout, output already in the layout bn2's backward
wants. g rides SMEM (it is [N, 10] scalars).

The wrapper ``fc_t`` is a custom_vjp over (y, kernel2d, bias) with the
f32 [H*C*W, K] kernel PARAMETER as the primal (not its bf16 4D view).
The weight-grad is the same contraction the autodiff path ran, with f32
accumulation; it is NOT bit-identical to the kill-switch einsum path —
autodiff routes the k4 cotangent through a bf16 rounding at the astype
boundary that this formulation skips, so the Pallas-path wgrad carries
full f32 mantissas (strictly less rounding). Equality is pinned to
tolerance, not bits, in tests/test_pallas_fc_t.py. Used by models/convnet_s2d_t.py::_DenseT (kill switch:
TPU_SANDBOX_NO_PALLAS_FC=1, read at trace time like the other levers).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_sandbox.ops.pallas_common import default_interpret

_VMEM_LIMIT = 100_000_000


def _pick_block_h(h: int, c: int, w: int, n: int, k: int) -> int:
    """Rows per grid block: wT block (k) + dy block (n), bf16,
    double-buffered."""
    per_bh = w * c * (n + k) * 2 * 2
    cap = max(1, int(40_000_000 // max(per_bh, 1)))
    for bh in (15, 10, 6, 5, 3, 2, 1):
        if bh <= cap and h % bh == 0:
            return bh
    return 1


def _dgrad_kernel(g_ref, wt_ref, dy_ref, *, n_batch: int, k_cls: int):
    for n in range(n_batch):
        acc = g_ref[n, 0] * wt_ref[0].astype(jnp.float32)
        for k in range(1, k_cls):
            acc = acc + g_ref[n, k] * wt_ref[k].astype(jnp.float32)
        dy_ref[n] = acc.astype(dy_ref.dtype)


def fc_dgrad_t(g, wt, out_dtype, interpret=None):
    """g [N, K] f32, wT [K, H, C, W] (kernel rows k-major) ->
    dy [N, H, C, W] in ``out_dtype``, f32 accumulation."""
    n, k = g.shape
    kk, h, c, w = wt.shape
    assert kk == k, (kk, k)
    bh = _pick_block_h(h, c, w, n, k)
    nblk = h // bh
    return pl.pallas_call(
        functools.partial(_dgrad_kernel, n_batch=n, k_cls=k),
        out_shape=jax.ShapeDtypeStruct((n, h, c, w), out_dtype),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((k, bh, c, w), lambda i: (0, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((n, bh, c, w), lambda i: (0, i, 0, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=default_interpret(interpret),
    )(g, wt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fc_t(y, kernel2d, bias, dtype, interpret=None):
    """The transposed plan's fc: y [N, H, C, W], kernel2d [H*C*W, K] f32
    (canonical (h, c, w) row order — models/convnet.py), bias [K] f32 ->
    logits [N, K] in ``dtype``. The weight is staged ONCE per step in
    the K-MAJOR form wT [K, H, C, W] (kernel2d's own physical layout is
    already k-major — {0,1} in the AOT dump — so .T is a bitcast and
    this is one convert): forward contracts against it, the input-grad
    kernel reads it as-is (saved as a residual: one weight-sized bf16
    buffer held through the backward, vs re-deriving it from the f32
    param at 1.1 GB of traffic), and the weight-grad is emitted k-major
    too, so its flatten back to the canonical 2D rows is a
    transpose-BITCAST instead of the {2,3,1,0}->k-major relayout copy
    XLA's hcwk-minor einsum paid (copy_bitcast_fusion, ~4.6 ms est /
    1.4 GB in measured/hlo_cycles). Wgrad numerics vs the kill-switch
    einsum path: same contraction, tolerance-equal, not bit-equal (see
    module docstring)."""
    return _fc_fwd_core(y, kernel2d, bias, dtype)[0]


def _fc_fwd_core(y, kernel2d, bias, dtype):
    n, h, c, w = y.shape
    k = kernel2d.shape[-1]
    wt = kernel2d.T.reshape(k, h, c, w).astype(dtype)
    out = jnp.einsum("nhcw,khcw->nk", y, wt)
    return out + bias.astype(dtype), wt


def _fc_vjp_fwd(y, kernel2d, bias, dtype, interpret):
    out, wt = _fc_fwd_core(y, kernel2d, bias, dtype)
    return out, (y, wt)


def _fc_vjp_bwd(dtype, interpret, res, g):
    y, wt = res
    k = wt.shape[0]
    gf = g.astype(jnp.float32)
    dy = fc_dgrad_t(gf, wt, y.dtype, interpret)
    # weight-grad k-major, then a transpose-bitcast to the canonical
    # [H*C*W, K] rows (physically identical buffers — the param's {0,1}
    # layout IS k-major)
    dkt = jnp.einsum("nhcw,nk->khcw", y, gf,
                     preferred_element_type=jnp.float32)
    dkernel = dkt.reshape(k, -1).T.astype(jnp.float32)
    db = gf.sum(0).astype(jnp.float32)
    return dy, dkernel, db


fc_t.defvjp(_fc_vjp_fwd, _fc_vjp_bwd)
