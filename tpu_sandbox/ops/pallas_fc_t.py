"""The fc head of the TRANSPOSED plan, against the weight as it is stored.

The s2dt step's fc is the ~18M-feature dense layer (reference
mnist_onegpu.py:27-30's LazyLinear at 3000^2): ``out[n, k] = sum_f
y[n, f] * kernel[f, k]`` with f = (h, c, w) flattened in that order,
K = 10, N = 5. On the v5e the f32 ``[F, K]`` parameter lives as
``{0,1:T(8,128)}``: the K classes on sublanes, the features on lanes —
physically ``kernel.T``, ``[K, F]``. Any other view of it is a copy of
the whole weight: up to PR 23 this file took a ``[K, H, C, W]`` view,
called it a bitcast, and XLA moved the weight through two per-class
``while`` loops every step, 36 ms of an 89.7 ms step on the chip
(PERF.md, PR 24). Nothing here touches the weight in any layout but
``[K, F]`` now. What crosses between the activation's native layout
``[N, H, C, W]`` (c on sublanes, w on lanes, 750 padded to 768) and the
flat feature order is the activation, inside two small kernels:

- ``fc_flatten_t``: y ``[N, H, C, W]`` -> y2 ``[N, F]``. Each (h, c) row
  of w lanes is stored at its flat offset in a VMEM row (a lane-unaligned
  store). The forward contraction and the weight gradient are plain XLA
  contractions of y2 against ``kernel.T``; y2 is the saved residual.
- ``fc_dgrad_t``: ``dy[n, f] = sum_k g[n, k] * wT[k, f]`` on ``[K, L]``
  blocks of the f32 parameter — K scalars per output element, an
  outer-product accumulation on the VPU (the MXU would want a new
  stationary tile every 128 features) — un-flattened row by row in VMEM
  and written in the native layout bn2's backward kernel reads.

The weight gradient ``einsum('nf,nk->kf')`` is written ``[K, F]``, i.e.
in the parameter's own layout, so on one chip XLA fuses it into the SGD
update and on several it stands in front of the gradient's ``psum``.

``fc_t`` is a custom_vjp over (y, kernel2d, bias) with the f32
``[H*C*W, K]`` kernel PARAMETER as the primal. Numerics against the
kill-switch einsum path (``TPU_SANDBOX_NO_PALLAS_FC=1`` in
models/convnet_s2d_t.py::_DenseT, read at trace time): the same
contractions with f32 accumulation; the weight gradient takes the f32
cotangent (autodiff rounds it to bf16 at the astype boundary) and the
input gradient the f32 weight, so equality is pinned to tolerance, not
bits, in tests/test_pallas_fc_t.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_sandbox.ops.pallas_common import (
    LANE,
    default_interpret,
    kernel_site,
    round_up,
)

_VMEM_LIMIT = 100_000_000
#: lanes of the flat row handled per accumulation pass of the dgrad kernel
#: (16 f32 vregs of 8 sublanes: the accumulator stays in registers)
_CHUNK = 16 * LANE


def _pick_block_h(h: int, c: int, w: int) -> int:
    """Rows of h per grid block: the fewest whose flat extent ``bh*c*w``
    is a whole number of 128-lane tiles, so that a block starts on a tile
    at both ends (at 3000^2: 2 rows x 32 channels x 750 = 375 tiles, a
    3 MB block of the f32 weight). No such divisor of h: one block holds
    the whole map (block == array is always legal; tiny maps only)."""
    for bh in range(1, h):
        if h % bh == 0 and (bh * c * w) % LANE == 0:
            return bh
    return h


def _rows(bh: int, c: int, w: int):
    """(h, c, flat lane offset) of every w-row of a block, in flat order."""
    return [(hh, cc, (hh * c + cc) * w) for hh in range(bh) for cc in range(c)]


def _flatten_kernel(y_ref, y2_ref, flat_scr, *, n_batch, bh, c, w):
    for n in range(n_batch):
        for hh, cc, off in _rows(bh, c, w):
            flat_scr[n:n + 1, off:off + w] = y_ref[n, hh, cc:cc + 1, :]
    y2_ref[...] = flat_scr[0:n_batch, :]


def fc_flatten_t(y, interpret=None):
    """y [N, H, C, W] -> y2 [N, H*C*W], rows flattened in (h, c, w)
    order: the reshape, done in VMEM instead of by XLA's relayout loop."""
    n, h, c, w = y.shape
    bh = _pick_block_h(h, c, w)
    lanes = bh * c * w
    with kernel_site("fc_t_flatten"):
        return pl.pallas_call(
            functools.partial(_flatten_kernel, n_batch=n, bh=bh, c=c, w=w),
            out_shape=jax.ShapeDtypeStruct((n, h * c * w), y.dtype),
            grid=(h // bh,),
            in_specs=[pl.BlockSpec((n, bh, c, w), lambda i: (0, i, 0, 0))],
            out_specs=pl.BlockSpec((n, lanes), lambda i: (0, i)),
            # rows move in y's own dtype (packed bf16 rows and all: 0.90 ms a
            # step on the chip against 1.24 through f32, PERF.md PR 24); 16 rows
            # are a whole bf16 tile
            scratch_shapes=[pltpu.VMEM((round_up(n, 16), lanes), y.dtype)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=default_interpret(interpret),
        )(y)


def _dgrad_kernel(g_ref, wt_ref, dy_ref, flat_scr, nat_scr, *,
                  n_batch, k_cls, bh, c, w):
    lanes = bh * c * w
    g = g_ref[...]
    # flat dy for every image at once: [N, chunk] += g[:, k] (x) wT[k, chunk]
    for lo in range(0, lanes, _CHUNK):
        hi = min(lo + _CHUNK, lanes)
        acc = g[:, 0:1] * wt_ref[0:1, lo:hi]
        for k in range(1, k_cls):
            acc = acc + g[:, k:k + 1] * wt_ref[k:k + 1, lo:hi]
        flat_scr[0:n_batch, lo:hi] = acc

    # a loop over the images, not an unrolled one: a dynamic sublane index
    # on the load costs nothing on the chip and keeps the program short
    # (the unrolled rows are what the head adds to every trace + lower);
    # the same index on the flatten kernel's store cost 0.15 ms a step, and
    # a loop over the chunks above 0.1 ms (PERF.md, PR 24): those stay
    # unrolled
    def one_image(n, carry):
        for hh, cc, off in _rows(bh, c, w):
            nat_scr[hh, cc:cc + 1, :] = flat_scr[pl.ds(n, 1), off:off + w]
        dy_ref[n] = nat_scr[...].astype(dy_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n_batch, one_image, 0)


def fc_dgrad_t(g, wt, hcw, out_dtype, interpret=None):
    """g [N, K] f32, wT [K, H*C*W] f32 (the parameter, transposed: its
    stored layout) -> dy [N, H, C, W] in ``out_dtype``, f32 accumulation."""
    n, k = g.shape
    h, c, w = hcw
    assert wt.shape == (k, h * c * w), (wt.shape, g.shape, hcw)
    bh = _pick_block_h(h, c, w)
    lanes = bh * c * w
    with kernel_site("fc_t_dgrad"):
        return pl.pallas_call(
            functools.partial(_dgrad_kernel, n_batch=n, k_cls=k, bh=bh, c=c,
                              w=w),
            out_shape=jax.ShapeDtypeStruct((n, h, c, w), out_dtype),
            grid=(h // bh,),
            in_specs=[
                pl.BlockSpec((n, k), lambda i: (0, 0)),
                pl.BlockSpec((k, lanes), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((n, bh, c, w), lambda i: (0, i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((round_up(n, 8), lanes), jnp.float32),
                pltpu.VMEM((bh, c, w), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=default_interpret(interpret),
        )(g, wt)


def fc_t(y, kernel2d, bias, dtype, interpret=None):
    """The transposed plan's fc: y [N, H, C, W], kernel2d [H*C*W, K] f32
    (canonical (h, c, w) row order — models/convnet.py), bias [K] f32 ->
    logits [N, K] in ``dtype``. Every contraction runs against
    ``kernel2d.T`` ([K, F], the parameter's stored layout: see the module
    docstring); the saved residual is the flat activation y2 [N, F], not
    a copy of the weight."""
    return _fc(y, kernel2d, bias, tuple(y.shape[1:]), dtype, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fc(y, kernel2d, bias, hcw, dtype, interpret):
    return _fc_vjp_fwd(y, kernel2d, bias, hcw, dtype, interpret)[0]


def _fc_vjp_fwd(y, kernel2d, bias, hcw, dtype, interpret):
    y2 = fc_flatten_t(y, interpret)
    out = jnp.einsum("nf,kf->nk", y2, kernel2d.T.astype(dtype))
    return out + bias.astype(dtype), (y2, kernel2d)


def _fc_vjp_bwd(hcw, dtype, interpret, res, g):
    y2, kernel2d = res
    gf = g.astype(jnp.float32)
    dy = fc_dgrad_t(gf, kernel2d.T, hcw, y2.dtype, interpret)
    # [K, F] is the parameter's own layout: the .T back is a bitcast
    dkernel = jnp.einsum("nf,nk->kf", y2, gf,
                         preferred_element_type=jnp.float32).T
    return dy, dkernel, gf.sum(0)


_fc.defvjp(_fc_vjp_fwd, _fc_vjp_bwd)
