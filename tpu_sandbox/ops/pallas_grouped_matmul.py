"""Pallas TPU kernel: grouped matrix product over a row buffer whose tiles
each belong to one group (expert).

``x [P, K]`` is a buffer of rows sorted by group, every group's rows
aligned to the row tile ``tm``; ``tile_group [P // tm]`` says which group's
weights ``w [G, K, N]`` each row tile is multiplied by (scalar prefetch: the
index map of the weight block reads it). **Every tile is multiplied**,
whatever it holds: a tile of zero rows costs what a full one costs, so the
time of a call is a function of the shapes alone. Nothing in the kernels
branches or bounds a loop on the group sizes.

Three products, one ``custom_vjp``:

- forward ``out[i] = x[i] @ w[g(i)]`` and the input gradient
  ``dx[i] = dy[i] @ w[g(i)].T``: one kernel, grid (row tiles, column tiles,
  contraction tiles), contraction innermost with an fp32 accumulator;
- the weight gradient ``dw[g] = sum over the tiles i of g of x[i].T @ dy[i]``:
  grid (K tiles, N tiles, row tiles), row tiles innermost. ``tile_group`` is
  non-decreasing, so the tiles of one group are consecutive, the output
  block of a group stays in VMEM while they accumulate, and is written when
  the group changes. The accumulator is cleared by a select on the
  prefetched "first tile of its group" flag, not by a branch. Every group
  must own at least one tile (the caller aligns an empty group to one tile
  of zero rows), or its gradient block would never be written.

Operands in the input dtype (bf16 on the MXU), accumulation in fp32.
Interpreted off-TPU, like ops.pallas_attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tpu_sandbox.ops.pallas_common import LANE, default_interpret, kernel_site


def _tile(dim: int, cap: int) -> int:
    """The largest multiple of the lane width that divides ``dim`` and is at
    most ``cap``; ``dim`` itself where there is none (small test shapes)."""
    best = 0
    for t in range(LANE, min(dim, cap) + 1, LANE):
        if dim % t == 0:
            best = t
    return best or dim


def _gmm_kernel(group_ref, x_ref, w_ref, o_ref, acc, *, transpose_rhs: bool):
    del group_ref  # read by the weight block's index map only
    kk = pl.program_id(2)
    dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
    part = jax.lax.dot_general(x_ref[...], w_ref[0], dims,
                               preferred_element_type=jnp.float32)
    acc[...] = jnp.where(kk == 0, part, acc[...] + part)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _emit():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _gmm(x, w, tile_group, tm: int, transpose_rhs: bool, interpret):
    """``x [P, K] @ w[g] [K, N]`` (or ``w[g] [N, K]`` transposed) -> [P, N]."""
    from jax.experimental.pallas import tpu as pltpu

    p, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    tk, tn = _tile(k, 2048), _tile(n, 512)
    if transpose_rhs:
        w_spec = pl.BlockSpec((1, tn, tk), lambda i, j, kk, g: (g[i], j, kk))
    else:
        w_spec = pl.BlockSpec((1, tk, tn), lambda i, j, kk, g: (g[i], kk, j))
    with kernel_site("gmm"):
        return pl.pallas_call(
            functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
            out_shape=jax.ShapeDtypeStruct((p, n), x.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(p // tm, n // tn, k // tk),
                in_specs=[pl.BlockSpec((tm, tk), lambda i, j, kk, g: (i, kk)),
                          w_spec],
                out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk, g: (i, j)),
                scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=default_interpret(interpret),
        )(tile_group, x, w)


def _tgmm_kernel(group_ref, first_ref, x_ref, dy_ref, dw_ref, acc):
    del group_ref
    i = pl.program_id(2)
    part = jax.lax.dot_general(x_ref[...], dy_ref[...],
                               (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    acc[...] = jnp.where(first_ref[i] == 1, part, acc[...] + part)
    # the block stays in VMEM while the group lasts; what is written back
    # when the group changes is its last, complete value
    dw_ref[0] = acc[...].astype(dw_ref.dtype)


def _tgmm(x, dy, tile_group, n_groups: int, tm: int, interpret):
    """``dw[g] = sum_{tiles i of g} x[i].T @ dy[i]`` -> [G, K, N]."""
    from jax.experimental.pallas import tpu as pltpu

    p, k = x.shape
    n = dy.shape[1]
    tk, tn = _tile(k, 2048), _tile(n, 512)
    first = jnp.concatenate([
        jnp.ones((1,), jnp.int32),
        (tile_group[1:] != tile_group[:-1]).astype(jnp.int32)])
    with kernel_site("tgmm"):
        return pl.pallas_call(
            _tgmm_kernel,
            out_shape=jax.ShapeDtypeStruct((n_groups, k, n), x.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(k // tk, n // tn, p // tm),
                in_specs=[pl.BlockSpec((tm, tk), lambda a, b, i, g, f: (i, a)),
                          pl.BlockSpec((tm, tn), lambda a, b, i, g, f: (i, b))],
                out_specs=pl.BlockSpec((1, tk, tn),
                                       lambda a, b, i, g, f: (g[i], a, b)),
                scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=default_interpret(interpret),
        )(tile_group, first, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(x, w, tile_group, tm: int = 256, interpret=None):
    """``out[r] = x[r] @ w[tile_group[r // tm]]``: x [P, K], w [G, K, N],
    tile_group [P // tm] int32, non-decreasing, every group present.
    Differentiable in ``x`` and ``w``."""
    return _gmm(x, w, tile_group, tm, False, interpret)


def _fwd(x, w, tile_group, tm, interpret):
    return _gmm(x, w, tile_group, tm, False, interpret), (x, w, tile_group)


def _bwd(tm, interpret, res, dy):
    x, w, tile_group = res
    dx = _gmm(dy, w, tile_group, tm, True, interpret)
    dw = _tgmm(x, dy, tile_group, w.shape[0], tm, interpret)
    return dx, dw.astype(w.dtype), None


grouped_matmul.defvjp(_fwd, _bwd)
