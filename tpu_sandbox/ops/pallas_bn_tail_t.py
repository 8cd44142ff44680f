"""Fused BN-apply + ReLU + 2x2 block max-pool for the TRANSPOSED layout
[N, H, C, W] — the tail companion of ops/pallas_conv_t.py.

Same math and the same exactness contract as ops/pallas_bn_tail.py (the
NHWC pair): z = relu(round(y*a + b)) with a = gamma*rsqrt(var+eps),
b = beta - mu*a; 2x2 pool inside the channel dim; train-mode BN backward
with gradients flowing through the batch statistics; pool VJP with exact
0.5/0.5 tie splitting on values rounded to the activation dtype. The
only difference is orientation: channels live on SUBLANES here, so

- the per-channel vectors (a, b, mu, inv, ...) are [C, 1] columns
  broadcast over lanes instead of [1, C] lane vectors;
- the pool partners of channel c = (a*blk+b)*co + k sit at SUBLANE
  offsets co (b's low bit) and blk*co (a's low bit) — the roll-and-max
  runs along sublanes;
- the compaction/scatter matmuls flip sides: out = selT [C/4, C] @ m2
  [C, W] and g_exp = sel [C, C/4] @ g [C/4, W], both clean [M,K]x[K,N]
  MXU forms with W on lanes.

Reference chain being fused: models/convnet_s2d.py _GroupedBN(train) +
relu + block_max_pool, transposed (see convnet_s2d_t.py); ultimately the
BN/ReLU/MaxPool tails of /root/reference/mnist_onegpu.py:11-31.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from tpu_sandbox.ops.pallas_bn_tail import selection_matrix
from tpu_sandbox.ops.pallas_common import default_interpret, kernel_site


def _pool_fronts(z, co: int, blk: int):
    """(zb, m1, m1a): rolled partners and pairwise maxima along SUBLANES;
    m2 = max(m1, m1a) holds each 4-way max at its representative row."""
    zb = jnp.roll(z, -co, axis=0)
    m1 = jnp.maximum(z, zb)
    m1a = jnp.roll(m1, -blk * co, axis=0)
    return zb, m1, m1a


def _route(z, g_exp, co: int, blk: int):
    """Pool VJP on one [C, W] row: winner takes the cotangent, exact ties
    split 0.5/0.5 (same contract as pallas_bn_tail._route; the rolls run
    along sublanes here). Nonzero values never wrap: representatives +
    blk*co + co < C."""
    s, ss = co, blk * co
    zb, m1, m1a = _pool_fronts(z, co, blk)

    def weights(x, xb):
        return 0.5 * ((x > xb).astype(jnp.float32)
                      + (x >= xb).astype(jnp.float32))

    w2 = weights(m1, m1a)
    dm1 = g_exp * w2 + jnp.roll(g_exp * (1.0 - w2), ss, axis=0)
    w1 = weights(z, zb)
    dz = dm1 * w1 + jnp.roll(dm1 * (1.0 - w1), s, axis=0)
    return dz


def _rounded_relu(y_ref, a_ref, b_ref, r, dtype):
    """One row's z in the OUTPUT dtype, held in f32 (same rounding/tie
    rationale as pallas_bn_tail._rounded_relu; vectors are columns)."""
    zpre = y_ref[0, r].astype(jnp.float32) * a_ref[...] + b_ref[...]
    return jnp.maximum(zpre.astype(dtype), 0).astype(jnp.float32)


def _fwd_kernel(y_ref, a_ref, b_ref, st_ref, out_ref, *, co: int, blk: int):
    hb = y_ref.shape[1]
    for r in range(hb):
        z = _rounded_relu(y_ref, a_ref, b_ref, r, out_ref.dtype)
        _, m1, m1a = _pool_fronts(z, co, blk)
        m2 = jnp.maximum(m1, m1a)
        out_ref[0, r] = jax.lax.dot_general(
            st_ref[...], m2, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(out_ref.dtype)


def _row_dz(y_ref, a_ref, b_ref, g_ref, s_ref, r, co, blk, dtype):
    """Recompute one row's (rounded) z and route its pooled cotangent."""
    z = _rounded_relu(y_ref, a_ref, b_ref, r, dtype)
    g_exp = jax.lax.dot_general(  # [C, C/4] @ [C/4, W]: scatter to reps
        s_ref[...], g_ref[0, r].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    return _route(z, g_exp, co, blk) * (z > 0)


def _bwd_reduce_kernel(y_ref, a_ref, b_ref, g_ref, s_ref, mu_ref, inv_ref,
                       s1_ref, s2_ref, s1_scr, s2_scr,
                       *, co: int, blk: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init():
        s1_scr[:] = jnp.zeros_like(s1_scr)
        s2_scr[:] = jnp.zeros_like(s2_scr)

    hb = y_ref.shape[1]
    for r in range(hb):
        dz = _row_dz(y_ref, a_ref, b_ref, g_ref, s_ref, r, co, blk,
                     y_ref.dtype)
        y = y_ref[0, r].astype(jnp.float32)
        t_hat = (y - mu_ref[...]) * inv_ref[...]
        s1_scr[:] = s1_scr[:] + jnp.sum(dz, axis=1, keepdims=True)
        s2_scr[:] = s2_scr[:] + jnp.sum(dz * t_hat, axis=1, keepdims=True)

    @pl.when(jnp.logical_and(i == pl.num_programs(0) - 1,
                             j == pl.num_programs(1) - 1))
    def _emit():
        s1_ref[...] = s1_scr[:]
        s2_ref[...] = s2_scr[:]


def _bwd_apply_kernel(y_ref, a_ref, b_ref, g_ref, s_ref, mu_ref, inv_ref,
                      gi_ref, c1_ref, c2_ref, dy_ref, *, co: int, blk: int):
    hb = y_ref.shape[1]
    for r in range(hb):
        dz = _row_dz(y_ref, a_ref, b_ref, g_ref, s_ref, r, co, blk,
                     y_ref.dtype)
        y = y_ref[0, r].astype(jnp.float32)
        t_hat = (y - mu_ref[...]) * inv_ref[...]
        dy = gi_ref[...] * (dz - c1_ref[...] - t_hat * c2_ref[...])
        dy_ref[0, r] = dy.astype(dy_ref.dtype)


def _col_expand(v_co, reps: int):
    """per-co vector -> sublane column [reps*co, 1] (co minor)."""
    return jnp.tile(v_co.astype(jnp.float32), reps)[:, None]


def _grid_rows(h: int, w: int, c: int) -> int:
    """Same VMEM-budget rule as pallas_bn_tail (the row loop keeps ~a
    dozen [c, w] f32 intermediates live)."""
    cap = max(1, int(6 * 1024 * 1024 // max(w * c * 14, 1)))
    for hb in (10, 6, 5, 4, 3, 2, 1):
        if hb <= cap and h % hb == 0:
            return hb
    return 1


def unfused_reference_t(y, gamma, beta, co: int, blk: int, eps: float = 1e-5):
    """The unfused transposed tail exactly as ConvNetS2DT computes it in
    train mode: (pooled, mu, var). Contract for tests."""
    from tpu_sandbox.models.convnet_s2d_t import block_max_pool_t

    *lead, c, w = y.shape
    yf = y.astype(jnp.float32).reshape(*lead, c // co, co, w)
    red = tuple(i for i in range(yf.ndim) if i != yf.ndim - 2)
    mu = jnp.mean(yf, axis=red)
    var = jnp.maximum(0.0, jnp.mean(jnp.square(yf), axis=red)
                      - jnp.square(mu))
    z = (yf - mu[:, None]) * (jax.lax.rsqrt(var + eps)
                              * gamma.astype(jnp.float32))[:, None] \
        + beta.astype(jnp.float32)[:, None]
    z = jax.nn.relu(z.reshape(*lead, c, w).astype(y.dtype))
    return block_max_pool_t(z, blk, co), mu, var


def _stats_t(y, co):
    yf = y.astype(jnp.float32)
    n, h, c, w = yf.shape
    yg = yf.reshape(n, h, c // co, co, w)
    mu = jnp.mean(yg, axis=(0, 1, 2, 4))
    var = jnp.maximum(
        0.0, jnp.mean(jnp.square(yg), axis=(0, 1, 2, 4)) - jnp.square(mu)
    )
    return mu, var


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def fused_bn_relu_pool_t(y, gamma, beta, co, blk, eps=1e-5, interpret=None,
                         ysums=None):
    """[N,H,blk*blk*co,W] conv output -> ([N,H,(blk//2)**2*co,W] pooled,
    mu [co], var [co]) with train-mode batch statistics.

    Numerically the transposed _GroupedBN(train=True) + relu +
    block_max_pool_t chain, in one HBM pass. mu/var cotangents ignored
    (stats update not differentiated — flax BatchNorm behaves the same).

    ``ysums=(sum [C,1], sumsq [C,1])`` f32 per-CHANNEL reductions of y,
    e.g. from ops/pallas_conv_t.py::conv3x3_t_stats — skips this
    function's own stats pass. Their cotangents are zero by the same
    contract as mu/var: the train-mode backward here already routes the
    statistics' dependence on y through dy."""
    out, mu, var, _ = _forward(y, gamma, beta, co, blk, eps, interpret,
                               ysums)
    return out, mu, var


def _forward(y, gamma, beta, co, blk, eps, interpret, ysums=None):
    n, h, c, w = y.shape
    assert c == blk * blk * co, (c, blk, co)
    if ysums is None:
        mu, var = _stats_t(y, co)
    else:
        s_co = ysums[0][:, 0].astype(jnp.float32).reshape(-1, co).sum(0)
        ss_co = ysums[1][:, 0].astype(jnp.float32).reshape(-1, co).sum(0)
        count = y.size // co
        mu = s_co / count
        var = jnp.maximum(0.0, ss_co / count - jnp.square(mu))
    inv = jax.lax.rsqrt(var + eps)
    a_co = inv * gamma.astype(jnp.float32)
    a_col = _col_expand(a_co, blk * blk)
    b_col = _col_expand(beta.astype(jnp.float32) - mu * a_co, blk * blk)
    sel_t = jnp.asarray(selection_matrix(blk, co).T, jnp.float32)
    hb = _grid_rows(h, w, c)
    with kernel_site("bn_tail_t_fwd"):
        out = pl.pallas_call(
            functools.partial(_fwd_kernel, co=co, blk=blk),
            out_shape=jax.ShapeDtypeStruct((n, h, sel_t.shape[0], w), y.dtype),
            grid=(n, h // hb),
            in_specs=[
                pl.BlockSpec((1, hb, c, w), lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec((c, 1), lambda i, j: (0, 0)),
                pl.BlockSpec((c, 1), lambda i, j: (0, 0)),
                pl.BlockSpec(sel_t.shape, lambda i, j: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, hb, sel_t.shape[0], w),
                                   lambda i, j: (i, j, 0, 0)),
            interpret=default_interpret(interpret),
        )(y, a_col, b_col, sel_t)
    return out, mu, var, (a_col, b_col, inv)


def _vjp_fwd(y, gamma, beta, co, blk, eps, interpret, ysums=None):
    out, mu, var, (a_col, b_col, inv) = _forward(
        y, gamma, beta, co, blk, eps, interpret, ysums
    )
    return (out, mu, var), (y, gamma, mu, inv, a_col, b_col, ysums)


def bwd_reduce(y, g, co, blk, a_col, b_col, mu, inv, interpret):
    """The backward's FIRST pass — per-channel s1 = Σdz and
    s2 = Σ dz·t_hat over the whole batch — exposed as a function so the
    conv1+tail fused backward (ops/pallas_conv1_tail_t.py) can run the
    identical reduction before its own fused apply+wgrad pass.
    Returns (s1_co [co], s2_co [co], mu_col, inv_col, sel)."""
    from jax.experimental.pallas import tpu as pltpu

    n, h, c, w = y.shape
    hb = _grid_rows(h, w, c)
    interp = default_interpret(interpret)
    sel = jnp.asarray(selection_matrix(blk, co), jnp.float32)
    mu_col = _col_expand(mu, blk * blk)
    inv_col = _col_expand(inv, blk * blk)

    def vec():
        return pl.BlockSpec((c, 1), lambda i, j: (0, 0))

    with kernel_site("bn_tail_t_bwd_reduce"):
        s1, s2 = pl.pallas_call(
            functools.partial(_bwd_reduce_kernel, co=co, blk=blk),
            out_shape=(jax.ShapeDtypeStruct((c, 1), jnp.float32),
                       jax.ShapeDtypeStruct((c, 1), jnp.float32)),
            grid=(n, h // hb),
            in_specs=[
                pl.BlockSpec((1, hb, c, w), lambda i, j: (i, j, 0, 0)),
                vec(), vec(),
                pl.BlockSpec((1, hb, sel.shape[1], w),
                             lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec(sel.shape, lambda i, j: (0, 0)),
                vec(), vec(),
            ],
            out_specs=(pl.BlockSpec((c, 1), lambda i, j: (0, 0)),
                       pl.BlockSpec((c, 1), lambda i, j: (0, 0))),
            scratch_shapes=[
                pltpu.VMEM((c, 1), jnp.float32),
                pltpu.VMEM((c, 1), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
            ),
            interpret=interp,
        )(y, a_col, b_col, g, sel, mu_col, inv_col)
    groups = blk * blk
    s1_co = jnp.sum(s1[:, 0].reshape(groups, co), axis=0)
    s2_co = jnp.sum(s2[:, 0].reshape(groups, co), axis=0)
    return s1_co, s2_co, mu_col, inv_col, sel


def bwd_scales(s1_co, s2_co, gamma, inv, groups: int, m_count: int):
    """The BN backward's per-channel normalization columns — the
    gamma·inv gain and the two centering terms dy = gi·(dz − c1 −
    t̂·c2) needs. Shared with the fused conv1/tail backward
    (ops/pallas_conv1_tail_t.py): its equality contract is that this
    math is THE SAME function, not a copy that can drift."""
    gi_col = _col_expand(gamma.astype(jnp.float32) * inv, groups)
    c1_col = _col_expand(s1_co / m_count, groups)
    c2_col = _col_expand(s2_co / m_count, groups)
    return gi_col, c1_col, c2_col


def _vjp_bwd(co, blk, eps, interpret, res, cts):
    g = cts[0]  # stats cotangents (cts[1:]) ignored — see docstring
    y, gamma, mu, inv, a_col, b_col, ysums = res
    n, h, c, w = y.shape
    hb = _grid_rows(h, w, c)
    interp = default_interpret(interpret)

    s1_co, s2_co, mu_col, inv_col, sel = bwd_reduce(
        y, g, co, blk, a_col, b_col, mu, inv, interpret)
    groups = blk * blk
    m_count = n * h * w * groups
    gi_col, c1_col, c2_col = bwd_scales(s1_co, s2_co, gamma, inv,
                                        groups, m_count)

    def vec():
        return pl.BlockSpec((c, 1), lambda i, j: (0, 0))

    with kernel_site("bn_tail_t_bwd"):
        dy = pl.pallas_call(
            functools.partial(_bwd_apply_kernel, co=co, blk=blk),
            out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
            grid=(n, h // hb),
            in_specs=[
                pl.BlockSpec((1, hb, c, w), lambda i, j: (i, j, 0, 0)),
                vec(), vec(),
                pl.BlockSpec((1, hb, sel.shape[1], w),
                             lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec(sel.shape, lambda i, j: (0, 0)),
                vec(), vec(), vec(), vec(), vec(),
            ],
            out_specs=pl.BlockSpec((1, hb, c, w), lambda i, j: (i, j, 0, 0)),
            interpret=interp,
        )(y, a_col, b_col, g, sel, mu_col, inv_col, gi_col, c1_col, c2_col)
    dsums = jax.tree.map(jnp.zeros_like, ysums)  # see docstring; None -> None
    return dy, s2_co.astype(gamma.dtype), s1_co.astype(gamma.dtype), dsums


fused_bn_relu_pool_t.defvjp(_vjp_fwd, _vjp_bwd)
