"""Pallas TPU kernels: fused BatchNorm-apply + ReLU + in-lane 2x2 max-pool.

The space-to-depth ConvNet (models/convnet_s2d.py) keeps channels on the
lane dim, so after each conv the whole BN/ReLU/pool tail is elementwise-
and-lane-local — yet XLA executes it as several HBM passes over the
~1.4 GB conv output (apply, pool, layout copies). These kernels do the
tail in ONE read of the conv output per direction.

Forward: z = relu(y*a + b) with a = gamma*rsqrt(var+eps) and
b = beta - mu*a as per-lane vectors. The 2x2 pool happens inside the lane
dim: the pool partners of lane c = (a*blk+b)*co + k sit at lane offsets
co (b's low bit) and blk*co (a's low bit), so two roll-and-max steps put
every 4-way max at its representative lane (a,b both even), and a
constant 0/1 selection matrix compacts representatives to the
(blk//2)^2*co output lanes with one MXU dot per row — exact, because each
output column selects a single lane.

Backward: train-mode BN backward (gradients flow through the batch
statistics) needs per-channel reductions, so it is two kernels:
``_bwd_reduce_kernel`` recomputes z from y (cheap VPU work — no big
residual is saved), routes the pooled cotangent back through the pool
with jnp.maximum's exact VJP semantics (winner takes it; exact ties split
0.5/0.5 — common in bf16, where comparisons happen on values rounded to
the activation dtype just like the unfused chain) and the ReLU mask, and
accumulates s1 = sum(dz) and s2 = sum(dz * t_hat) per lane across the
grid; ``_bwd_apply_kernel`` recomputes the same routing and
emits dy = gamma*inv*(dz - s1/M - t_hat*s2/M). dgamma = s2 (folded per
co), dbeta = s1.

Traffic per layer: fwd reads y once and writes the 4x-smaller pooled
output; bwd reads y twice, the pooled cotangent twice, and writes dy once
— vs the unfused path's additional full-tensor passes. Exactness vs the
unfused chain is pinned by tests/test_pallas_bn_tail.py; Mosaic lowering
by tests/test_mosaic_lowering.py. Used by ConvNetS2D(fused_tail=True) in
train mode (eval keeps the plain path: running stats are constants there,
which is a different backward).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from tpu_sandbox.ops.pallas_common import default_interpret, kernel_site


def selection_matrix(blk: int, co: int) -> np.ndarray:
    """[C, C/4] 0/1 matrix: column o=(a1*(blk//2)+b1)*co+k selects the
    representative lane c=(2*a1*blk + 2*b1)*co + k (a0 = b0 = 0)."""
    c_in, c_out = blk * blk * co, (blk // 2) ** 2 * co
    s = np.zeros((c_in, c_out), np.float32)
    for a1 in range(blk // 2):
        for b1 in range(blk // 2):
            for k in range(co):
                o = (a1 * (blk // 2) + b1) * co + k
                c = (2 * a1 * blk + 2 * b1) * co + k
                s[c, o] = 1.0
    return s


def _pool_fronts(z, co: int, blk: int):
    """(zb, m1, m1a): the rolled partners and pairwise maxima; m2 =
    max(m1, m1a) holds each 4-way max at its representative lane."""
    zb = jnp.roll(z, -co, axis=-1)
    m1 = jnp.maximum(z, zb)
    m1a = jnp.roll(m1, -blk * co, axis=-1)
    return zb, m1, m1a


def _route(z, g_exp, co: int, blk: int):
    """Pool VJP on one [W, C] row: cotangent g_exp lives at representative
    lanes; route it through the two pairwise maxima with jnp.maximum's
    exact VJP semantics — the winner takes the cotangent, EXACT ties split
    it 0.5/0.5 (ties are common in bf16, where the unfused chain compares
    rounded values; winner-take-all would diverge from it there). Nonzero
    values never wrap in the rolls: representatives + blk*co + co < C."""
    s, ss = co, blk * co
    zb, m1, m1a = _pool_fronts(z, co, blk)

    def weights(x, xb):
        # 1 / 0.5 / 0 for win / tie / loss, written as the mean of two
        # strict-and-weak comparisons: Mosaic cannot relayout the i1 mask
        # an `eq`-plus-select chain produces here ("Invalid relayout:
        # non-singleton logical dimension is replicated")
        return 0.5 * ((x > xb).astype(jnp.float32)
                      + (x >= xb).astype(jnp.float32))

    w2 = weights(m1, m1a)
    dm1 = g_exp * w2 + jnp.roll(g_exp * (1.0 - w2), ss, axis=-1)
    w1 = weights(z, zb)
    dz = dm1 * w1 + jnp.roll(dm1 * (1.0 - w1), s, axis=-1)
    return dz


def _rounded_relu(y_ref, a_ref, b_ref, r, dtype):
    """One row's z in the OUTPUT dtype: the unfused chain rounds the BN
    result to the activation dtype before relu/pool, so comparisons (pool
    argmax, relu mask) must happen on the rounded values to match it —
    in bf16 the rounding creates the very ties _route's 0.5-splitting
    exists for."""
    zpre = y_ref[0, r].astype(jnp.float32) * a_ref[0][None] + b_ref[0][None]
    # round to the activation dtype, then hold the values in f32: bf16 is
    # exactly embeddable, Mosaic's vector compare only supports f32, and
    # the ties live on the ROUNDED values either way
    return jnp.maximum(zpre.astype(dtype), 0).astype(jnp.float32)


def _fwd_kernel(y_ref, a_ref, b_ref, s_ref, out_ref, *, co: int, blk: int):
    hb = y_ref.shape[1]
    for r in range(hb):
        z = _rounded_relu(y_ref, a_ref, b_ref, r, out_ref.dtype)
        _, m1, m1a = _pool_fronts(z, co, blk)
        m2 = jnp.maximum(m1, m1a)
        out_ref[0, r] = jax.lax.dot_general(
            m2, s_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(out_ref.dtype)


def _row_dz(y_ref, a_ref, b_ref, g_ref, st_ref, r, co, blk, dtype):
    """Recompute one row's (rounded) z and route its pooled cotangent."""
    z = _rounded_relu(y_ref, a_ref, b_ref, r, dtype)
    g_exp = jax.lax.dot_general(  # [W, C/4] @ [C/4, C]: scatter to reps
        g_ref[0, r].astype(jnp.float32), st_ref[...],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    return _route(z, g_exp, co, blk) * (z > 0)


def _bwd_reduce_kernel(y_ref, a_ref, b_ref, g_ref, st_ref, mu_ref, inv_ref,
                       s1_ref, s2_ref, s1_scr, s2_scr,
                       *, co: int, blk: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init():
        s1_scr[:] = jnp.zeros_like(s1_scr)
        s2_scr[:] = jnp.zeros_like(s2_scr)

    hb = y_ref.shape[1]
    for r in range(hb):
        dz = _row_dz(y_ref, a_ref, b_ref, g_ref, st_ref, r, co, blk,
                     y_ref.dtype)
        y = y_ref[0, r].astype(jnp.float32)
        t_hat = (y - mu_ref[0][None]) * inv_ref[0][None]
        s1_scr[:] = s1_scr[:] + jnp.sum(dz, axis=0, keepdims=True)
        s2_scr[:] = s2_scr[:] + jnp.sum(dz * t_hat, axis=0, keepdims=True)

    @pl.when(jnp.logical_and(i == pl.num_programs(0) - 1,
                             j == pl.num_programs(1) - 1))
    def _emit():
        s1_ref[...] = s1_scr[:]
        s2_ref[...] = s2_scr[:]


def _bwd_apply_kernel(y_ref, a_ref, b_ref, g_ref, st_ref, mu_ref, inv_ref,
                      gi_ref, c1_ref, c2_ref, dy_ref, *, co: int, blk: int):
    hb = y_ref.shape[1]
    for r in range(hb):
        dz = _row_dz(y_ref, a_ref, b_ref, g_ref, st_ref, r, co, blk,
                     y_ref.dtype)
        y = y_ref[0, r].astype(jnp.float32)
        t_hat = (y - mu_ref[0][None]) * inv_ref[0][None]
        dy = gi_ref[0][None] * (dz - c1_ref[0][None] - t_hat * c2_ref[0][None])
        dy_ref[0, r] = dy.astype(dy_ref.dtype)


def _lane_expand(v_co, reps: int):
    """per-co vector -> lane vector [1, reps*co] (co minor, like the data)."""
    return jnp.tile(v_co.astype(jnp.float32), reps)[None]


def _grid_rows(h: int, w: int, c: int) -> int:
    """Rows per grid block, budgeted against scoped VMEM: the row loop
    keeps ~a dozen [w, c] f32 intermediates live, so rows are capped such
    that rows*w*c*14B stays under ~6 MB (at the ConvNet's 750x256 that is
    2 rows; tiny test shapes keep up to 10)."""
    cap = max(1, int(6 * 1024 * 1024 // max(w * c * 14, 1)))
    for hb in (10, 6, 5, 4, 3, 2, 1):
        if hb <= cap and h % hb == 0:
            return hb
    return 1


def unfused_reference(y, gamma, beta, co: int, blk: int, eps: float = 1e-5):
    """The unfused tail exactly as ConvNetS2D computes it in train mode:
    (pooled, mu, var). Single home for the contract the kernels are checked
    against (tests/test_pallas_bn_tail.py)."""
    from tpu_sandbox.models.convnet_s2d import block_max_pool

    *lead, c = y.shape
    yf = y.astype(jnp.float32).reshape(*lead, c // co, co)
    red = tuple(range(yf.ndim - 1))
    mu = jnp.mean(yf, axis=red)
    var = jnp.maximum(0.0, jnp.mean(jnp.square(yf), axis=red)
                      - jnp.square(mu))
    z = (yf - mu) * (jax.lax.rsqrt(var + eps) * gamma) + beta
    z = jax.nn.relu(z.reshape(*lead, c).astype(y.dtype))
    return block_max_pool(z, blk, co), mu, var


def _stats(y, co):
    yf = y.astype(jnp.float32).reshape(-1, y.shape[-1] // co, co)
    mu = jnp.mean(yf, axis=(0, 1))
    var = jnp.maximum(
        0.0, jnp.mean(jnp.square(yf), axis=(0, 1)) - jnp.square(mu)
    )
    return mu, var


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def fused_bn_relu_pool(y, gamma, beta, co, blk, eps=1e-5, interpret=None,
                       ysums=None):
    """[N,H,W,blk*blk*co] conv output -> ([N,H,W,(blk//2)**2*co] pooled,
    mu [co], var [co]) with train-mode batch statistics.

    Numerically the _GroupedBN(train=True) + relu + block_max_pool chain of
    models/convnet_s2d.py, in one HBM pass. mu/var are returned for the
    running-stats update; their cotangents are ignored (the stats update is
    not differentiated — flax BatchNorm behaves the same).

    ``ysums=(sum, sumsq)`` — per-LANE [1, c] f32 reductions of y, e.g. from
    ops/pallas_conv.py::conv3x3_stats — skips this function's own stats
    pass (a full extra HBM read of y). Their cotangents are zero by the
    same contract as mu/var: the train-mode backward here already routes
    the statistics' dependence on y through dy."""
    out, mu, var, _ = _forward(y, gamma, beta, co, blk, eps, interpret,
                               ysums)
    return out, mu, var


def _forward(y, gamma, beta, co, blk, eps, interpret, ysums=None):
    n, h, w, c = y.shape
    assert c == blk * blk * co, (c, blk, co)
    if ysums is None:
        mu, var = _stats(y, co)
    else:
        s_co = ysums[0][0].astype(jnp.float32).reshape(-1, co).sum(0)
        ss_co = ysums[1][0].astype(jnp.float32).reshape(-1, co).sum(0)
        count = y.size // co
        mu = s_co / count
        var = jnp.maximum(0.0, ss_co / count - jnp.square(mu))
    inv = jax.lax.rsqrt(var + eps)
    a_co = inv * gamma.astype(jnp.float32)
    a_lane = _lane_expand(a_co, blk * blk)
    b_lane = _lane_expand(beta.astype(jnp.float32) - mu * a_co, blk * blk)
    sel = jnp.asarray(selection_matrix(blk, co), jnp.float32)
    hb = _grid_rows(h, w, c)
    with kernel_site("bn_tail_fwd"):
        out = pl.pallas_call(
            functools.partial(_fwd_kernel, co=co, blk=blk),
            out_shape=jax.ShapeDtypeStruct((n, h, w, sel.shape[1]), y.dtype),
            grid=(n, h // hb),
            in_specs=[
                pl.BlockSpec((1, hb, w, c), lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec((1, c), lambda i, j: (0, 0)),
                pl.BlockSpec((1, c), lambda i, j: (0, 0)),
                pl.BlockSpec(sel.shape, lambda i, j: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, hb, w, sel.shape[1]),
                                   lambda i, j: (i, j, 0, 0)),
            interpret=default_interpret(interpret),
        )(y, a_lane, b_lane, sel)
    return out, mu, var, (a_lane, b_lane, inv)


def _vjp_fwd(y, gamma, beta, co, blk, eps, interpret, ysums=None):
    out, mu, var, (a_lane, b_lane, inv) = _forward(
        y, gamma, beta, co, blk, eps, interpret, ysums
    )
    return (out, mu, var), (y, gamma, mu, inv, a_lane, b_lane, ysums)


def _vjp_bwd(co, blk, eps, interpret, res, cts):
    from jax.experimental.pallas import tpu as pltpu

    g = cts[0]  # stats cotangents (cts[1:]) ignored — see docstring
    y, gamma, mu, inv, a_lane, b_lane, ysums = res
    n, h, w, c = y.shape
    hb = _grid_rows(h, w, c)
    interp = default_interpret(interpret)
    sel_t = jnp.asarray(selection_matrix(blk, co).T, jnp.float32)
    mu_lane = _lane_expand(mu, blk * blk)
    inv_lane = _lane_expand(inv, blk * blk)

    def vec():
        return pl.BlockSpec((1, c), lambda i, j: (0, 0))

    with kernel_site("bn_tail_bwd_reduce"):
        s1, s2 = pl.pallas_call(
            functools.partial(_bwd_reduce_kernel, co=co, blk=blk),
            out_shape=(jax.ShapeDtypeStruct((1, c), jnp.float32),
                       jax.ShapeDtypeStruct((1, c), jnp.float32)),
            grid=(n, h // hb),
            in_specs=[
                pl.BlockSpec((1, hb, w, c), lambda i, j: (i, j, 0, 0)),
                vec(), vec(),
                pl.BlockSpec((1, hb, w, sel_t.shape[0]),
                             lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec(sel_t.shape, lambda i, j: (0, 0)),
                vec(), vec(),
            ],
            out_specs=(pl.BlockSpec((1, c), lambda i, j: (0, 0)),
                       pl.BlockSpec((1, c), lambda i, j: (0, 0))),
            scratch_shapes=[
                pltpu.VMEM((1, c), jnp.float32),
                pltpu.VMEM((1, c), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
            ),
            interpret=interp,
        )(y, a_lane, b_lane, g, sel_t, mu_lane, inv_lane)

    groups = blk * blk
    m_count = n * h * w * groups
    s1_co = jnp.sum(s1[0].reshape(groups, co), axis=0)
    s2_co = jnp.sum(s2[0].reshape(groups, co), axis=0)
    gi_lane = _lane_expand(gamma.astype(jnp.float32) * inv, groups)
    c1_lane = _lane_expand(s1_co / m_count, groups)
    c2_lane = _lane_expand(s2_co / m_count, groups)

    with kernel_site("bn_tail_bwd"):
        dy = pl.pallas_call(
            functools.partial(_bwd_apply_kernel, co=co, blk=blk),
            out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
            grid=(n, h // hb),
            in_specs=[
                pl.BlockSpec((1, hb, w, c), lambda i, j: (i, j, 0, 0)),
                vec(), vec(),
                pl.BlockSpec((1, hb, w, sel_t.shape[0]),
                             lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec(sel_t.shape, lambda i, j: (0, 0)),
                vec(), vec(), vec(), vec(), vec(),
            ],
            out_specs=pl.BlockSpec((1, hb, w, c), lambda i, j: (i, j, 0, 0)),
            interpret=interp,
        )(y, a_lane, b_lane, g, sel_t, mu_lane, inv_lane, gi_lane, c1_lane,
          c2_lane)
    dsums = jax.tree.map(jnp.zeros_like, ysums)  # see docstring; None -> None
    return dy, s2_co.astype(gamma.dtype), s1_co.astype(gamma.dtype), dsums


fused_bn_relu_pool.defvjp(_vjp_fwd, _vjp_bwd)
