"""Per-stage timing breakdown of the 3000x3000 ConvNet train step.

Diagnoses WHERE the headline step time goes on the real chip (the r02
question: honest timing said ~0.41 s/step = 1.2% MFU, ~20x above the
bandwidth floor). Each stage runs as its own jitted fori_loop whose
iterations are data-chained through a scalar tap (`x0 + tap*eps`), so XLA
can neither hoist nor CSE the op, and timing is the fetch-synced
differential (utils/profiling.py::measure_per_step).

Known suspect (from the compiler's allocator dump): activations shaped
[B, 3000, 3000, 16] are tiled T(8,128) with C=16 in the 128-lane minor dim
=> 8x padded bytes and lane-starved conv MACs. The NCHW variants and the
spatial-minor matmul formulation quantify what a layout change would buy.

Usage: python tools/convnet_breakdown.py [--batch 5] [--size 3000] [--n 3]
Prints one JSON line per stage: {"stage", "sec", "note"}.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

# runnable as `python tools/convnet_breakdown.py` from anywhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from tpu_sandbox.utils.profiling import measure_per_step


def chained(f, x0, n: int):
    """Time f applied to (a tap-perturbed copy of) x0, n-vs-2n differential.

    The tap (last element of f's output) feeds the next iteration's input,
    so the k applications form a serial data chain inside ONE compiled
    while_loop — no per-step dispatch, no hoisting.
    """

    @jax.jit
    def loop(x_init, k):
        def body(i, carry):
            x, acc = carry
            y = f(x)
            tap = jnp.ravel(y)[-1].astype(jnp.float32)
            return (x0 + (tap * 1e-30).astype(x0.dtype), acc + tap)

        _, acc = jax.lax.fori_loop(0, k, body, (x_init, jnp.float32(0)))
        return acc

    return measure_per_step(lambda k: loop(x0, k), n)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=5)
    p.add_argument("--size", type=int, default=3000)
    p.add_argument("--n", type=int, default=3)
    p.add_argument("--stages", default="",
                   help="comma-separated subset to run (default: all)")
    p.add_argument("--force-cpu", action="store_true",
                   help="run on the CPU backend even if an accelerator "
                        "is present")
    args = p.parse_args()
    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
    b, hw, n = args.batch, args.size, args.n
    only = set(s for s in args.stages.split(",") if s)

    rng = np.random.default_rng(0)
    f32, bf16 = jnp.float32, jnp.bfloat16

    def arr(*shape, dtype=bf16):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    x_raw = arr(b, 28, 28, 1)
    x_big = arr(b, hw, hw, 1)
    w1 = arr(5, 5, 1, 16)
    y1 = arr(b, hw, hw, 16)
    x2 = arr(b, hw // 2, hw // 2, 16)
    w2 = arr(5, 5, 16, 32)
    x3 = arr(b, hw // 4, hw // 4, 32)
    wfc = arr(32 * (hw // 4) ** 2, 10)

    conv = functools.partial(
        jax.lax.conv_general_dilated, window_strides=(1, 1), padding="SAME")

    def nhwc(x, w):
        return conv(x, w, dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def nchw(x, w):
        return conv(x, w, dimension_numbers=("NCHW", "OIHW", "NCHW"))

    def bn_relu_pool(y):
        mean = jnp.mean(y.astype(f32), axis=(0, 1, 2))
        var = jnp.var(y.astype(f32), axis=(0, 1, 2))
        yn = (y.astype(f32) - mean) * jax.lax.rsqrt(var + 1e-5)
        return jax.lax.reduce_window(
            jax.nn.relu(yn).astype(y.dtype), jnp.array(-jnp.inf, y.dtype),
            jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    # spatial-minor matmul conv: activations [B, C, H, W] with W in lanes
    # (no channel padding); k5 conv = 25 shift-slices contracted over C via
    # dot_general with H*W as the lane-major free dim
    def conv_spatial_minor(x_chw, w_oihw):
        bb, ci, hh, ww = x_chw.shape
        co = w_oihw.shape[0]
        xp = jnp.pad(x_chw, ((0, 0), (0, 0), (2, 2), (2, 2)))
        out = jnp.zeros((bb, co, hh, ww), f32)
        for dx in range(5):
            for dy in range(5):
                sl = jax.lax.dynamic_slice(
                    xp, (0, 0, dx, dy), (bb, ci, hh, ww))
                # [co, ci] @ [b, ci, h, w] -> [b, co, h, w]
                out = out + jax.lax.dot_general(
                    w_oihw[:, :, dx, dy], sl,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=f32,
                ).transpose(1, 0, 2, 3)
        return out.astype(x_chw.dtype)

    stages = {
        "resize": (lambda x: jax.image.resize(
            x, (b, hw, hw, 1), "bilinear"), x_raw),
        "conv1_nhwc": (lambda x: nhwc(x, w1), x_big),
        "conv1_nchw": (lambda x: nchw(
            x, jnp.transpose(w1, (3, 2, 0, 1))), jnp.transpose(x_big, (0, 3, 1, 2))),
        "conv1_spatial_minor": (lambda x: conv_spatial_minor(
            x, jnp.transpose(w1, (3, 2, 0, 1))), jnp.transpose(x_big, (0, 3, 1, 2))),
        "bn_relu_pool1": (bn_relu_pool, y1),
        "conv2_nhwc": (lambda x: nhwc(x, w2), x2),
        "conv2_nchw": (lambda x: nchw(
            x, jnp.transpose(w2, (3, 2, 0, 1))), jnp.transpose(x2, (0, 3, 1, 2))),
        "conv2_spatial_minor": (lambda x: conv_spatial_minor(
            x, jnp.transpose(w2, (3, 2, 0, 1))), jnp.transpose(x2, (0, 3, 1, 2))),
        "head_matmul": (lambda x: x.reshape(b, -1) @ wfc, x3),
        "fwd_conv1_grad": (lambda x: jax.grad(
            lambda xx: nhwc(xx, w1).astype(f32).sum())(x), x_big),
    }

    # the space-to-depth plan's two convs (models/convnet_s2d.py): k3 on a
    # 4x-coarser grid with fat channels — the lane-friendly replacements
    from tpu_sandbox.models.convnet_s2d import scatter_kernel
    x1s = arr(b, hw // 4, hw // 4, 16)
    w1s = scatter_kernel(w1, 4)                       # [3,3,16,256]
    x2s = arr(b, hw // 4, hw // 4, 64)
    w2s = scatter_kernel(w2, 2)                       # [3,3,64,128]
    stages.update({
        "conv1_s2d": (lambda x: nhwc(x, w1s), x1s),
        "conv2_s2d": (lambda x: nhwc(x, w2s), x2s),
        "conv1_s2d_grad": (lambda x: jax.grad(
            lambda xx: nhwc(xx, w1s).astype(f32).sum())(x), x1s),
        "conv2_s2d_grad": (lambda x: jax.grad(
            lambda xx: nhwc(xx, w2s).astype(f32).sum())(x), x2s),
    })

    # the r03 production kernels (ops/pallas_conv.py, ops/pallas_bn_tail.py):
    # per-stage times for the exact ops the fused plan runs, fwd and VJP —
    # measured against the XLA rows above, these attribute any gap between
    # the AOT traffic/compute floors and the whole-step headline
    from tpu_sandbox.ops.pallas_bn_tail import fused_bn_relu_pool
    from tpu_sandbox.ops.pallas_conv import conv3x3, conv3x3_stats

    b1s = arr(256, dtype=bf16)
    b2s = arr(128, dtype=bf16)
    gam1 = jnp.ones(16, f32)
    bet1 = jnp.zeros(16, f32)
    y1s = arr(b, hw // 4, hw // 4, 256)

    stages.update({
        "conv1_pallas": (lambda x: conv3x3(x, w1s.astype(bf16), b1s), x1s),
        "conv1_pallas_stats": (
            lambda x: conv3x3_stats(x, w1s.astype(bf16), b1s)[0], x1s),
        "conv2_pallas": (lambda x: conv3x3(x, w2s.astype(bf16), b2s), x2s),
        "conv1_pallas_vjp": (lambda x: jax.grad(
            lambda xx: conv3x3(xx, w1s.astype(bf16), b1s)
            .astype(f32).sum())(x), x1s),
        "conv2_pallas_vjp": (lambda x: jax.grad(
            lambda xx: conv3x3(xx, w2s.astype(bf16), b2s)
            .astype(f32).sum())(x), x2s),
        "tail1_pallas": (
            lambda y: fused_bn_relu_pool(y, gam1, bet1, 16, 4)[0], y1s),
        "tail1_pallas_vjp": (lambda y: jax.grad(
            lambda yy: fused_bn_relu_pool(yy, gam1, bet1, 16, 4)[0]
            .astype(f32).sum())(y), y1s),
    })

    for name, (f, x0) in stages.items():
        if only and name not in only:
            continue
        try:
            t = chained(f, x0, n)
            print(json.dumps({"stage": name,
                              "sec": round(t["sec_per_step"], 6),
                              "t_n": round(t["t_n_sec"], 4),
                              "t_2n": round(t["t_2n_sec"], 4)}), flush=True)
        except Exception as e:
            print(json.dumps({"stage": name,
                              "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)


if __name__ == "__main__":
    main()
