"""Per-kernel conv micro-benchmark at the s2d plan's REAL shapes.

Round-3 motive: the first on-chip run of the fused-conv plan measured
254 ms/step at bs=16 against a 33 ms AOT traffic floor and a ~48 ms
compute floor (BASELINE.md "The 10x target, argued") — the Pallas convs
are executing near ~21 TF/s where the shape analysis predicted ~110.
This tool separates WHICH kernel (conv1/conv2 x fwd/bwd, Pallas vs the
XLA lax.conv it replaced) eats the step, with the fetch-synced
differential timing of utils/profiling.py, so the optimization targets the
measured hot spot instead of the estimate.

Usage (chip): python tools/conv_micro.py [--batch 16] [--ops conv1_fwd,...]
Writes one JSON line per timed op to stdout.

Shapes (models/convnet_s2d.py, 3000^2 input):
  conv1: x [B,750,750,16]  w [3,3,16,256]   (r=4 scatter of 5x5 1->16)
  conv2: x [B,750,750,64]  w [3,3,64,128]   (r=2 scatter of 5x5 16->32)
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--iters", type=int, default=8)
    p.add_argument("--repeats", type=int, default=3,
                   help="timing repeats per op; rows publish the min and "
                        "the full sample list (run-to-run spread)")
    p.add_argument("--ops", type=str, default="")
    p.add_argument("--hw", type=int, default=750)
    p.add_argument("--force-cpu", action="store_true",
                   help="smoke-test the tool off-chip (interpret-mode "
                        "kernels; timings are not TPU claims)")
    p.add_argument("--trace", type=str, default="",
                   help="directory for a jax.profiler trace of each timed "
                        "op")
    args = p.parse_args()

    if args.force_cpu:
        from tpu_sandbox.utils.cli import ensure_devices
        ensure_devices(1, force_cpu=True)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_sandbox.ops.pallas_conv import (
        _flip_transpose,
        conv3x3,
        conv3x3_reference,
        conv3x3_stats,
    )
    from tpu_sandbox.ops.pallas_conv_t import (
        conv3x3_t,
        conv3x3_t_stats,
        conv3x3_t_wgrad,
    )
    from tpu_sandbox.utils.profiling import (
        host_sync,
        measure_per_step_repeated,
        trace as profiling_trace,
    )

    b, hw = args.batch, args.hw
    rng = np.random.default_rng(0)
    dev = jax.devices()[0]

    def mk(shape, dt=jnp.bfloat16):
        # standard_normal(dtype=f32): rng.normal would stage a float64
        # host transient (~4.6 GB for conv2 at bs=16) next to a live chip
        return jnp.asarray(
            rng.standard_normal(size=shape, dtype=np.float32) * 0.1, dt)

    shapes = {
        "conv1": dict(x=(b, hw, hw, 16), w=(3, 3, 16, 256)),
        "conv2": dict(x=(b, hw, hw, 64), w=(3, 3, 64, 128)),
    }

    def fwd_flops(x, w):
        bb, h, wd, c = x
        return 2 * bb * h * wd * 9 * c * w[-1]

    def time_op(name, step_fn, flops, traffic_bytes, *ops):
        """step_fn(acc, *ops)->scalar must data-depend on acc. The
        operands are REAL jit arguments, not closure captures: captured
        arrays bake into the HLO as constants (288 MB at bs=16)."""
        jstep = jax.jit(step_fn)

        def run_steps(k):
            acc = jnp.float32(0.0)
            for _ in range(k):
                acc = jstep(acc, *ops)
            return acc

        t = measure_per_step_repeated(run_steps, args.iters,
                                      repeats=args.repeats)
        spc = t["sec_per_step"]
        if args.trace:
            try:
                with profiling_trace(os.path.join(args.trace, name)):
                    host_sync(run_steps(2))
            except Exception as e:  # tracing is best-effort diagnostics
                print(json.dumps({"op": name,
                                  "trace_failed": f"{type(e).__name__}: "
                                                  f"{str(e)[:200]}"}),
                      flush=True)
        rec = {
            "op": name, "batch": b, "sec_per_call": round(spc, 6),
            "tflops": round(flops / spc / 1e12, 2) if spc > 0 else None,
            "hbm_gbps": round(traffic_bytes / spc / 1e9, 1)
            if spc > 0 else None,
            "flops": flops, "traffic_bytes_min": traffic_bytes,
            "device_kind": str(dev.device_kind),
            "timing_method": t["timing_method"],
            "repeats": t.get("repeats", 1),
            "sec_per_call_samples": t.get("sec_per_step_samples"),
            "spread_frac": t.get("spread_frac"),
        }
        if spc <= 0:
            # a non-positive differential is timing jitter, not a
            # measurement — never rank kernels by this row
            rec["degraded"] = "non-positive differential; noise, not a time"
        print(json.dumps(rec), flush=True)

    want = set(filter(None, args.ops.split(",")))

    for cname, sh in shapes.items():
        x = mk(sh["x"])
        w = mk(sh["w"])
        bias = mk((sh["w"][-1],))
        fl = fwd_flops(sh["x"], sh["w"])
        nbytes = lambda s: int(np.prod(s)) * 2
        io_fwd = nbytes(sh["x"]) + nbytes(sh["x"][:3] + (sh["w"][-1],))

        # -------- forward: pallas (stats variant = production), pallas
        # plain, and the XLA conv it replaced --------
        # The timed scalar must be a FULL reduction of every computed
        # array: an element slice like y[0,0,0,0] lets XLA push the slice
        # through the conv and compute a handful of pixels — observed
        # on-chip as conv1_bwd_xla "321 TF/s" (> the 197 peak). The sum
        # adds one fused output pass to both sides identically.
        def red(a):
            return jnp.sum(a.astype(jnp.float32)) * 1e-9

        if not want or f"{cname}_fwd" in want:
            def s_pallas(acc, x, w, bias):
                y, s, ss = conv3x3_stats(x + acc.astype(x.dtype), w, bias)
                return red(y)
            time_op(f"{cname}_fwd_pallas_stats", s_pallas, fl, io_fwd,
                    x, w, bias)

            def s_plain(acc, x, w, bias):
                y = conv3x3(x + acc.astype(x.dtype), w, bias)
                return red(y)
            time_op(f"{cname}_fwd_pallas", s_plain, fl, io_fwd, x, w, bias)

            def s_xla(acc, x, w, bias):
                y = conv3x3_reference(x + acc.astype(x.dtype), w, bias)
                return red(y)
            time_op(f"{cname}_fwd_xla", s_xla, fl, io_fwd, x, w, bias)

        # -------- backward (dx+dw+db together, via vjp), pallas vs XLA ----
        if not want or f"{cname}_bwd" in want:
            g = mk(sh["x"][:3] + (sh["w"][-1],))

            def s_bwd(acc, x, w, bias, g):
                _, vjp = jax.vjp(
                    lambda xx, ww, bb: conv3x3(xx, ww, bb),
                    x + acc.astype(x.dtype), w, bias)
                dx, dw, db = vjp(g)
                return red(dx) + red(dw) + red(db)
            time_op(f"{cname}_bwd_pallas", s_bwd, 2 * fl,
                    2 * nbytes(sh["x"]) + 2 * nbytes(g.shape),
                    x, w, bias, g)

            def s_bwd_xla(acc, x, w, bias, g):
                _, vjp = jax.vjp(
                    lambda xx, ww, bb: conv3x3_reference(xx, ww, bb),
                    x + acc.astype(x.dtype), w, bias)
                dx, dw, db = vjp(g)
                return red(dx) + red(dw) + red(db)
            time_op(f"{cname}_bwd_xla", s_bwd_xla, 2 * fl,
                    2 * nbytes(sh["x"]) + 2 * nbytes(g.shape),
                    x, w, bias, g)

        # -------- transposed-layout kernels (pallas_conv_t): x [B,H,C,W]
        # — the round-3 rework; same math, channels on sublanes. The
        # big device arrays are shared across sections and dropped per
        # conv: per-section fresh 4.6 GB cotangents accumulated across
        # sections OOM'd the 16 GB chip on the first run --------
        t_ops = {f"{cname}_{o}" for o in
                 ("fwd_t", "bwd_t", "wgrad_t", "dgrad_t")}
        if cname == "conv1":
            t_ops.add("conv1_sparse")
        g_ops = t_ops - {f"{cname}_fwd_t"}
        if not want or (want & t_ops):
            xt = mk((sh["x"][0], sh["x"][1], sh["x"][3], sh["x"][2]))
        if not want or (want & g_ops):
            # only when a backward op needs it: at conv1 bs=16 this is a
            # 4.6 GB array on a 16 GB chip
            gt = mk((sh["x"][0], sh["x"][1], sh["w"][-1], sh["x"][2]))

        if not want or f"{cname}_fwd_t" in want:
            def s_t(acc, xt, w, bias):
                y = conv3x3_t(xt + acc.astype(xt.dtype), w, bias)
                return red(y)
            time_op(f"{cname}_fwd_pallas_t", s_t, fl, io_fwd, xt, w, bias)

            def s_t_stats(acc, xt, w, bias):
                y, s, ss = conv3x3_t_stats(xt + acc.astype(xt.dtype),
                                           w, bias)
                return red(y)
            time_op(f"{cname}_fwd_pallas_t_stats", s_t_stats, fl, io_fwd,
                    xt, w, bias)

        if not want or f"{cname}_bwd_t" in want:
            def s_bwd_t(acc, xt, w, bias, gt):
                _, vjp = jax.vjp(
                    lambda xx, ww, bb: conv3x3_t(xx, ww, bb),
                    xt + acc.astype(xt.dtype), w, bias)
                dx, dw, db = vjp(gt)
                return red(dx) + red(dw) + red(db)
            time_op(f"{cname}_bwd_pallas_t", s_bwd_t, 2 * fl,
                    2 * nbytes(sh["x"]) + 2 * nbytes(gt.shape),
                    xt, w, bias, gt)

        # wgrad alone (the isolated fused dw+db pass — what conv1's
        # backward pays in the real step, where dx is DCE'd) and dgrad
        # alone (fwd kernel on flipped weights)
        if not want or f"{cname}_wgrad_t" in want:
            # r05 restage race: explicit-gT native dot vs Mosaic's own
            # lane-lane handling (VERDICT r04 next-2, the named wgrad
            # per-row-transpose bottleneck). Same math (equality-tested);
            # sec_per_call decides the production default.
            for restage in ("gt", "auto"):
                def s_wgrad_t(acc, xt, gt, _r=restage):
                    dwt, db = conv3x3_t_wgrad(xt + acc.astype(xt.dtype),
                                              gt, restage=_r)
                    return red(dwt) + red(db)
                time_op(f"{cname}_wgrad_pallas_t[{restage}]", s_wgrad_t,
                        fl, nbytes(sh["x"]) + nbytes(gt.shape), xt, gt)

        if not want or f"{cname}_dgrad_t" in want:
            wf = _flip_transpose(w)
            zb = jnp.zeros((sh["x"][-1],), gt.dtype)

            def s_dgrad_t(acc, gt, wf, zb):
                y = conv3x3_t(gt + acc.astype(gt.dtype), wf, zb)
                return red(y)
            time_op(f"{cname}_dgrad_pallas_t", s_dgrad_t,
                    fwd_flops((sh["x"][0], sh["x"][1], sh["x"][2],
                               sh["w"][-1]), wf.shape),
                    nbytes(gt.shape) + nbytes(sh["x"]),
                    gt, wf, zb)

        # -------- the r04 sparse-tap conv1 (union tap tile, K=64):
        # race it against the scattered-3x3 rows above. Executed-flop
        # basis differs by design (64 vs 144 K-rows) — compare
        # sec_per_call, not tflops, across kernels --------
        if cname == "conv1" and (not want or "conv1_sparse" in want):
            from tpu_sandbox.ops.pallas_conv5_t import (
                conv1_s2d_t,
                conv1_s2d_t_stats,
                conv1_s2d_t_wgrad,
            )

            fl_sp = 2 * b * hw * hw * 64 * 256
            k5 = mk((5, 5, 1, 16))
            b16 = mk((16,))

            def s_sparse(acc, xt, k5, b16):
                y = conv1_s2d_t(xt + acc.astype(xt.dtype), k5, b16)
                return red(y)
            time_op("conv1_fwd_sparse", s_sparse, fl_sp, io_fwd,
                    xt, k5, b16)

            def s_sparse_stats(acc, xt, k5, b16):
                y, s, ss = conv1_s2d_t_stats(xt + acc.astype(xt.dtype),
                                             k5, b16)
                return red(y)
            time_op("conv1_fwd_sparse_stats", s_sparse_stats, fl_sp,
                    io_fwd, xt, k5, b16)

            for restage in ("gt", "auto"):
                def s_sparse_wgrad(acc, xt, gt, _r=restage):
                    dw1, db = conv1_s2d_t_wgrad(
                        xt + acc.astype(xt.dtype), gt, restage=_r)
                    return red(dw1) + red(db)
                time_op(f"conv1_wgrad_sparse[{restage}]", s_sparse_wgrad,
                        fl_sp, nbytes(sh["x"]) + nbytes(gt.shape), xt, gt)

        if not want or (want & t_ops):
            del xt
        if not want or (want & g_ops):
            del gt

        # -------- dgrad alone (fwd kernel, flipped weights) --------
        if not want or f"{cname}_dgrad" in want:
            g = mk(sh["x"][:3] + (sh["w"][-1],))
            wf = _flip_transpose(w)
            zb = jnp.zeros((sh["x"][-1],), g.dtype)

            def s_dgrad(acc, g, wf, zb):
                y = conv3x3(g + acc.astype(g.dtype), wf, zb)
                return red(y)
            time_op(f"{cname}_dgrad_pallas", s_dgrad,
                    fwd_flops(g.shape, wf.shape),
                    nbytes(g.shape) + nbytes(sh["x"]),
                    g, wf, zb)

    print(json.dumps({"note": "pair tflops against the shape's MXU "
                              "ceiling and hbm_gbps against ~819 GB/s "
                              "(v5e) to see which wall each kernel hits"}),
          flush=True)


if __name__ == "__main__":
    main()
