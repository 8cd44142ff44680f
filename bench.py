"""Headline benchmark: images/sec training the 3000x3000-MNIST ConvNet.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Timing is *fetch-synced differential* (utils/profiling.py::measure_per_step):
the sync is a device->host fetch of a value that data-depends on the
computation, and its fixed round-trip is cancelled by timing n and 2n steps
and differencing. Every number here is cross-checked against an analytic
FLOP model and the chip's published bf16 peak (utils/flops.py); an
implausible MFU marks the run ``degraded`` instead of being published as a
win.

The device metrics (images_per_sec, sweep, capacity, lm, pallas,
allreduce_bw, seq_scaling, convergence) run on the TPU or exit non-zero
without printing a metric line; ``--quick`` is the explicit CPU check of the
harness itself and its line says ``"platform": "cpu"``.

Baseline accounting (BASELINE.md): the reference publishes no throughput —
only that 2x RTX A5000 under DDP train effective batch 10 at 3000x3000.
``--baseline`` therefore defaults to an *estimated upper bound* for that rig:
~195 GFLOP/image of training compute at an optimistic 50% fp32 utilization
of 2x27.8 TF/s => ~142 img/s; we use 75 img/s from the older conservative
estimate's midpoint, ignoring the reference's real bottleneck (its
single-threaded host-side PIL 28->3000 resize, num_workers=0, caps it far
lower). Comparing against a generous estimate means vs_baseline understates,
never overstates, the win.

Run config mirrors the reference experiment: bs=5 per device, 3000x3000,
bf16 compute (fp32 params), synthetic MNIST (zero-egress), data-parallel
over all available devices (1 chip = plain jit path of the same step).
"""

import argparse
import functools
import json
import os
import subprocess
import sys
import time


def annotate_loss(result: dict, final_loss: float) -> None:
    """Loss-plausibility gate (VERDICT r03 next-3, same spirit as the MFU
    gate): init CE for 10 classes is ln(10) ~= 2.3 nats; a post-warmup
    loss past 2x that — or NaN/inf — is flagged. It is NOT zeroed,
    because the explanation is known and measured: the reference's own
    recipe (SGD 1e-4 on the ~18M-feature fc head at 3000^2) is divergent
    — one update shifts logits by lr*g*||f||^2 = O(100-1000), and the
    torch reference model itself measures loss 2.26 -> 110 -> 421 in two
    steps on this exact config (tools/reference_dynamics_probe.py;
    BASELINE.md "Loss dynamics at 3000^2"). The throughput number is
    sound; the chaotic loss is the architecture's, shared with the
    reference, not a kernel defect (pinned by tests/test_convnet_s2d_t
    ::test_equality_at_production_row_width_bf16)."""
    import math

    if (not (final_loss <= 2 * math.log(10))
            or not math.isfinite(final_loss)):  # NaN/±inf also flagged
        result["loss_flag"] = (
            f"post-warmup loss {final_loss:.2f} > 2x ln(10) init floor: "
            "the reference recipe's own divergence at this scale (torch "
            "reference: 2.26 -> 421 nats in 2 steps at 3000^2, "
            "tools/reference_dynamics_probe.py), not a numerics defect"
        )
    if not math.isfinite(final_loss):
        result["final_loss"] = repr(final_loss)  # keep the JSON standard


def bench(image_size: int, batch_per_device: int, steps: int, warmup: int,
          dtype_name: str, force_cpu: bool, baseline: float,
          plan: str = "auto", model_overrides: dict | None = None) -> dict:
    from tpu_sandbox.utils.cli import ensure_devices

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    if force_cpu:
        ensure_devices(1, force_cpu=True)
    n_dev = jax.device_count()
    devices = jax.devices()

    from tpu_sandbox.data import synthetic_mnist
    from tpu_sandbox.data.mnist import normalize
    from tpu_sandbox.models import pick_convnet
    from tpu_sandbox.parallel import DataParallel
    from tpu_sandbox.runtime.mesh import make_mesh
    from tpu_sandbox.train import TrainState
    from tpu_sandbox.utils.flops import convnet_flops
    from tpu_sandbox.utils.parity import numerics_preflight
    from tpu_sandbox.utils.profiling import host_sync, measure_per_step

    dtype = jnp.bfloat16 if dtype_name == "bf16" else jnp.float32
    model_overrides = dict(model_overrides or {})
    # the sweep race expresses plan switches through the same overrides
    # dict as the kernel toggles
    plan = model_overrides.pop("plan", plan)
    model = pick_convnet(image_size, plan=plan, dtype=dtype,
                         **model_overrides)
    tx = optax.sgd(1e-4)
    global_batch = batch_per_device * n_dev

    images, labels = synthetic_mnist(n=global_batch * 64, seed=0)
    images, labels = normalize(images), labels.astype("int32")
    # The blob task is linearly separable and saturates to loss 0.0 within
    # the warmup (VERDICT r01/r02: a dead loss demonstrates nothing about
    # the timed window). 25% uniform label flips (effective corruption
    # 22.5%) put a ~1.0-nat CE floor under any non-memorizing fit. The
    # first on-chip r03 run still printed 0.0: with only 8 staged batches
    # the 180M-param head saw each fixed flipped label ~24 times and
    # memorized it. 64 staged batches (raw 28x28, ~4 KB each — resize is
    # on-device) cap reuse at ~3 epochs over a bench run, keeping the
    # floor practical. Shapes/FLOPs/traffic are untouched.
    noise_rng = np.random.default_rng(1)
    flip = noise_rng.random(len(labels)) < 0.25
    labels = np.where(
        flip, noise_rng.integers(0, 10, size=len(labels)), labels
    ).astype("int32")

    state = TrainState.create(
        model, jax.random.key(0), jnp.zeros((1, image_size, image_size, 1), dtype), tx
    )
    mesh = make_mesh({"data": n_dev}, devices=devices)
    dp = DataParallel(model, tx, mesh, image_size=(image_size, image_size))
    state = dp.shard_state(state)

    # Pre-stage batches on device so no host->device transfer sits inside the
    # timed region (raw 28x28 batches are ~4 KB; the 3000x3000 resize happens
    # on device inside the step).
    rng = np.random.default_rng(0)
    staged = []
    for _ in range(64):
        sel = rng.integers(0, len(images), size=global_batch)
        staged.append(dp.shard_batch(images[sel], labels[sel]))

    cursor = 0

    def run_steps(k: int):
        # persistent cursor: the staged pool must cycle ACROSS calls, or
        # measure_per_step's repeated run_steps(n) would retrain the same
        # leading batches every call and final_loss would be evaluated on
        # the most-memorized batch — the failure the 64-batch pool fixes
        nonlocal state, cursor
        loss = None
        for _ in range(k):
            im, lb = staged[cursor % len(staged)]
            cursor += 1
            state, loss = dp.train_step(state, im, lb)
        return loss

    for _ in range(max(warmup - 1, 0)):
        run_steps(1)

    timing = measure_per_step(run_steps, steps)
    sec_per_step = timing["sec_per_step"]

    # The same steps on the other clock (host clock ended by
    # block_until_ready), for the record: ROADMAP A2 decides between them.
    host_sync(run_steps(1))  # drain the queue
    t0 = time.perf_counter()
    jax.block_until_ready(run_steps(steps))
    bur_per_step = (time.perf_counter() - t0) / steps
    final_loss = host_sync(run_steps(1))

    per_image = convnet_flops(image_size)
    flops_per_step = per_image.train * global_batch
    # guard BEFORE dividing: an exactly-zero differential must still print
    timing_ok = sec_per_step > 0
    util = _utilization(flops_per_step, sec_per_step if timing_ok else 1.0,
                        devices[0], n_devices=n_dev)

    # XLA's own FLOP count for the compiled step, when the backend exposes
    # it — an independent cross-check on the analytic model. Under the
    # Pallas plans XLA cannot see into the custom calls (VERDICT r03
    # weak-7: 26.5 GF reported vs thousands executed), so the custom
    # calls' analytic EXECUTED flops are counted from the optimized HLO
    # and composed; `flops_xla_partial` marks lines where that applies.
    flops_xla = flops_xla_composed = custom_flops = None
    im, lb = staged[0]
    compiled = dp.lower_step(state, im, lb).compile()
    cost = compiled.cost_analysis()
    if cost and "flops" in cost:
        flops_xla = float(cost["flops"])
    from tpu_sandbox.utils.flops import (
        model_runs_sparse_conv1,
        s2d_custom_call_flops,
    )
    custom = s2d_custom_call_flops(compiled.as_text(), global_batch,
                                   image_size,
                                   plan=type(model).__name__,
                                   sparse_conv1=model_runs_sparse_conv1(
                                       model))
    if custom["custom_calls_counted"] and flops_xla is not None:
        custom_flops = custom
        # a kernel the analytic table doesn't know: the composed number
        # would silently undercount — don't publish it
        if not custom["unmatched_pallas_calls"]:
            flops_xla_composed = flops_xla + custom["total"]

    ips = global_batch / sec_per_step if timing_ok else 0.0
    result = {
        "metric": "train_images_per_sec_3000x3000_mnist",
        "value": round(ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(ips / baseline, 3),
        "baseline_images_per_sec": baseline,
        "baseline_kind": "estimated 2xA5000 DDP upper bound (see bench.py docstring)",
        "devices": n_dev,
        "platform": devices[0].platform,
        "device_kind": str(devices[0].device_kind),
        "global_batch": global_batch,
        "image_size": image_size,
        "dtype": dtype_name,
        "execution_plan": type(model).__name__,
        "steps_timed": timing["n"] * 3,
        "sec_per_step": sec_per_step,
        "timing_method": timing["timing_method"],
        "t_n_sec": timing["t_n_sec"],
        "t_2n_sec": timing["t_2n_sec"],
        "sec_per_step_block_until_ready": bur_per_step,
        "flops_per_step_model": flops_per_step,
        "flops_per_step_xla": flops_xla,
        "flops_xla_partial": custom_flops is not None,
        "flops_per_step_xla_composed": flops_xla_composed,
        "flops_custom_calls_analytic": custom_flops,
        "achieved_tflops": round(util["achieved_tflops"], 2),
        "peak_tflops_bf16": util["peak_tflops_bf16"],
        "mfu": round(util["mfu"], 4) if util["mfu"] is not None else None,
        "final_loss": round(final_loss, 4),
    }
    annotate_loss(result, final_loss)
    # framework-regression gate (VERDICT r04 weak-4): the loss_flag's
    # "reference-recipe chaos" explanation is only available while the
    # plan provably matches the plain ConvNet at this row width. A plan
    # whose arithmetic is wrong on this device has no throughput to report.
    pf = numerics_preflight(model, image_size)
    if not pf["ok"]:
        raise RuntimeError(
            "numerics preflight FAILED: the plan deviates from the plain "
            f"ConvNet beyond tolerance on this device: {pf}")
    result["numerics_preflight"] = pf

    if not timing_ok:
        # differential came out non-positive (timing noise dominated): no
        # throughput claim at all
        result.update(value=0.0, vs_baseline=0.0, achieved_tflops=0.0,
                      mfu=None)
        result["degraded"] = (
            f"non-positive differential step time ({sec_per_step:.6f}s): "
            "timing noise; no number published"
        )
    elif not util["plausible"]:
        # an untrusted number is not published at all (the r01 lesson)
        result.update(value=0.0, vs_baseline=0.0)
        result["degraded"] = (
            f"implausible mfu {util['mfu']:.2f} (> 1.0): timing on this "
            "platform does not reflect device execution; "
            f"untrusted images/sec was {round(ips, 2)}"
        )
    return result


def _utilization(flops_per_step: float, sec_per_step: float, device,
                 n_devices: int = 1) -> dict:
    """``utils.flops.mfu`` on the TPU. On the CPU (``--quick``, the harness
    check) there is no peak to hold the number against: achieved FLOP/s
    only, and nothing to call implausible."""
    from tpu_sandbox.utils.flops import mfu

    if device.platform == "tpu":
        return mfu(flops_per_step, sec_per_step, str(device.device_kind),
                   n_devices=n_devices)
    return {"achieved_tflops": flops_per_step / sec_per_step / 1e12,
            "peak_tflops_bf16": None, "mfu": None, "plausible": True}


def _terminal_verdict(client, rid: str, timeout: float) -> dict:
    """result() that treats a burnt retry budget as data: the benches
    audit terminal SHED verdicts alongside oks, so unwrap the exception
    back into the verdict body it carries."""
    from tpu_sandbox.serve.client import RetriesExhausted
    try:
        return client.result(rid, timeout=timeout)
    except RetriesExhausted as err:
        return err.verdict


def _is_oom(msg: str) -> bool:
    """Allocator-failure detection: PJRT's RESOURCE_EXHAUSTED / 'out of
    memory' at run time, plus the compiler's own phrasing when a program
    cannot fit, 'Allocation (size=N) would exceed memory (size=HBM)'."""
    return ("RESOURCE_EXHAUSTED" in msg or "OOM" in msg.upper()
            or "out of memory" in msg.lower()
            or "would exceed memory" in msg)


def bench_sweep(image_size: int, steps: int, warmup: int, baseline: float,
                force_cpu: bool, quick: bool = False,
                plan: str = "auto") -> dict:
    """Batch-size x dtype sweep at the reference's 3000x3000 shape — the
    'chase real MFU' table VERDICT r01 item 2 asks for: for each config,
    step time (fetch-synced differential), images/sec, and MFU; headline =
    the best honest images/sec. OOM configs are recorded as rows, not
    errors (the capacity boundary is part of the table)."""
    if quick:
        image_size, configs = 128, [("fp32", 2, None, None),
                                    ("fp32", 4, None, None)]
    else:
        # ladder around the chipless AOT capacity estimates (r04 step:
        # bs=21 fits at ~15.1 GB peak, 22 over —
        # measured/aot_capacity_s2dt_r04.jsonl): dense near the expected
        # best point up to the capacity edge. The kernel-plan rows race
        # the execution plans (and the r04 sparse-vs-scattered conv1) at
        # the headline batch — which plan actually wins on hardware is a
        # measured question, not an estimated one.
        configs = [("bf16", 5, None, None), ("bf16", 8, None, None),
                   ("bf16", 12, None, None), ("bf16", 16, None, None),
                   ("bf16", 20, None, None), ("fp32", 5, None, None)]
        from tpu_sandbox.models import resolve_plan, resolves_to_s2d
        if resolves_to_s2d(image_size, plan):
            # the overrides are meaningless under the plain plan — labeled
            # race rows there would publish three copies of the same run.
            # The nhwc_pallas row only races when the main rows run the
            # transposed plan (else it would duplicate them byte-for-byte).
            if resolve_plan(image_size, plan) == "s2dt":
                configs += [
                    ("bf16", 16, dict(plan="s2d"), "nhwc_pallas"),
                    # the r04 conv1 race: transposed plan, scattered-3x3
                    # conv1 instead of the sparse union-tile kernel
                    ("bf16", 16, dict(plan="s2dt", sparse_conv1=False),
                     "s2dt_scat_conv1"),
                    # the r05 backward race: unfused conv1/tail backward
                    # (the cotangent round-trips HBM) vs the default
                    # fused kernel — the -9.4 GB/step claim, measured
                    ("bf16", 16, dict(plan="s2dt", fused_conv1_bwd=False),
                     "s2dt_unfused_bwd"),
                    ("bf16", 21, None, None),  # AOT r04/r05: max batch 21
                ]
            configs += [
                ("bf16", 16, dict(plan="s2d", fused_conv=False),
                 "xla_conv+tail"),
                ("bf16", 16, dict(plan="s2d", fused_conv=False,
                                  fused_tail=False), "xla_conv_unfused"),
                ("bf16", 5, dict(plan="s2d", fused_conv=False),
                 "xla_conv+tail")]
    rows, best = [], None
    for dtype_name, bs, overrides, plan_label in configs:
        try:
            r = bench(image_size, bs, steps, warmup, dtype_name, force_cpu,
                      baseline, plan=plan, model_overrides=overrides)
            row = {"dtype": dtype_name, "batch": bs,
                   "sec_per_step": r["sec_per_step"],
                   "images_per_sec": r["value"], "mfu": r["mfu"]}
            if plan_label:
                row["kernel_plan"] = plan_label
            if "degraded" in r:
                row["degraded"] = r["degraded"]
            elif best is None or r["value"] > best["images_per_sec"]:
                best = row
        except Exception as e:
            msg = f"{type(e).__name__}: {e}"
            oom = _is_oom(msg)
            row = {"dtype": dtype_name, "batch": bs,
                   "oom" if oom else "error": True if oom else msg[:200]}
            if plan_label:
                row["kernel_plan"] = plan_label
        rows.append(row)

    import jax
    result = {
        "metric": "train_images_per_sec_sweep",
        "value": best["images_per_sec"] if best else 0.0,
        "unit": f"images/sec (best of sweep @ {image_size}x{image_size})",
        "vs_baseline": round(best["images_per_sec"] / baseline, 3) if best else 0.0,
        "best": best,
        "rows": rows,
        "device_kind": str(jax.devices()[0].device_kind),
    }
    if any(r.get("kernel_plan") for r in rows):
        # only when plan-race rows actually ran (full sweep at 3000^2)
        result["plan_race_caveat"] = (
            "NHWC rows (nhwc_pallas, xla_*) include the canonical-fc-order "
            "transpose of [N,750,750,32] (~0.54 GB bf16/direction at "
            "bs=16, >=1.3 ms/step of HBM traffic — models/convnet.py); "
            "the s2dt rows' fc is transpose-free, so part of any "
            "s2dt-vs-NHWC delta is that canonicalization, not the conv "
            "kernels (ADVICE r04)."
        )
    if best is None:
        result["degraded"] = "no config produced a trusted number (see rows)"
    return result


def bench_convergence(image_size: int, steps: int, force_cpu: bool,
                      plan: str = "auto", batch: int = 5) -> dict:
    """Tamed-lr convergence at the reference geometry (VERDICT r04
    next-4): demonstrate the production plan can DECREASE a loss at
    3000^2 — not merely match a reference recipe that itself diverges
    (BASELINE.md 'Loss dynamics at 3000^2': SGD 1e-4 moves the next
    step's logits by lr*g*||f||^2 = O(100-1000) through the ~18M-feature
    fc head, torch-measured 2.26 -> 421 nats in two steps). The tamed
    recipe keeps the reference's SGD 1e-4 on the conv/BN trunk and
    scales the fc head's lr by ~1/||f||^2 (1e-4 / 1e4 -> 1e-8), so the
    head moves logits O(0.1)/step — the minimal change that makes the
    architecture trainable at this scale (reference recipe being tamed:
    /root/reference/mnist_onegpu.py:68-74). Publishes the full loss
    curve + trend verdict; the numerics preflight runs alongside so a
    decrease cannot be claimed on a numerically-broken plan."""
    from tpu_sandbox.utils.cli import ensure_devices

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    if force_cpu:
        ensure_devices(1, force_cpu=True)

    from tpu_sandbox.data import synthetic_mnist
    from tpu_sandbox.data.mnist import normalize
    from tpu_sandbox.models import pick_convnet
    from tpu_sandbox.train import TrainState, make_train_step
    from tpu_sandbox.utils.parity import numerics_preflight
    from tpu_sandbox.utils.profiling import host_sync

    model = pick_convnet(image_size, plan=plan, dtype=jnp.bfloat16)
    tx = optax.multi_transform(
        {"head": optax.sgd(1e-8), "trunk": optax.sgd(1e-4)},
        lambda params: {
            k: jax.tree.map(lambda _: "head" if k == "fc" else "trunk", v)
            for k, v in params.items()
        },
    )
    state = TrainState.create(
        model, jax.random.key(0),
        jnp.zeros((1, image_size, image_size, 1), jnp.bfloat16), tx)
    step = make_train_step(model, tx, image_size=(image_size, image_size),
                           donate=False)

    images, labels = synthetic_mnist(n=batch * 64, seed=0)
    images, labels = normalize(images), labels.astype("int32")
    noise_rng = np.random.default_rng(1)
    flip = noise_rng.random(len(labels)) < 0.25
    labels = np.where(
        flip, noise_rng.integers(0, 10, size=len(labels)), labels
    ).astype("int32")
    sel_rng = np.random.default_rng(2)

    losses = []
    for i in range(steps):
        sel = sel_rng.integers(0, len(images), size=batch)
        im = jnp.asarray(images[sel])  # normalize() already emits [N,28,28,1]
        lb = jnp.asarray(labels[sel])
        state, loss = step(state, im, lb)
        losses.append(float(host_sync(loss)))

    k = max(1, min(5, steps // 4))
    first, last = float(np.mean(losses[:k])), float(np.mean(losses[-k:]))
    drop = first - last
    rises = sum(1 for a, b in zip(losses, losses[1:]) if b > a + 1e-6)
    decreased = drop > 0.02 and last < losses[0]
    pf = numerics_preflight(model, image_size)
    if not pf["ok"]:
        raise RuntimeError(
            "numerics preflight FAILED: the plan deviates from the plain "
            f"ConvNet beyond tolerance — convergence claim void: {pf}")
    result = {
        "metric": "convergence_tamed_lr",
        "value": round(drop, 4),
        "unit": f"nats decrease (mean first {k} -> mean last {k} steps)",
        "vs_baseline": None,
        "baseline_kind": ("n/a: the reference's own recipe diverges at "
                          "this scale (BASELINE.md, torch-measured "
                          "2.26 -> 421 nats in 2 steps); any decrease "
                          "beats it"),
        "decreased": bool(decreased),
        "image_size": image_size, "batch": batch, "steps": steps,
        "recipe": "SGD trunk 1e-4, fc head 1e-8 (lr/||f||^2 scaling)",
        "loss_first_mean": round(first, 4),
        "loss_last_mean": round(last, 4),
        "loss_curve": [round(x, 4) for x in losses],
        "monotone_violations": rises,
        "execution_plan": type(model).__name__,
        "device_kind": str(jax.devices()[0].device_kind),
        "numerics_preflight": pf,
    }
    return result


def bench_allreduce_bw(force_cpu: bool) -> dict:
    """All-reduce bus bandwidth over all devices — the second north-star
    metric BASELINE.md names (NCCL-style busbw accounting)."""
    from tpu_sandbox.utils.cli import ensure_devices

    if force_cpu:
        ensure_devices(8, force_cpu=True)
    import jax

    from tpu_sandbox.parallel.collectives import world_group

    g = world_group()
    r = g.allreduce_bandwidth()
    result = {
        "metric": "allreduce_bus_bandwidth",
        "value": round(r["busbw_GBps"], 3),
        "unit": "GB/s",
        "vs_baseline": 0.0,  # reference published no bandwidth number
        "algbw_GBps": round(r["algbw_GBps"], 3),
        "payload_bytes": r["bytes"],
        "timing_method": r["timing_method"],
        "devices": jax.device_count(),
        "device_kind": str(jax.devices()[0].device_kind),
    }
    if "degraded" in r:  # e.g. non-positive differential after retry
        result["degraded"] = r["degraded"]
    elif jax.device_count() == 1:
        # busbw = algbw * 2*(n-1)/n is identically 0 at n=1; say why
        result["degraded"] = "single device; no interconnect to measure"
    return result


def bench_grad_compress_traffic(world: int = 8) -> dict:
    """Cross-replica collective bytes per train step under each
    --grad-compress mode, from the optimized SPMD HLO of a CPU-mesh
    compile — the measured-artifact counterpart of the compression claim
    (~2x for bf16, ~4x payload for int8 plus its fp32 block scales).

    Chipless and deliberately CPU-forced: XLA:CPU keeps the collective
    instructions (all-reduce / all-to-all / all-gather) with inline
    operand shapes in ``compile().as_text()``, so the accounting in
    ``tools/hlo_traffic.collective_bytes`` reads the same numbers a TPU
    compile would produce for the gradient-sync payload. Estimates of
    wire payload per participant, not measurements of fabric time."""
    import sys as _sys

    from tpu_sandbox.utils.cli import ensure_devices

    devices = ensure_devices(world, force_cpu=True)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    _sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    from hlo_traffic import collective_bytes

    from tpu_sandbox.models import ConvNet
    from tpu_sandbox.parallel import DataParallel
    from tpu_sandbox.runtime.mesh import make_mesh
    from tpu_sandbox.train import TrainState

    mesh = make_mesh({"data": world}, devices=devices)
    # BN-free so the grad sync is the ONLY cross-replica traffic in the step
    model = ConvNet(use_bn=False)
    tx = optax.sgd(1e-2, momentum=0.9)
    state = TrainState.create(
        model, jax.random.key(0), jnp.zeros((1, 28, 28, 1)), tx
    )
    leaf_sizes = [int(np.prod(np.shape(p)))
                  for p in jax.tree.leaves(state.params)]
    images = np.zeros((2 * world, 28, 28, 1), np.float32)
    labels = np.zeros((2 * world,), np.int32)

    modes = {}
    for mode in ("none", "bf16", "int8"):
        dp = DataParallel(model, tx, mesh, grad_compress=mode, donate=False)
        dstate = dp.shard_state(state)
        text = dp.lower_step(
            dstate, *dp.shard_batch(images, labels)
        ).compile().as_text()
        hlo = collective_bytes(text)
        est = dp.compress.wire_bytes(leaf_sizes, world)
        modes[mode] = {
            "hlo_collective_bytes": hlo["total"],
            "by_opcode": hlo["by_opcode"],
            "estimated_wire_bytes": est["total"],
            "estimated_payload_bytes": est["payload"],
            "estimated_overhead_bytes": est["overhead"],
        }
    hlo_base = modes["none"]["hlo_collective_bytes"] or 1
    est_base = modes["none"]["estimated_wire_bytes"] or 1
    pay_base = modes["none"]["estimated_payload_bytes"] or 1
    for mode, row in modes.items():
        # headline 2x/4x is the payload ratio; the all-in wire ratio
        # additionally pays int8's fp32 block scales + block padding (the
        # padding dominates on this deliberately small model's tiny leaves)
        row["hlo_reduction_vs_fp32"] = round(
            hlo_base / (row["hlo_collective_bytes"] or 1), 2)
        row["est_wire_reduction_vs_fp32"] = round(
            est_base / (row["estimated_wire_bytes"] or 1), 2)
        row["est_payload_reduction_vs_fp32"] = round(
            pay_base / (row["estimated_payload_bytes"] or 1), 2)
    if (modes["bf16"]["hlo_collective_bytes"]
            == modes["none"]["hlo_collective_bytes"]):
        modes["bf16"]["hlo_note"] = (
            "XLA:CPU upcasts the bf16 all-reduce operand to f32, so the "
            "HLO bytes match fp32 here; a TPU compile keeps bf16 on the "
            "wire — trust the estimated path for this mode")
    return {
        "metric": "grad_compress_traffic",
        "world": world,
        "param_count": int(sum(leaf_sizes)),
        "modes": modes,
        "source": "optimized SPMD HLO collective-operand accounting on the "
                  f"{world}-virtual-CPU-device mesh (chipless estimate, not "
                  "a measurement)",
    }


# Stub tenants for --metric cluster: real subprocesses speaking the
# scheduler's protocol (job-namespaced heartbeats, preemption vote,
# verdict) with zero training inside, so the reported latencies isolate
# the scheduler's own reaction times. The resumed life smuggles its
# first-step wall-clock stamp out through the verdict — the one record
# that survives the job-namespace sweep.
_CLUSTER_AGENT = """\
import json, signal, sys, time
sys.path.insert(0, {root!r})
from tpu_sandbox.runtime.kvstore import KVClient, for_job
aid = int(sys.argv[1]); port = int(sys.argv[2]); job = sys.argv[3]
mode = sys.argv[4]
kv = for_job(KVClient(port=port), job)
stop = []
signal.signal(signal.SIGTERM, lambda s, f: stop.append(1))

def verdict(ok, preempted=False, extra=None):
    v = {{"ok": ok, "preempted": preempted, "reason": "bench stub",
          "summary": "", "restarts": 0, "preemptions": 0,
          "generations": 1}}
    v.update(extra or {{}})
    kv.set("job/done", json.dumps(v))

if mode == "work":            # the high-priority arrival: brief and done
    t0 = time.monotonic()
    while time.monotonic() - t0 < 0.4:
        kv.set_ttl(f"agent_hb/{{aid}}", repr(time.time()), 5.0)
        time.sleep(0.02)
    verdict(True)
    time.sleep(0.2)
elif mode == "preemptible":   # the victim tenant
    lives = kv.add("bench/lives", 1)
    if lives >= 2:            # resumed life: stamp the first step, finish
        verdict(True, extra={{"first_step_walltime": time.time()}})
        time.sleep(0.2)
        sys.exit(0)
    while not stop:           # first life: run until the scheduler preempts
        kv.set_ttl(f"agent_hb/{{aid}}", repr(time.time()), 5.0)
        time.sleep(0.02)
    verdict(False, preempted=True)  # checkpoint-through-vote stand-in
    sys.exit(75)
"""


def bench_cluster(pool: int = 1) -> dict:
    """Scheduler control-plane latencies from a scripted two-job run: a
    low-priority tenant fills the pool, a high-priority job arrives and
    preempts it, the victim resumes after the arrival drains. Reports the
    three receipts the multi-tenant claim stands on — queue wait,
    preempt-to-checkpoint, and resume-to-first-step — computed from the
    scheduler's own event stamps (runtime/scheduler.py::job_events) plus
    the stub agents' verdicts. Chipless: no jax, no training; these are
    the scheduler's overheads, to be added on top of a real job's own
    checkpoint-save and first-step times."""
    import tempfile

    from tpu_sandbox.runtime.scheduler import (
        ClusterScheduler,
        JobSpec,
        job_events,
        k_state,
        k_verdict,
    )

    root = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "bench_cluster_agent.py")
        with open(script, "w", encoding="utf-8") as f:
            f.write(_CLUSTER_AGENT.format(root=root))

        def argv(mode):
            return [sys.executable, script, "{agent_id}", "{kv_port}",
                    "{job_id}", mode]

        with ClusterScheduler(pool, poll=0.02,
                              extra_env={"PYTHONPATH": root},
                              verbose=False) as sched:
            sched.submit(JobSpec(job_id="victim", hosts=1, world_size=1,
                                 agent_argv=argv("preemptible")))
            # outrank the victim only once its agent is demonstrably up
            # (heartbeating, SIGTERM handler installed) — preempting a gang
            # mid-exec() measures the kill escalation, not the vote
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                sched._tick()
                if (sched.kv.try_get(k_state("victim")) or b"") \
                        == b"running" \
                        and sched.kv.keys("job/victim/agent_hb/"):
                    break
                time.sleep(0.02)
            sched.submit(JobSpec(job_id="arrival", hosts=1, world_size=1,
                                 priority=5, agent_argv=argv("work")))
            states = sched.serve(timeout=120)
            if states != {"victim": "done", "arrival": "done"}:
                raise RuntimeError(f"scripted run went sideways: {states}")
            ev_v = job_events(sched.kv, "victim")
            ev_a = job_events(sched.kv, "arrival")
            verdict = json.loads(sched.kv.get(k_verdict("victim")))

    return {
        "metric": "cluster_scheduler_latency",
        "pool_hosts": pool,
        "unit": "seconds",
        # how long each job sat in the queue before its gang launched
        # (the arrival's wait covers the whole preemption round trip)
        "queue_wait_s": {
            "victim": round(ev_v["admitted"] - ev_v["submitted"], 4),
            "arrival": round(ev_a["admitted"] - ev_a["submitted"], 4),
        },
        # SIGTERM sent -> preempted verdict posted (the window a real job
        # spends checkpointing through the preemption vote)
        "preempt_to_checkpoint_s": round(
            ev_v["preempted"] - ev_v["preempt_sent"], 4),
        # requeued-job readmission -> its first step after resume
        "resume_to_first_step_s": round(
            verdict["first_step_walltime"] - ev_v["readmitted"], 4),
        "events": {"victim": ev_v, "arrival": ev_a},
        "source": "scripted two-job preemption round on a 1-host pool with "
                  "protocol-stub agents (scheduler overhead only; add the "
                  "job's own checkpoint-save and first-step cost)",
    }


#: BENCH_r07's staged-transport shipping numbers — the fast-fabric claim
#: is anchored against these (equal slots, equal bytes, >=10x lower
#: total get latency on the device path).
_R07_SHIP = {"get_ms_total": 4448.308, "gets": 160, "bytes_out": 1310720}

#: committed fabric-profile baseline for the tracediff gate; regenerate
#: with ``bench.py --metric mpmd --archive <dir>`` and commit the
#: ``mpmd_fabric_profile.json`` artifact here after intentional fabric
#: changes
_FABRIC_CONTROL = os.path.join("measured", "mpmd_fabric_control.json")


def _fabric_profile(merged) -> dict:
    """Fold an MPMD run's trace into a critpath-schema profile whose
    segments are the fabric's own health numbers — per-stage bubble
    seconds per steady-state step and per-slot ship latencies — so
    ``tools/tracediff.py`` gates fabric regressions exactly like
    request-path regressions. A device path silently degrading to
    staged shipping shows up as a >=10x ``ship:get`` ratio; a schedule
    regression shows up in the ``bubble:stage<s>`` rows."""
    import statistics

    from tpu_sandbox.obs import critpath

    walls: dict[tuple, float] = {}
    comp: dict[tuple, float] = {}
    segs: dict[str, list[float]] = {}
    for r in merged:
        if r.get("ph") != "X":
            continue
        name, args = r.get("name"), r.get("args") or {}
        dur = float(r.get("dur", 0.0))
        if name == "stage:step":
            key = (int(args.get("stage", -1)), int(args.get("step", -1)))
            walls[key] = walls.get(key, 0.0) + dur
        elif name == "stage:op":
            key = (int(args.get("stage", -1)), int(args.get("step", -1)))
            comp[key] = comp.get(key, 0.0) + dur
        elif name == "slot:get":
            segs.setdefault("ship:get", []).append(dur)
        elif name == "slot:put":
            segs.setdefault("ship:put", []).append(dur)
        elif name == "stage:wait":
            segs.setdefault("ship:wait", []).append(dur)
    for (stage, step), wall in walls.items():
        if step < 1:  # step 0 pays compile on every arm
            continue
        segs.setdefault(f"bubble:stage{stage}", []).append(
            max(0.0, wall - comp.get((stage, step), 0.0)))
    step_walls = sorted(w for (_, st), w in walls.items() if st >= 1)
    total = sum(step_walls) or 1.0
    segments = {}
    for name in sorted(segs):
        samples = sorted(round(x, 9) for x in segs[name])
        tot = sum(samples)
        segments[name] = {
            "total_s": round(tot, 9),
            "share": round(tot / total, 6),
            "n": len(samples),
            "median_s": round(statistics.median(samples), 9),
            "samples": samples,
        }
    return {
        "schema": critpath.PROFILE_SCHEMA,
        "requests": len(step_walls),
        "ok": len(step_walls),
        "wall_s_total": round(total, 9),
        "wall_s_median": round(statistics.median(step_walls), 9)
        if step_walls else 0.0,
        "coverage_min": 1.0, "coverage_mean": 1.0,
        "segments": segments, "blame": {}, "by_proc": {},
    }


def bench_mpmd(*, steps: int = 20, quick: bool = False,
               aot: bool = True) -> dict:
    """Fast-fabric MPMD receipts, four arms over the SAME model/init:

    1. **Staged control** — KVTransport over a live KV server, the wire
       every cross-host deployment pays: whole-slot staging, chunked
       puts, the r07 shape (2 stages / 4 microbatches, 160 slots /
       1310720 bytes at the full config).
    2. **Device fast path** — DeviceTransport (device buffers published
       in-process, journal underneath for recovery): same slots, same
       bytes, params bitwise vs the fused SPMD pipeline. The tentpole
       claim: total ``get`` latency >= 10x lower than BENCH_r07's
       staged 4448.3 ms at equal shipped bytes.
    3. **Measured ZB-H1 schedule** — 3 even stages, per-op costs
       measured from a short probe, ``schedule.autotune_plan`` picks
       (kind, microbatches); the chosen zb_h1 run's measured bubble
       (online gauge AND offline trace, agreeing within 0.03) must land
       below the analytic 1F1B ``(S-1)/(M+S-1)``.
    4. **Fault audit** — a mid-run stage kill with in-process recovery:
       params bitwise vs the unfaulted twin, zero duplicate claims
       across generations (the zero-dup/zero-loss microbatch audit).

    The fast arm's trace folds into a fabric profile
    (:func:`_fabric_profile`) and ``tools/tracediff.py`` gates it — in
    every run against the staged arm (the fast path must never regress
    toward staged shipping), and additionally against the committed
    ``measured/mpmd_fabric_control.json`` when present (full runs
    only). ``--metric mpmd`` exits nonzero when the gate fails, like
    the tracediff CLI itself. Chipless: CPU times are harness truth;
    the ratios, parity bits and audits are the claims."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    import contextlib
    import statistics
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpu_sandbox.models.transformer import TransformerConfig
    from tpu_sandbox.mpmd import MPMDPipeline, bubble_fraction
    from tpu_sandbox.mpmd.schedule import autotune_plan
    from tpu_sandbox.mpmd.transport import DeviceTransport, KVTransport
    from tpu_sandbox.obs import (ENV_TRACE_DIR, collect, critpath,
                                 get_recorder, reset_recorder)
    from tpu_sandbox.parallel.pipeline import PipelineParallel
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.runtime.mesh import make_mesh

    @contextlib.contextmanager
    def recorder_arm(trace_dir):
        prior = os.environ.pop(ENV_TRACE_DIR, None)
        if trace_dir is not None:
            os.environ[ENV_TRACE_DIR] = trace_dir
        reset_recorder()
        try:
            yield
        finally:
            get_recorder().flush()
            if prior is None:
                os.environ.pop(ENV_TRACE_DIR, None)
            else:
                os.environ[ENV_TRACE_DIR] = prior
            reset_recorder()

    steps = 6 if quick else steps
    microbatches, n_stages = 4, 2
    cfg = TransformerConfig(vocab_size=64, d_model=32 if quick else 64,
                            n_heads=2 if quick else 4, n_layers=4,
                            d_ff=64 if quick else 128, max_len=64)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(8, 16)).astype(np.int32)
    targets = ((tokens + 7) % cfg.vocab_size).astype(np.int32)
    tx = optax.adam(1e-2)
    devs = jax.devices()

    mesh = make_mesh({"data": 1, "pipe": n_stages}, devices=devs[:n_stages])
    pp = PipelineParallel(cfg, tx, mesh, microbatches=microbatches,
                          donate=False)
    state = pp.init_state(jax.random.key(0), jnp.asarray(tokens))
    flat = pp.merged_params(state)

    def run_arm(transport, devices, *, trace_dir=None):
        pipe = MPMDPipeline(cfg, tx, n_stages=n_stages,
                            microbatches=microbatches, transport=transport,
                            devices=devices)
        pipe.init_from_flat(flat)
        with recorder_arm(trace_dir):
            pipe.train(steps, tokens, targets)
        return pipe

    # -- arm 1: staged control (the KV wire, chunk-pipelined reads) ----------
    server = KVServer()
    kv = KVClient(port=server.port)
    try:
        staged_dir = tempfile.mkdtemp(prefix="mpmd-staged-")
        staged = run_arm(KVTransport(kv, prefix="fab"),
                         devs[n_stages:2 * n_stages], trace_dir=staged_dir)
        staged_stats = staged.transport.stats.snapshot()
    finally:
        kv.close()
        server.stop()

    # -- arm 2: device fast path, same slots/bytes ---------------------------
    fast_dir = tempfile.mkdtemp(prefix="mpmd-fast-")
    pipe = run_arm(DeviceTransport(), devs[n_stages:2 * n_stages],
                   trace_dir=fast_dir)
    stats = pipe.transport.stats.snapshot()
    stage_ms = [sorted(1e3 * t for t in w.step_seconds.values())
                for w in pipe.workers]

    # -- SPMD baseline: same init, same batch, fused scan --------------------
    sstate = pp.shard_state(state)
    batch = pp.shard_batch(tokens, targets)
    spmd_ms = []
    for _ in range(steps):
        t0 = time.perf_counter()
        sstate, loss = pp.train_step(sstate, *batch)
        jax.block_until_ready(loss)
        spmd_ms.append(1e3 * (time.perf_counter() - t0))
    spmd_ms.sort()

    spmd = pp.merged_params(sstate)
    mpmd = pipe.merged_params()
    mismatched = [
        1 for a, b in zip(jax.tree.leaves(spmd), jax.tree.leaves(mpmd))
        if not np.array_equal(np.asarray(a), np.asarray(b))
    ]

    # the tentpole ship claim, at the r07 anchor's exact shape only
    fast_get_ms = 1e3 * stats["get_seconds"]
    shape_matches_r07 = (not quick
                         and stats["gets"] == _R07_SHIP["gets"]
                         and stats["bytes_out"] == _R07_SHIP["bytes_out"])
    ship_speedup_vs_r07 = (round(_R07_SHIP["get_ms_total"] / fast_get_ms, 1)
                           if fast_get_ms > 0 else None)
    ship_speedup_vs_staged = (
        round(1e3 * staged_stats["get_seconds"] / fast_get_ms, 1)
        if fast_get_ms > 0 else None)

    # -- arm 3: measured ZB-H1 schedule on 3 even stages ---------------------
    # heavy enough that per-op compute dominates dispatch overhead —
    # otherwise the measured bubble is all harness, not schedule
    cfg3 = TransformerConfig(vocab_size=64, d_model=128, n_heads=4,
                             n_layers=6, d_ff=512, max_len=64)
    S3, M3 = 3, 8
    zb_steps = 5 if quick else 10
    rng3 = np.random.default_rng(3)
    tokens3 = rng3.integers(0, cfg3.vocab_size, size=(16, 32)).astype(
        np.int32)
    targets3 = ((tokens3 + 7) % cfg3.vocab_size).astype(np.int32)

    def run_zb(kind, m_count, nsteps, *, trace_dir=None):
        p3 = MPMDPipeline(cfg3, tx, n_stages=S3, microbatches=m_count,
                          transport=DeviceTransport(), devices=devs[:S3],
                          kind=kind)
        p3.init(jax.random.key(1), jnp.asarray(tokens3))
        with recorder_arm(trace_dir):
            p3.train(nsteps, tokens3, targets3)
        return p3

    probe = run_zb("zb_h1", M3, 3 if quick else 4)
    op_costs = probe.measured_op_costs()
    plan = autotune_plan(op_costs, n_stages=S3, measured_microbatches=M3,
                         candidates=(2, 4))
    zb_dir = tempfile.mkdtemp(prefix="mpmd-zb-")
    zb = run_zb(plan["kind"], plan["microbatches"], zb_steps,
                trace_dir=zb_dir)
    online = statistics.median(
        b for w in zb.workers
        for s, b in w.bubble_by_step.items() if s >= 1)
    per_step = critpath.bubble_fractions(
        collect.load_merged(zb_dir))["per_step"]
    offline = statistics.median(
        r["bubble"] for r in per_step if r["step"] >= 1)
    analytic_1f1b = bubble_fraction(S3, plan["microbatches"])

    # -- arm 4: kill mid-run, recover, audit ---------------------------------
    fa_steps = 4 if quick else 8
    ckpt = tempfile.mkdtemp(prefix="mpmd-fault-")

    def run_fault(sub, fail_at):
        p = MPMDPipeline(cfg, tx, n_stages=n_stages,
                         microbatches=microbatches,
                         transport=DeviceTransport(),
                         devices=devs[n_stages:2 * n_stages],
                         ckpt_root=os.path.join(ckpt, sub))
        p.init_from_flat(flat)
        if fail_at is not None:
            p.workers[1].fail_at = fail_at
        p.train(fa_steps, tokens, targets, recover=fail_at is not None)
        return p

    twin = run_fault("twin", None)
    faulted = run_fault("kill", (fa_steps // 2, 1))
    fa_mismatch = [
        1 for a, b in zip(jax.tree.leaves(twin.merged_params()),
                          jax.tree.leaves(faulted.merged_params()))
        if not np.array_equal(np.asarray(a), np.asarray(b))
    ]
    dup_claims = {k: v for k, v in faulted.transport.audit()["claims"].items()
                  if v != 1}
    fault_audit_ok = (not fa_mismatch and not dup_claims
                      and faulted.workers[1].generation == 1)

    # -- tracediff gate over the fabric profile ------------------------------
    fast_profile = _fabric_profile(collect.load_merged(fast_dir))
    profile_path = os.path.join(fast_dir, "mpmd_fabric_profile.json")
    critpath.save_profile(fast_profile, profile_path)
    staged_profile_path = os.path.join(staged_dir, "fabric_profile.json")
    critpath.save_profile(_fabric_profile(collect.load_merged(staged_dir)),
                          staged_profile_path)
    td = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", "tracediff.py")
    # thresholds sized to catch transport-tier changes (device -> staged
    # is >=10x on ship:get) and schedule breakage, not CPU step jitter
    gate_args = ["--threshold", "0.5", "--min-ms", "1.0",
                 "--min-share", "0.02"]
    gates = {}
    gates["vs_staged"] = subprocess.run(
        [sys.executable, td, staged_profile_path, profile_path, *gate_args],
        capture_output=True, text=True).returncode
    control = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           _FABRIC_CONTROL)
    if not quick and os.path.isfile(control):
        gates["vs_archived"] = subprocess.run(
            [sys.executable, td, control, profile_path, *gate_args],
            capture_output=True, text=True).returncode
    tracediff_gate_ok = all(rc == 0 for rc in gates.values())

    result = {
        "metric": "mpmd_pipeline",
        "unit": "milliseconds",
        "geometry": {
            "n_stages": n_stages, "microbatches": microbatches,
            "steps": steps, "d_model": cfg.d_model,
            "n_layers": cfg.n_layers,
        },
        # steady-state medians; step 0 carries compile time on both sides
        "per_stage_step_ms": [
            round(ms[len(ms) // 2], 3) for ms in stage_ms],
        "spmd_step_ms": round(spmd_ms[len(spmd_ms) // 2], 3),
        "bubble_fraction": bubble_fraction(n_stages, microbatches),
        "params_bitwise_vs_spmd": not mismatched,
        "transport": {
            "puts": stats["puts"], "gets": stats["gets"],
            "bytes_out": stats["bytes_out"],
            "bytes_in": stats["bytes_in"],
            "put_ms_total": round(1e3 * stats["put_seconds"], 3),
            "get_ms_total": round(fast_get_ms, 3),
            # time consumers sat blocked on unproduced slots — the
            # measured face of the schedule bubble
            "get_wait_ms_total": round(1e3 * stats["get_wait_seconds"], 3),
            "device_hits": stats.get("device_hits", 0),
            "journal_fallbacks": stats.get("journal_fallbacks", 0),
        },
        "transport_staged": {
            "gets": staged_stats["gets"],
            "bytes_out": staged_stats["bytes_out"],
            "put_ms_total": round(1e3 * staged_stats["put_seconds"], 3),
            "get_ms_total": round(1e3 * staged_stats["get_seconds"], 3),
            "get_wait_ms_total": round(
                1e3 * staged_stats["get_wait_seconds"], 3),
        },
        "ship": {
            "r07_staged_get_ms": _R07_SHIP["get_ms_total"],
            "speedup_vs_r07": ship_speedup_vs_r07,
            "speedup_vs_staged_arm": ship_speedup_vs_staged,
            "equal_bytes_vs_r07": bool(shape_matches_r07),
            "note": "r07 predates the wait/wire accounting split (its "
                    "get total folds in schedule wait); the in-run "
                    "staged arm is the like-for-like wire baseline",
        },
        "device_path_10x_ok": bool(
            shape_matches_r07 and ship_speedup_vs_r07 is not None
            and ship_speedup_vs_r07 >= 10.0),
        "autotune": {
            "chosen_kind": plan["kind"],
            "chosen_microbatches": plan["microbatches"],
            "predicted": plan["predicted"],
            "candidates": plan["candidates"],
            "measured_op_cost_ms": {
                s: {op: round(1e3 * v, 3) for op, v in ops.items()}
                for s, ops in op_costs.items()},
        },
        "zb_bubble": {
            "n_stages": S3, "microbatches": plan["microbatches"],
            "steps": zb_steps,
            "online_median": round(online, 6),
            "offline_median": round(offline, 6),
            "analytic_1f1b": round(analytic_1f1b, 6),
        },
        "zb_below_1f1b_ok": bool(plan["kind"] == "zb_h1"
                                 and offline < analytic_1f1b),
        "bubble_gauge_ok": bool(abs(online - offline) <= 0.03),
        "fault_audit": {
            "params_bitwise_vs_twin": not fa_mismatch,
            "dup_claims": len(dup_claims),
            "respawned_generation": faulted.workers[1].generation,
        },
        "fault_audit_ok": bool(fault_audit_ok),
        "tracediff": {
            "gate_exits": gates,
            "control": _FABRIC_CONTROL if "vs_archived" in gates else None,
        },
        "tracediff_gate_ok": bool(tracediff_gate_ok),
        "_artifacts": {
            "mpmd_fabric_profile.json": profile_path,
            "trace_fast": fast_dir,
            "trace_zb": zb_dir,
        },
        "source": "in-process MPMD arms (threads, one CPU device per "
                  "stage): KVTransport staged wire vs DeviceTransport "
                  "fast path at equal slots/bytes vs the fused SPMD "
                  "pipeline; 3-stage probe-measured autotuned ZB-H1 with "
                  "online/offline/analytic bubble; kill-recover claim "
                  "audit; tracediff as the committed CLI on fabric "
                  "profiles; CPU times are harness truth, the ratios, "
                  "parity bits and audits are the claims",
    }
    if aot and not quick:
        from tools.aot_mpmd import mpmd_aot_report
        result["aot"] = mpmd_aot_report(
            n_stages=2, microbatches=microbatches, vocab_size=2048,
            d_model=128, n_layers=4, d_ff=256)
        # the ZB twin: uneven split, backward split into B/W programs
        result["aot_zb"] = mpmd_aot_report(
            n_stages=3, microbatches=microbatches, vocab_size=2048,
            d_model=128, n_layers=6, d_ff=256, layer_split=[3, 2, 1],
            zb=True)
    return result


def bench_serve(*, n_requests: int = 32, mean_interarrival_ms: float = 2.5,
                quick: bool = False, seed: int = 0, aot: bool = True) -> dict:
    """Serving SLOs from a Poisson load generator: tokens/sec and p50/p99
    TTFT (arrival -> first token) / ITL (gap between consecutive tokens),
    continuous batching vs the static-batch baseline on the SAME compiled
    steps, same request trace, same paged cache geometry — the comparison
    isolates the scheduling policy. Chipless: tiny transformer on the CPU
    backend; the absolute numbers are harness truth, the continuous/static
    ratio is the claim. A chipless v5e AOT receipt for the decode step's
    cache donation rides along (tools/aot_serve.py)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from collections import deque

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_sandbox.models.transformer import (TransformerConfig,
                                                TransformerLM)
    from tpu_sandbox.serve import (CacheConfig, ContinuousEngine, Request,
                                   ServeConfig, StaticEngine)
    from tpu_sandbox.serve.decode import build_decode_step

    if quick:
        n_requests = min(n_requests, 10)

    mcfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                             n_layers=2, d_ff=64, max_len=128,
                             dtype=jnp.float32)
    # quick mode is the tier-1 smoke: every prompt in the trace fits the
    # 16 bucket, so skip compiling the 32 one
    buckets = (16,) if quick else (16, 32)
    scfg = ServeConfig(model=mcfg,
                       cache=CacheConfig(num_blocks=40, block_size=8,
                                         max_blocks_per_seq=8),
                       max_batch=4, buckets=buckets)
    params = TransformerLM(mcfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    step = build_decode_step(mcfg, scfg.cache, max_batch=scfg.max_batch,
                             buckets=scfg.buckets)

    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(
        mean_interarrival_ms / 1e3, n_requests))
    # arrival rate saturates the 4-wide decode (~5 tokens/ms on this box),
    # and generation lengths vary 4-19: the static baseline's batch barrier
    # idles finished slots until the longest member completes, which is the
    # makespan continuous batching reclaims
    trace = [(float(arrivals[i]), f"r{i}",
              [int(t) for t in rng.integers(1, 64, size=int(rng.integers(4, 17)))],
              int(rng.integers(4, 20)))
             for i in range(n_requests)]

    def run(engine_cls):
        eng = engine_cls(params, scfg, step=step)
        pending = deque(trace)
        start = time.monotonic()
        while pending or not eng.idle:
            now = time.monotonic() - start
            while pending and pending[0][0] <= now:
                off, rid, prompt, mn = pending.popleft()
                eng.submit(Request(rid=rid, prompt=prompt,
                                   max_new_tokens=mn, arrival=start + off))
            if eng.idle:
                time.sleep(min(1e-3, max(0.0, pending[0][0] - now)))
                continue
            eng.step()
        total = time.monotonic() - start
        ttft = np.array([r.ttft for r in eng.results.values()])
        itl = np.array([g for r in eng.results.values() for g in r.itl])
        toks = sum(len(r.tokens) for r in eng.results.values())
        return eng, {
            "tokens_per_sec": round(toks / total, 1),
            "total_sec": round(total, 3),
            "p50_ttft_ms": round(float(np.percentile(ttft, 50)) * 1e3, 2),
            "p99_ttft_ms": round(float(np.percentile(ttft, 99)) * 1e3, 2),
            "p50_itl_ms": round(float(np.percentile(itl, 50)) * 1e3, 2),
            "p99_itl_ms": round(float(np.percentile(itl, 99)) * 1e3, 2),
            "preemptions": sum(r.preemptions for r in eng.results.values()),
            "prefix_hits": eng.cache.stats["prefix_hits"],
        }

    cont_eng, cont = run(ContinuousEngine)
    stat_eng, stat = run(StaticEngine)
    outputs_match = all(
        cont_eng.results[rid].tokens == stat_eng.results[rid].tokens
        for _, rid, _, _ in trace)

    result = {
        "metric": "serve",
        "unit": "tokens/sec; ms",
        "requests": n_requests,
        "mean_interarrival_ms": mean_interarrival_ms,
        "generated_tokens": sum(len(stat_eng.results[rid].tokens)
                                for _, rid, _, _ in trace),
        "continuous": cont,
        "static": stat,
        # the tentpole claim: more throughput without giving back tail
        # first-token latency (scheduling policy only — same steps, cache,
        # and trace)
        "continuous_beats_static": bool(
            cont["tokens_per_sec"] > stat["tokens_per_sec"]
            and cont["p99_ttft_ms"] <= stat["p99_ttft_ms"]),
        "outputs_match": bool(outputs_match),
        "source": "measured wall time, Poisson open-loop load on the CPU "
                  "backend (tiny transformer); continuous/static share "
                  "compiled steps and trace",
    }
    if aot and not quick:
        result["aot_decode_donation"] = _serve_aot_receipt()
    return result


def _serve_aot_receipt() -> dict:
    """Chipless v5e decode-step donation receipt, subprocess-isolated like
    the other AOT paths (graceful degradation off-toolchain)."""
    import subprocess
    import sys as _sys

    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "aot_serve.py")
    try:
        out = subprocess.run(
            [_sys.executable, tool], capture_output=True, text=True,
            timeout=900,
        )
        if out.returncode == 0 and out.stdout.strip():
            return json.loads(out.stdout.strip().splitlines()[-1])
        tail = (out.stderr or out.stdout).strip().splitlines()
        err = tail[-1] if tail else f"exit {out.returncode}"
    except Exception as e:  # missing libtpu, timeout, ...
        err = f"{type(e).__name__}: {e}"
    return {
        "metric": "serve_aot_donation",
        "degraded": (
            f"TPU AOT compile unavailable ({err}); the CPU backend does "
            "not implement buffer donation — run on a box with the TPU "
            "toolchain"
        ),
    }


def bench_serve_slo(*, n_requests: int = 96, quick: bool = False,
                    seed: int = 0) -> dict:
    """Serving under stress: a 2x-capacity Poisson overload trace through
    three configurations of the SAME compiled steps, cache geometry, and
    request shapes — (a) guardrailed: bounded admission queue plus
    per-request deadlines, shedding on overload with explicit SHED
    verdicts; (b) unguarded: unbounded queue, no deadlines; (c) a
    capacity-matched reference at half the arrival rate. The claim: under
    2x overload the guardrails keep admitted p99 TTFT near the
    capacity-matched tail and goodput (requests finishing inside the SLO
    budget, per second) at or above ~90% of the capacity-matched run,
    where the unguarded queue's TTFT grows with the backlog and its
    goodput collapses. Chipless (tiny transformer, CPU backend): absolute
    numbers are harness truth, the guarded/unguarded/capacity ratios are
    the claim."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from collections import deque

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_sandbox.models.transformer import (TransformerConfig,
                                                TransformerLM)
    from tpu_sandbox.serve import (CacheConfig, ContinuousEngine, Request,
                                   ServeConfig)
    from tpu_sandbox.serve.decode import build_decode_step

    if quick:
        n_requests = min(n_requests, 12)

    mcfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                             n_layers=2, d_ff=64, max_len=128,
                             dtype=jnp.float32)
    buckets = (16,) if quick else (16, 32)
    cache = CacheConfig(num_blocks=40, block_size=8, max_blocks_per_seq=8)
    params = TransformerLM(mcfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    step = build_decode_step(mcfg, cache, max_batch=4, buckets=buckets)

    max_waiting = 8         # guardrail: 2x max_batch admission bound

    def make_trace(mean_ia_ms):
        rng = np.random.default_rng(seed)
        arrivals = np.cumsum(rng.exponential(mean_ia_ms / 1e3, n_requests))
        return [(float(arrivals[i]), f"r{i}",
                 [int(t) for t in
                  rng.integers(1, 64, size=int(rng.integers(4, 17)))],
                 int(rng.integers(4, 20)))
                for i in range(n_requests)]

    def run(trace, *, bound: bool, slo: float | None):
        scfg = ServeConfig(model=mcfg, cache=cache, max_batch=4,
                           buckets=buckets,
                           max_waiting=max_waiting if bound else 0)
        eng = ContinuousEngine(params, scfg, step=step)
        pending = deque(trace)
        start = time.monotonic()
        while pending or not eng.idle:
            now = time.monotonic() - start
            while pending and pending[0][0] <= now:
                off, rid, prompt, mn = pending.popleft()
                eng.submit(Request(
                    rid=rid, prompt=prompt, max_new_tokens=mn,
                    arrival=start + off,
                    deadline=start + off + slo if bound and slo else None))
            if eng.idle:
                time.sleep(min(1e-3, max(0.0, pending[0][0] - now)))
                continue
            eng.step()
        total = time.monotonic() - start
        lat = {rid: r.ttft + sum(r.itl)
               for rid, r in eng.results.items()}
        within = sum(1 for v in lat.values()
                     if slo is None or v <= slo)
        ttft = np.array([r.ttft for r in eng.results.values()] or [0.0])
        return {
            "completed": len(eng.results),
            "shed": len(eng.shed),
            "within_slo": within,
            "goodput_rps": round(within / total, 1),
            "p50_ttft_ms": round(float(np.percentile(ttft, 50)) * 1e3, 2),
            "p99_ttft_ms": round(float(np.percentile(ttft, 99)) * 1e3, 2),
            "total_sec": round(total, 3),
        }

    # calibrate to THIS box: a closed-loop batch run (everything arrives
    # at t=0, no bound, no deadlines) measures the engine's service rate;
    # the capacity trace matches it, the overload trace doubles it, and
    # the SLO budget is ~2x the bounded-queue residence time (queue of 8
    # + batch of 4 in the system, plus generation)
    calib = run(make_trace(0.0), bound=False, slo=None)
    service_rps = max(calib["completed"] / calib["total_sec"], 1.0)
    capacity_ia_ms = 1e3 / service_rps
    overload_ia_ms = capacity_ia_ms / 2
    slo_s = 24.0 / service_rps

    overload = make_trace(overload_ia_ms)
    guarded = run(overload, bound=True, slo=slo_s)
    unguarded = run(overload, bound=False, slo=slo_s)
    capacity = run(make_trace(capacity_ia_ms), bound=False, slo=slo_s)

    return {
        "metric": "serve_slo",
        "unit": "requests/sec within SLO; ms",
        "requests": n_requests,
        "calibrated_service_rps": round(service_rps, 1),
        "slo_ms": round(slo_s * 1e3, 2),
        "overload_interarrival_ms": round(overload_ia_ms, 3),
        "capacity_interarrival_ms": round(capacity_ia_ms, 3),
        "max_waiting": max_waiting,
        "guarded_overload": guarded,
        "unguarded_overload": unguarded,
        "capacity_matched": capacity,
        # the tentpole claims: shedding keeps the admitted tail near the
        # capacity-matched tail, goodput holds, and every request gets a
        # verdict (completed + shed = submitted)
        "tail_bounded": bool(
            guarded["p99_ttft_ms"]
            <= max(3 * capacity["p99_ttft_ms"], slo_s * 1e3)),
        "goodput_holds": bool(
            guarded["goodput_rps"] >= 0.9 * capacity["goodput_rps"]),
        "unguarded_collapses": bool(
            unguarded["p99_ttft_ms"] > 2 * guarded["p99_ttft_ms"]
            or unguarded["goodput_rps"] < guarded["goodput_rps"]),
        "every_request_verdicted": bool(
            guarded["completed"] + guarded["shed"] == n_requests),
        "source": "measured wall time, Poisson open-loop overload on the "
                  "CPU backend (tiny transformer); all three runs share "
                  "compiled steps and request shapes",
    }


def bench_gateway(*, n_requests: int = 96, replicas: int = 3,
                  quick: bool = False, seed: int = 0) -> dict:
    """The gateway's two claims, measured over real sockets.

    **Routing** — a prefix-heavy open-loop trace (8 prompt families, each
    sharing a 3-block prefix) through the full network path: GatewayClient
    -> TCP -> Gateway -> targeted KV queues -> ReplicaWorker threads, once
    with prefix-hash routing and once with the random-routing control arm.
    Claim: p99 TTFT under hash routing beats random, because requests land
    where their prefix is already resident and prefill only pays for the
    uncached suffix.

    **Admission** — the same path at 2x the calibrated fleet capacity with
    per-request deadlines, SLO-feasibility admission vs the classic
    occupancy bound. Claim: feasibility goodput (ok verdicts/sec; the
    engine never lands a result past its deadline, so every ok IS within
    SLO) at least matches occupancy, while shedding infeasible work at the
    door with an explicit verdict instead of letting it rot in a queue.

    Honesty note: the engine here is the real ContinuousEngine over the
    real paged allocator, but the *step* is a stub whose prefill sleeps
    proportionally to the UNCACHED token count (non-null dest indices from
    the allocator). That models the prefill-compute saving that suffix-only
    prefill would give a real model; this repo's real prefill still
    recomputes shared spans (it skips only the K/V stores), so the TTFT
    win is a model of the mechanism, not a measurement of the tiny
    transformer. The sockets, wire protocol, routing, queues, claims,
    leases, and verdicts are all the real thing.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import threading

    import numpy as np

    from tpu_sandbox.gateway import FleetSpec, Gateway, GatewayClient
    from tpu_sandbox.models.transformer import TransformerConfig
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.serve.cache import CacheConfig
    from tpu_sandbox.serve.engine import ContinuousEngine, ServeConfig
    from tpu_sandbox.serve.replica import ReplicaWorker

    if quick:
        n_requests = min(n_requests, 24)
        replicas = min(replicas, 2)

    BLOCK = 8
    PREFIX_BLOCKS = 3
    PREFILL_TOKEN_S = 1.2e-3   # modeled per-uncached-token prefill cost
    DECODE_STEP_S = 0.8e-3     # modeled per-engine-step decode cost
    n_families = 4 if quick else 8
    mcfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                             n_layers=2, d_ff=64, max_len=128)
    ccfg = CacheConfig(num_blocks=48, block_size=BLOCK, max_blocks_per_seq=8)

    class _ModeledStep:
        """Stub step: next token = last + 1 mod vocab (deterministic, so
        requeue/hedge replays stay bitwise), prefill cost = uncached
        tokens (the allocator redirects resident-prefix positions to the
        null block, so their dest index is 0)."""

        buckets = (32,)
        vocab = 64

        def __init__(self):
            self.prefill = {b: self._prefill for b in self.buckets}

        def pick_bucket(self, plen):
            for b in self.buckets:
                if plen <= b:
                    return b
            raise ValueError(f"prompt of {plen} exceeds {self.buckets}")

        def _prefill(self, params, k, v, toks, dest, last):
            uncached = int(np.count_nonzero(np.asarray(dest)))
            time.sleep(PREFILL_TOKEN_S * uncached)
            toks = np.asarray(toks)
            logits = np.zeros((self.vocab,), np.float32)
            logits[(int(toks[0, int(last)]) + 1) % self.vocab] = 1.0
            return logits, k, v

        def decode(self, params, k, v, tokens, lengths, tables):
            time.sleep(DECODE_STEP_S)
            tokens = np.asarray(tokens)
            logits = np.zeros((tokens.shape[0], self.vocab), np.float32)
            for i in range(tokens.shape[0]):
                logits[i, (int(tokens[i, 0]) + 1) % self.vocab] = 1.0
            return logits, k, v

    rng = np.random.default_rng(seed)
    families = [[int(t) for t in rng.integers(1, 64, PREFIX_BLOCKS * BLOCK)]
                for _ in range(n_families)]

    def make_trace(mean_ia_s, tag):
        """Open-loop arrivals; each request = family prefix + fresh
        suffix, so chains collide exactly on the shared blocks."""
        offs = (np.zeros(n_requests) if mean_ia_s == 0.0
                else np.cumsum(rng.exponential(mean_ia_s, n_requests)))
        out = []
        for i in range(n_requests):
            fam = families[int(rng.integers(0, n_families))]
            suffix = [int(t) for t in
                      rng.integers(1, 64, int(rng.integers(4, 9)))]
            out.append((float(offs[i]), f"{tag}-{i}", fam + suffix, 4))
        return out

    def run(trace, *, policy, admission, deadline_s, rate_rps):
        """One fully isolated fleet: fresh store, replicas, gateway."""
        server = KVServer()
        kv = KVClient(port=server.port)
        stop = threading.Event()
        workers, threads, clones = [], [], []
        for i in range(replicas):
            wkv = kv.clone()
            clones.append(wkv)
            eng = ContinuousEngine(
                None,
                ServeConfig(model=mcfg, cache=ccfg, max_batch=4,
                            buckets=_ModeledStep.buckets, max_waiting=0),
                step=_ModeledStep())
            w = ReplicaWorker(wkv, eng, tag=f"r{i}", lease_ttl=1.0,
                              load_interval=0.05)
            workers.append(w)

            def loop(worker=w):
                while not stop.is_set():
                    worker.tick()
                    if worker.engine.idle:
                        time.sleep(0.001)

            t = threading.Thread(target=loop, daemon=True,
                                 name=f"bench-replica-{i}")
            threads.append(t)
            t.start()
        spec = FleetSpec(block_size=BLOCK, service_rate_rps=rate_rps,
                         occupancy_bound=8)
        gw = Gateway(kv, [spec], admission=admission, policy=policy,
                     policy_seed=seed, refresh_min_s=0.01,
                     max_report_age_s=2.0).start()
        client = GatewayClient(gw.port, deadline_s=deadline_s,
                               max_retries=0)
        time.sleep(0.2)  # first load reports land before the trace starts
        try:
            t0 = time.monotonic()
            admitted, refused = [], []
            for off, rid, prompt, max_new in trace:
                now = time.monotonic() - t0
                if off > now:
                    time.sleep(off - now)
                ok = client.submit(rid, prompt, max_new)
                (admitted if ok else refused).append(rid)
            verdicts = {rid: _terminal_verdict(client, rid, 120.0)
                        for rid in admitted}
            total = time.monotonic() - t0
            ok_ttfts = [v["ttft_s"] for v in verdicts.values()
                        if v.get("verdict") == "ok"]
            n_ok = len(ok_ttfts)
            # audit: every rid — admitted, engine-shed, or door-shed —
            # has exactly one terminal verdict (done marker still == 1)
            results = set(kv.keys("serve/result/"))
            audit = all(
                f"serve/result/{rid}" in results
                and kv.try_get(f"serve/done/{rid}") == b"1"
                for rid in admitted + refused
            ) and len(results) == len(trace)
            ttft = np.array(ok_ttfts or [0.0])
            return {
                "submitted": len(trace),
                "admitted": len(admitted),
                "door_shed": len(refused),
                "completed_ok": n_ok,
                "engine_shed": len(admitted) - n_ok,
                "goodput_rps": round(n_ok / total, 1),
                "p50_ttft_ms": round(float(np.percentile(ttft, 50)) * 1e3,
                                     2),
                "p99_ttft_ms": round(float(np.percentile(ttft, 99)) * 1e3,
                                     2),
                "routed_prefix": gw.stats.routed_prefix,
                "routed_balance": gw.stats.routed_balance,
                "routed_shared": gw.stats.routed_shared,
                "total_sec": round(total, 3),
                "verdict_audit_ok": bool(audit),
            }
        finally:
            client.close()
            gw.close()
            stop.set()
            for t in threads:
                t.join(timeout=10.0)
            for w in workers:
                w.engine.drain_to_requests()  # leak-fixture hygiene
            for c in clones:
                c.close()
            kv.close()
            server.stop()

    # calibrate to THIS box: closed-loop (all arrivals at t=0), no door,
    # prefix routing -> the fleet's aggregate service rate
    calib = run(make_trace(0.0, "c"), policy="prefix", admission="none",
                deadline_s=None, rate_rps=1.0)
    fleet_rps = max(calib["completed_ok"] / calib["total_sec"], 1.0)
    replica_rps = fleet_rps / replicas

    # routing arms: moderate load (0.7x capacity) so queueing noise does
    # not swamp the prefill saving the arms differ by
    routed = run(make_trace(1.0 / (0.7 * fleet_rps), "p"),
                 policy="prefix", admission="none", deadline_s=None,
                 rate_rps=replica_rps)
    randomed = run(make_trace(1.0 / (0.7 * fleet_rps), "r"),
                   policy="random", admission="none", deadline_s=None,
                   rate_rps=replica_rps)

    # admission arms: 2x overload, deadline sized to ~12 requests of
    # residence on one replica — feasibility sheds the overflow at the
    # door, occupancy admits by queue depth and lets deadlines burn
    deadline_s = 12.0 / replica_rps
    feasible = run(make_trace(1.0 / (2.0 * fleet_rps), "f"),
                   policy="prefix", admission="feasible",
                   deadline_s=deadline_s, rate_rps=replica_rps)
    occupancy = run(make_trace(1.0 / (2.0 * fleet_rps), "o"),
                    policy="prefix", admission="occupancy",
                    deadline_s=deadline_s, rate_rps=replica_rps)

    return {
        "metric": "gateway",
        "unit": "ms TTFT; ok verdicts/sec",
        "requests_per_run": n_requests,
        "replicas": replicas,
        "calibrated_fleet_rps": round(fleet_rps, 1),
        "deadline_ms": round(deadline_s * 1e3, 2),
        "routing_prefix": routed,
        "routing_random": randomed,
        "admission_feasible": feasible,
        "admission_occupancy": occupancy,
        # the tentpole claims
        "prefix_beats_random_p99": bool(
            routed["p99_ttft_ms"] < randomed["p99_ttft_ms"]),
        "prefix_ttft_speedup": round(
            randomed["p99_ttft_ms"] / max(routed["p99_ttft_ms"], 1e-6), 2),
        "feasible_goodput_holds": bool(
            feasible["goodput_rps"] >= occupancy["goodput_rps"]),
        "every_request_verdicted": bool(all(
            r["verdict_audit_ok"]
            for r in (calib, routed, randomed, feasible, occupancy))),
        "source": "measured wall time over real sockets (gateway wire "
                  "protocol, targeted KV queues, replica threads); "
                  "prefill cost modeled as sleep proportional to "
                  "uncached-token count from the real paged allocator",
    }


def bench_chaos(*, quick: bool = False, seed: int = 0) -> dict:
    """HA front-door receipts: seeded chaos campaigns against a real
    multi-gateway fleet, TLS on every external wire.

    **Zero-loss under SIGKILL** — N real gateway *processes* (the
    ``python -m tpu_sandbox.gateway`` entrypoint, TLS certs from
    tests/fixtures/tls, shared-secret hello inside the channel) front a
    replica-thread fleet; a seeded campaign replays a canonical workload
    trace (obs/workload) and SIGKILLs the connected gateway mid-load.
    Claim: the failover client loses zero requests, every rid reaches
    exactly one terminal verdict (claim audit), and the failover cost is
    visible in submit p99 but bounded.

    **Seeded matrix** — >= 3 distinct seeded campaigns drawn by
    runtime/chaos.build_schedule over the gateway-kill / shed-storm /
    replica-stall families, each ending green on the same invariants;
    one seed replayed against a fresh fleet must produce a byte-identical
    claim audit (the determinism receipt).

    **Tracediff gate** — the SIGKILL campaign's critical-path profile is
    gated by tools/tracediff.py against a fault-free control over the
    same trace: losing a gateway may cost availability blips at the
    door, but the per-request serve path (prefill/decode/queue) must not
    regress.

    Honesty note: replicas are in-process threads over the real engine
    with a sleep-modeled step (bench_gateway's stub); gateways are real
    processes and the SIGKILL is a real ``os.kill``. The wire is TLS end
    to end — a plaintext connect must be refused with a clean close and
    show up in the surviving gateway's handshake-failure counter.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import contextlib
    import signal as _signal
    import socket as _socket
    import struct
    import tempfile
    import threading

    import numpy as np

    from tpu_sandbox.gateway import (FleetSpec, GatewayClient,
                                     make_client_ssl_context)
    from tpu_sandbox.gateway import wire as gwire
    from tpu_sandbox.gateway.server import live_gateway_endpoints
    from tpu_sandbox.models.transformer import TransformerConfig
    from tpu_sandbox.obs import (ENV_TRACE_DIR, collect, critpath,
                                 get_recorder, reset_recorder, workload)
    from tpu_sandbox.runtime.chaos import (ChaosCampaign, ChaosFault,
                                           build_schedule,
                                           check_alert_claims, prefix_probe)
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.serve.cache import CacheConfig, chain_digest
    from tpu_sandbox.serve.engine import ContinuousEngine, ServeConfig
    from tpu_sandbox.serve.replica import ReplicaWorker, read_load_reports

    repo = os.path.dirname(os.path.abspath(__file__))
    tlsdir = os.path.join(repo, "tests", "fixtures", "tls")
    cert = os.path.join(tlsdir, "server.pem")
    key = os.path.join(tlsdir, "server.key")
    ca = os.path.join(tlsdir, "ca.pem")
    TOKEN = "bench-chaos-secret"

    n_gateways = 2 if quick else 3
    n_replicas = 2 if quick else 3
    # moderate utilization: the gate compares per-request serve segments
    # ctrl-vs-kill, which only pairs cleanly when arrivals don't saturate
    # the host (post-failover bunching would deepen batches and inflate
    # every segment on a loaded box)
    n_requests = 12 if quick else 48
    duration_s = 0.8 if quick else 4.0
    matrix_seeds = [seed + 11, seed + 22] if quick \
        else [seed + 11, seed + 22, seed + 33]

    BLOCK = 8
    PREFILL_TOKEN_S = 0.4e-3
    DECODE_STEP_S = 0.8e-3
    mcfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                             n_layers=2, d_ff=64, max_len=128)
    ccfg = CacheConfig(num_blocks=64, block_size=BLOCK, max_blocks_per_seq=8)

    class _ModeledStep:
        buckets = (32,)
        vocab = 64

        def __init__(self):
            self.prefill = {b: self._prefill for b in self.buckets}

        def pick_bucket(self, plen):
            for b in self.buckets:
                if plen <= b:
                    return b
            raise ValueError(f"prompt of {plen} exceeds {self.buckets}")

        def _prefill(self, params, k, v, toks, dest, last):
            time.sleep(PREFILL_TOKEN_S
                       * int(np.count_nonzero(np.asarray(dest))))
            toks = np.asarray(toks)
            logits = np.zeros((self.vocab,), np.float32)
            logits[(int(toks[0, int(last)]) + 1) % self.vocab] = 1.0
            return logits, k, v

        def decode(self, params, k, v, tokens, lengths, tables):
            time.sleep(DECODE_STEP_S)
            tokens = np.asarray(tokens)
            logits = np.zeros((tokens.shape[0], self.vocab), np.float32)
            for i in range(tokens.shape[0]):
                logits[i, (int(tokens[i, 0]) + 1) % self.vocab] = 1.0
            return logits, k, v

    @contextlib.contextmanager
    def recorder_arm(trace_dir):
        prior = os.environ.pop(ENV_TRACE_DIR, None)
        if trace_dir is not None:
            os.environ[ENV_TRACE_DIR] = trace_dir
        reset_recorder()
        try:
            yield
        finally:
            get_recorder().flush()
            if prior is None:
                os.environ.pop(ENV_TRACE_DIR, None)
            else:
                os.environ[ENV_TRACE_DIR] = prior
            reset_recorder()

    def spawn_gateway(kv_port, gid):
        """One real gateway process, TLS-only, parsed for its port."""
        proc = subprocess.Popen(
            [sys.executable, "-m", "tpu_sandbox.gateway",
             "--kv-port", str(kv_port), "--gateway-id", gid,
             "--token", TOKEN, "--admission", "none",
             "--policy", "prefix",
             "--tls-cert", cert, "--tls-key", key],
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=repo,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        line = proc.stdout.readline()
        if "listening on" not in line or "tls=on" not in line:
            proc.kill()
            raise RuntimeError(f"gateway {gid} failed to start: {line!r}")
        port = int(line.split("listening on ")[1]
                   .split()[0].rsplit(":", 1)[1])
        return proc, port

    def plaintext_probe(port):
        """A cleartext frame against the TLS listener: the server must
        close that connection cleanly (EOF, no bytes served back)."""
        s = _socket.create_connection(("127.0.0.1", port), timeout=5.0)
        try:
            s.sendall(struct.pack("!BI", gwire.OP_HELLO, 2) + b"{}")
            s.settimeout(5.0)
            try:
                return s.recv(64) == b""
            except (ConnectionError, OSError):
                return True  # reset is a close too, just less polite
        finally:
            s.close()

    def run_campaign(campaign_seed, *, schedule_for, trace_dir=None,
                     probe=False, plaintext=False):
        """One fully isolated fleet + one seeded campaign against it."""
        server = KVServer()
        kv = KVClient(port=server.port)
        stop = threading.Event()
        workers, threads, clones = [], [], []
        for i in range(n_replicas):
            wkv = kv.clone()
            clones.append(wkv)
            eng = ContinuousEngine(
                None,
                ServeConfig(model=mcfg, cache=ccfg, max_batch=4,
                            buckets=_ModeledStep.buckets, max_waiting=0),
                step=_ModeledStep())
            w = ReplicaWorker(wkv, eng, tag=f"r{i}", lease_ttl=1.0,
                              load_interval=0.05)
            workers.append(w)

            def loop(worker=w):
                while not stop.is_set():
                    worker.tick()
                    if worker.engine.idle:
                        time.sleep(0.001)

            t = threading.Thread(target=loop, daemon=True,
                                 name=f"chaos-replica-{i}")
            threads.append(t)
            t.start()
        procs = {}
        endpoints = []
        for i in range(n_gateways):
            gid = f"gw{i}"
            proc, port = spawn_gateway(server.port, gid)
            procs[gid] = proc
            endpoints.append(("127.0.0.1", port))
        trace = workload.synthesize(campaign_seed, n_requests,
                                    duration_s=duration_s,
                                    prompt_tokens=(8, 24),
                                    decode_tokens=(2, 6))
        schedule = schedule_for(campaign_seed)
        client = GatewayClient(endpoints=list(endpoints), token=TOKEN,
                               tls=make_client_ssl_context(ca),
                               backoff_base=0.02)
        submit_s = []

        def door(rid, prompt, max_new):
            t0 = time.monotonic()
            ok = client.submit(rid, prompt, max_new)
            submit_s.append(time.monotonic() - t0)
            return ok

        def sigkill(gid):
            procs[gid].send_signal(_signal.SIGKILL)

        out = {}
        cm = recorder_arm(trace_dir) if trace_dir is not None \
            else contextlib.nullcontext()
        try:
            time.sleep(0.3)  # first load reports + hb leases land
            out["live_gateways"] = len(live_gateway_endpoints(kv))
            with cm:
                campaign = ChaosCampaign(
                    kv, trace, door, seed=campaign_seed,
                    schedule=schedule,
                    hooks={"kill_gateway": sigkill},
                    block_size=BLOCK, verdict_timeout=180.0)
                res = campaign.run()
            sub = np.array(submit_s or [0.0])
            out.update(
                seed=campaign_seed, submitted=res.submitted,
                admitted=res.admitted, retried=res.retried,
                lost=len(res.lost),
                verdicts_ok=sum(1 for v in res.verdicts.values()
                                if v["verdict"] == "ok"),
                fired=[f["action"] for f in res.fired],
                failovers=client.stats.failovers,
                submit_p50_ms=round(float(np.percentile(sub, 50)) * 1e3, 2),
                submit_p99_ms=round(float(np.percentile(sub, 99)) * 1e3, 2),
                exactly_once_ok=res.ok,
                alert_claims_ok=check_alert_claims(kv) == [],
                audit=res.audit_bytes(),
            )
            if plaintext:
                # the survivor the client is parked on keeps serving;
                # a plaintext probe against it is refused cleanly
                host, port = client.endpoint
                out["plaintext_refused"] = plaintext_probe(port)
                before = client.gateway_stats()["stats"]
                out["tls_handshake_failures"] = int(
                    before.get("tls_handshake_failures", 0))
                out["serves_after_plaintext"] = bool(
                    client.gateway_stats()["admission"] == "none")
            if probe:
                row = dict(workload.replay_order(trace)[0])
                row["prompt_tokens"] = max(int(row["prompt_tokens"]),
                                           BLOCK)
                prompt = campaign.prompt_for(row)
                head = chain_digest(prompt[:BLOCK], BLOCK)[0]
                deadline = time.monotonic() + 30.0
                while time.monotonic() < deadline:
                    if any(head in r.get("prefix_digest", ())
                           for r in read_load_reports(kv).values()):
                        break
                    time.sleep(0.02)
                rid = f"probe-{campaign_seed}"
                out["prefix_probe_routed"] = bool(
                    prefix_probe(client, prompt, rid))
                client.result(rid, timeout=60.0)
        finally:
            client.close()
            for proc in procs.values():
                if proc.poll() is None:
                    proc.send_signal(_signal.SIGTERM)
            for proc in procs.values():
                try:
                    proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait(timeout=10)
                proc.stdout.close()
            stop.set()
            for t in threads:
                t.join(timeout=10.0)
            for w in workers:
                w.engine.drain_to_requests()
            for c in clones:
                c.close()
            kv.close()
            server.stop()
        return out

    kill_seed = seed + 1
    mid_kill = [ChaosFault(at_s=round(duration_s * 0.4, 6),
                           action="kill_gateway", target="gw0")]

    def matrix_schedule(s):
        return mid_kill + build_schedule(s, duration_s=duration_s, targets={
            "shed_storm": [f"r{i}" for i in range(n_replicas)],
            "stall_replica": [f"r{i}:0.2" for i in range(n_replicas)],
        }, n_faults=3)

    dirs = {arm: tempfile.mkdtemp(prefix=f"chaos-{arm}-")
            for arm in ("ctrl", "kill")}
    # fault-free control over the same trace, recorded for the gate
    ctrl = run_campaign(kill_seed, schedule_for=lambda s: [],
                        trace_dir=dirs["ctrl"])
    # the headline arm: SIGKILL the connected gateway mid-load, recorded
    killarm = run_campaign(kill_seed, schedule_for=lambda s: mid_kill,
                           trace_dir=dirs["kill"], probe=True,
                           plaintext=True)
    # determinism receipt: same seed, fresh fleet, byte-identical audit
    killarm_replay = run_campaign(kill_seed, schedule_for=lambda s: mid_kill)
    # the seeded matrix: full fault families, distinct seeds
    matrix = [run_campaign(s, schedule_for=matrix_schedule)
              for s in matrix_seeds]

    for arm, d in dirs.items():
        analysis = critpath.analyze(collect.load_merged(d))
        critpath.save_profile(analysis["profile"],
                              os.path.join(d, "critpath_profile.json"))
    td = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "tracediff.py"),
         os.path.join(dirs["ctrl"], "critpath_profile.json"),
         os.path.join(dirs["kill"], "critpath_profile.json"),
         "--min-share", "0.05"],
        capture_output=True, text=True)

    def green(c):
        return bool(c["exactly_once_ok"] and c["lost"] == 0
                    and c["verdicts_ok"] == c["submitted"]
                    and c["alert_claims_ok"])

    audit_identical = killarm["audit"] == killarm_replay["audit"]
    for c in (ctrl, killarm, killarm_replay, *matrix):
        c.pop("audit", None)
    return {
        "metric": "chaos",
        "unit": "requests lost; campaigns green",
        "gateways": n_gateways,
        "replicas": n_replicas,
        "requests_per_campaign": n_requests,
        "control": ctrl,
        "sigkill_campaign": killarm,
        "seeded_campaigns": matrix,
        "campaigns_green": sum(green(c) for c in (killarm, *matrix)),
        "all_campaigns_green": bool(all(green(c)
                                        for c in (killarm, *matrix))),
        "sigkill_zero_loss": bool(killarm["lost"] == 0
                                  and killarm["failovers"] >= 1),
        "audit_replay_identical": bool(audit_identical),
        "tls_plaintext_refused": bool(killarm.get("plaintext_refused")),
        "tls_handshake_failures_counted": bool(
            killarm.get("tls_handshake_failures", 0) >= 1),
        "prefix_probe_routed": bool(killarm.get("prefix_probe_routed")),
        "tracediff_gate_exit": td.returncode,
        "tracediff_gate_ok": bool(td.returncode == 0),
        "source": "real gateway processes (TLS wire, shared-secret hello) "
                  "SIGKILLed mid-load by os signal; replica threads over "
                  "the real engine with sleep-modeled step; claim audit "
                  "read straight from the KV store; tracediff run as the "
                  "committed CLI on saved critpath profiles",
    }


def bench_obs(*, quick: bool = False, seed: int = 0) -> dict:
    """Flight-recorder overhead receipts: is tracing cheap enough to
    leave ON?

    Three measurements, all chipless:

    1. **Step-time overhead** — a jitted 512x512 matmul step timed with
       the trainer's per-step instrumentation (one retrospective
       ``complete("train:step")`` per step), recorder off vs on. The
       claim: <= 3% regression.
    2. **Gateway p99 TTFT delta** — a self-contained 2-replica modeled
       fleet (the bench_gateway stub step: prefill cost proportional to
       uncached tokens) run off vs on, p99 TTFT pooled over repeats. The
       claim: <= 5% regression. The on-arm also yields the trace-
       completeness receipt: every non-shed request leaves one connected
       submit->...->verdict chain.
    3. **Artifacts** — the on-arm logs must export as valid Chrome
       trace-event JSON, and a sample per-request waterfall is committed
       into the round record.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import contextlib
    import statistics
    import tempfile
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_sandbox.gateway import FleetSpec, Gateway, GatewayClient
    from tpu_sandbox.models.transformer import TransformerConfig
    from tpu_sandbox.obs import (ENV_TRACE_DIR, collect, get_recorder,
                                 reset_recorder)
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.serve.cache import CacheConfig
    from tpu_sandbox.serve.engine import ContinuousEngine, ServeConfig
    from tpu_sandbox.serve.replica import ReplicaWorker

    @contextlib.contextmanager
    def recorder_arm(trace_dir):
        """Point the process-global recorder at ``trace_dir`` (or disable
        it for the control arm) for the duration."""
        prior = os.environ.pop(ENV_TRACE_DIR, None)
        if trace_dir is not None:
            os.environ[ENV_TRACE_DIR] = trace_dir
        reset_recorder()
        try:
            yield
        finally:
            get_recorder().flush()
            if prior is None:
                os.environ.pop(ENV_TRACE_DIR, None)
            else:
                os.environ[ENV_TRACE_DIR] = prior
            reset_recorder()

    # -- 1. step-time overhead ------------------------------------------------
    # Paired design: run-to-run drift on a shared CPU box dwarfs the
    # ~8us emit cost, so each round times an off arm and an on arm
    # back-to-back and the receipt is the MEDIAN of per-round deltas —
    # drift cancels within a round instead of masquerading as overhead.
    n_steps = 30 if quick else 80
    rounds = 6 if quick else 16
    x = jnp.ones((512, 512), jnp.float32)
    step = jax.jit(lambda a: a @ a / 512.0)
    step(x).block_until_ready()  # compile outside both arms

    def run_steps():
        rec = get_recorder()
        times = []
        for _ in range(n_steps):
            t0 = time.monotonic()
            step(x).block_until_ready()
            rec.complete("train:step", t0)
            times.append(time.monotonic() - t0)
        return statistics.median(times)

    run_steps()  # warm the loop shape itself
    step_dir = tempfile.mkdtemp(prefix="obs-step-")
    offs, deltas = [], []
    step_events = 0
    for _ in range(rounds):
        with recorder_arm(None):
            off = run_steps()
        with recorder_arm(step_dir):
            on = run_steps()
            step_events += get_recorder().stats()["events"]
        offs.append(off)
        deltas.append(on - off)
    step_off = statistics.median(offs)
    step_delta = statistics.median(deltas)
    step_overhead = step_delta / step_off

    # -- 2. gateway p99 TTFT delta -------------------------------------------
    BLOCK = 8
    PREFILL_TOKEN_S = 1.2e-3
    DECODE_STEP_S = 0.8e-3
    n_requests = 16 if quick else 48
    repeats = 2 if quick else 3
    mcfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                             n_layers=2, d_ff=64, max_len=128)
    ccfg = CacheConfig(num_blocks=48, block_size=BLOCK, max_blocks_per_seq=8)

    class _ModeledStep:
        buckets = (32,)
        vocab = 64

        def __init__(self):
            self.prefill = {b: self._prefill for b in self.buckets}

        def pick_bucket(self, plen):
            for b in self.buckets:
                if plen <= b:
                    return b
            raise ValueError(f"prompt of {plen} exceeds {self.buckets}")

        def _prefill(self, params, k, v, toks, dest, last):
            uncached = int(np.count_nonzero(np.asarray(dest)))
            time.sleep(PREFILL_TOKEN_S * uncached)
            toks = np.asarray(toks)
            logits = np.zeros((self.vocab,), np.float32)
            logits[(int(toks[0, int(last)]) + 1) % self.vocab] = 1.0
            return logits, k, v

        def decode(self, params, k, v, tokens, lengths, tables):
            time.sleep(DECODE_STEP_S)
            tokens = np.asarray(tokens)
            logits = np.zeros((tokens.shape[0], self.vocab), np.float32)
            for i in range(tokens.shape[0]):
                logits[i, (int(tokens[i, 0]) + 1) % self.vocab] = 1.0
            return logits, k, v

    rng = np.random.default_rng(seed)
    prefix = [int(t) for t in rng.integers(1, 64, 2 * BLOCK)]

    def run_fleet(tag):
        """One isolated 2-replica fleet pass; returns ok TTFTs (s)."""
        server = KVServer()
        kv = KVClient(port=server.port)
        stop = threading.Event()
        workers, threads, clones = [], [], []
        for i in range(2):
            wkv = kv.clone()
            clones.append(wkv)
            eng = ContinuousEngine(
                None,
                ServeConfig(model=mcfg, cache=ccfg, max_batch=4,
                            buckets=_ModeledStep.buckets, max_waiting=0),
                step=_ModeledStep())
            w = ReplicaWorker(wkv, eng, tag=f"{tag}{i}", lease_ttl=1.0,
                              load_interval=0.05)
            workers.append(w)

            def loop(worker=w):
                while not stop.is_set():
                    worker.tick()
                    if worker.engine.idle:
                        time.sleep(0.001)

            t = threading.Thread(target=loop, daemon=True,
                                 name=f"obs-replica-{tag}{i}")
            threads.append(t)
            t.start()
        gw = Gateway(kv, [FleetSpec(block_size=BLOCK)], admission="none",
                     refresh_min_s=0.01, max_report_age_s=2.0).start()
        client = GatewayClient(gw.port, max_retries=0)
        time.sleep(0.2)
        try:
            offs = np.cumsum(rng.exponential(0.03, n_requests))
            t0 = time.monotonic()
            rids = []
            for i in range(n_requests):
                now = time.monotonic() - t0
                if offs[i] > now:
                    time.sleep(offs[i] - now)
                rid = f"{tag}-{i}"
                suffix = [int(t) for t in
                          rng.integers(1, 64, int(rng.integers(4, 9)))]
                if client.submit(rid, prefix + suffix, 4):
                    rids.append(rid)
            verdicts = [_terminal_verdict(client, rid, 120.0)
                        for rid in rids]
            return [v["ttft_s"] for v in verdicts
                    if v.get("verdict") == "ok"]
        finally:
            client.close()
            gw.close()
            stop.set()
            for t in threads:
                t.join(timeout=10.0)
            for w in workers:
                w.engine.drain_to_requests()
            for c in clones:
                c.close()
            kv.close()
            server.stop()

    # same paired discipline as the step arm: one discarded warmup run
    # (cold sockets/threads), then alternating off/on passes
    with recorder_arm(None):
        run_fleet("warm")
    gw_dir = tempfile.mkdtemp(prefix="obs-gw-")
    ttfts_off, ttfts_on = [], []
    for r in range(repeats):
        with recorder_arm(None):
            ttfts_off.extend(run_fleet(f"off{r}"))
        with recorder_arm(gw_dir):
            ttfts_on.extend(run_fleet(f"on{r}"))
    p99_off = float(np.percentile(ttfts_off, 99))
    p99_on = float(np.percentile(ttfts_on, 99))
    p99_delta = (p99_on - p99_off) / p99_off

    # -- 3. artifacts from the on-arm logs ------------------------------------
    merged = collect.load_merged(gw_dir)
    chains = collect.trace_chains(merged)
    checks = [collect.chain_check(rs) for rs in chains.values()]
    full = sum(1 for c in checks
               if {"submit", "route", "enqueue", "claim", "admit",
                   "decode", "verdict"} <= set(c["names"]))
    doc = json.loads(json.dumps(collect.to_chrome_trace(merged)))
    chrome_ok = (doc["displayTimeUnit"] == "ms"
                 and len(doc["traceEvents"]) > len(merged))
    waterfall = collect.format_waterfall(
        collect.request_waterfall(merged, rid="on0-0"))

    return {
        "metric": "obs",
        "unit": "fractional overhead, recorder on vs off",
        "step": {
            "steps_per_arm": n_steps,
            "paired_rounds": rounds,
            "off_ms": round(step_off * 1e3, 4),
            "on_ms": round((step_off + step_delta) * 1e3, 4),
            "overhead_frac": round(step_overhead, 4),
            "events_recorded": step_events,
        },
        "gateway": {
            "requests_per_arm": n_requests * repeats,
            "ok_off": len(ttfts_off),
            "ok_on": len(ttfts_on),
            "p99_ttft_off_ms": round(p99_off * 1e3, 2),
            "p99_ttft_on_ms": round(p99_on * 1e3, 2),
            "p99_delta_frac": round(p99_delta, 4),
        },
        "trace": {
            "traces": len(chains),
            "full_chains": full,
            "connected_frac": round(
                sum(1 for c in checks if c["connected"]) / len(checks), 4)
            if checks else None,
        },
        "chrome_trace_valid": bool(chrome_ok),
        "sample_waterfall": waterfall.splitlines(),
        # the tentpole claims: tracing is cheap enough to leave on
        "step_overhead_ok": bool(step_overhead <= 0.03),
        "gateway_p99_ok": bool(p99_delta <= 0.05),
        "source": "measured wall time, recorder-off vs recorder-on arms; "
                  "gateway fleet modeled as in bench_gateway (real "
                  "sockets/queues/engine, sleep-modeled step)",
    }


def bench_critpath(*, quick: bool = False, seed: int = 0) -> dict:
    """Trace-analytics receipts: does critical-path attribution explain
    the wall clock, does tracediff gate real regressions (and only real
    ones), and does the online pipeline-bubble gauge agree with the
    trace?

    Four measurements, all chipless:

    1. **Attribution coverage** — a gateway-served open-loop modeled
       fleet (real sockets/KV/engine, sleep-modeled step) run with the
       recorder on; every served request's causal critical path is
       attributed to named segments. The claim: >= 95% of request wall
       attributed, residue reported as ``unattributed``.
    2. **Regression gating** — the same fleet rerun twice: once
       identically, once with decode modeled ~20% slower.
       ``tools/tracediff.py`` must flag the slowdown (exit 1, decode
       named) while passing the identical rerun (exit 0) — the noise
       floor separates real regressions from run-to-run jitter.
    3. **Online bubble accounting** — a 2-stage / 4-microbatch 1F1B
       pipeline over sleep-modeled stage programs. The online
       ``mpmd.bubble_fraction`` gauge (read back through the tsdb
       ring), the offline trace-derived bubble, and the analytic
       ``(S-1)/(M+S-1) = 0.2`` (BENCH_r07's offline measurement) must
       agree within 5 points.
    4. **Workload export** — the control run's trace exports as a
       canonical replayable workload trace that round-trips
       byte-identically through dumps -> loads -> dumps.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import contextlib
    import statistics
    import tempfile
    import threading

    import numpy as np

    from tpu_sandbox.gateway import FleetSpec, Gateway, GatewayClient
    from tpu_sandbox.models.transformer import TransformerConfig
    from tpu_sandbox.mpmd.driver import StageWorker
    from tpu_sandbox.mpmd.transport import LocalTransport
    from tpu_sandbox.obs import (ENV_TRACE_DIR, collect, critpath,
                                 get_recorder, reset_recorder, tsdb,
                                 workload)
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.serve.cache import CacheConfig
    from tpu_sandbox.serve.engine import ContinuousEngine, ServeConfig
    from tpu_sandbox.serve.replica import ReplicaWorker

    @contextlib.contextmanager
    def recorder_arm(trace_dir):
        prior = os.environ.pop(ENV_TRACE_DIR, None)
        if trace_dir is not None:
            os.environ[ENV_TRACE_DIR] = trace_dir
        reset_recorder()
        try:
            yield
        finally:
            get_recorder().flush()
            if prior is None:
                os.environ.pop(ENV_TRACE_DIR, None)
            else:
                os.environ[ENV_TRACE_DIR] = prior
            reset_recorder()

    # -- 1+2. gateway fleet: control / identical rerun / slow decode ---------
    BLOCK = 8
    PREFILL_TOKEN_S = 0.4e-3
    DECODE_STEP_S = 10e-3
    n_requests = 12 if quick else 32
    max_new = 8
    mcfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                             n_layers=2, d_ff=64, max_len=128)
    ccfg = CacheConfig(num_blocks=48, block_size=BLOCK, max_blocks_per_seq=8)

    class _ModeledStep:
        buckets = (32,)
        vocab = 64

        def __init__(self, decode_step_s=DECODE_STEP_S):
            self.decode_step_s = decode_step_s
            self.prefill = {b: self._prefill for b in self.buckets}

        def pick_bucket(self, plen):
            for b in self.buckets:
                if plen <= b:
                    return b
            raise ValueError(f"prompt of {plen} exceeds {self.buckets}")

        def _prefill(self, params, k, v, toks, dest, last):
            uncached = int(np.count_nonzero(np.asarray(dest)))
            time.sleep(PREFILL_TOKEN_S * uncached)
            toks = np.asarray(toks)
            logits = np.zeros((self.vocab,), np.float32)
            logits[(int(toks[0, int(last)]) + 1) % self.vocab] = 1.0
            return logits, k, v

        def decode(self, params, k, v, tokens, lengths, tables):
            time.sleep(self.decode_step_s)
            tokens = np.asarray(tokens)
            logits = np.zeros((tokens.shape[0], self.vocab), np.float32)
            for i in range(tokens.shape[0]):
                logits[i, (int(tokens[i, 0]) + 1) % self.vocab] = 1.0
            return logits, k, v

    prefix = [int(t) for t in
              np.random.default_rng(seed).integers(1, 64, 2 * BLOCK)]

    def run_fleet(tag, decode_step_s=DECODE_STEP_S):
        """One isolated 2-replica fleet pass. A fresh rng seeded the
        same way every pass: identical arrivals/prompts, so profiles
        pair request-for-request and only the modeled costs differ."""
        rng = np.random.default_rng(seed + 1)
        server = KVServer()
        kv = KVClient(port=server.port)
        stop = threading.Event()
        workers, threads, clones = [], [], []
        for i in range(2):
            wkv = kv.clone()
            clones.append(wkv)
            eng = ContinuousEngine(
                None,
                ServeConfig(model=mcfg, cache=ccfg, max_batch=4,
                            buckets=_ModeledStep.buckets, max_waiting=0),
                step=_ModeledStep(decode_step_s))
            w = ReplicaWorker(wkv, eng, tag=f"{tag}{i}", lease_ttl=1.0,
                              load_interval=0.05)
            workers.append(w)

            def loop(worker=w):
                while not stop.is_set():
                    worker.tick()
                    if worker.engine.idle:
                        time.sleep(0.001)

            t = threading.Thread(target=loop, daemon=True,
                                 name=f"critpath-replica-{tag}{i}")
            threads.append(t)
            t.start()
        gw = Gateway(kv, [FleetSpec(block_size=BLOCK)], admission="none",
                     refresh_min_s=0.01, max_report_age_s=2.0).start()
        client = GatewayClient(gw.port, max_retries=0)
        time.sleep(0.2)
        try:
            offs = np.cumsum(rng.exponential(0.12, n_requests))
            t0 = time.monotonic()
            rids = []
            for i in range(n_requests):
                now = time.monotonic() - t0
                if offs[i] > now:
                    time.sleep(offs[i] - now)
                rid = f"{tag}-{i}"
                suffix = [int(t) for t in
                          rng.integers(1, 64, int(rng.integers(4, 9)))]
                if client.submit(rid, prefix + suffix, max_new):
                    rids.append(rid)
            for rid in rids:
                _terminal_verdict(client, rid, 120.0)
        finally:
            client.close()
            gw.close()
            stop.set()
            for t in threads:
                t.join(timeout=10.0)
            for w in workers:
                w.engine.drain_to_requests()
            for c in clones:
                c.close()
            kv.close()
            server.stop()

    with recorder_arm(None):
        run_fleet("warm")  # cold sockets/threads, discarded
    dirs = {arm: tempfile.mkdtemp(prefix=f"critpath-{arm}-")
            for arm in ("ctrl", "same", "slow")}
    with recorder_arm(dirs["ctrl"]):
        run_fleet("ctl")
    with recorder_arm(dirs["same"]):
        run_fleet("sam")
    with recorder_arm(dirs["slow"]):
        run_fleet("slo", decode_step_s=DECODE_STEP_S * 1.2)

    profiles = {}
    merged_ctrl = None
    for arm, d in dirs.items():
        merged = collect.load_merged(d)
        if arm == "ctrl":
            merged_ctrl = merged
        analysis = critpath.analyze(merged)
        profiles[arm] = analysis["profile"]
        critpath.save_profile(
            analysis["profile"], os.path.join(d, "critpath_profile.json"))
    prof = profiles["ctrl"]
    covs = [r["coverage"] for r in critpath.analyze(merged_ctrl)["requests"]]
    frac_covered = (sum(1 for c in covs if c >= critpath.COVERAGE_TARGET)
                    / len(covs)) if covs else 0.0

    # the gate itself, end to end: the committed CLI on the saved profiles
    td = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", "tracediff.py")
    # gate on segments carrying >= 5% of wall: the modeled workload's
    # signal lives in decode/prefill; the sub-3% control segments
    # (route, claim, queue_wait) jitter with host scheduling noise
    MIN_SHARE = 0.05
    gate = {}
    for arm in ("same", "slow"):
        r = subprocess.run(
            [sys.executable, td,
             os.path.join(dirs["ctrl"], "critpath_profile.json"),
             os.path.join(dirs[arm], "critpath_profile.json"),
             "--min-share", str(MIN_SHARE)],
            capture_output=True, text=True)
        gate[arm] = r.returncode
    cmp_slow = critpath.compare_profiles(profiles["ctrl"], profiles["slow"],
                                         min_share=MIN_SHARE)
    cmp_same = critpath.compare_profiles(profiles["ctrl"], profiles["same"],
                                         min_share=MIN_SHARE)
    decode_row = next((r for r in cmp_slow["segments"]
                       if r["segment"] == "decode"), {})

    # -- 3. online vs offline vs analytic pipeline bubble --------------------
    S, M = 2, 4
    OP_S = 8e-3
    mpmd_steps = 5 if quick else 8

    class _StubStage:
        """Sleep-modeled stage program with uniform op cost, so the
        1F1B schedule's measured bubble lands on the analytic
        (S-1)/(M+S-1). ``loss_grad`` covers the last stage's F AND B,
        hence 2x the unit cost."""

        def __init__(self, stage):
            self.stage = stage
            self.n_stages = S
            self.microbatches = M
            self.is_first = stage == 0
            self.is_last = stage == S - 1

        def place(self, x):
            return x

        def init_opt_state(self, params):
            return {"t": np.zeros((), np.float32)}

        def fwd(self, params, x):
            time.sleep(OP_S)
            return np.asarray(x, np.float32)

        def loss_grad(self, params, x, y):
            time.sleep(2 * OP_S)
            return (np.float32(0.0), {"w": np.zeros((1,), np.float32)},
                    np.asarray(x, np.float32))

        def bwd(self, params, x, g):
            time.sleep(OP_S)
            return ({"w": np.zeros((1,), np.float32)},
                    np.asarray(g, np.float32))

        def apply_grads(self, params, opt_state, grads):
            return params, opt_state

    mpmd_dir = tempfile.mkdtemp(prefix="critpath-mpmd-")
    tr = LocalTransport()
    stages = [StageWorker(_StubStage(s), {"w": np.zeros((1,), np.float32)},
                          None, tr) for s in range(S)]
    tokens = np.zeros((M, 1, 4), np.float32)
    targets = np.zeros((M, 1, 4), np.float32)
    errors: dict[int, BaseException] = {}

    def stage_loop(w):
        try:
            for step in range(mpmd_steps):
                w.run_step(
                    step,
                    tokens=tokens if w.program.is_first else None,
                    targets=targets if w.program.is_last else None)
        except BaseException as e:  # noqa: BLE001 — reraised below
            errors[w.program.stage] = e

    with recorder_arm(mpmd_dir):
        ts = [threading.Thread(target=stage_loop, args=(w,), daemon=True,
                               name=f"critpath-stage-{w.program.stage}")
              for w in stages]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60.0)
    if errors:
        raise next(iter(errors.values()))

    # steady state only (step 0 pays the one-time pipeline fill), and
    # the per-stage MEDIAN over steps: one descheduled thread must not
    # skew the receipt on a noisy host
    online = {s: round(statistics.median(
        v for k, v in stages[s].bubble_by_step.items() if k >= 1), 6)
        for s in range(S)}
    bub = critpath.bubble_fractions(collect.load_merged(mpmd_dir))
    offline = {}
    for row in bub["per_step"]:
        if row["step"] >= 1:
            offline.setdefault(row["stage"], []).append(row["bubble"])
    offline = {s: round(statistics.median(v), 6)
               for s, v in sorted(offline.items())}
    analytic = (S - 1) / (M + S - 1)

    # the gauge path the fleet console reads: flush the global registry
    # (run_step set the per-stage gauges) into a live KV, read it back
    server = KVServer()
    kv = KVClient(port=server.port)
    try:
        tsdb.TimeSeriesFlusher(kv, "critpath-bench").flush()
        gauge = {}
        for row in tsdb.read_series(kv, "mpmd.bubble_fraction"):
            series = row["series"]
            if "stage=" in series and row["kind"] != "counter":
                stage = series.split("stage=", 1)[1].rstrip("}")
                gauge[int(stage)] = float(row["v"])
        published_series = critpath.publish_profile(kv, prof)
        cov_gauge = tsdb.latest_value(
            tsdb.read_series(kv, "critpath.coverage"))
    finally:
        kv.close()
        server.stop()
    bubble_err = max(abs(v - analytic)
                     for v in list(online.values()) + list(offline.values()))

    # -- 4. workload export round-trip ---------------------------------------
    wl = workload.from_trace(merged_ctrl, source="bench critpath ctrl arm")
    blob = workload.dumps(wl)
    wl_path = os.path.join(dirs["ctrl"], "workload.json")
    workload.save(wl, wl_path)
    roundtrip = workload.dumps(workload.load(wl_path))
    byte_identical = roundtrip == blob

    top = sorted(prof["segments"].items(), key=lambda kv_: -kv_[1]["total_s"])
    return {
        "metric": "critpath",
        "unit": "attribution coverage / regression gate verdicts / "
                "bubble fraction",
        "attribution": {
            "requests": prof["requests"],
            "ok": prof["ok"],
            "coverage_mean": prof["coverage_mean"],
            "coverage_min": prof["coverage_min"],
            "frac_requests_ge_95": round(frac_covered, 4),
            "top_segments": {seg: s["share"] for seg, s in top[:6]},
        },
        "tracediff": {
            "identical_rerun_exit": gate["same"],
            "slowdown_exit": gate["slow"],
            "identical_regressions": cmp_same["regressions"],
            "slowdown_regressions": cmp_slow["regressions"],
            "decode_ratio": decode_row.get("ratio"),
        },
        "bubble": {
            "stages": S, "microbatches": M, "steps": mpmd_steps,
            "online_per_stage": online,
            "offline_per_stage": offline,
            "gauge_per_stage": gauge,
            "analytic": round(analytic, 6),
            "max_abs_err": round(bubble_err, 6),
        },
        "workload": {
            "schema": wl["schema"],
            "rows": len(wl["requests"]),
            "byte_identical": bool(byte_identical),
        },
        "fleetop_feed": {
            "series_published": published_series,
            "coverage_gauge": cov_gauge,
        },
        # the tentpole claims
        "attribution_ok": bool(prof["coverage_mean"]
                               >= critpath.COVERAGE_TARGET),
        "gating_ok": bool(gate["slow"] == 1 and gate["same"] == 0
                          and "decode" in cmp_slow["regressions"]),
        "bubble_ok": bool(bubble_err <= 0.05),
        "workload_ok": bool(byte_identical),
        "_artifacts": {
            "trace_ctrl": dirs["ctrl"],
            "trace_slow": dirs["slow"],
            "trace_mpmd": mpmd_dir,
        },
        "source": "measured wall time over the bench_obs modeled fleet "
                  "(real sockets/queues/engine, sleep-modeled step); "
                  "tracediff run as the committed CLI on saved profiles; "
                  "bubble from sleep-modeled 1F1B stage workers vs the "
                  "trace-derived and analytic fractions",
    }


def bench_health(*, quick: bool = False, seed: int = 0) -> dict:
    """Health-plane receipts: is the durable metrics plane cheap enough
    to leave ON, and does it catch the pathologies fast enough to act?

    Three measurements, all chipless:

    1. **Flush overhead** — per-flush wall cost of a replica-sized
       registry (counters/gauges/histograms with label variants) into a
       live KV, plus paired step-loop arms flushing on the production
       cadence (once per tsdb bucket). The claim: <= 1% of step time.
    2. **Detection latency** — a stub-clock ``HealthMonitor`` against
       each seeded pathology (autoscale flapping, tenant starvation,
       preemption cascade): evaluation windows from pathology visible to
       alert claimed. The claim: <= 2 windows each.
    3. **fleetop** — the ops console renders from a live 2-replica
       modeled fleet (real sockets/KV/engine, sleep-modeled step) whose
       time series came off the replicas' own load-report cadence.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import statistics
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_sandbox.gateway import FleetSpec, Gateway, GatewayClient
    from tpu_sandbox.models.transformer import TransformerConfig
    from tpu_sandbox.obs.health import (CascadeDetector, HealthMonitor,
                                        OscillationDetector,
                                        StarvationDetector)
    from tpu_sandbox.obs.metrics import MetricsRegistry
    from tpu_sandbox.obs.record import Recorder
    from tpu_sandbox.obs.tsdb import TimeSeriesFlusher, list_series
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.serve.cache import CacheConfig
    from tpu_sandbox.serve.engine import ContinuousEngine, ServeConfig
    from tpu_sandbox.serve.replica import ReplicaWorker

    # -- 1. flush overhead ---------------------------------------------------
    server = KVServer()
    kv = KVClient(port=server.port)
    try:
        reg = MetricsRegistry()
        for i in range(12):
            reg.counter(f"bench.counter.c{i}",
                        labels={"kind": str(i % 3)}).inc(i)
        for i in range(6):
            reg.gauge(f"bench.gauge.g{i}").set(float(i))
        h = reg.histogram("bench.lat.s")
        for v in range(256):
            h.observe(v / 256.0)
        bucket_s = 1.0
        flusher = TimeSeriesFlusher(kv, "bench-rep", bucket_s=bucket_s,
                                    registry=reg, recorder=Recorder(None))
        keys_per_flush = flusher.flush()  # warm (first flush writes all)
        n_flush = 20 if quick else 60
        flush_times = []
        for i in range(n_flush):
            reg.counter("bench.counter.c0", labels={"kind": "0"}).inc()
            t0 = time.monotonic()
            flusher.flush()
            flush_times.append(time.monotonic() - t0)
        flush_ms = statistics.median(flush_times) * 1e3
        # the production cadence is one flush per bucket: the fraction of
        # every bucket interval spent flushing IS the step-time overhead
        flush_frac = flush_ms / (bucket_s * 1e3)

        # paired corroboration: identical step loops, the on arm also
        # flushing whenever the bucket rolls over
        x = jnp.ones((512, 512), jnp.float32)
        step = jax.jit(lambda a: a @ a / 512.0)
        step(x).block_until_ready()
        t0 = time.monotonic()
        for _ in range(50):
            step(x).block_until_ready()
        step_ms = (time.monotonic() - t0) / 50 * 1e3
        loop_s = 0.4 if quick else 1.0
        n_steps = max(50, int(loop_s / (step_ms / 1e3)))
        rounds = 3 if quick else 6

        def run_loop(flush_bucket_s=None):
            nxt = time.monotonic() + (flush_bucket_s or 1e9)
            t0 = time.monotonic()
            for _ in range(n_steps):
                step(x).block_until_ready()
                if time.monotonic() >= nxt:
                    reg.counter("bench.counter.c1",
                                labels={"kind": "1"}).inc()
                    flusher.flush()
                    nxt += flush_bucket_s
            return time.monotonic() - t0

        run_loop()  # warm the loop shape
        paired = []
        for _ in range(rounds):
            off = run_loop()
            on = run_loop(flush_bucket_s=bucket_s)
            paired.append((on - off) / off)
        paired_delta = statistics.median(paired)
    finally:
        kv.close()
        server.stop()

    # -- 2. detection latency (stub clock) -----------------------------------
    def _windows_to_alert(seed_pathology, detector, setup=None):
        """Evaluation windows from 'pathology visible in durable state'
        to 'alert claimed', on a monitor stepped once per window.
        ``setup`` seeds the healthy pre-pathology state the baseline
        evaluation observes."""
        srv = KVServer()
        dkv = KVClient(port=srv.port)
        try:
            t = [9000.0]
            mon = HealthMonitor(dkv, "bench-h0", window_s=1.0, rules=[],
                                detectors=[detector],
                                clock=lambda: t[0])
            if setup is not None:
                setup(dkv)
            mon.step()  # baseline evaluation before the pathology
            windows = 0
            while windows < 8:
                seed_pathology(dkv, windows)
                t[0] += 1.0
                windows += 1
                if mon.step():
                    return windows
            return None
        finally:
            dkv.close()
            srv.stop()

    def seed_flapping(dkv, i):
        if i > 0:
            return
        tail = 0
        for action in ("scale_up", "scale_down") * 2:
            dkv.set(f"serve/autoscale/events/{tail}", json.dumps(
                {"action": action, "reason": "queue_depth", "wall": 0.0}))
            tail += 1
        dkv.set("serve/autoscale/tail", str(tail))

    def setup_tenants(dkv):
        # both tenants known (and the mouse already queued) before onset
        dkv.set("sched/vtime/hog", repr(0.0))
        dkv.set("sched/vtime/mouse", repr(0.0))
        dkv.set("sched/queued/mouse", "2")

    def seed_starvation(dkv, i):
        # onset: the hog's vtime advances every window, the mouse's not
        dkv.set("sched/vtime/hog", repr(10.0 * (i + 1)))

    def seed_cascade(dkv, i):
        if i == 0:
            for _ in range(3):
                dkv.add("sched/preempts/victim")

    latencies = {
        "autoscale_oscillation": _windows_to_alert(
            seed_flapping, OscillationDetector()),
        "tenant_starvation": _windows_to_alert(
            seed_starvation, StarvationDetector(), setup=setup_tenants),
        "preemption_cascade": _windows_to_alert(
            seed_cascade, CascadeDetector()),
    }

    # -- 3. fleetop renders from a live fleet --------------------------------
    BLOCK = 8
    mcfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                             n_layers=2, d_ff=64, max_len=128)
    ccfg = CacheConfig(num_blocks=48, block_size=BLOCK, max_blocks_per_seq=8)
    rng = np.random.default_rng(seed)

    class _ModeledStep:
        buckets = (32,)
        vocab = 64

        def __init__(self):
            self.prefill = {b: self._prefill for b in self.buckets}

        def pick_bucket(self, plen):
            for b in self.buckets:
                if plen <= b:
                    return b
            raise ValueError(f"prompt of {plen} exceeds {self.buckets}")

        def _prefill(self, params, k, v, toks, dest, last):
            time.sleep(1e-3)
            toks = np.asarray(toks)
            logits = np.zeros((self.vocab,), np.float32)
            logits[(int(toks[0, int(last)]) + 1) % self.vocab] = 1.0
            return logits, k, v

        def decode(self, params, k, v, tokens, lengths, tables):
            time.sleep(5e-4)
            tokens = np.asarray(tokens)
            logits = np.zeros((tokens.shape[0], self.vocab), np.float32)
            for i in range(tokens.shape[0]):
                logits[i, (int(tokens[i, 0]) + 1) % self.vocab] = 1.0
            return logits, k, v

    server = KVServer()
    kv = KVClient(port=server.port)
    stop = threading.Event()
    workers, threads, clones = [], [], []
    gw = client = None
    try:
        for i in range(2):
            wkv = kv.clone()
            clones.append(wkv)
            eng = ContinuousEngine(
                None,
                ServeConfig(model=mcfg, cache=ccfg, max_batch=4,
                            buckets=_ModeledStep.buckets, max_waiting=0),
                step=_ModeledStep())
            w = ReplicaWorker(wkv, eng, tag=f"hw{i}", lease_ttl=1.0,
                              load_interval=0.05)
            workers.append(w)

            def loop(worker=w):
                while not stop.is_set():
                    worker.tick()
                    if worker.engine.idle:
                        time.sleep(0.001)

            t = threading.Thread(target=loop, daemon=True,
                                 name=f"health-replica-hw{i}")
            threads.append(t)
            t.start()
        gw = Gateway(kv, [FleetSpec(block_size=BLOCK)], admission="none",
                     refresh_min_s=0.01, max_report_age_s=2.0).start()
        client = GatewayClient(gw.port, max_retries=0)
        time.sleep(0.2)
        n_req = 6 if quick else 16
        rids = []
        for i in range(n_req):
            prompt = [int(t) for t in rng.integers(1, 64, 2 * BLOCK)]
            if client.submit(f"h{i}", prompt, 3):
                rids.append(f"h{i}")
        served = sum(1 for rid in rids
                     if _terminal_verdict(client, rid, 60.0).get("verdict")
                     == "ok")
        time.sleep(0.2)  # one more load-report/flush cadence
        mon = HealthMonitor(kv, "bench-live-h0", window_s=0.5)
        mon.step()
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools"))
        import fleetop
        console = fleetop.render(kv)
        n_series = len(list_series(kv))
    finally:
        if client is not None:
            client.close()
        if gw is not None:
            gw.close()
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        for w in workers:
            w.engine.drain_to_requests()
        for c in clones:
            c.close()
        kv.close()
        server.stop()

    fleetop_ok = ("replicas:" in console and "hw0" in console
                  and "hw1" in console and n_series > 0)
    return {
        "metric": "health",
        "unit": "fractional overhead / evaluation windows",
        "flush": {
            "keys_per_flush": keys_per_flush,
            "flush_ms": round(flush_ms, 4),
            "bucket_s": bucket_s,
            "overhead_frac": round(flush_frac, 5),
            "paired_loop_delta_frac": round(paired_delta, 5),
            "paired_rounds": rounds,
            "steps_per_arm": n_steps,
        },
        "detection_windows": latencies,
        "fleet": {
            "replicas": 2,
            "requests_served": served,
            "live_series": n_series,
            "fleetop_renders": bool(fleetop_ok),
        },
        "fleetop_sample": console.splitlines()[:24],
        # the tentpole claims
        "flush_overhead_ok": bool(flush_frac <= 0.01),
        "detection_ok": bool(all(w is not None and w <= 2
                                 for w in latencies.values())),
        "fleetop_ok": bool(fleetop_ok),
        "source": "measured wall time against a live KV store; detectors "
                  "driven by a stub-clock monitor over seeded durable "
                  "state; fleet modeled as in bench_obs (real "
                  "sockets/queues/engine, sleep-modeled step)",
    }


def bench_deploy(*, quick: bool = False, seed: int = 0) -> dict:
    """Continuous-deployment receipts: can the train->serve loop close
    without dropping traffic, and does the canary actually pull the cord?

    Three measurements, all chipless:

    1. **Zero-downtime rolling update** — a 2-replica fleet (real
       sockets/KV/gateway/engine, sleep-modeled step as in bench_health)
       under steady open-loop load, with a version published and a live
       :class:`DeployController` rolling it out mid-stream, against a
       no-deploy control arm of the identical load. The claims: zero
       lost verdicts, zero late (end-to-end > budget), and no shed spike
       over the control arm, while the fleet converges on the new
       version and the canary split is cleaned up.
    2. **Canary rollback latency** — a stub fleet whose canary's p99
       TTFT degrades 10x in the tsdb (rows seeded: the in-process
       metrics registry is shared, so real flushes cannot separate
       canary from baseline). Measured in controller evaluations from
       regression-visible to the fail verdict; claimed <= the
       configured ``regress_streak``, plus full convergence back and
       the durable ``canary_regression`` alert.
    3. **The closed loop** — generate -> distill-train -> publish ->
       promote, two generations of real transformer weights through the
       sealed-artifact path, each generation's request served on that
       generation's promoted version and the distillation objective
       strictly improving.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tempfile
    import threading

    import numpy as np

    from tpu_sandbox.deploy.controller import DeployConfig, DeployController
    from tpu_sandbox.deploy.registry import (audit_registry, current_target,
                                             deploy_events, read_shares,
                                             rollout_phase)
    from tpu_sandbox.gateway import FleetSpec, Gateway, GatewayClient
    from tpu_sandbox.models.transformer import TransformerConfig
    from tpu_sandbox.obs.health import active_subjects
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer
    from tpu_sandbox.serve.cache import CacheConfig
    from tpu_sandbox.serve.engine import ContinuousEngine, ServeConfig
    from tpu_sandbox.serve.replica import (ReplicaWorker, k_load,
                                           read_load_reports, read_result,
                                           submit_request)
    from tpu_sandbox.train.trainer import publish_checkpoint

    BLOCK = 8
    mcfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                             n_layers=2, d_ff=64, max_len=128)
    ccfg = CacheConfig(num_blocks=48, block_size=BLOCK, max_blocks_per_seq=8)
    rng = np.random.default_rng(seed)

    class _ModeledStep:
        buckets = (32,)
        vocab = 64

        def __init__(self):
            self.prefill = {b: self._prefill for b in self.buckets}

        def pick_bucket(self, plen):
            for b in self.buckets:
                if plen <= b:
                    return b
            raise ValueError(f"prompt of {plen} exceeds {self.buckets}")

        def _prefill(self, params, k, v, toks, dest, last):
            time.sleep(1e-3)
            toks = np.asarray(toks)
            logits = np.zeros((self.vocab,), np.float32)
            logits[(int(toks[0, int(last)]) + 1) % self.vocab] = 1.0
            return logits, k, v

        def decode(self, params, k, v, tokens, lengths, tables):
            time.sleep(5e-4)
            tokens = np.asarray(tokens)
            logits = np.zeros((tokens.shape[0], self.vocab), np.float32)
            for i in range(tokens.shape[0]):
                logits[i, (int(tokens[i, 0]) + 1) % self.vocab] = 1.0
            return logits, k, v

    def _stub_engine():
        return ContinuousEngine(
            None,
            ServeConfig(model=mcfg, cache=ccfg, max_batch=4,
                        buckets=_ModeledStep.buckets, max_waiting=0),
            step=_ModeledStep())

    ckpt_params = {"w": np.arange(8, dtype=np.float32)}

    # -- 1. rolling update under open-loop load vs no-deploy control ---------
    # arrival rate sized under the fleet's real drain rate (the bottleneck
    # is KV round-trips + the GIL across worker/gateway/collector threads,
    # not the modeled sleeps): open-loop load that keeps both replicas
    # busy without unbounded backlog, so "late" isolates
    # deployment-induced stalls from plain overload
    n_req = 80 if quick else 400
    interval_s = 20e-3
    late_budget_s = 2.0

    def run_arm(deploy: bool) -> dict:
        server = KVServer()
        kv = KVClient(port=server.port)
        stop = threading.Event()
        workers, threads, clones = [], [], []
        gw = client = ctrl = None
        tmp = tempfile.TemporaryDirectory()
        lat, bodies = {}, {}
        pending, pend_lock = {}, threading.Lock()
        try:
            for i in range(2):
                wkv = kv.clone()
                clones.append(wkv)
                # stub weight loads (any version is resident instantly);
                # publish_ts stays on — the controller's canary reads the
                # replicas' own flushed ttft/logprob series
                w = ReplicaWorker(
                    wkv, _stub_engine(), tag=f"dw{i}", lease_ttl=1.0,
                    load_interval=0.05,
                    swap_loader=lambda cmd: ("stub", int(cmd["ver"])))
                workers.append(w)

                def loop(worker=w):
                    while not stop.is_set():
                        worker.tick()
                        if worker.engine.idle:
                            time.sleep(0.001)

                t = threading.Thread(target=loop, daemon=True,
                                     name=f"deploy-replica-dw{i}")
                threads.append(t)
                t.start()
            gw = Gateway(kv, [FleetSpec(block_size=BLOCK)], admission="none",
                         refresh_min_s=0.01, max_report_age_s=2.0).start()
            client = GatewayClient(gw.port, max_retries=0)
            time.sleep(0.2)  # first load reports

            # collector: stamps each verdict as it lands in durable state
            ckv = kv.clone()
            clones.append(ckv)

            def collect():
                while not stop.is_set():
                    with pend_lock:
                        rids = list(pending)
                    for rid in rids:
                        raw = ckv.try_get(f"serve/result/{rid}")
                        if raw is None:
                            continue
                        t_done = time.monotonic()
                        with pend_lock:
                            t_sub = pending.pop(rid)
                        lat[rid] = t_done - t_sub
                        bodies[rid] = json.loads(raw)
                    time.sleep(0.002)

            col = threading.Thread(target=collect, daemon=True,
                                   name="deploy-collector")
            threads.append(col)
            col.start()

            ver = None
            if deploy:
                ctrl_kv = kv.clone()
                clones.append(ctrl_kv)
                ctrl = DeployController(
                    ctrl_kv, member_id="bench-roll", election_ttl=1.0,
                    cfg=DeployConfig(swap_resend_s=0.1))

                # 50ms cadence: an eternity for the canary windows, but
                # the controller's registry scans stop competing with the
                # serving path for the KV server and the GIL
                def ctrl_loop():
                    while not stop.is_set():
                        ctrl.tick()
                        time.sleep(0.05)

            # open loop: arrivals on a fixed clock, blind to completions
            next_t = time.monotonic()
            for i in range(n_req):
                if deploy and i == n_req // 3:
                    ver = publish_checkpoint(kv, ckpt_params,
                                             export_dir=tmp.name, step=1)
                    t = threading.Thread(target=ctrl_loop, daemon=True,
                                         name="deploy-ctrl")
                    threads.append(t)
                    t.start()
                rid = f"d{i}"
                prompt = [int(t) for t in rng.integers(1, 64, 2 * BLOCK)]
                t_sub = time.monotonic()
                if client.submit(rid, prompt, 3):
                    with pend_lock:
                        pending[rid] = t_sub
                else:  # door verdict is still terminal, still counted
                    bodies[rid] = _terminal_verdict(client, rid, 10.0)
                    lat[rid] = time.monotonic() - t_sub
                next_t += interval_s
                time.sleep(max(0.0, next_t - time.monotonic()))

            # drain: every rid must reach SOME terminal verdict (lost = 0)
            drain_deadline = time.monotonic() + 30.0
            while time.monotonic() < drain_deadline:
                with pend_lock:
                    if not pending:
                        break
                time.sleep(0.01)
            with pend_lock:
                lost = sorted(pending)
                pending.clear()

            rollout = None
            if deploy:
                # the rollout keeps rolling after the stream: wait for the
                # fleet to converge on the published version
                conv_deadline = time.monotonic() + 30.0
                while time.monotonic() < conv_deadline:
                    reps = read_load_reports(kv)
                    if (current_target(kv) == ver and len(reps) == 2
                            and all(r.get("ver") == ver
                                    for r in reps.values())):
                        break
                    time.sleep(0.02)
                reps = read_load_reports(kv)
                rollout = {
                    "ver": ver,
                    "promoted": bool(current_target(kv) == ver),
                    "replicas_on_target": sum(
                        1 for r in reps.values() if r.get("ver") == ver),
                    "events": [e["action"] for e in deploy_events(kv)],
                    "shares_cleared": read_shares(kv) is None,
                }
        finally:
            if client is not None:
                client.close()
            if gw is not None:
                gw.close()
            stop.set()
            for t in threads:
                t.join(timeout=10.0)
            if ctrl is not None:
                ctrl.resign()
            for w in workers:
                w.engine.drain_to_requests()
            for c in clones:
                c.close()
            kv.close()
            server.stop()
            tmp.cleanup()

        lats = sorted(lat.values())

        def pct(p):
            return (round(lats[min(len(lats) - 1, int(p * len(lats)))], 4)
                    if lats else None)

        return {
            "requests": n_req,
            "ok": sum(1 for b in bodies.values()
                      if b.get("verdict") == "ok"),
            "shed": sum(1 for b in bodies.values()
                        if b.get("verdict") == "SHED"),
            "lost": len(lost),
            "late": sum(1 for v in lat.values() if v > late_budget_s),
            "p50_s": pct(0.50),
            "p99_s": pct(0.99),
            "rollout": rollout,
        }

    control = run_arm(deploy=False)
    rolling = run_arm(deploy=True)

    # -- 2. canary regression -> auto-rollback latency -----------------------
    server = KVServer()
    kv = KVClient(port=server.port)
    tmp = tempfile.TemporaryDirectory()
    clones = []

    def clone():
        c = kv.clone()
        clones.append(c)
        return c

    try:
        workers = [
            ReplicaWorker(clone(), _stub_engine(), tag=f"cw{i}",
                          lease_ttl=0.5, load_interval=0.02,
                          publish_ts=False,
                          swap_loader=lambda cmd: ("stub", int(cmd["ver"])))
            for i in range(2)
        ]
        cfg = DeployConfig(swap_resend_s=0.05)
        ctrl = DeployController(clone(), member_id="bench-canary",
                                election_ttl=1.0, cfg=cfg)
        ver = publish_checkpoint(kv, ckpt_params, export_dir=tmp.name,
                                 step=1)

        def drive(until, timeout=30.0):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                for w in workers:
                    w.tick()
                ctrl.tick()
                if until():
                    return
                time.sleep(0.005)
            raise RuntimeError("bench_deploy: drive condition not reached")

        # canary swapped, split live — then its p99 TTFT degrades 10x
        drive(lambda: read_shares(kv) is not None)

        def seed_ttft(proc, p99):
            bucket = int(time.time())
            kv.set_ttl(
                f"obs/ts/{proc}/engine.ttft/{bucket % 120}",
                json.dumps({"kind": "histogram",
                            "v": {"count": 1, "p50": p99, "p90": p99,
                                  "p99": p99, "mean": p99},
                            "bucket": bucket, "wall": time.time()}), 60.0)

        seed_ttft("cw0", 10.0)
        seed_ttft("cw1", 1.0)
        evals, fail_evals = 0, None
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            for w in workers:
                w.tick()
            ctrl.tick()
            evals += 1
            if any(e["action"] == "canary_fail" for e in deploy_events(kv)):
                fail_evals = evals
                break
            time.sleep(0.005)
        drive(lambda: rollout_phase(kv, "", ver)["done"] is not None)
        phase = rollout_phase(kv, "", ver)
        canary = {
            "evals_to_fail_verdict": fail_evals,
            "regress_streak": cfg.regress_streak,
            "rolled_back": bool(phase["done"] is not None
                                and phase["done"]["outcome"]
                                == "rolled_back"),
            "target_after": current_target(kv),
            "canary_reverted": bool(
                json.loads(kv.get(k_load("cw0")))["ver"] == 0),
            "alerted": "default" in active_subjects(kv,
                                                    "canary_regression"),
            "shares_cleared": read_shares(kv) is None,
        }
        ctrl.resign()
    finally:
        for c in clones:
            c.close()
        kv.close()
        server.stop()
        tmp.cleanup()

    # -- 3. the closed loop: generate -> train -> publish -> promote ---------
    import jax
    import jax.numpy as jnp
    import optax

    from tpu_sandbox.models.transformer import TransformerLM
    from tpu_sandbox.serve.decode import build_decode_step

    mcfg3 = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                              n_layers=2, d_ff=64, max_len=128,
                              dtype=jnp.float32)
    ccfg3 = CacheConfig(num_blocks=24, block_size=4, max_blocks_per_seq=8)
    model = TransformerLM(mcfg3)
    dstep = build_decode_step(mcfg3, ccfg3, max_batch=2, buckets=(8, 16))

    def params_for(s):
        return model.init(jax.random.key(s),
                          jnp.zeros((1, 8), jnp.int32))["params"]

    teacher = params_for(7)
    student = params_for(0)
    opt = optax.adam(3e-3)
    opt_state = opt.init(student)
    rng3 = np.random.default_rng(seed)
    eval_toks = jnp.asarray(rng3.integers(0, 64, (8, 16)), jnp.int32)

    @jax.jit
    def distill_loss(params, toks):
        t_prob = jax.nn.softmax(model.apply({"params": teacher}, toks), -1)
        s_logits = model.apply({"params": params}, toks)
        return -jnp.mean(jnp.sum(
            t_prob * jax.nn.log_softmax(s_logits, -1), -1))

    grad_fn = jax.jit(jax.value_and_grad(distill_loss))
    train_steps = 12 if quick else 30

    server = KVServer()
    kv = KVClient(port=server.port)
    tmp = tempfile.TemporaryDirectory()
    wkv, ckv = kv.clone(), kv.clone()
    worker = ReplicaWorker(
        wkv,
        ContinuousEngine(params_for(0), ServeConfig(
            model=mcfg3, cache=ccfg3, max_batch=2, buckets=(8, 16)),
            step=dstep),
        tag="loop0", lease_ttl=0.5, load_interval=0.02, publish_ts=False)
    ctrl = DeployController(ckv, member_id="bench-loop", election_ttl=1.0,
                            cfg=DeployConfig(swap_resend_s=0.05))
    losses = [float(distill_loss(student, eval_toks))]
    served_vers = []
    try:
        def drive3(until, timeout=120.0):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                worker.tick()
                ctrl.tick()
                if until():
                    return
                time.sleep(0.005)
            raise RuntimeError("bench_deploy: closed loop stalled")

        for gen in range(2):
            for _ in range(train_steps):
                batch = jnp.asarray(rng3.integers(0, 64, (8, 16)),
                                    jnp.int32)
                _, grads = grad_fn(student, batch)
                updates, opt_state = opt.update(grads, opt_state)
                student = optax.apply_updates(student, updates)
            losses.append(float(distill_loss(student, eval_toks)))
            # sealed export + registry + controller promotion: the same
            # artifact path production checkpoints take (no stub loads)
            ver = publish_checkpoint(kv, student, export_dir=tmp.name,
                                     step=gen + 1)
            drive3(lambda v=ver: current_target(kv) == v)
            rid = f"loopgen{gen}"
            submit_request(kv, rid, [3, 1, 4, 1, 5], 3)
            drive3(lambda r=rid: kv.try_get(f"serve/result/{r}") is not None,
                   timeout=60.0)
            served_vers.append(read_result(kv, rid).get("ver"))
        statuses = {row["ver"]: row["status"]
                    for row in audit_registry(kv)["versions"]}
    finally:
        ctrl.resign()
        wkv.close()
        ckv.close()
        kv.close()
        server.stop()
        tmp.cleanup()

    closed_loop = {
        "generations": 2,
        "train_steps_per_gen": train_steps,
        "losses": [round(v, 5) for v in losses],
        "served_vers": served_vers,
        "registry_statuses": statuses,
    }

    zero_regression = bool(
        control["lost"] == 0 and rolling["lost"] == 0
        and control["late"] == 0 and rolling["late"] == 0
        and rolling["shed"] <= control["shed"])
    rollout_ok = bool(
        rolling["rollout"] is not None and rolling["rollout"]["promoted"]
        and rolling["rollout"]["replicas_on_target"] == 2
        and rolling["rollout"]["shares_cleared"])
    rollback_ok = bool(
        canary["rolled_back"] and canary["alerted"]
        and canary["canary_reverted"] and canary["target_after"] == 0
        and canary["evals_to_fail_verdict"] is not None
        and canary["evals_to_fail_verdict"] <= canary["regress_streak"])
    loop_ok = bool(
        closed_loop["served_vers"] == [1, 2]
        and closed_loop["losses"][2] < closed_loop["losses"][1]
        < closed_loop["losses"][0])
    return {
        "metric": "deploy",
        "unit": "verdict counts / controller evaluations / loss",
        "open_loop": {"arrival_interval_s": interval_s,
                      "late_budget_s": late_budget_s,
                      "control": control, "rolling": rolling},
        "canary": canary,
        "closed_loop": closed_loop,
        # the tentpole claims
        "zero_downtime_ok": bool(zero_regression and rollout_ok),
        "rollback_ok": rollback_ok,
        "closed_loop_ok": loop_ok,
        "source": "measured against live KV/gateway/replica sockets; load "
                  "fleet modeled as in bench_health (real queues/engine, "
                  "sleep-modeled step, stub weight loads); canary tsdb "
                  "rows seeded (the in-process metrics registry is shared, "
                  "so real flushes cannot separate canary from baseline); "
                  "closed loop is real transformer weights through the "
                  "sealed-artifact path",
    }


def _measure_input_stall(n_batches: int = 30, load_ms: float = 10.0,
                         step_ms: float = 10.0) -> dict:
    """Measured wall-time of a sleep-modeled train loop with and without
    the background prefetcher. The sleeps model a host-side batch assembly
    (``load_ms``) and a device step (``step_ms``) of comparable cost — the
    regime double-buffering exists for; the THREADING under test
    (data/loader.PrefetchLoader's queue + producer) is the real one.
    ``input_stall`` is time the consumer spends blocked in ``next()``."""
    import time

    from tpu_sandbox.data.loader import PrefetchLoader

    class SlowLoader:
        def __len__(self):
            return n_batches

        def __iter__(self):
            for i in range(n_batches):
                time.sleep(load_ms / 1e3)
                yield i, i  # payload irrelevant: the stall is the metric

    def consume(loader):
        t0 = time.monotonic()
        stall = 0.0
        it = iter(loader)
        while True:
            t1 = time.monotonic()
            try:
                next(it)
            except StopIteration:
                break
            stall += time.monotonic() - t1
            time.sleep(step_ms / 1e3)  # the "train step"
        return time.monotonic() - t0, stall

    total_sync, stall_sync = consume(SlowLoader())
    total_pre, stall_pre = consume(PrefetchLoader(SlowLoader()))
    return {
        "batches": n_batches,
        "host_load_ms_per_batch": load_ms,
        "step_ms": step_ms,
        "total_sec_sync": round(total_sync, 4),
        "total_sec_prefetch": round(total_pre, 4),
        "input_stall_sec_sync": round(stall_sync, 4),
        "input_stall_sec_prefetch": round(stall_pre, 4),
        "stall_reduction_frac": round(
            1.0 - stall_pre / stall_sync, 4) if stall_sync > 0 else None,
        "source": "measured wall time; load/step modeled by sleeps, "
                  "prefetch threading real (data/loader.PrefetchLoader)",
    }


def bench_overlap(world: int = 8) -> dict:
    """The overlapped-step-pipeline receipts: (1) XLA schedule structure of
    the bucketed gradient sync from a chipless multi-chip v5e AOT compile
    (tools/hlo_schedule.py — how many per-bucket all-reduces are issued
    before the last backward compute op, and the exposed-comm fraction);
    (2) measured input-stall reduction from the double-buffered prefetch
    loader. Chipless + host-threads."""
    import subprocess
    import sys as _sys

    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "hlo_schedule.py")
    # subprocess isolation: the AOT tool initializes libtpu and flips
    # jax_platforms — neither survives nor belongs in this process
    sched, err = None, None
    try:
        out = subprocess.run(
            [_sys.executable, tool], capture_output=True, text=True,
            timeout=600,
        )
        if out.returncode == 0 and out.stdout.strip():
            sched = json.loads(out.stdout.strip().splitlines()[-1])
        else:
            tail = (out.stderr or out.stdout).strip().splitlines()
            err = tail[-1] if tail else f"exit {out.returncode}"
    except Exception as e:  # missing libtpu, timeout, ...
        err = f"{type(e).__name__}: {e}"

    if sched is None:
        # CPU SPMD fallback: still PROVES the bucket split happened (one
        # collective per bucket in the HLO), but XLA:CPU lowers collectives
        # synchronously and prints no schedule worth reading — say so.
        from tpu_sandbox.utils.cli import ensure_devices

        devices = ensure_devices(world, force_cpu=True)
        _sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools"))
        from hlo_schedule import build_overlapped_hlo, schedule_report

        text = build_overlapped_hlo(devices, compiler_options={})
        sched = schedule_report(text)
        sched.pop("collectives", None)
        sched["degraded"] = (
            f"TPU AOT compile unavailable ({err}); CPU SPMD compile shows "
            "the per-bucket collective split but carries no latency-hiding "
            "schedule to audit"
        )

    sched.pop("collectives", None)
    return {
        "metric": "overlap",
        "exposed_comm_fraction": sched.get("exposed_comm_fraction"),
        "all_reduce_issues_before_last_bwd_compute": sched.get(
            "all_reduce_issues_before_last_bwd_compute"),
        "schedule": sched,
        "input_stall": _measure_input_stall(),
    }


def bench_donation() -> dict:
    """The donation receipt (graftlint GL-H201's measured counterpart):
    chipless AOT peak-memory delta between donate=True and donate=False
    for the DP and ZeRO step compiles, from XLA's memory analysis.
    Subprocess-isolated like the other AOT paths; the CPU backend cannot
    witness aliasing, so off-toolchain this degrades to a statement, not
    a fake zero."""
    import subprocess
    import sys as _sys

    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "aot_donation.py")
    try:
        out = subprocess.run(
            [_sys.executable, tool], capture_output=True, text=True,
            timeout=900,
        )
        if out.returncode == 0 and out.stdout.strip():
            return json.loads(out.stdout.strip().splitlines()[-1])
        tail = (out.stderr or out.stdout).strip().splitlines()
        err = tail[-1] if tail else f"exit {out.returncode}"
    except Exception as e:  # missing libtpu, timeout, ...
        err = f"{type(e).__name__}: {e}"
    return {
        "metric": "donation",
        "degraded": (
            f"TPU AOT compile unavailable ({err}); the CPU backend does "
            "not implement buffer donation, so there is no aliasing to "
            "measure — run on a box with the TPU toolchain"
        ),
    }


def bench_capacity(image_size: int, dtype_name: str, force_cpu: bool,
                   max_batch: int = 512, plan: str = "auto") -> dict:
    """The reference's published experiment, measured: max batch at
    image_size² on ONE device (reference README.md:9-15 — bs=10 OOMs a
    24 GB A5000, bs=5 runs; DDP trains effective 10). Doubling probe then
    binary search, each trial in a fresh jit with its own allocation;
    allocator failures (RESOURCE_EXHAUSTED / XlaRuntimeError OOM) are the
    signal, not an error. Each working batch runs ONE fetch-synced train
    step so the number means 'trains', not 'allocates'."""
    from tpu_sandbox.utils.cli import ensure_devices

    if force_cpu:
        ensure_devices(1, force_cpu=True)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpu_sandbox.data import synthetic_mnist
    from tpu_sandbox.data.mnist import normalize
    from tpu_sandbox.models import pick_convnet
    from tpu_sandbox.train import TrainState, make_train_step
    from tpu_sandbox.utils.profiling import host_sync

    dtype = jnp.bfloat16 if dtype_name == "bf16" else jnp.float32
    model = pick_convnet(image_size, plan=plan, dtype=dtype)
    tx = optax.sgd(1e-4)
    images, labels = synthetic_mnist(n=max(max_batch, 10), seed=0)
    images, labels = normalize(images), labels.astype("int32")

    def trial(bs: int, remat: bool = False) -> bool:
        try:
            state = TrainState.create(
                model, jax.random.key(0),
                jnp.zeros((1, image_size, image_size, 1), dtype), tx,
            )
            step = make_train_step(
                model, tx, image_size=(image_size, image_size), donate=True,
                remat=remat,
            )
            state, loss = step(state, jnp.asarray(images[:bs]),
                               jnp.asarray(labels[:bs]))
            ok = bool(np.isfinite(host_sync(loss)))
            del state
            return ok
        except Exception as e:  # allocator failure IS the measurement
            if _is_oom(f"{type(e).__name__}: {e}"):
                return False
            raise

    def bisect(remat: bool, start: int = 1):
        lo, hi, bs = 0, None, start
        while bs <= max_batch:
            if trial(bs, remat):
                lo = bs
                bs *= 2
            else:
                hi = bs
                break
        if hi is None:
            hi = max_batch + 1  # never failed up to the cap
        while hi - lo > 1:
            mid = (lo + hi) // 2
            if trial(mid, remat):
                lo = mid
            else:
                hi = mid
        return lo, hi

    lo, hi = bisect(remat=False)
    # the capacity lever: recompute-forward backward (make_train_step
    # remat) drops the saved conv activations from peak memory — the
    # one-device counterpart of "just buy a second GPU". Start the
    # doubling from the plain max (remat can only help).
    lo_r, hi_r = bisect(remat=True, start=max(lo, 1))

    # the reference's workaround story, demonstrated on one chip: if the
    # effective batch 10 doesn't fit directly, 2-step gradient accumulation
    # at bs=5/microbatch must still train it (reference README.md:14-15
    # does this with DDP across 2 GPUs instead)
    accum_ok = None
    if lo < 10:
        try:
            state = TrainState.create(
                model, jax.random.key(0),
                jnp.zeros((1, image_size, image_size, 1), dtype), tx,
            )
            step = make_train_step(
                model, tx, image_size=(image_size, image_size), donate=True,
                accum_steps=2,
            )
            _, loss = step(state, jnp.asarray(images[:10]),
                           jnp.asarray(labels[:10]))
            accum_ok = bool(np.isfinite(host_sync(loss)))
        except Exception as e:
            accum_ok = f"{type(e).__name__}: {e}"[:200]

    dev = jax.devices()[0]
    result = {
        "metric": "max_train_batch_one_device",
        "value": lo,
        "unit": f"images @ {image_size}x{image_size} {dtype_name}",
        "vs_baseline": round(lo / 5.0, 2),  # reference: bs=5 fits, 10 OOMs
        "baseline_kind": "reference A5000 24GB: bs=5 runs, bs=10 OOMs "
                         "(README.md:9-15)",
        "first_oom_batch": hi if hi <= max_batch else None,
        "max_batch_remat": lo_r,
        "first_oom_batch_remat": hi_r if hi_r <= max_batch else None,
        "probe_cap": max_batch,
        "effective_batch_10_via_accum2": accum_ok,
        "execution_plan": type(model).__name__,
        "device_kind": str(dev.device_kind),
    }
    if dev.platform == "cpu":
        result["degraded"] = ("CPU host memory, not accelerator HBM — "
                              "capacity number not comparable")
    return result


def bench_seq_scaling(force_cpu: bool, seq_lens=None, devices_wanted: int = 4,
                      quick: bool = False) -> dict:
    """Sequence-parallel attention scaling: ring (jnp) vs flash-ring
    (Pallas) vs Ulysses (all-to-all + flash) forward+backward step time at
    growing S on a 1-axis 'sp' mesh — VERDICT r01 item 5's seq-len table.
    On one real chip the mesh folds to 1 device (collectives are identity;
    still measures the kernels); on CPU it runs the full ring semantics on
    virtual devices."""
    from tpu_sandbox.utils.cli import ensure_devices

    if force_cpu:
        ensure_devices(devices_wanted, force_cpu=True)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_sandbox.parallel import (
        make_flash_ring_attention,
        make_ring_attention,
        make_ulysses_attention,
    )
    from tpu_sandbox.runtime.mesh import make_mesh
    from tpu_sandbox.utils.profiling import measure_per_step_repeated

    n_dev = jax.device_count()
    mesh = make_mesh({"sp": n_dev})
    b, h, d = (1, 4, 64) if quick else (1, 8, 128)
    if seq_lens is None:
        seq_lens = [256, 512] if quick else [4096, 8192, 16384, 32768]

    makers = {
        "ring": lambda: make_ring_attention(mesh, "sp"),
        "flash_ring": lambda: make_flash_ring_attention(mesh, "sp"),
        "ulysses": lambda: make_ulysses_attention(mesh, "sp"),
    }
    rng = np.random.default_rng(0)
    rows = []
    for s in seq_lens:
        row = {"seq_len": s}
        q, k, v = (jnp.asarray(
            rng.standard_normal((b, s, h, d)), jnp.bfloat16) for _ in range(3))
        for name, make in makers.items():
            try:
                attn = make()
                fwdbwd = jax.jit(jax.grad(
                    lambda q: jnp.sum(attn(q, k, v).astype(jnp.float32))
                ))

                def run(steps):
                    x = q
                    for _ in range(steps):
                        x = fwdbwd(x).astype(jnp.bfloat16)
                    return x

                t = measure_per_step_repeated(run, 2)
                # noise-negative differentials are not published (see
                # BASELINE.md "the r01 anomaly"); record why instead
                if t["sec_per_step"] > 0:
                    row[name + "_sec"] = t["sec_per_step"]
                    row[name + "_spread_frac"] = t["spread_frac"]
                else:
                    row[name + "_sec"] = None
                    row[name + "_error"] = (
                        f"non-positive differential {t['sec_per_step']:.3e}s"
                    )
            except Exception as e:
                row[name + "_sec"] = None
                row[name + "_error"] = f"{type(e).__name__}: {e}"[:200]
        rows.append(row)

    base = rows[-1].get("ring_sec")
    best = rows[-1].get("flash_ring_sec")
    result = {
        "metric": "sp_attention_seq_scaling",
        "value": round(base / best, 3) if base and best else 0.0,
        "unit": f"ring/flash_ring speedup @ S={rows[-1]['seq_len']} (fwd+bwd)",
        "vs_baseline": 0.0,
        "devices": n_dev,
        "device_kind": str(jax.devices()[0].device_kind),
        "shape": {"batch": b, "heads": h, "head_dim": d},
        "rows": rows,
    }
    if not (base and best):
        result["degraded"] = "headline pair unmeasured (see rows *_error)"
    return result


def bench_lm(force_cpu: bool, quick: bool = False) -> dict:
    """Transformer-LM training throughput (tokens/sec + MFU) on one device:
    the long-context model family's headline number, with the Pallas flash
    attention kernel on the hot path and the same fetch-synced differential
    timing + FLOP cross-check as the ConvNet bench."""
    from tpu_sandbox.utils.cli import ensure_devices

    if force_cpu:
        ensure_devices(1, force_cpu=True)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
    from tpu_sandbox.ops.losses import cross_entropy_loss
    from tpu_sandbox.ops.pallas_attention import flash_attention_fn
    from tpu_sandbox.train import TrainState
    from tpu_sandbox.utils.flops import transformer_flops
    from tpu_sandbox.utils.profiling import measure_per_step

    on_tpu = jax.devices()[0].platform == "tpu"
    if quick:
        cfg = TransformerConfig(vocab_size=256, d_model=64, n_heads=2,
                                n_layers=2, d_ff=128, max_len=256,
                                dtype=jnp.float32)
        batch, seq, steps = 2, 128, 3
    else:
        cfg = TransformerConfig(vocab_size=32768, d_model=1024, n_heads=8,
                                n_layers=12, d_ff=4096, max_len=2048,
                                dtype=jnp.bfloat16, remat=True,
                                remat_policy="dots",
                                # the fused Pallas CE upcasts in VMEM —
                                # skip the 4.3 GB fp32 logits round-trip
                                fp32_logits=False)
        # batch 16: fits under dots-remat (chipless AOT: ~12.7 GB peak) and
        # amortizes the fixed AdamW pass — 4.10 vs 4.78 MB/token at b8
        batch, seq, steps = 16, 2048, 5
    attn = flash_attention_fn() if on_tpu else None
    model = TransformerLM(cfg, attention_fn=attn)
    tx = optax.adamw(3e-4)
    state = TrainState.create(
        model, jax.random.key(0), jnp.zeros((1, seq), jnp.int32), tx
    )

    def loss_fn(params, tokens, targets):
        logits = model.apply({"params": params}, tokens)
        return cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
        )

    # donate the state like the ConvNet benches (and real training) do:
    # in-place AdamW updates instead of fresh param/mu/nu output buffers
    # (~2+ GB at this config), and it matches what tools/aot_lm_cycles.py
    # attributes chiplessly
    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, tokens, targets)
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        return state.replace(
            step=state.step + 1,
            params=optax.apply_updates(state.params, updates),
            opt_state=new_opt,
        ), loss

    rng = np.random.default_rng(0)
    staged = []
    for _ in range(4):
        toks = jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(batch, seq)), jnp.int32
        )
        staged.append((toks, (toks + 1) % cfg.vocab_size))

    def run(k):
        nonlocal state
        loss = None
        for i in range(k):
            t, tg = staged[i % len(staged)]
            state, loss = step(state, t, tg)
        return loss

    timing = measure_per_step(run, steps)
    spt = timing["sec_per_step"]
    tokens_per_step = batch * seq
    flops = transformer_flops(
        cfg.n_layers, cfg.d_model, cfg.d_ff, seq, cfg.vocab_size
    )["train"] * tokens_per_step
    util = _utilization(flops, spt if spt > 0 else 1.0, jax.devices()[0])
    result = {
        "metric": "lm_train_tokens_per_sec",
        "value": round(tokens_per_step / spt, 1) if spt > 0 else 0.0,
        "unit": "tokens/sec",
        "vs_baseline": 0.0,  # reference has no LM at all (SURVEY §2.2)
        "config": {"d_model": cfg.d_model, "n_layers": cfg.n_layers,
                   "d_ff": cfg.d_ff, "seq": seq, "batch": batch,
                   "vocab": cfg.vocab_size,
                   "dtype": str(cfg.dtype.__name__ if hasattr(cfg.dtype, "__name__")
                                else cfg.dtype),
                   "flash_attention": bool(attn), "remat": cfg.remat,
                   "remat_policy": cfg.remat_policy},
        "sec_per_step": spt,
        "timing_method": timing["timing_method"],
        "flops_per_step_model": flops,
        "achieved_tflops": round(util["achieved_tflops"], 2),
        "peak_tflops_bf16": util["peak_tflops_bf16"],
        "mfu": round(util["mfu"], 4) if util["mfu"] is not None else None,
        "device_kind": str(jax.devices()[0].device_kind),
    }
    if spt <= 0:
        result.update(value=0.0, achieved_tflops=0.0, mfu=None)
        result["degraded"] = (
            f"non-positive differential step time ({spt:.6f}s)"
        )
    elif not util["plausible"]:
        result.update(value=0.0)
        result["degraded"] = (
            f"implausible mfu {util['mfu']:.2f}; number untrusted"
        )
    return result


def bench_pallas(force_cpu: bool) -> dict:
    """Compile-and-run the Pallas kernels on the real device and compare
    against the jnp reference — the driver-visible Mosaic-lowering check
    VERDICT r01 item 4 asked for. Exits nonzero (exception) if lowering or
    numerics break."""
    from tpu_sandbox.utils.cli import ensure_devices

    if force_cpu:
        ensure_devices(1, force_cpu=True)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_sandbox.ops.attention import causal_attention
    from tpu_sandbox.ops.losses import cross_entropy_loss
    from tpu_sandbox.ops.pallas_attention import flash_attention
    from tpu_sandbox.ops.pallas_ce import pallas_cross_entropy
    from tpu_sandbox.utils.profiling import host_sync, measure_per_step

    on_tpu = jax.devices()[0].platform == "tpu"
    interpret = not on_tpu  # real Mosaic lowering on TPU; interpreter on CPU
    rng = np.random.default_rng(0)
    checks = {}

    # Non-multiple-of-block seq len AND bf16 — the hard cases VERDICT names.
    # Layout is [B, S, H, D] (the transformer's).
    for (b, s, h, d, dt) in [(2, 512, 4, 64, "float32"),
                             (2, 384, 4, 64, "bfloat16"),
                             (1, 1024, 8, 128, "bfloat16")]:
        q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)), dtype=dt)
                   for _ in range(3))
        out = flash_attention(q, k, v, interpret=interpret)
        # Reference at HIGHEST matmul precision: on TPU the default f32
        # einsum rounds operands to bf16 on the MXU, which would make the
        # reference as noisy as the thing under test.
        with jax.default_matmul_precision("highest"):
            ref = causal_attention(q, k, v)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        # f32 bound on TPU is the MXU operand-rounding floor (the kernel
        # feeds the systolic array bf16-rounded inputs with f32
        # accumulation; one rounding step is 2^-8 relative, amplified ~2x
        # through softmax) — measured 6.5e-3 on v5e. It is NOT an
        # accumulation-bug budget: interpret mode has no MXU rounding, so
        # the CPU path keeps the tight bound and still catches real
        # accumulation regressions off-chip.
        if dt == "bfloat16":
            tol = 2e-2
        else:
            tol = 1.5e-2 if on_tpu else 2e-3
        assert err < tol, (b, s, h, d, dt, err)
        checks[f"flash_s{s}_{dt}"] = err

    logits = jnp.asarray(rng.normal(size=(64, 32000)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 32000, size=(64,)), jnp.int32)
    ce = pallas_cross_entropy(logits, labels, interpret=interpret)
    # optax DIRECTLY: losses.cross_entropy_loss now dispatches LM-scale
    # vocabs to the very kernel under test, which would compare the
    # kernel against itself
    import optax as _optax
    ce_ref = _optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()
    ce_err = float(jnp.abs(ce - ce_ref))
    assert ce_err < 1e-3, ce_err
    checks["ce_64x32000"] = ce_err

    # the s2d ConvNet's fused BN/ReLU/pool tail vs the unfused jnp chain
    from tpu_sandbox.ops.pallas_bn_tail import (
        fused_bn_relu_pool,
        unfused_reference,
    )

    co, blk = (16, 4) if on_tpu else (4, 2)
    hw = 40 if on_tpu else 8
    c = blk * blk * co
    yb = jnp.asarray(rng.normal(size=(2, hw, hw, c)), jnp.bfloat16)
    gam = jnp.asarray(1 + 0.1 * rng.normal(size=co), jnp.float32)
    bet = jnp.asarray(rng.normal(size=co), jnp.float32)
    fout, fmu, fvar = fused_bn_relu_pool(yb, gam, bet, co, blk, 1e-5,
                                         interpret)
    tail_ref, mu_r, var_r = unfused_reference(yb, gam, bet, co, blk)
    assert float(jnp.max(jnp.abs(fmu - mu_r))) < 1e-4
    assert float(jnp.max(jnp.abs(fvar - var_r))) < 1e-4
    tail_err = float(jnp.max(jnp.abs(fout.astype(jnp.float32)
                                     - tail_ref.astype(jnp.float32))))
    assert tail_err < 2e-2, tail_err
    checks[f"bn_tail_blk{blk}_co{co}"] = tail_err

    # the s2d conv kernels (fwd + stats variant + full VJP) vs lax.conv —
    # fused_conv is the pick_convnet TPU default, so an on-chip run of the
    # headline path depends on these compiling AND agreeing numerically
    from tpu_sandbox.ops.pallas_conv import (
        conv3x3,
        conv3x3_reference,
        conv3x3_stats,
    )

    ch, cco, chw = (16, 256, 40) if on_tpu else (4, 8, 10)
    xc = jnp.asarray(rng.normal(size=(2, chw, chw, ch)), jnp.bfloat16)
    kc = jnp.asarray(0.1 * rng.normal(size=(3, 3, ch, cco)), jnp.bfloat16)
    bc = jnp.asarray(rng.normal(size=(cco,)), jnp.bfloat16)
    yc, sc, ssc = conv3x3_stats(xc, kc, bc, interpret)
    yc_ref = conv3x3_reference(xc, kc, bc)
    conv_err = float(jnp.max(jnp.abs(yc.astype(jnp.float32)
                                     - yc_ref.astype(jnp.float32))))
    assert conv_err < 0.15, conv_err  # bf16 conv, K up to 9*16 taps
    yf = yc.astype(jnp.float32).reshape(-1, cco)
    assert float(jnp.max(jnp.abs(sc[0] - yf.sum(0)))
                 / max(1.0, float(jnp.max(jnp.abs(sc))))) < 1e-3
    checks[f"conv3x3_{ch}to{cco}"] = conv_err
    gc = jax.grad(
        lambda x, k, b: jnp.sum(conv3x3(x, k, b, interpret)
                                .astype(jnp.float32) ** 2),
        argnums=(0, 1, 2),
    )(xc, kc, bc)
    gr = jax.grad(
        lambda x, k, b: jnp.sum(conv3x3_reference(
            x.astype(jnp.float32), k.astype(jnp.float32),
            b.astype(jnp.float32)) ** 2),
        argnums=(0, 1, 2),
    )(xc, kc, bc)
    for a, r, nm in zip(gc, gr, ("dx", "dw", "db")):
        scale = max(1.0, float(jnp.max(jnp.abs(r))))
        rel = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - r.astype(jnp.float32)))) / scale
        assert rel < 0.05, (nm, rel)
        checks[f"conv3x3_grad_{nm}"] = rel

    # the TRANSPOSED plan's kernels (pallas_conv_t + pallas_bn_tail_t) —
    # what plan=auto actually runs on TPU since round 3, so the on-chip
    # headline number depends on these agreeing numerically too
    from tpu_sandbox.ops.pallas_bn_tail_t import (
        fused_bn_relu_pool_t,
        unfused_reference_t,
    )
    from tpu_sandbox.ops.pallas_conv_t import conv3x3_t, conv3x3_t_stats

    xt = jnp.transpose(xc, (0, 1, 3, 2))
    yt, st, sst = conv3x3_t_stats(xt, kc, bc, interpret)
    convt_err = float(jnp.max(jnp.abs(
        yt.astype(jnp.float32)
        - jnp.transpose(yc_ref, (0, 1, 3, 2)).astype(jnp.float32))))
    assert convt_err < 0.15, convt_err
    assert float(jnp.max(jnp.abs(st[:, 0] - yf.sum(0)))
                 / max(1.0, float(jnp.max(jnp.abs(st))))) < 1e-3
    checks[f"conv3x3_t_{ch}to{cco}"] = convt_err
    gt = jax.grad(
        lambda x, k, b: jnp.sum(conv3x3_t(x, k, b, interpret)
                                .astype(jnp.float32) ** 2),
        argnums=(0, 1, 2),
    )(xt, kc, bc)
    for a, r, nm in zip(gt, gr, ("dx", "dw", "db")):
        if nm == "dx":
            a = jnp.transpose(a, (0, 1, 3, 2))
        scale = max(1.0, float(jnp.max(jnp.abs(r))))
        rel = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - r.astype(jnp.float32)))) / scale
        assert rel < 0.05, (nm, rel)
        checks[f"conv3x3_t_grad_{nm}"] = rel

    # the r04 sparse-tap conv1 (what the transposed plan actually runs)
    from tpu_sandbox.ops.pallas_conv5_t import (
        conv1_s2d_t,
        conv1_s2d_t_reference,
    )

    s_hw = 40 if on_tpu else 12
    xs = jnp.asarray(rng.normal(size=(2, s_hw, 16, s_hw)), jnp.bfloat16)
    k5s = jnp.asarray(0.1 * rng.normal(size=(5, 5, 1, 16)), jnp.bfloat16)
    b5s = jnp.asarray(rng.normal(size=(16,)), jnp.bfloat16)
    ysp = conv1_s2d_t(xs, k5s, b5s, interpret)
    ysp_ref = conv1_s2d_t_reference(xs, k5s, b5s)
    sp_err = float(jnp.max(jnp.abs(ysp.astype(jnp.float32)
                                   - ysp_ref.astype(jnp.float32))))
    assert sp_err < 0.15, sp_err
    checks["conv1_sparse_tap"] = sp_err
    gsp = jax.grad(
        lambda k, b: jnp.sum(conv1_s2d_t(xs, k, b, interpret)
                             .astype(jnp.float32) ** 2),
        argnums=(0, 1),
    )(k5s, b5s)
    gsp_ref = jax.grad(
        lambda k, b: jnp.sum(conv1_s2d_t_reference(
            xs.astype(jnp.float32), k.astype(jnp.float32),
            b.astype(jnp.float32)) ** 2),
        argnums=(0, 1),
    )(k5s, b5s)
    for a, r, nm in zip(gsp, gsp_ref, ("dk5", "db")):
        scale = max(1.0, float(jnp.max(jnp.abs(r))))
        rel = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - r.astype(jnp.float32)))) / scale
        assert rel < 0.05, (nm, rel)
        checks[f"conv1_sparse_grad_{nm}"] = rel

    ytail = jnp.transpose(yb, (0, 1, 3, 2))
    tout, tmu, tvar = fused_bn_relu_pool_t(ytail, gam, bet, co, blk, 1e-5,
                                           interpret)
    tref, tmu_r, tvar_r = unfused_reference_t(ytail, gam, bet, co, blk)
    assert float(jnp.max(jnp.abs(tmu - tmu_r))) < 1e-4
    assert float(jnp.max(jnp.abs(tvar - tvar_r))) < 1e-4
    tailt_err = float(jnp.max(jnp.abs(tout.astype(jnp.float32)
                                      - tref.astype(jnp.float32))))
    assert tailt_err < 2e-2, tailt_err
    checks[f"bn_tail_t_blk{blk}_co{co}"] = tailt_err

    # Micro-throughput of the flash kernel at a real shape (honest timing).
    # Interpret mode runs the kernel body per grid cell in Python — the
    # s=4096 shape would take hours on CPU, so the fallback shrinks it
    # (shape is in the JSON; a tiny interpret number is obviously not a
    # TPU claim).
    if interpret:
        b, s, h, d, iters = 1, 256, 2, 64, 1
    else:
        b, s, h, d, iters = 4, 4096, 8, 128, 8
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.bfloat16)
               for _ in range(3))
    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=interpret))
    host_sync(fa(q, k, v))
    from tpu_sandbox.utils.profiling import measure_per_step_repeated
    timing = measure_per_step_repeated(
        lambda n: _chain_attn(fa, q, k, v, n), iters,
        repeats=1 if interpret else 3)
    # causal attention: ~2 * 2 * b*h*s^2*d / 2 FLOPs (QK^T + PV, causal half)
    flops = 2 * 2 * b * h * s * s * d / 2
    tflops = flops / timing["sec_per_step"] / 1e12

    return {
        "metric": "pallas_kernel_check",
        "value": round(tflops, 2),
        "unit": f"TFLOP/s (flash fwd, b{b} s{s} h{h} d{d} bf16)",
        "vs_baseline": 0.0,
        "mode": "mosaic" if on_tpu else "interpret",
        "device_kind": str(jax.devices()[0].device_kind),
        "max_abs_errors": {k: round(v, 6) for k, v in checks.items()},
        "sec_per_call": timing["sec_per_step"],
        "timing_method": timing["timing_method"],
        "sec_per_call_samples": timing.get("sec_per_step_samples"),
        "spread_frac": timing.get("spread_frac"),
    }


def _chain_attn(fa, q, k, v, n):
    """n data-dependent attention calls (output feeds next q)."""
    out = q
    for _ in range(n):
        out = fa(out, k, v)
    return out


def _emit(result: dict, args) -> None:
    """Print the one-line round record and, with ``--archive DIR`` (or
    ``BENCH_ARCHIVE`` in the env), land the run's analysis artifacts —
    trace dirs, critpath profiles, the workload trace — next to the
    BENCH_rNN.json the driver commits, so every round's number stays
    re-derivable from its raw trace. Benches opt in by returning an
    ``_artifacts`` mapping of name -> file-or-dir; it never appears in
    the printed record."""
    import shutil

    artifacts = result.pop("_artifacts", None)
    line = json.dumps(result)
    dest = getattr(args, "archive", None) or os.environ.get("BENCH_ARCHIVE")
    if dest:
        os.makedirs(dest, exist_ok=True)
        with open(os.path.join(dest, "result.json"), "w",
                  encoding="utf-8") as fh:
            fh.write(line + "\n")
        for name, path in (artifacts or {}).items():
            target = os.path.join(dest, name)
            if os.path.isdir(path):
                shutil.copytree(path, target, dirs_exist_ok=True)
            elif os.path.isfile(path):
                shutil.copy2(path, target)
    print(line)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--metric",
                   choices=["grad_compress", "overlap", "donation",
                            "cluster", "serve", "serve_slo", "gateway",
                            "chaos",
                            "obs", "health", "deploy", "mpmd", "critpath",
                            "images_per_sec",
                            "allreduce_bw", "pallas",
                            "capacity", "seq_scaling", "lm", "sweep",
                            "convergence"],
                   default="images_per_sec",
                   help="which benchmark to run (driver default: images/sec)")
    p.add_argument("--image-size", type=int, default=3000)
    p.add_argument("--batch-per-device", type=int, default=5)
    p.add_argument("--steps", type=int, default=10,
                   help="n for the differential timer (runs ~4n steps total)")
    p.add_argument("--warmup", type=int, default=1)
    p.add_argument("--dtype", choices=["bf16", "fp32"], default="bf16")
    p.add_argument("--plan", choices=["auto", "s2dt", "s2d", "plain"],
                   default="auto",
                   help="ConvNet execution plan: s2dt = transposed "
                        "space-to-depth (models/convnet_s2d_t.py), s2d = "
                        "NHWC space-to-depth (models/convnet_s2d.py) — "
                        "same function either way, tested; auto picks "
                        "s2dt on TPU when the image size allows")
    p.add_argument("--baseline", type=float, default=75.0)
    p.add_argument("--quick", action="store_true",
                   help="tiny CPU config to validate the harness itself")
    p.add_argument("--archive", default=None, metavar="DIR",
                   help="also land the run's trace/profile artifacts and "
                        "result.json under DIR (next to the committed "
                        "BENCH_rNN.json); BENCH_ARCHIVE in the env does "
                        "the same")
    args = p.parse_args()
    from tpu_sandbox.runtime.bootstrap import configure_compile_cache

    configure_compile_cache()
    if args.metric == "grad_compress":
        # chipless by design (CPU SPMD compile)
        _emit(bench_grad_compress_traffic(), args)
        return
    if args.metric == "overlap":
        # chipless AOT schedule + host-thread stall timing
        _emit(bench_overlap(), args)
        return
    if args.metric == "donation":
        # chipless AOT memory analysis (subprocess-isolated)
        _emit(bench_donation(), args)
        return
    if args.metric == "cluster":
        # chipless scheduler control-plane timing (stub tenants)
        _emit(bench_cluster(), args)
        return
    if args.metric == "serve":
        # chipless serving SLOs (tiny model, CPU backend).
        # --quick shrinks the trace and skips the AOT donation receipt.
        _emit(bench_serve(quick=args.quick), args)
        return
    if args.metric == "serve_slo":
        # chipless overload/shedding guardrail receipt
        _emit(bench_serve_slo(quick=args.quick), args)
        return
    if args.metric == "gateway":
        # chipless routing/admission receipt over real sockets
        _emit(bench_gateway(quick=args.quick), args)
        return
    if args.metric == "chaos":
        # chipless HA/chaos receipt: real gateway processes over TLS,
        # seeded fault campaigns, claim audit from the store
        _emit(bench_chaos(quick=args.quick), args)
        return
    if args.metric == "obs":
        # chipless flight-recorder overhead receipt
        _emit(bench_obs(quick=args.quick), args)
        return
    if args.metric == "health":
        # chipless health-plane overhead + detection-latency receipt
        _emit(bench_health(quick=args.quick), args)
        return
    if args.metric == "critpath":
        # chipless trace-analytics receipt: attribution coverage,
        # tracediff gating, online-vs-offline pipeline bubble
        _emit(bench_critpath(quick=args.quick), args)
        return
    if args.metric == "deploy":
        # chipless train->serve deployment receipt
        _emit(bench_deploy(quick=args.quick), args)
        return
    if args.metric == "mpmd":
        # chipless fast-fabric receipt: staged vs device transport, the
        # autotuned ZB-H1 bubble, fault claim audit, tracediff gate on
        # fabric profiles (fails the process, CI-style).
        # --quick shrinks and skips the AOT + archived-control gate.
        mpmd_steps = (20 if args.steps == p.get_default("steps")
                      else args.steps)
        result = bench_mpmd(steps=mpmd_steps, quick=args.quick)
        _emit(result, args)
        if not result.get("tracediff_gate_ok", True):
            sys.exit(1)
        return
    # Everything below measures the device. --quick is the explicit CPU
    # check of the harness (tiny shapes, virtual CPU devices); otherwise the
    # first device must be a TPU, or the run ends non-zero with no line.
    if not args.quick:
        _require_tpu(args.metric)
    if args.metric == "images_per_sec":
        if args.quick:
            result = bench(128, 2, 3, 1, "fp32", True, args.baseline,
                           plan=args.plan)
        else:
            result = bench(args.image_size, args.batch_per_device,
                           args.steps, args.warmup, args.dtype, False,
                           args.baseline, plan=args.plan)
    elif args.metric == "allreduce_bw":
        result = bench_allreduce_bw(force_cpu=args.quick)
    elif args.metric == "pallas":
        result = bench_pallas(force_cpu=args.quick)
    elif args.metric == "capacity":
        result = bench_capacity(
            256 if args.quick else args.image_size, args.dtype,
            force_cpu=args.quick, max_batch=8 if args.quick else 512,
            plan=args.plan)
    elif args.metric == "sweep":
        result = bench_sweep(args.image_size, args.steps, args.warmup,
                             args.baseline, force_cpu=args.quick,
                             quick=args.quick, plan=args.plan)
    elif args.metric == "lm":
        result = bench_lm(force_cpu=args.quick, quick=args.quick)
    elif args.metric == "convergence":
        # --steps' global default (10) is sized for the differential
        # timer; a convergence CURVE needs more. Only the untouched
        # default is upgraded — an explicit --steps N is honored.
        conv_steps = (40 if args.steps == p.get_default("steps")
                      else args.steps)
        result = bench_convergence(
            128 if args.quick else args.image_size, conv_steps,
            force_cpu=args.quick, plan=args.plan)
    else:
        result = bench_seq_scaling(force_cpu=args.quick, quick=args.quick)
    import jax

    result["platform"] = jax.devices()[0].platform
    if args.quick:
        # a CPU number never goes out under a device metric's name
        result["metric"] = f"cpu_harness_check({result['metric']})"
        result["degraded"] = (
            (result["degraded"] + "; " if "degraded" in result else "")
            + "--quick: CPU harness check at shrunken shapes, not a device "
              "measurement")
    _emit(result, args)


def _require_tpu(metric: str) -> None:
    """Device metrics never fall back: no TPU, no line, exit code 1."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"bench.py --metric {metric} measures the TPU, and "
                 f"jax.devices()[0].platform is {platform!r}. Nothing was "
                 "measured. (--quick runs the CPU check of the harness; the "
                 "chipless --metric modes need no device.)")


if __name__ == "__main__":
    main()
