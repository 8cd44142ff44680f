"""Chip smoke: the quickest proof that the main path still runs on the TPU.

``python chip_smoke.py`` (no arguments, from the repo root) drives what
``mnist_onegpu.py`` runs — the 3000x3000 MNIST ConvNet, batch 5, bf16, plan
``s2dt`` with every Pallas kernel compiled, through ``pick_convnet`` ->
``TrainState.create`` -> ``make_train_step`` -> ``Trainer.fit`` — for a few
steps on one chip, checks the kernels' arithmetic against the plain
``ConvNet`` on the chip, and, on a host with four chips, does what
``mnist_distributed.py -g 4`` runs and checks the data-parallel step against
the one-chip step shard by shard. Weights are random from seed 0; nothing is
cut from the model.

It is a gate, not a benchmark: the compile seconds and the two step clocks
it prints are evidence for ROADMAP A2, not results. Any phase that raises
ends the run non-zero. The last line of stdout is the verdict,
``{"ok": true, "device": {...}}``; without a TPU there is no verdict and the
exit code is 1.
"""

import json
import math
import os
import sys
import time

STEPS = 4  # Trainer.fit steps per phase: one cold, three steady
BATCH = 5  # the reference's per-device batch (mnist_onegpu.py:45)
IMAGE = 3000

#: Trace-time switches that swap a Pallas kernel for a reference or for the
#: interpreter. With any of them set the run would prove a different program.
KILL_SWITCHES = (
    "TPU_SANDBOX_NO_SPARSE_CONV1",
    "TPU_SANDBOX_NO_PALLAS_FC",
    "TPU_SANDBOX_NO_FUSED_CONV1_BWD",
    "TPU_SANDBOX_WGRAD_RESTAGE",
    "TPU_SANDBOX_FORCE_COMPILED_KERNELS",
)

#: Pallas scopes the compiled step must hold as ``tpu_custom_call``s, by the
#: differentiation direction of their ``op_name`` (the fc head's forward
#: kernel flattens the activation, its backward one is the input-grad; the
#: contractions between them are XLA dots).
KERNEL_SCOPES = {
    "/bn1.fused_conv1/": ("forward", "backward"),
    "/conv2/": ("forward", "backward"),
    "/bn2.fused/": ("forward", "backward"),
    "/fc/": ("forward", "backward"),
}

#: First-step loss band around ln(10): ten classes, lecun-normal head over
#: ~18M post-BN features gives logits of unit scale, so a 5-image mean CE
#: sits within a factor of two of ln 10. Later
#: losses need only be finite: the reference recipe (SGD 1e-4 on this head)
#: diverges at this size in torch too (BASELINE.md "Loss dynamics at 3000^2").
FIRST_LOSS_BAND = (0.5 * math.log(10), 2.0 * math.log(10))

#: |DP rank loss - one-chip loss on that rank's shard|. Same kernels, same
#: initial state, per-replica BN: the identity is exact in exact arithmetic.
#: The two programs may tile the 18M-feature fc reduction differently, and
#: logits are rounded to bf16 (1 ulp = 2**-6 for |logit| in [2, 4)).
DP_LOSS_ATOL = 2.0 ** -6


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check_kernels(hlo: str, label: str) -> int:
    """Every expected Pallas scope must appear as a compiled kernel (a
    ``tpu_custom_call``; an interpreted kernel leaves no custom call) in
    each expected direction — a kernel that gave way to a reference is a
    failure here, not a slower pass. Returns the kernel-call count."""
    from tpu_sandbox.utils.flops import pallas_call_paths

    paths = pallas_call_paths(hlo)
    for scope, directions in KERNEL_SCOPES.items():
        for direction in directions:
            backward = direction == "backward"
            if not any(scope in p and ("transpose(" in p) == backward
                       for p in paths):
                fail(f"{label}: no {direction} Pallas kernel under "
                     f"{scope!r}; kernels present: {sorted(set(paths))}")
    return len(paths)


def timed(step, records: list):
    """Wrap a train step so each call is clocked twice from one start: to
    the return of ``jax.block_until_ready`` on everything it produced, and
    then to a scalar fetched from the loss (``host_sync``)."""
    import jax

    from tpu_sandbox.utils.profiling import host_sync

    def run(state, images, labels):
        t0 = time.perf_counter()
        state, loss = step(state, images, labels)
        jax.block_until_ready((state, loss))
        t_block = time.perf_counter() - t0
        host_sync(loss)
        t_fetch = time.perf_counter() - t0
        records.append({"block_until_ready_s": round(t_block, 4),
                        "scalar_fetch_s": round(t_fetch, 4)})
        return state, loss

    return run


def check_losses(losses: list, label: str) -> None:
    if len(losses) != STEPS:
        fail(f"{label}: logged {len(losses)} losses for {STEPS} steps")
    if not all(math.isfinite(x) for x in losses):
        fail(f"{label}: non-finite loss in {losses}")
    lo, hi = FIRST_LOSS_BAND
    if not lo <= losses[0] <= hi:
        fail(f"{label}: first loss {losses[0]:.4f} outside "
             f"[{lo:.3f}, {hi:.3f}] around ln 10")


def peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def aot_compile(lower, label: str):
    """Trace+lower, then compile, clocked apart: a warm compile cache saves
    the second and never the first."""
    t0 = time.perf_counter()
    lowered = lower()
    t1 = time.perf_counter()
    compiled = lowered.compile()
    seconds = {"trace_lower_s": round(t1 - t0, 2),
               "compile_s": round(time.perf_counter() - t1, 2)}
    say(f"{label}: traced and lowered in {seconds['trace_lower_s']} s, "
        f"compiled in {seconds['compile_s']} s")
    return compiled, seconds


def smoke_one_chip(report: dict):
    """What ``mnist_onegpu.py`` runs. Returns the jitted step for the
    four-chip phase to reuse as its one-chip reference."""
    import jax

    import mnist_onegpu
    from tpu_sandbox.train import Trainer
    from tpu_sandbox.utils.parity import numerics_preflight

    args = mnist_onegpu.build_parser().parse_args([
        "--epochs", "1", "--limit-steps", str(STEPS), "--log-every", "1",
        "--synthetic-n", str(STEPS * BATCH)])
    if (args.image_size, args.batch_size, args.dtype, args.plan) != (
            IMAGE, BATCH, "bf16", "auto"):
        fail(f"mnist_onegpu's defaults moved: {args}")
    t0 = time.perf_counter()
    model, state, step, loader = mnist_onegpu.build(args)
    jax.block_until_ready(state)
    init_s = round(time.perf_counter() - t0, 2)
    say(f"one chip: TrainState.create (batch-1 init by tracing) {init_s} s")
    if type(model).__name__ != "ConvNetS2DT" or not model.fused_tail:
        fail(f"pick_convnet gave {model!r}, not the fused s2dt plan")

    images, labels = next(iter(loader))
    compiled, compile_s = aot_compile(
        lambda: step.lower(state, images, labels), "one chip: train step")
    n_calls = check_kernels(compiled.as_text(), "one-chip train step")

    records: list = []
    trainer = Trainer(timed(step, records), log_every=1)
    trainer.fit(state, loader, args.epochs)
    check_losses(trainer.losses, "one chip")
    say(f"one chip: losses {[round(x, 4) for x in trainer.losses]}")
    say(f"one chip: per-step clocks {records}")

    preflight = numerics_preflight(model, IMAGE)
    say(f"numerics preflight vs plain ConvNet: {preflight}")
    if not preflight["ok"]:
        fail(f"numerics preflight failed: {preflight}")

    report["one_chip"] = {
        "init_s": init_s, "train_step": compile_s,
        "tpu_custom_calls": n_calls, "losses": trainer.losses,
        # step 1 reuses the program compiled just above; its scalar fetch
        # compiles the fetch itself, once
        "steps": records, "numerics_preflight": preflight,
        "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
    }
    return step


def smoke_four_chips(report: dict, one_chip_step) -> None:
    """What ``mnist_distributed.py -g 4`` runs, plus the identity that makes
    it a correctness test: with per-replica BN, rank i's first-step loss IS
    the one-chip step's loss on rank i's shard from the same initial state."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mnist_distributed
    from tpu_sandbox.train import Trainer

    world = 4
    args = mnist_distributed.build_parser().parse_args([
        "-g", str(world), "--epochs", "1", "--limit-steps", str(STEPS),
        "--log-every", "1", "--synthetic-n", str(STEPS * BATCH * world)])
    dp, state, loader = mnist_distributed.build(args, world)
    images, labels = next(iter(loader))
    if len(images) != world * BATCH:
        fail(f"global batch is {len(images)}, wanted {world * BATCH}")

    # the reference first: the one-chip step donates its state, so each
    # shard gets its own copy of the initial state
    want = []
    for r in range(world):
        rows = slice(r * BATCH, (r + 1) * BATCH)
        _, loss = one_chip_step(jax.tree.map(jnp.copy, state),
                                images[rows], labels[rows])
        want.append(float(loss))

    param_bytes = sum(x.nbytes for x in jax.tree.leaves(state.params))
    dstate = dp.shard_state(state)
    del state
    batch = dp.shard_batch(images, labels)
    devices = set(dp.mesh.devices.flat)
    for leaf in jax.tree.leaves((dstate, batch)):
        if leaf.sharding.device_set != devices:
            fail(f"a {leaf.shape} leaf lives on {leaf.sharding.device_set}, "
                 f"not on all of {devices}")

    compiled, compile_s = aot_compile(
        lambda: dp.lower_step(dstate, *batch),
        "four chips: DataParallel step")
    hlo = compiled.as_text()
    n_calls = check_kernels(hlo, "DataParallel train step")
    if "all-reduce" not in hlo:
        fail("the compiled DataParallel step holds no all-reduce")

    records: list = []
    rank_losses: list = []

    def step(s, images_np, labels_np):
        s, loss = dp.train_step(s, *dp.shard_batch(images_np, labels_np))
        rank_losses.append(np.asarray(loss, np.float64))
        return s, loss

    trainer = Trainer(timed(step, records), log_every=1, log_rank=0)
    trainer.fit(dstate, loader, args.epochs, set_epoch=False)
    check_losses(trainer.losses, "four chips")
    say(f"four chips: rank-0 losses {[round(x, 4) for x in trainer.losses]}")
    say(f"four chips: per-step clocks {records}")

    got = rank_losses[0]
    say(f"four chips: first-step rank losses {got.tolist()} vs one-chip "
        f"on the same shards {want}")
    worst = float(np.max(np.abs(got - np.asarray(want))))
    if worst > DP_LOSS_ATOL or abs(got.mean() - np.mean(want)) > DP_LOSS_ATOL:
        fail(f"DP identity broken: rank losses {got.tolist()} vs one-chip "
             f"{want} (max |diff| {worst:.5f} > {DP_LOSS_ATOL})")

    peaks = {str(d): peak_bytes(d) for d in dp.mesh.devices.flat}
    for dev, peak in peaks.items():
        if peak <= param_bytes:
            fail(f"{dev} peaked at {peak} bytes, not above the {param_bytes} "
                 "bytes of parameters: the replica never lived there")

    report["four_chips"] = {
        "dp_step": compile_s, "tpu_custom_calls": n_calls,
        "losses_rank0": trainer.losses, "steps": records,
        "first_step_rank_losses": got.tolist(),
        "one_chip_losses_on_rank_shards": want,
        "max_abs_loss_diff": worst, "param_bytes": param_bytes,
        "peak_bytes_in_use": peaks,
    }


def main() -> None:
    set_switches = [k for k in KILL_SWITCHES if k in os.environ]
    if set_switches:
        fail(f"kernel kill-switch(es) in the environment: {set_switches}")

    from tpu_sandbox.runtime.bootstrap import configure_compile_cache

    cache_dir = configure_compile_cache()

    import importlib.metadata

    import jax
    import jaxlib

    from tpu_sandbox.models import resolve_plan
    from tpu_sandbox.ops.pallas_common import default_interpret
    from tpu_sandbox.utils.flops import device_peak_tflops

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"device {device}; jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu "
        f"{importlib.metadata.version('libtpu')}; compile cache {cache_dir}")
    if dev.platform != "tpu":
        fail(f"needs a TPU; jax.devices()[0].platform is {dev.platform!r}")
    device_peak_tflops(dev.device_kind)  # an unknown kind raises here
    if resolve_plan(IMAGE, "auto") != "s2dt":
        fail(f"resolve_plan({IMAGE}, 'auto') is "
             f"{resolve_plan(IMAGE, 'auto')!r}, not 's2dt'")
    if default_interpret(None) is not False:
        fail("Pallas kernels would run interpreted on this backend")

    report: dict = {"device": device, "compile_cache_dir": cache_dir}
    one_chip_step = smoke_one_chip(report)
    if jax.local_device_count() >= 4:
        smoke_four_chips(report, one_chip_step)
    else:
        say(f"{jax.local_device_count()} local device(s): four-chip phase "
            "not applicable")
    print(json.dumps(report), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
