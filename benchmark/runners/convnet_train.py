"""Runner kind ``convnet_train``: the source paper's big-image ConvNet
trainer, as ``mnist_onegpu.py`` builds it on one chip and as
``mnist_distributed.py`` builds it over the cell's chips. The benchmark
drives ``build()`` -> the compiled train step -> ``Trainer.fit`` over the
program's loader; it brings the data (from ``--seed``), the clocks and the
checks."""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Any

import numpy as np

from benchmark.lib import manifest, peaks, traffic
from benchmark.lib.observe import Observations
from benchmark.lib.train_window import (BoundedLoader, Window,
                                        compile_clocked, train_step_ms)

#: |DP rank loss - one-chip loss on that rank's shard|: same kernels, same
#: initial state, per-replica BN, so the identity is exact in exact
#: arithmetic; logits are rounded to bf16 (1 ulp = 2^-6 for |logit| in
#: [2, 4)) and the two programs may tile the 18M-feature reduction
#: differently (copied from ``chip_smoke.DP_LOSS_ATOL``).
DP_LOSS_ATOL = 2.0 ** -6
EPOCHS = 64  # more than any window holds; the bounded loader ends the run


@dataclass
class Session:
    world: int
    state: Any
    call: Any            # (state, images, labels) -> (state, loss), compiled
    loader: Any
    place: Any           # numpy batch -> what ``call`` takes
    window: Window | None = None
    dp_want: list | None = None  # one-chip losses on each rank's shard


def _cli(dep: dict, batch: int, world: int) -> list[str]:
    argv = ["--epochs", "1", "--batch-size", str(batch), "--image-size",
            str(dep["image_size"]), "--dtype", dep["dtype"], "--plan",
            dep["plan"], "--synthetic-n", str(batch * world),
            "--log-every", str(10 ** 9)]
    return argv + (["-g", str(world)] if world > 1 else [])


def _compile_train_step(obs: Observations, lower):
    """The step the window runs: compiled, its Pallas kernels noted for the
    trace, and every kernel scope the configuration names held to be there
    as a compiled kernel in each direction (a kernel that gave way to a
    reference is a failure, not a slower pass)."""
    compiled = compile_clocked(obs, lower)
    obs.note_program(compiled.as_text())
    kernels = obs.op_scopes
    obs.facts["pallas_calls"] = len(kernels)
    for scope, directions in obs.cell["config"].get("kernel_scopes", {}).items():
        for direction in directions:
            backward = direction == "backward"
            if not any(scope in p and ("transpose(" in p) == backward
                       for p in kernels.values()):
                obs.problem(f"no {direction} Pallas kernel under {scope!r} "
                            "in the compiled step")
    return compiled


def _check_against_reference(obs: Observations, model, image_size: int) -> None:
    """The plan's logits, loss and fc gradient against the plain float32
    reference on a [2, 16, width] slab: at width 3000 the rows have the
    production 750-lane geometry, and the slab is what the reference can
    hold (the whole image's float32 activations are 5.8 GB an image)."""
    import jax
    import jax.numpy as jnp

    from tpu_sandbox.models.convnet import ConvNet
    from tpu_sandbox.ops.losses import cross_entropy_loss

    reference = manifest.module("reference", obs.cell["reference"])
    dtype = jnp.dtype(model.dtype)
    rng = np.random.default_rng([obs.seed, 99])
    x = jnp.asarray(rng.standard_normal((2, 16, image_size, 1)), dtype)
    labels = jnp.asarray(rng.integers(0, 10, size=(2,)), jnp.int32)
    variables = ConvNet(dtype=dtype).init(jax.random.key(obs.seed), x)
    params, stats = variables["params"], variables["batch_stats"]

    def system(p):
        logits, _ = model.apply({"params": p, "batch_stats": stats}, x,
                                train=True, mutable=["batch_stats"])
        return cross_entropy_loss(logits, labels), logits

    (loss, logits), grads = jax.jit(
        jax.value_and_grad(system, has_aux=True))(params)
    dev, bad = reference.compare(
        {"loss": loss, "logits": logits, "fc_grad": grads["fc"]["kernel"]},
        params, jnp.asarray(x, jnp.float32), labels)
    obs.notes["reference_deviation"] = dev
    for text in bad:
        obs.problem(text)


def _setup_one_chip(obs, dep, batch, images, labels) -> Session:
    import jax

    import mnist_onegpu
    from tpu_sandbox.data.mnist import normalize

    args = mnist_onegpu.build_parser().parse_args(_cli(dep, batch, 1))
    t0 = time.perf_counter()
    model, state, step, loader0 = mnist_onegpu.build(args)
    jax.block_until_ready(state)
    obs.facts["init_s"] = time.perf_counter() - t0
    _check_plan(obs, model, dep)
    loader = type(loader0)(normalize(images), labels.astype("int32"), batch,
                           shuffle=True, seed=obs.seed, drop_last=True)
    first = next(iter(loader))
    compiled = _compile_train_step(obs, lambda: step.lower(state, *first))
    t0 = time.perf_counter()
    _check_against_reference(obs, model, dep["image_size"])
    obs.facts["reference_check_s"] = time.perf_counter() - t0
    return Session(1, state, compiled, loader, lambda i, l: (i, l))


def _setup_data_parallel(obs, dep, batch, images, labels, world) -> Session:
    import jax
    import jax.numpy as jnp

    import mnist_distributed
    from tpu_sandbox.data.mnist import normalize
    from tpu_sandbox.train import make_train_step

    args = mnist_distributed.build_parser().parse_args(_cli(dep, batch, world))
    t0 = time.perf_counter()
    dp, state, loader0 = mnist_distributed.build(args, world)
    jax.block_until_ready(state)
    obs.facts["init_s"] = time.perf_counter() - t0
    _check_plan(obs, dp.model, dep)
    loader = type(loader0)(normalize(images), labels.astype("int32"), batch,
                           world, shuffle=True, seed=obs.seed)
    first = next(iter(loader))

    # rank i's first-step loss IS the one-chip step's loss on rank i's
    # shard from the same initial state (per-replica BN). The one-chip step
    # donates its state, so every shard gets a copy.
    one_chip = make_train_step(dp.model, dp.tx,
                               image_size=(dep["image_size"],) * 2)
    rows0 = (first[0][:batch], first[1][:batch])
    one = compile_clocked(obs, lambda: one_chip.lower(state, *rows0))
    want = []
    for r in range(world):
        rows = slice(r * batch, (r + 1) * batch)
        _, loss = one(jax.tree.map(jnp.copy, state), first[0][rows],
                      first[1][rows])
        want.append(float(loss))
    del one

    dstate = dp.shard_state(state)
    del state
    placed = dp.shard_batch(*first)
    compiled = _compile_train_step(
        obs, lambda: dp.lower_step(dstate, *placed))
    if "all-reduce" not in compiled.as_text():
        obs.problem("the compiled DataParallel step holds no all-reduce")
    t0 = time.perf_counter()
    _check_against_reference(obs, dp.model, dep["image_size"])
    obs.facts["reference_check_s"] = time.perf_counter() - t0
    return Session(world, dstate, compiled, loader, dp.shard_batch,
                   dp_want=want)


def _check_plan(obs, model, dep) -> None:
    if dep["plan"] == "s2dt" and (type(model).__name__ != "ConvNetS2DT"
                                  or not model.fused_tail):
        obs.problem(f"the program built {model!r}, not the fused s2dt plan")


def setup(obs: Observations) -> Session:
    import jax

    cell = obs.cell
    dep, spec, world = cell["deployment"], cell["traffic"], cell["chips"]
    batch = int(spec["batch_per_chip"])
    images, labels = traffic.image_batches(spec, obs.seed)
    if world == 1:
        session = _setup_one_chip(obs, dep, batch, images, labels)
    else:
        session = _setup_data_parallel(obs, dep, batch, images, labels, world)

    # warm-up on the first batches: the first step's loss is the one of the
    # initial weights, held to the band around ln(classes)
    t0 = time.perf_counter()
    state, first_losses = session.state, []
    for n, (im, lab) in enumerate(session.loader):
        if n == 3:
            break
        state, loss = session.call(state, *session.place(im, lab))
        first_losses.append(np.asarray(loss, np.float64).ravel())
    # what Trainer.fit itself dispatches before its first step (it reads
    # state.step through two tiny jitted programs): an empty fit warms them
    from tpu_sandbox.train import Trainer

    state = Trainer(session.call, verbose=False).fit(state, [], 0)
    session.state = state
    jax.block_until_ready(state)
    obs.facts["warmup_s"] = time.perf_counter() - t0
    lo, hi = 0.5 * math.log(10), 2.0 * math.log(10)
    if not lo <= first_losses[0][0] <= hi:
        obs.problem(f"first loss {first_losses[0][0]:.4f} outside "
                    f"[{lo:.3f}, {hi:.3f}] around ln 10")
    want = session.dp_want
    if want is not None:
        worst = float(np.max(np.abs(first_losses[0] - np.asarray(want))))
        obs.notes["dp_identity_max_abs"] = worst
        if worst > DP_LOSS_ATOL:
            obs.problem(f"DP identity broken: rank losses "
                        f"{first_losses[0].tolist()} vs one-chip {want}")
    obs.facts["flops_per_step"] = (
        peaks.convnet_train_flops(dep["image_size"]) * batch * world)
    return session


def measure(obs: Observations, session: Session, seconds: float) -> None:
    from tpu_sandbox.train import Trainer

    window = Window(obs, seconds, obs.cell["traffic"]["steps_per_chunk"])
    session.window = window

    def step(state, images, labels):
        with obs.span("place_batch"):
            placed = session.place(images, labels)
        return window.step(session.call, state, *placed)

    trainer = Trainer(step, log_every=10 ** 9, verbose=False,
                      log_rank=0 if session.world > 1 else None)
    session.state = trainer.fit(session.state,
                                BoundedLoader(session.loader, window), EPOCHS)


def finish(obs: Observations, session: Session) -> None:
    session.window.close()


def end_to_end(obs: Observations) -> dict:
    return {"train_step_ms": train_step_ms(obs)}
