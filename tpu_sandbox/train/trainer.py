"""Training engine: jit'd train step + epoch loop with reference log parity.

Single-device parity target is the reference train() (mnist_onegpu.py:34-84):
CE loss, plain SGD(lr=1e-4), loss print every 100 steps in the exact format
``Epoch [e/E], Step [s/S], Loss: L``, and a final
``Training complete in: <timedelta>`` wall-clock line.

TPU-first differences:
- The whole update (forward, loss, backward, SGD apply, BN stats update) is
  ONE jit'd pure function with donated state — XLA fuses and schedules it;
  there is no zero_grad/backward/step choreography.
- The 28x28 -> HxW upsample happens INSIDE the step, on device
  (``jax.image.resize``, bilinear like torchvision's default Resize). The
  reference resizes per-image on the host with PIL (mnist_onegpu.py:53),
  which would starve a TPU: feeding 3000x3000 fp32 frames is 180 MB/step
  of host->device traffic vs 4 KB/step for raw 28x28.
- Optional bf16 compute (model dtype) keeps the MXU fed; the loss/params
  stay fp32.
"""

from __future__ import annotations

import contextlib
import os
import signal
import time
from dataclasses import dataclass
from datetime import timedelta
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax

from tpu_sandbox.obs import get_recorder, get_registry
from tpu_sandbox.ops.losses import cross_entropy_loss
from tpu_sandbox.train.state import TrainState
from tpu_sandbox.utils.metrics import MetricsWriter

#: Exit code the supervisor treats as "preempted: saved, restart for free".
#: Canonical home is runtime/supervisor.py; mirrored here so the training
#: layer does not import the process-management layer.
PREEMPTED_EXIT_CODE = 75

#: KV key a preempted rank raises so every peer stops at the same boundary
#: (must match supervisor.PREEMPT_KEY; the supervisor clears it between
#: generations).
PREEMPT_KEY = "preempt/requested"

#: Env vars a supervisor/host-agent sets on every rank it spawns. Mirrored
#: from runtime/{supervisor,host_agent}.py (same no-process-layer-import
#: rule as PREEMPTED_EXIT_CODE above).
ENV_GENERATION = "TPU_SANDBOX_GENERATION"
ENV_AGENT_ID = "TPU_SANDBOX_AGENT_ID"
ENV_JOB_ID = "TPU_SANDBOX_JOB_ID"


@dataclass(frozen=True)
class ElasticEnv:
    """The elastic identity a rank inherits from whoever spawned it:
    which relaunch generation this process belongs to (stamps checkpoints
    and KV claims), which host agent owns it (``None`` outside the
    cross-host agent topology — e.g. under the single-host Supervisor),
    and which job's KV namespace it coordinates in (empty string = the
    default job, bare key schema; see ``runtime.kvstore.for_job``)."""

    generation: str
    agent_id: int | None
    job_id: str = ""

    @classmethod
    def from_env(cls, environ=None) -> "ElasticEnv":
        env = os.environ if environ is None else environ
        raw = env.get(ENV_AGENT_ID, "")
        return cls(
            generation=env.get(ENV_GENERATION, "1"),
            agent_id=int(raw) if raw else None,
            job_id=env.get(ENV_JOB_ID, ""),
        )


def resize_on_device(images, image_size):
    """[N,h,w,C] -> [N,H,W,C] bilinear, channel-layout safe: a size-1
    channel is squeezed around the resize so no [N,H,W,1] intermediate is
    laid out with the degenerate dim on the 128-wide lane axis (XLA:TPU
    pads the minor dim to 128 — measured 8-128x HBM inflation on big
    spatial tensors). Resize never mixes channels, so this is exact."""
    n, _, _, c = images.shape
    if c == 1:
        out = jax.image.resize(
            images[..., 0], (n, *image_size), method="bilinear"
        )
        return out[..., None]
    return jax.image.resize(images, (n, *image_size, c), method="bilinear")


def prepare_inputs(model, images, image_size):
    """The model-plan-aware input stage: models exposing
    ``fused_input_stage`` (ConvNetS2DT) consume the raw small batch
    directly — resize + space-to-depth in two small contractions, no
    full-size [N,H,W] intermediate — and their ``__call__`` detects the
    pre-s2d shape. Every other model gets the plain on-device resize.
    Single home: the trainer and both parallel engines route through
    here."""
    stage = getattr(model, "fused_input_stage", None)
    if stage is not None:
        return stage(images, image_size)
    return resize_on_device(images, image_size)


def make_train_step(
    model,
    tx: optax.GradientTransformation,
    *,
    image_size: tuple[int, int] | None = None,
    accum_steps: int = 1,
    donate: bool = True,
    remat: bool = False,
) -> Callable:
    """Build the jit'd (state, images, labels) -> (state, loss) step.

    ``image_size``: if set, inputs [N,h,w,C] are bilinearly resized to
    [N,H,W,C] on device before the forward pass.

    ``accum_steps``: gradient accumulation — the batch is split into
    ``accum_steps`` microbatches scanned sequentially; gradients are
    averaged and ONE optimizer update is applied. This is the
    single-device counterpart of the reference's OOM workaround (its DDP
    splits effective batch 10 across 2 GPUs; accumulation trains the same
    effective batch on one device with 1/k the activation memory, at k
    sequential passes). BN statistics update per microbatch, sequentially —
    the same semantics k torch forward passes would produce. The resize
    also happens per microbatch, so the full-size image batch never
    materializes at once.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    def loss_fn(params, batch_stats, images, labels):
        if image_size is not None:
            images = prepare_inputs(model, images, image_size)
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
        logits, mutated = model.apply(
            variables, images, train=True, mutable=["batch_stats"]
        )
        with jax.named_scope("loss"):
            loss = cross_entropy_loss(logits, labels)
        return loss, mutated.get("batch_stats", {})

    # ``remat``: recompute the whole forward during backward instead of
    # saving activations (jax.checkpoint over the loss). The capacity
    # lever for the reference's OOM experiment — on the 3000² ConvNet the
    # dominant saved residual is conv1's [N,750,750,256] output (~300 MB/
    # image); remat trades it for one extra forward pass of (cheap, at
    # these MFUs) FLOPs. BN batch-stats semantics are unchanged: the aux
    # stats output is part of the checkpointed function.
    if remat:
        loss_fn = jax.checkpoint(loss_fn)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    @partial(jax.jit, donate_argnums=(0,) if donate else ())
    def train_step(state: TrainState, images: jax.Array, labels: jax.Array):
        if accum_steps == 1:
            (loss, new_stats), grads = grad_fn(
                state.params, state.batch_stats, images, labels
            )
        else:
            n = images.shape[0]
            if n % accum_steps:
                raise ValueError(
                    f"batch {n} not divisible by accum_steps {accum_steps}"
                )
            micro = n // accum_steps
            m_images = images.reshape(accum_steps, micro, *images.shape[1:])
            m_labels = labels.reshape(accum_steps, micro, *labels.shape[1:])

            def body(carry, mb):
                grads_acc, loss_acc, stats = carry
                (loss, stats), grads = grad_fn(
                    state.params, stats, mb[0], mb[1]
                )
                grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
                return (grads_acc, loss_acc + loss, stats), None

            zeros = jax.tree.map(jnp.zeros_like, state.params)
            (grads, loss, new_stats), _ = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32), state.batch_stats),
                (m_images, m_labels),
            )
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            loss = loss / accum_steps
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        return (
            state.replace(
                step=state.step + 1,
                params=new_params,
                batch_stats=new_stats,
                opt_state=new_opt,
            ),
            loss,
        )

    return train_step


def make_eval_step(model, *, image_size: tuple[int, int] | None = None) -> Callable:
    """Jit'd (state, images, labels) -> (correct_count, loss_sum)."""

    @jax.jit
    def eval_step(state: TrainState, images: jax.Array, labels: jax.Array):
        if image_size is not None:
            images = prepare_inputs(model, images, image_size)
        logits = model.apply(state.variables(), images, train=False)
        loss = cross_entropy_loss(logits, labels)
        correct = jnp.sum(jnp.argmax(logits, -1) == labels)
        return correct, loss

    return eval_step


class LoopSpans:
    """The spans of a training loop (``obs/record.py``: the profiler's
    timeline, the registry, and the JSONL when enabled), in one place for
    ``Trainer`` and ``lm_train.train``. ``train:next_batch``,
    ``train:dispatch`` and ``train:sync`` tile an iteration; ``train:step``
    is the interval from one return of the step call to the next — the
    loop's cadence, which the device paces once its queue is full (a loop's
    first step has no predecessor and records none). None of them waits for
    the device where the loop did not already.

    From a loop's first ``returned()`` to its ``ended()`` the recorder's
    ``loop_step`` holds the step last returned from: a program that compiles
    in between is counted where it happens (``compile.in_loop``, the instant
    ``compile:in_loop{program, step}``; ``runtime/bootstrap.py``). The first
    step's own compile lies before the first return and is not one."""

    def __init__(self):
        self.rec = get_recorder()
        self.steps = get_registry().counter("train.steps")
        self._returned: float | None = None

    def batches(self, loader):
        """``loader``'s batches, each ``next()`` under ``train:next_batch``
        (the draw that finds the loader empty included)."""
        done = object()
        it = iter(loader)
        while True:
            with self.rec.span("train:next_batch",
                               hist="train.next_batch_s", loop=True):
                batch = next(it, done)
            if batch is done:
                return
            yield batch

    def dispatch(self):
        return self.rec.span("train:dispatch", hist="train.dispatch_s",
                             loop=True)

    def returned(self, step: int) -> None:
        """The step call has returned: close the interval since the last."""
        if self._returned is not None:
            self.rec.complete("train:step", self._returned,
                              args={"step": step}, hist="train.step_s",
                              loop=True)
        self._returned = time.monotonic()
        self.steps.inc()
        self.rec.loop_step = step

    def ended(self) -> None:
        """The loop has ended, by its last batch or by an exception."""
        self.rec.loop_step = None

    def sync(self, why: str):
        """Around every place the loop does wait for the device."""
        return self.rec.span("train:sync", hist="train.sync_s", loop=True,
                             args={"why": why})


class Trainer:
    """Epoch loop with the reference's logging contract."""

    def __init__(
        self,
        train_step: Callable,
        *,
        log_every: int = 100,
        log_rank: int | None = None,
        verbose: bool = True,
        ckpt_dir: str | None = None,
        ckpt_every: int = 0,
        state_for_checkpoint: Callable | None = None,
    ):
        """``ckpt_every`` > 0 (with ``ckpt_dir``) saves every N optimizer
        steps — the crash-recovery companion of the watchdog subsystem (the
        reference trains fire-and-forget; a dead run loses everything).
        ``state_for_checkpoint`` maps the live (possibly engine-sharded)
        state to the layout to save, e.g. DataParallel.unshard_state."""
        self.train_step = train_step
        self.log_every = log_every
        self.log_rank = log_rank  # None: single-device format; int: DDP format
        self.verbose = verbose
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every if ckpt_dir else 0
        self.state_for_checkpoint = state_for_checkpoint or (lambda s: s)
        self._saver = None
        self.losses: list[float] = []

    def _maybe_checkpoint(self, state, opt_step: int) -> None:
        """``opt_step`` is a host-side counter (each train_step increments
        state.step by one) — reading state.step here would sync the device
        every step and kill host/device overlap."""
        if not self.ckpt_every or opt_step % self.ckpt_every:
            return
        if self._saver is None:
            from tpu_sandbox.train.checkpoint import AsyncSaver

            self._saver = AsyncSaver(self.ckpt_dir)
        with self._spans.sync("checkpoint"):
            saved = self._saver.save(self.state_for_checkpoint(state),
                                     opt_step)
        if saved:
            if self.verbose:
                print(f"checkpoint saved at step {opt_step}")
        elif self.verbose:
            print(
                f"checkpoint SKIPPED at step {opt_step}: {self.ckpt_dir} "
                "already holds a later step (stale dir from a previous run? "
                "pass --resume or a fresh --ckpt-dir)"
            )

    def fit(self, state: TrainState, loader, epochs: int, *,
            set_epoch: bool = False, prefetch: bool = False,
            metrics_path: str | None = None):
        """Run ``epochs`` epochs. ``set_epoch=False`` reproduces the
        reference quirk of never reshuffling the sharded data
        (no ``sampler.set_epoch``, SURVEY §2.1 C14).

        ``prefetch=True`` wraps the loader in a
        :class:`~tpu_sandbox.data.loader.PrefetchLoader` (double-buffered
        background batch assembly) unless it already is one — same batches
        in the same order, assembled while the previous step runs.

        ``metrics_path`` writes a JSONL metrics record per log event; the
        writer's lifetime is the fit call (context-managed, so the fd
        closes on every exit path, raising included)."""
        loader = _maybe_prefetch(loader, prefetch)
        start = time.monotonic()
        total_step = len(loader)
        opt_step = int(jax.numpy.ravel(state.step)[0])  # resume-safe seed
        self._spans = LoopSpans()  # a fit's first step has no predecessor
        try:
            with (MetricsWriter(metrics_path) if metrics_path
                  else contextlib.nullcontext()) as mw:
                state = self._run_epochs(state, loader, epochs, set_epoch,
                                         total_step, opt_step, mw=mw)
        finally:
            self._spans.ended()
            if self._saver is not None:
                # drain in-flight async writes even when the loop raised —
                # an abandoned background save is an orphaned tmp dir, i.e.
                # a lost crash-recovery checkpoint
                self._saver.close()
                self._saver = None
        with self._spans.sync("fit_end"):
            jax.block_until_ready(state)
        self._spans.rec.flush()  # off the hot path: the loop has ended
        self.elapsed = timedelta(seconds=time.monotonic() - start)
        if self.verbose:
            print("Training complete in: " + str(self.elapsed))
        return state

    def _run_epochs(self, state, loader, epochs, set_epoch, total_step,
                    opt_step, mw=None):
        spans = self._spans
        for epoch in range(epochs):
            if set_epoch:
                loader.set_epoch(epoch)
            for i, (images, labels) in enumerate(spans.batches(loader)):
                with spans.dispatch():
                    state, loss = self.train_step(state, images, labels)
                spans.returned(opt_step + 1)
                opt_step += 1
                self._maybe_checkpoint(state, opt_step)
                if (i + 1) % self.log_every == 0:
                    # DP steps return per-rank losses; log rank 0's, which is
                    # what the reference prints (mnist_distributed.py:104-106).
                    # In multi-controller runs the loss array spans processes;
                    # read this process's addressable shard instead.
                    if (
                        hasattr(loss, "is_fully_addressable")
                        and not loss.is_fully_addressable
                    ):
                        loss_host = loss.addressable_shards[0].data
                    else:
                        loss_host = loss
                    with spans.sync("log"):
                        loss_val = float(jax.numpy.ravel(loss_host)[0])
                    self.losses.append(loss_val)
                    if mw is not None:
                        mw.write(opt_step, loss=loss_val, epoch=epoch + 1)
                    if self.verbose:
                        if self.log_rank is not None:
                            print(
                                "Rank [{}], Epoch [{}/{}], Step [{}/{}], Loss: {:.4f}".format(
                                    self.log_rank, epoch + 1, epochs, i + 1,
                                    total_step, loss_val,
                                )
                            )
                        else:
                            print(
                                "Epoch [{}/{}], Step [{}/{}], Loss: {:.4f}".format(
                                    epoch + 1, epochs, i + 1, total_step, loss_val
                                )
                            )
        return state


def _maybe_prefetch(loader, prefetch: bool):
    """Wrap ``loader`` for background prefetch when asked (idempotent)."""
    if not prefetch:
        return loader
    from tpu_sandbox.data.loader import PrefetchLoader

    if isinstance(loader, PrefetchLoader):
        return loader
    return PrefetchLoader(loader)


# -- train -> serve handoff -------------------------------------------------


def publish_checkpoint(kv, params, *, export_dir, step: int,
                       fleet: str = "", extra: dict | None = None,
                       compress: bool = False) -> int:
    """Seal ``params`` as a one-rank export and register it in the deploy
    model registry; returns the allocated version number. This is the
    trainer's side of the zero-downtime handoff: the export either seals
    completely (manifest written last) or raises — a torn artifact is
    never registered, and the DeployController re-verifies checksums
    before any replica is told to load it. Imports stay lazy so the plain
    training path never pulls in the deploy plane."""
    from tpu_sandbox.deploy.registry import publish_version
    from tpu_sandbox.train.checkpoint import export_params

    step_dir = export_params(export_dir, params, int(step), extra=extra,
                             compress=compress)
    return publish_version(kv, step_dir, fleet=fleet, step=int(step),
                           extra=extra)


# -- elastic / resumable training -----------------------------------------

class Preempted(RuntimeError):
    """Raised by ``train_resumable`` after a SIGTERM-initiated checkpoint:
    state is saved, the process should exit with ``exit_code`` so the
    supervisor restarts it without charging the restart budget."""

    exit_code = PREEMPTED_EXIT_CODE

    def __init__(self, step: int):
        super().__init__(
            f"preempted at optimizer step {step}; checkpoint saved"
        )
        self.step = step


class AbortOnAnomaly(RuntimeError):
    """``max_bad_steps`` consecutive non-finite losses: the run is
    diverging, not glitching — restarting would replay the same batches
    into the same blowup, so fail for real (charges the restart budget)."""


class PreemptionHandler:
    """SIGTERM → finish the in-flight step, checkpoint, exit preempted.

    The handler itself only flips a flag (a signal handler that touched
    the KV client could re-enter its request lock mid-call and deadlock);
    all real work happens at the next step boundary via :meth:`sync`,
    which also *propagates* the preemption through the KV store — in a
    multi-controller job the save must happen at the same boundary on
    every rank, and peers that never received the signal learn about it
    from the ``preempt/requested`` key.
    """

    def __init__(self, kv=None, key: str = PREEMPT_KEY):
        self.kv = kv
        self.key = key
        self._flag = False
        self._announced = False
        self._prev = None

    def install(self) -> "PreemptionHandler":
        try:
            self._prev = signal.signal(signal.SIGTERM, self._on_signal)
        except ValueError:
            self._prev = None  # not the main thread (tests); KV still works
        return self

    def uninstall(self) -> None:
        if self._prev is not None:
            try:
                signal.signal(signal.SIGTERM, self._prev)
            except ValueError:
                pass
            self._prev = None

    def _on_signal(self, signum, frame) -> None:
        self._flag = True  # flag only — see class docstring

    def preempt_now(self) -> None:
        """Programmatic preemption (tests)."""
        self._flag = True

    def requested(self) -> bool:
        """True once this rank should stop: locally signaled or a peer
        announced through the store. Call at step boundaries only."""
        if self._flag:
            if self.kv is not None and not self._announced:
                try:
                    self.kv.set(self.key, b"1")
                except Exception:
                    pass  # store gone: still honor the local signal
                self._announced = True
            return True
        if self.kv is not None:
            try:
                if self.kv.try_get(self.key) is not None:
                    self._flag = True
                    return True
            except Exception:
                pass
        return False


def _loss_is_finite(loss) -> bool:
    """Finite check that works for scalars, per-rank loss vectors, and
    multi-controller global arrays (where the on-device reduction yields a
    replicated scalar, so every process reaches the same verdict)."""
    if isinstance(loss, jax.Array) and not loss.is_fully_addressable:
        return int((~jnp.isfinite(loss)).sum()) == 0
    return bool(np.isfinite(np.asarray(loss)).all())


def _host_loss(loss) -> float:
    if hasattr(loss, "is_fully_addressable") and not loss.is_fully_addressable:
        loss = loss.addressable_shards[0].data
    return float(np.ravel(np.asarray(loss))[0])


@dataclass
class ResumableReport:
    resumed_step: int | None  # optimizer step restored from, None = fresh
    start_epoch: int
    start_offset: int
    steps_applied: int  # optimizer updates this call actually performed
    skipped_nonfinite: int
    final_step: int
    losses: list[float]


def build_elastic_checkpoint(
    directory,
    *,
    dp,
    template,
    rank: int,
    world_size: int,
    sharded: bool | None = None,
    kv=None,
    injector=None,
    verify_interval: float = 0.0,
    commit_timeout: float = 60.0,
    generation: int | str | None = None,
    keep: int = 3,
    verbose: bool = True,
    compress: bool = False,
):
    """Build the (save_fn, restore_fn, verifier) triple ``train_resumable``
    consumes, picking the checkpoint backend for an elastic run.

    ``sharded=None`` auto-selects: ZeRO mode (``dp.zero``) *requires* the
    sharded backend — the rank-0-only ``HostCheckpoint`` would silently
    drop every other rank's optimizer shard — and plain DP defaults to it
    too unless explicitly disabled. ``sharded=False`` keeps the PR-1 npz
    path (single rank-0 writer, no manifests).

    - save: each rank hands its host-local view + placement spec to
      :class:`ShardedCheckpoint`; rank 0 seals with the manifest after the
      two-phase commit. ``injector.maybe_fire_commit`` is wired into the
      commit window so ``kill_during_commit`` faults land at the exact
      nastiest instants.
    - restore: reassemble + checksum-verify; at unchanged world size every
      rank gets its own BN-stats replica back bitwise, at a changed world
      size per-replica leaves fold to replica 0 and ZeRO optimizer shards
      are re-sliced for the new world (the cross-shard reshard).
    - verifier: a rank-0 :class:`CheckpointVerifier` when
      ``verify_interval`` > 0 (caller starts/stops it around training).
    """
    from tpu_sandbox.train.checkpoint import (
        CheckpointVerifier,
        HostCheckpoint,
        ShardedCheckpoint,
        fold_per_replica,
    )

    if sharded is None:
        sharded = True
    # Engines with step-persistent sync state (the compressed-gradient
    # error-feedback residual) extend the restore template here: leaves a
    # template does not name are never restored, so this must run before
    # either backend captures it.
    if hasattr(dp, "checkpoint_template"):
        template = dp.checkpoint_template(template)
    if dp.zero and not sharded:
        raise ValueError(
            "ZeRO optimizer-state sharding needs the sharded checkpoint "
            "backend: HostCheckpoint is rank-0-only and would lose every "
            "other rank's optimizer shard"
        )

    if not sharded:
        hc = HostCheckpoint(directory, keep=keep)

        def restore_fn():
            res = hc.restore(template)
            if res is None:
                return None
            host_state, meta = res
            return dp.shard_state(host_state), meta

        def save_fn(dstate, step, epoch, offset):
            if rank == 0:
                host = jax.tree.map(
                    lambda h, t: np.asarray(h).reshape(np.shape(t)),
                    dstate.host_view(), template,
                )
                hc.save(host, step, epoch=epoch, offset=offset)

        return save_fn, restore_fn, None

    sc = ShardedCheckpoint(
        directory, rank=rank, world_size=world_size, kv=kv, keep=keep,
        commit_timeout=commit_timeout, generation=generation,
        verbose=verbose, compress=compress,
    )

    def save_fn(dstate, step, epoch, offset):
        hook = None
        if injector is not None:
            def hook(phase, _step=step):
                injector.maybe_fire_commit(_step)
        sc.save(
            dstate.host_view(), dp.checkpoint_spec(dstate), step,
            epoch=epoch, offset=offset, commit_hook=hook,
        )

    def restore_fn():
        # Partial fast path: at unchanged world size each rank reads only
        # rank 0's shard and its own (2 files + 2 hash passes instead of
        # world_size) and places its blocks directly, skipping the global
        # reassembly buffer. Valid only when every process owns exactly
        # its own mesh slot; anything surprising — world changed, missing
        # shard, checksum mismatch — falls back to the full restore below,
        # which reshards across worlds and can quarantine a rotten step
        # and walk back to an older sealed one.
        if (hasattr(dp, "shard_state_local")
                and jax.process_count() == world_size
                and jax.local_device_count() == 1):
            try:
                res = sc.restore_partial(template)
            except Exception as e:
                if verbose:
                    print(f"[elastic] partial restore unavailable ({e}); "
                          "falling back to full restore", flush=True)
            else:
                if res is None:
                    return None
                local_state, meta = res
                return dp.shard_state_local(local_state, template), meta
        res = sc.restore(template)
        if res is None:
            return None
        host_state, meta = res
        if int(meta.get("world_size", world_size)) == world_size:
            # same world: place every rank's own BN replica back bitwise
            return dp.shard_state(host_state, stats_expanded=True), meta
        folded = fold_per_replica(host_state, template)
        return dp.shard_state(folded), meta

    verifier = None
    if verify_interval > 0 and rank == 0:
        verifier = CheckpointVerifier(sc, interval=verify_interval)
    return save_fn, restore_fn, verifier


def train_resumable(
    step_fn: Callable,
    state: TrainState,
    loader,
    epochs: int,
    *,
    save_fn: Callable[[TrainState, int, int, int], None] | None = None,
    restore_fn: Callable[[], tuple[TrainState, dict] | None] | None = None,
    ckpt_every: int = 0,
    preemption: PreemptionHandler | None = None,
    agree_fn: Callable[[bool], bool] | None = None,
    injector=None,
    max_bad_steps: int = 3,
    log_every: int = 100,
    log_rank: int | None = None,
    verbose: bool = True,
    set_epoch: bool = False,
    prefetch: bool = False,
) -> tuple[TrainState, ResumableReport]:
    """The crash-safe epoch loop: checkpoint every ``ckpt_every`` optimizer
    steps *with data-order state*, resume exactly where the stream stood,
    survive preemption, and refuse to train on garbage.

    - **Exact data order.** Each checkpoint records (epoch, batch offset);
      resume re-seeds the loader's deterministic per-epoch order and skips
      exactly the consumed batches — no batch replayed, none skipped. With
      ``save_fn=None`` the loop still runs (plain training with guards).
    - **Preemption.** ``preemption.requested()`` is polled every boundary;
      when set the in-flight step has already finished, so the loop saves
      and raises :class:`Preempted` — the caller exits with
      ``PREEMPTED_EXIT_CODE`` and the supervisor restarts for free.
      In a multi-controller job pass ``agree_fn`` (an OR-reduction across
      ranks, e.g. a tiny psum): the KV flag alone is racy — a peer can
      read its boundary a hair before the signaled rank announces, walk
      into the next step's collective, and block there forever. The
      collective vote forces every rank to the same verdict at the same
      boundary, so the whole world saves and exits 75 together.
    - **Anomaly guard.** A non-finite loss discards that update (the
      previous state is kept — ``step_fn`` must therefore NOT donate its
      input state; build engines with ``donate=False`` for elastic runs)
      and counts against ``max_bad_steps`` consecutive anomalies, after
      which :class:`AbortOnAnomaly` ends the run as a real failure. The
      per-step finite check syncs the loss to host, trading a little
      step-overlap for the guarantee — the resilience tax.
    - **Fault injection.** ``injector.maybe_fire(opt_step)`` runs after
      every applied update, so test faults land at exact, reproducible
      optimizer steps.

    ``restore_fn() -> (state, meta) | None`` and
    ``save_fn(state, step, epoch, offset)`` keep this loop agnostic of the
    checkpoint backend (orbax single-process, HostCheckpoint
    multi-controller) and of engine sharding.

    ``prefetch=True`` wraps the loader in a background
    :class:`~tpu_sandbox.data.loader.PrefetchLoader`. The prefetcher's
    determinism contract (same batches, same order, delegated
    ``set_epoch``) keeps the (epoch, offset) checkpoint metadata exact, so
    resume parity is unchanged — tested in tests/test_prefetch.py.
    """
    loader = _maybe_prefetch(loader, prefetch)
    steps_per_epoch = len(loader)
    resumed_step = None
    start_epoch, start_offset = 0, 0
    if restore_fn is not None:
        res = restore_fn()
        if res is not None:
            state, meta = res
            resumed_step = int(meta.get("step", 0))
            # sidecar is authoritative; derive from the step count when it
            # is missing/corrupt (possible after a kill mid-sidecar-write)
            start_epoch = int(meta.get("epoch", resumed_step // steps_per_epoch))
            start_offset = int(
                meta.get("offset", resumed_step % steps_per_epoch)
            )
            if start_offset >= steps_per_epoch:
                start_epoch += 1
                start_offset = 0
    opt_step = resumed_step if resumed_step is not None else 0
    report = ResumableReport(
        resumed_step=resumed_step, start_epoch=start_epoch,
        start_offset=start_offset, steps_applied=0, skipped_nonfinite=0,
        final_step=opt_step, losses=[],
    )
    consecutive_bad = 0

    def checkpoint(epoch: int, offset: int) -> None:
        if save_fn is not None:
            save_fn(state, opt_step, epoch, offset)

    for epoch in range(start_epoch, epochs):
        if set_epoch:
            loader.set_epoch(epoch)
        for i, (images, labels) in enumerate(loader):
            if epoch == start_epoch and i < start_offset:
                continue  # consumed before the checkpoint: replay nothing
            new_state, loss = step_fn(state, images, labels)
            if _loss_is_finite(loss):
                state = new_state
                opt_step += 1
                report.steps_applied += 1
                consecutive_bad = 0
                applied = True
            else:
                report.skipped_nonfinite += 1
                consecutive_bad += 1
                applied = False
                if verbose:
                    print(
                        f"non-finite loss at epoch {epoch + 1} batch "
                        f"{i + 1}; update skipped "
                        f"({consecutive_bad}/{max_bad_steps} consecutive)"
                    )
                if consecutive_bad >= max_bad_steps:
                    raise AbortOnAnomaly(
                        f"{consecutive_bad} consecutive non-finite losses "
                        f"around optimizer step {opt_step}; aborting"
                    )
            saved_here = False
            if applied and ckpt_every and opt_step % ckpt_every == 0:
                checkpoint(epoch, i + 1)
                saved_here = True
            if injector is not None and applied:
                injector.maybe_fire(opt_step)
            if preemption is not None or agree_fn is not None:
                want = preemption is not None and preemption.requested()
                stop = agree_fn(want) if agree_fn is not None else want
                if stop:
                    if preemption is not None:
                        # a rank outvoted here (peer was signaled, we were
                        # not) must still exit with the preempted code
                        preemption.preempt_now()
                    if not saved_here:
                        checkpoint(epoch, i + 1)
                    report.final_step = opt_step
                    raise Preempted(opt_step)
            if applied and (i + 1) % log_every == 0:
                loss_val = _host_loss(loss)
                report.losses.append(loss_val)
                if verbose:
                    prefix = (
                        f"Rank [{log_rank}], " if log_rank is not None else ""
                    )
                    print(
                        "{}Epoch [{}/{}], Step [{}/{}], Loss: {:.4f}".format(
                            prefix, epoch + 1, epochs, i + 1,
                            steps_per_epoch, loss_val,
                        )
                    )
        start_offset = 0  # only the resumed epoch starts mid-stream
    report.final_step = opt_step
    return state, report
