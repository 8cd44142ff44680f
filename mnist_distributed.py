"""Data-parallel big-image MNIST training — TPU-native rebuild of the
reference ``mnist_distributed.py`` (same flags, same log lines, same
OOM-workaround experiment: bs=5 per rank, effective batch 5*world_size).

Reference behavior (mnist_distributed.py:48-127): spawn one process per GPU,
global rank = nr*gpus + gpu, NCCL process group, DDP-wrapped ConvNet,
DistributedSampler sharding (never reshuffled — no set_epoch call), CE +
SGD(1e-4), rank-0 prints ``Rank [r], Epoch [e/E], Step [s/S], Loss: L``
every 100 steps, wall-clock total. Its multi-node flags never actually
worked (hardcoded localhost master + fresh random port per invocation).

TPU-native shape: no spawning — ranks are devices of one process
(``-g`` = number of local devices: the host's chips, or virtual CPU devices
under ``--force-cpu``; asking for more chips than there are is an error).
The DDP engine is ``tpu_sandbox.parallel.DataParallel``: one jit'd
shard_map step with pmean'd grads, replicated params, per-replica BN stats.
Real multi-host runs initialize via tpu_sandbox.runtime.bootstrap
(jax.distributed) instead of the reference's broken localhost rendezvous.
"""

import argparse

from tpu_sandbox.utils.cli import (
    add_checkpoint_cli,
    add_elastic_cli,
    add_grad_compress_cli,
    add_prefetch_cli,
)

IMAGE_SHAPE = [3000, 3000]


def load_training_arrays(args, world_size):
    """Real MNIST if available, synthetic otherwise; normalized and trimmed
    to --limit-steps (shared by the single- and multi-process paths)."""
    from tpu_sandbox.data import load_mnist, synthetic_mnist
    from tpu_sandbox.data.mnist import normalize

    try:
        images, labels = load_mnist("train", args.data_dir)
    except FileNotFoundError:
        print("MNIST IDX files not found; using deterministic synthetic MNIST")
        images, labels = synthetic_mnist(n=args.synthetic_n, seed=0)
    images = normalize(images)
    labels = labels.astype("int32")
    if args.limit_steps:
        keep = args.limit_steps * args.batch_size * world_size
        images, labels = images[:keep], labels[:keep]
    return images, labels


def _zero_sgd_note():
    print("note: --zero with plain SGD shards no optimizer state "
          "(SGD is stateless); use --opt momentum|adamw for the memory win")


def make_optimizer(args):
    """--opt picks the optimizer; the reference schedule is plain SGD(1e-4)
    (mnist_distributed.py:65 in the reference), kept as the default for log
    parity. --zero only has state to shard for the stateful choices."""
    import optax

    if args.opt == "sgd":
        if args.zero and not getattr(args, "worker", False):
            _zero_sgd_note()
        return optax.sgd(learning_rate=1e-4)
    if args.opt == "momentum":
        return optax.sgd(learning_rate=1e-4, momentum=0.9)
    return optax.adamw(learning_rate=1e-4)


def build(args, world_size):
    """Everything ``train`` runs, built but not run: (DataParallel engine,
    unsharded initial state, sharded batch loader) over ``world_size``
    devices of this process. ``chip_smoke.py`` drives these same objects."""
    import jax
    import jax.numpy as jnp

    from tpu_sandbox.data import ShardedBatchLoader
    from tpu_sandbox.models import pick_convnet
    from tpu_sandbox.obs import get_recorder
    from tpu_sandbox.parallel import DataParallel
    from tpu_sandbox.runtime.mesh import make_mesh
    from tpu_sandbox.train import TrainState
    from tpu_sandbox.utils.cli import ensure_devices

    rec = get_recorder()
    # ``setup:build`` is the launch's root span (``mnist_onegpu.build``)
    with rec.span("setup:build", loop=True):
        devices = ensure_devices(world_size, force_cpu=args.force_cpu)
        mesh = make_mesh({"data": world_size}, devices=devices)

        rng = jax.random.key(0)  # parity: torch.manual_seed(0), reference :51
        image_shape = [args.image_size, args.image_size]
        dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
        model = pick_convnet(args.image_size, plan=args.plan,
                             num_classes=10, dtype=dtype)
        tx = make_optimizer(args)

        with rec.span("setup:data", hist="setup.data_s", loop=True):
            images, labels = load_training_arrays(args, world_size)

            # bs per rank (reference :60-61); sampler shards, loader never
            # reshuffles across epochs (reference quirk: no
            # sampler.set_epoch, SURVEY §2.1 C14)
            loader = ShardedBatchLoader(
                images, labels, args.batch_size, world_size, shuffle=True,
                seed=0
            )

        state = TrainState.create(
            model, rng, jnp.zeros([1, *image_shape, 1], dtype), tx)
        dp = DataParallel(model, tx, mesh, image_size=tuple(image_shape),
                          zero=args.zero, grad_compress=args.grad_compress,
                          error_feedback=not args.no_error_feedback)
    return dp, state, loader


def train(args, world_size):
    from tpu_sandbox.runtime import bootstrap
    from tpu_sandbox.train import Trainer

    dp, state, loader = build(args, world_size)
    bootstrap.init()
    if args.ckpt_dir and args.resume:
        from tpu_sandbox.train import checkpoint as ckpt

        if ckpt.latest_step(args.ckpt_dir) is not None:
            state = ckpt.restore(args.ckpt_dir, state)
            print(f"resumed from step {int(state.step)}")
    dstate = dp.shard_state(state)

    def step(s, images_np, labels_np):
        return dp.train_step(s, *dp.shard_batch(images_np, labels_np))

    trainer = Trainer(step, log_every=args.log_every, log_rank=0,
                      ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                      state_for_checkpoint=dp.unshard_state)
    dstate = trainer.fit(dstate, loader, args.epochs, set_epoch=False,
                         prefetch=args.prefetch)
    if args.ckpt_dir:
        from tpu_sandbox.train import checkpoint as ckpt

        # checkpoint the single-device view (rank 0's BN stats), the same
        # layout mnist_onegpu saves — the two scripts' checkpoints interop
        print(f"saved checkpoint at step "
              f"{ckpt.save(args.ckpt_dir, dp.unshard_state(dstate))}")
    bootstrap.cleanup()


def train_multiprocess_worker(args, world_size):
    """One OS process = one rank with one CPU device — the reference's
    actual topology (one proc per GPU, mnist_distributed.py:127), over
    jax.distributed + Gloo instead of NCCL. Each process feeds its
    DistributedSampler shard and assembles the global batch with
    make_array_from_process_local_data; the jit'd shard_map step then runs
    SPMD across processes with cross-process grad pmean."""
    from tpu_sandbox.utils.cli import configure_worker_cpu

    configure_worker_cpu(1)

    import jax  # noqa: F401  (platform configured above, before first use)
    import numpy as np

    from tpu_sandbox.runtime import Heartbeat, bootstrap, wait_for_world
    from tpu_sandbox.runtime.kvstore import KVClient

    # health plane: beat into the parent's KV store for the whole run and
    # rendezvous with a deadline BEFORE touching jax.distributed, so a rank
    # that never starts fails fast with names instead of hanging the group
    # (the reference's failure mode — SURVEY §5)
    hb = None
    if args.kv_port:
        kv = KVClient(port=int(args.kv_port))
        hb = Heartbeat(kv, args.rank, interval=1.0).start()
        wait_for_world(kv, world_size, args.rank, timeout=120.0)

    bootstrap.init(
        coordinator=f"127.0.0.1:{args.port}",
        num_processes=world_size,
        process_id=args.rank,
    )

    import jax.numpy as jnp

    from tpu_sandbox.data import BatchLoader
    from tpu_sandbox.data.sampler import DistributedSampler
    from tpu_sandbox.models import pick_convnet
    from tpu_sandbox.parallel import DataParallel
    from tpu_sandbox.runtime.mesh import make_mesh
    from tpu_sandbox.runtime.multihost import global_batch_from_local
    from tpu_sandbox.train import Trainer, TrainState

    rank = args.rank
    mesh = make_mesh({"data": world_size})  # one device per process
    image_shape = [args.image_size, args.image_size]
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32

    # same seed everywhere -> same init; shard_state places it replicated
    model = pick_convnet(args.image_size, plan=args.plan,
                         num_classes=10, dtype=dtype)
    tx = make_optimizer(args)
    state = TrainState.create(
        model, jax.random.key(0), jnp.zeros([1, *image_shape, 1], dtype), tx
    )

    images, labels = load_training_arrays(args, world_size)
    sampler = DistributedSampler(len(images), world_size, rank, seed=0)
    local_loader = BatchLoader(images, labels, args.batch_size,
                               sampler=sampler, drop_last=True)

    class GlobalLoader:
        """Each process contributes its sampler shard; batches come out as
        global process-spanning arrays (make_array_from_process_local_data)."""

        def __len__(self):
            return len(local_loader)

        def set_epoch(self, epoch):
            local_loader.set_epoch(epoch)

        def __iter__(self):
            for imgs, labs in local_loader:
                yield (
                    global_batch_from_local(mesh, np.asarray(imgs)),
                    global_batch_from_local(mesh, np.asarray(labs)),
                )

    dp = DataParallel(model, tx, mesh, image_size=tuple(image_shape),
                      zero=args.zero, grad_compress=args.grad_compress,
                      error_feedback=not args.no_error_feedback)
    dstate = dp.shard_state(state)
    trainer = Trainer(dp.train_step, log_every=args.log_every, log_rank=0,
                      verbose=rank == 0)
    trainer.fit(dstate, GlobalLoader(), args.epochs, set_epoch=False,
                prefetch=args.prefetch)
    bootstrap.cleanup()
    if hb is not None:
        hb.stop(deregister=True)


def train_elastic_worker(args, world_size):
    """One rank of an elastic generation: heartbeat + generation-scoped
    rendezvous, fault injection from the env plan, resumable training with
    coordination-free checkpointing (host: rank 0 writes npz files; sharded:
    every rank writes its own shard and rank 0 seals a manifest via
    two-phase commit — required under --zero, whose optimizer shards live on
    every rank), and SIGTERM → save → exit 75 so the supervisor restarts
    the generation without charging its budget."""
    import os
    import sys

    from tpu_sandbox.utils.cli import configure_worker_cpu

    configure_worker_cpu(1)

    import jax
    import numpy as np

    from tpu_sandbox.runtime import Heartbeat, bootstrap, wait_for_world
    from tpu_sandbox.runtime.faults import FaultInjector, FaultPlan
    from tpu_sandbox.runtime.kvstore import KVClient, for_job
    from tpu_sandbox.train import (
        PREEMPTED_EXIT_CODE,
        ElasticEnv,
        Preempted,
        PreemptionHandler,
        TrainState,
        build_elastic_checkpoint,
        train_resumable,
    )

    rank = args.rank
    eenv = ElasticEnv.from_env()  # generation + owning host agent (if any)
    # job-scoped store view: under the cluster scheduler every runtime key
    # this rank touches (heartbeats, fault claims, barriers, job/done)
    # lives inside job/<id>/ — a neighbor job can never see or be seen
    kv = for_job(KVClient(port=int(args.kv_port)), eenv.job_id)
    hb = Heartbeat(kv, rank, interval=0.5).start()
    preemption = PreemptionHandler(kv)
    plan = FaultPlan.from_env()
    injector = None
    if plan.faults:
        # hang_heartbeat: stop beating but stay alive — exercises the
        # supervisor's watchdog (wedged-not-dead) path; agent_id routes
        # kill_agent/partition_host to this rank's host agent's mailbox
        injector = FaultInjector(
            plan, rank, kv,
            on_hang_heartbeat=lambda: hb.stop(deregister=False),
            agent_id=eenv.agent_id,
        )
    wait_for_world(kv, world_size, rank, timeout=120.0)
    bootstrap.init(
        coordinator=f"127.0.0.1:{args.port}",
        num_processes=world_size,
        process_id=rank,
    )
    # AFTER bootstrap.init: jax.distributed installs XLA's own SIGTERM
    # notifier, and whoever installs last owns the signal — ours must win
    # or a preemption notice trains straight through to completion
    preemption.install()

    import jax.numpy as jnp

    from tpu_sandbox.data import BatchLoader
    from tpu_sandbox.data.sampler import DistributedSampler
    from tpu_sandbox.models import pick_convnet
    from tpu_sandbox.parallel import DataParallel
    from tpu_sandbox.runtime.mesh import make_mesh
    from tpu_sandbox.runtime.multihost import global_batch_from_local

    mesh = make_mesh({"data": world_size})
    image_shape = [args.image_size, args.image_size]
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    model = pick_convnet(args.image_size, plan=args.plan,
                         num_classes=10, dtype=dtype)
    tx = make_optimizer(args)
    state = TrainState.create(
        model, jax.random.key(0), jnp.zeros([1, *image_shape, 1], dtype), tx
    )
    template = state.host_view()  # restore target, before sharding

    images, labels = load_training_arrays(args, world_size)
    sampler = DistributedSampler(len(images), world_size, rank, seed=0)
    local_loader = BatchLoader(images, labels, args.batch_size,
                               sampler=sampler, drop_last=True)

    class GlobalLoader:
        def __len__(self):
            return len(local_loader)

        def set_epoch(self, epoch):
            local_loader.set_epoch(epoch)

        def __iter__(self):
            for imgs, labs in local_loader:
                yield (
                    global_batch_from_local(mesh, np.asarray(imgs)),
                    global_batch_from_local(mesh, np.asarray(labs)),
                )

    # donate=False: the non-finite guard keeps the PREVIOUS state when an
    # update is discarded, which donated (invalidated) buffers cannot do
    dp = DataParallel(model, tx, mesh, image_size=tuple(image_shape),
                      zero=args.zero, donate=False,
                      grad_compress=args.grad_compress,
                      error_feedback=not args.no_error_feedback)

    # per-boundary preemption vote: OR this rank's flag across the world
    # through a real collective, so every rank reaches the same stop
    # verdict at the same step (see train_resumable's docstring)
    _vote_sum = jax.jit(jnp.sum)

    def agree_preempt(flag: bool) -> bool:
        local = np.asarray([1.0 if flag else 0.0], np.float32)
        return bool(int(_vote_sum(global_batch_from_local(mesh, local))) > 0)

    gen = eenv.generation
    restore_fn = None
    save_fn = None
    verifier = None
    if args.ckpt_dir:
        save_fn, restore_fn, verifier = build_elastic_checkpoint(
            args.ckpt_dir, dp=dp, template=template, rank=rank,
            world_size=world_size,
            sharded=bool(args.ckpt_sharded or args.zero),
            kv=kv, injector=injector,
            verify_interval=args.ckpt_verify_interval,
            commit_timeout=float(
                os.environ.get("TPU_SANDBOX_COMMIT_TIMEOUT", 60.0)
            ),
            generation=gen, verbose=rank == 0,
            compress=args.ckpt_compress,
        )
    if verifier is not None:
        verifier.start()
    dstate = dp.shard_state(state)
    try:
        dstate, report = train_resumable(
            dp.train_step, dstate, GlobalLoader(), args.epochs,
            save_fn=save_fn, restore_fn=restore_fn,
            ckpt_every=args.ckpt_every, preemption=preemption,
            agree_fn=agree_preempt if world_size > 1 else None,
            injector=injector, log_every=args.log_every, log_rank=rank,
            verbose=rank == 0, set_epoch=False, prefetch=args.prefetch,
        )
        if rank == 0:
            resumed = (f"resumed from step {report.resumed_step}"
                       if report.resumed_step is not None else "fresh start")
            print(f"[gen {gen}] {resumed}; applied {report.steps_applied} "
                  f"step(s), final step {report.final_step}")
        if save_fn is not None:
            save_fn(dstate, report.final_step, args.epochs, 0)
    except Preempted:
        hb.stop(deregister=True)
        bootstrap.cleanup()
        sys.exit(PREEMPTED_EXIT_CODE)
    except BaseException:
        # a peer's preemption can surface here as a collective/dispatch
        # error on this rank; if the preempt flag is up, classify this exit
        # as preempted too so the supervisor's initiator-only rule holds
        if preemption.requested():
            hb.stop(deregister=True)
            sys.exit(PREEMPTED_EXIT_CODE)
        raise
    finally:
        preemption.uninstall()
        if verifier is not None:
            verifier.stop()
    bootstrap.cleanup()
    hb.stop(deregister=True)


def _elastic_passthrough(args):
    """The worker-facing flag subset, re-serialized for child processes
    (shared by the single-host supervisor path and the agent topology —
    their workers must parse identically)."""
    passthrough = [
        "-n", str(args.nodes), "-g", str(args.gpus),
        "--epochs", str(args.epochs), "--batch-size", str(args.batch_size),
        "--image-size", str(args.image_size),
        "--synthetic-n", str(args.synthetic_n),
        "--log-every", str(args.log_every), "--dtype", args.dtype,
        "--plan", args.plan, "--opt", args.opt,
    ]
    if args.data_dir:
        passthrough += ["--data-dir", args.data_dir]
    if args.limit_steps:
        passthrough += ["--limit-steps", str(args.limit_steps)]
    if args.ckpt_dir:
        passthrough += ["--ckpt-dir", args.ckpt_dir]
    if args.ckpt_every:
        passthrough += ["--ckpt-every", str(args.ckpt_every)]
    if args.zero:
        # safe under --elastic since PR 3: ZeRO auto-selects the sharded
        # checkpoint backend, so every rank's optimizer shard is persisted
        passthrough += ["--zero"]
    if args.ckpt_sharded:
        passthrough += ["--ckpt-sharded"]
    if args.ckpt_verify_interval:
        passthrough += ["--ckpt-verify-interval",
                        str(args.ckpt_verify_interval)]
    if args.ckpt_compress:
        passthrough += ["--ckpt-compress"]
    if args.grad_compress != "none":
        passthrough += ["--grad-compress", args.grad_compress]
    if args.no_error_feedback:
        passthrough += ["--no-error-feedback"]
    if args.prefetch:
        passthrough += ["--prefetch"]
    return passthrough


def _validate_fault_plan():
    from tpu_sandbox.runtime.faults import FaultPlan

    try:
        # fail fast here: a malformed plan would otherwise crash every
        # worker at startup and silently burn the whole restart budget
        FaultPlan.from_env()
    except (TypeError, ValueError) as e:
        raise SystemExit(f"invalid TPU_SANDBOX_FAULT_PLAN: {e}") from e


def spawn_elastic(args, world_size):
    """Run the multiprocess topology under the elastic supervisor: crashes
    and preemptions tear the generation down and relaunch it; workers
    resume from the newest valid checkpoint with exact data order."""
    import os
    import sys

    from tpu_sandbox.runtime.bootstrap import find_free_port
    from tpu_sandbox.runtime.supervisor import (
        RestartBudgetExceeded,
        Supervisor,
    )

    _validate_fault_plan()
    if not args.ckpt_dir:
        print("note: --elastic without --ckpt-dir restarts from step 0 "
              "(pass --ckpt-dir/--ckpt-every to resume where the crash hit)")

    passthrough = _elastic_passthrough(args)

    def build(gen, kv_port):
        port = find_free_port()  # fresh coordinator port per generation
        base = [sys.executable, __file__, "--elastic-worker",
                "--port", port, "--kv-port", str(kv_port)] + passthrough
        return [base + ["--rank", str(r)] for r in range(world_size)]

    sup = Supervisor(
        world_size, build,
        max_restarts=args.max_restarts,
        backoff=float(os.environ.get("TPU_SANDBOX_BACKOFF", 1.0)),
        heartbeat_timeout=float(
            os.environ.get("TPU_SANDBOX_WATCHDOG_TIMEOUT", 60.0)
        ),
        grace=float(os.environ.get("TPU_SANDBOX_WATCHDOG_GRACE", 180.0)),
        term_timeout=float(
            # how long a SIGTERM'd survivor (usually wedged in a collective
            # whose peer died) gets before the SIGKILL escalation
            os.environ.get("TPU_SANDBOX_TERM_TIMEOUT", 30.0)
        ),
    )
    try:
        result = sup.run()
    except RestartBudgetExceeded as e:
        raise SystemExit(str(e))
    if not result.ok:
        # preempted from outside: saved state, clean stop, propagate 75
        sys.exit(result.generations[-1].exit_codes[0] or 0)


def _agent_config_from_env(args, world_size, kv_port):
    """AgentConfig from CLI + the same env knobs the supervisor honors,
    plus the agent-plane extras (agent heartbeat timeout, lease TTL)."""
    import os

    from tpu_sandbox.runtime.host_agent import AgentConfig

    def knob(name, default):
        return float(os.environ.get(name, default))

    return AgentConfig(
        agent_id=args.agent_id or 0,
        num_agents=args.agents,
        world_size=world_size,
        kv_port=kv_port,
        job_id=args.job_id or os.environ.get("TPU_SANDBOX_JOB_ID", ""),
        max_restarts=args.max_restarts,
        backoff=knob("TPU_SANDBOX_BACKOFF", 1.0),
        heartbeat_timeout=knob("TPU_SANDBOX_WATCHDOG_TIMEOUT", 60.0),
        grace=knob("TPU_SANDBOX_WATCHDOG_GRACE", 180.0),
        term_timeout=knob("TPU_SANDBOX_TERM_TIMEOUT", 30.0),
        agent_timeout=knob("TPU_SANDBOX_AGENT_TIMEOUT", 10.0),
        lease_ttl=knob("TPU_SANDBOX_LEASE_TTL", 3.0),
        ack_timeout=knob("TPU_SANDBOX_ACK_TIMEOUT", 60.0),
        agent_wait=knob("TPU_SANDBOX_AGENT_WAIT", 120.0),
    )


def run_host_agent(args, world_size):
    """Run ONE host agent of an --agents N job (the per-process entry the
    AgentLauncher spawns; also usable directly, one invocation per host,
    with --leader hosting the KV store on the first host)."""
    import sys

    from tpu_sandbox.runtime.host_agent import HostAgent
    from tpu_sandbox.runtime.kvstore import KVServer

    if args.agents < 1:
        raise SystemExit("--agent-id requires --agents N (the topology)")
    if not (0 <= args.agent_id < args.agents):
        raise SystemExit(
            f"--agent-id {args.agent_id} out of range for "
            f"--agents {args.agents}"
        )
    server = None
    if args.leader:
        # bind/token make the store reachable off-host: --kv-bind 0.0.0.0
        # + TPU_SANDBOX_KV_TOKEN in the env (KVServer/KVClient both read
        # it, so workers inherit the secret without a flag)
        server = KVServer(port=int(args.kv_port or 0), bind=args.kv_bind)
        print(f"[agent {args.agent_id}] hosting KV store on "
              f"{args.kv_bind}:{server.port}", flush=True)
        kv_port = server.port
    elif args.kv_port:
        kv_port = int(args.kv_port)
    else:
        raise SystemExit("--agent-id needs --kv-port (or --leader)")

    passthrough = _elastic_passthrough(args)

    def rank_cmd(gen, rank, coord_port):
        return [sys.executable, __file__, "--elastic-worker",
                "--port", str(coord_port), "--kv-port", str(kv_port),
                *passthrough, "--rank", str(rank)]

    cfg = _agent_config_from_env(args, world_size, kv_port)
    try:
        rc = HostAgent(cfg, rank_cmd).run()
    finally:
        if server is not None:
            server.stop()
    sys.exit(rc)


def spawn_elastic_agents(args, world_size):
    """Cross-host elastic topology, proven on one machine: an
    AgentLauncher (the cluster-scheduler stand-in) owns the KV store and
    spawns --agents N HostAgent processes; the agents elect a leader that
    drives generation lifecycle, and the launcher replaces any agent that
    dies (host replacement). See runtime/host_agent.py."""
    import sys

    from tpu_sandbox.runtime.host_agent import AgentLauncher

    _validate_fault_plan()
    if world_size < args.agents:
        raise SystemExit(
            f"world size {world_size} gives --agents {args.agents} "
            "nothing to run on some hosts (every agent owns >= 1 rank)"
        )
    if not args.ckpt_dir:
        print("note: --elastic without --ckpt-dir restarts from step 0 "
              "(pass --ckpt-dir/--ckpt-every to resume where the crash hit)")

    passthrough = _elastic_passthrough(args)

    def agent_cmd(aid, kv_port):
        return [sys.executable, __file__, "--elastic",
                "--agents", str(args.agents), "--agent-id", str(aid),
                "--kv-port", str(kv_port),
                "--max-restarts", str(args.max_restarts), *passthrough]

    rc = AgentLauncher(args.agents, agent_cmd).run()
    if rc:
        sys.exit(rc)


def run_cluster_pool(args, world_size):
    """Multi-tenant cluster mode: gang-schedule this training job through
    the durable queue of runtime/scheduler.py on a pool of --pool host
    slots. Same agent topology as --agents N, but admitted (and possibly
    queued or preempted) by the scheduler instead of launched directly —
    the entry point that exercises one mesh as one tenant of a shared
    pool."""
    import sys

    from tpu_sandbox.runtime.scheduler import ClusterScheduler, JobSpec

    _validate_fault_plan()
    agents = args.agents or 1
    if not args.ckpt_dir:
        print("note: --elastic without --ckpt-dir restarts from step 0 "
              "(pass --ckpt-dir/--ckpt-every to resume where the crash hit)")

    passthrough = _elastic_passthrough(args)
    job_id = args.job_id or "job0"
    spec = JobSpec(
        job_id=job_id,
        hosts=agents,
        world_size=world_size,
        agent_argv=[sys.executable, __file__, "--elastic",
                    "--agents", str(agents), "--agent-id", "{agent_id}",
                    "--kv-port", "{kv_port}", "--job-id", "{job_id}",
                    "--max-restarts", str(args.max_restarts), *passthrough],
        priority=args.priority,
    )
    with ClusterScheduler(args.pool) as sched:
        sched.submit(spec)
        states = sched.serve()
    state = states.get(job_id)
    print(f"[cluster] job {job_id!r} finished: {state}", flush=True)
    if state != "done":
        sys.exit(1)


def spawn_multiprocess(args, world_size):
    import subprocess
    import sys
    import time

    from tpu_sandbox.runtime.bootstrap import find_free_port

    if args.zero and args.opt == "sgd":
        _zero_sgd_note()  # workers suppress it; say it once from here

    if args.ckpt_dir or args.resume:
        # orbax multi-controller checkpointing needs coordinated commits;
        # refuse loudly rather than silently not saving
        raise SystemExit(
            "--ckpt-dir/--resume are not supported with --multiprocess yet; "
            "run the single-process engine (-g N) for checkpointed training"
        )
    from tpu_sandbox.runtime import Watchdog
    from tpu_sandbox.runtime.kvstore import KVClient, KVServer

    kv_server = KVServer()
    port = find_free_port()
    cmd_base = [sys.executable, __file__, "--worker", "--port", port,
                "--kv-port", str(kv_server.port)]
    passthrough = [
        "-n", str(args.nodes), "-g", str(args.gpus),
        "--epochs", str(args.epochs), "--batch-size", str(args.batch_size),
        "--image-size", str(args.image_size),
        "--synthetic-n", str(args.synthetic_n),
        "--log-every", str(args.log_every), "--dtype", args.dtype,
        "--plan", args.plan, "--opt", args.opt,
    ]
    if args.data_dir:
        passthrough += ["--data-dir", args.data_dir]
    if args.limit_steps:
        passthrough += ["--limit-steps", str(args.limit_steps)]
    if args.zero:
        passthrough += ["--zero"]
    if args.grad_compress != "none":
        passthrough += ["--grad-compress", args.grad_compress]
    if args.no_error_feedback:
        passthrough += ["--no-error-feedback"]
    if args.prefetch:
        passthrough += ["--prefetch"]
    procs = [
        subprocess.Popen(cmd_base + ["--rank", str(r)] + passthrough)
        for r in range(world_size)
    ]
    # health plane: workers heartbeat into our KV store; the watchdog
    # catches the wedged-not-dead case (a rank alive as a process but
    # silent for >60s — e.g. stuck in a collective whose peer vanished)
    # that exit-code polling alone can never see
    import os

    watchdog = Watchdog(
        KVClient(port=kv_server.port), world_size,
        timeout=float(os.environ.get("TPU_SANDBOX_WATCHDOG_TIMEOUT", 60.0)),
        grace=float(os.environ.get("TPU_SANDBOX_WATCHDOG_GRACE", 180.0)),
    )

    def _kill_all(reason: str):
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()  # survivor ignored SIGTERM (wedged collective)
                p.wait()
        kv_server.stop()
        raise SystemExit(
            f"{reason}; worker exit codes: {[p.poll() for p in procs]}"
        )

    # fail fast: a dead worker leaves its peers blocked in a collective, so
    # on the first nonzero exit kill the survivors (the reference's mp.spawn
    # does the same)
    codes = [None] * world_size
    while any(c is None for c in codes):
        for i, p in enumerate(procs):
            if codes[i] is None:
                codes[i] = p.poll()
        if any(c not in (None, 0) for c in codes):
            _kill_all("worker failure detected")
        # only ranks whose PROCESS is still running count: a cleanly-exited
        # rank deregisters its heartbeat and must not read as dead
        dead = [r for r in watchdog.dead_ranks() if codes[r] is None]
        if dead:
            _kill_all(f"watchdog: rank(s) {dead} stopped heartbeating")
        time.sleep(0.2)
    # loop exit <=> every worker finished with code 0
    kv_server.stop()


def build_parser():
    parser = argparse.ArgumentParser()
    parser.add_argument("-n", "--nodes", type=int, default=1, metavar="N",
                        help="number of hosts (parity flag; >1 uses jax.distributed)")
    parser.add_argument("-g", "--gpus", type=int, default=1,
                        help="number of devices (ranks) per node")
    parser.add_argument("-nr", "--nr", type=int, default=0,
                        help="ranking of this node (parity flag)")
    parser.add_argument("--epochs", type=int, default=2, metavar="N",
                        help="number of epochs")
    parser.add_argument("--batch-size", type=int, default=5,
                        help="per-rank batch size (reference :60-61)")
    parser.add_argument("--image-size", type=int, default=IMAGE_SHAPE[0])
    parser.add_argument("--data-dir", type=str, default=None)
    parser.add_argument("--synthetic-n", type=int, default=60000)
    parser.add_argument("--limit-steps", type=int, default=None)
    parser.add_argument("--log-every", type=int, default=100)
    parser.add_argument("--opt", choices=["sgd", "momentum", "adamw"],
                        default="sgd",
                        help="optimizer (default: the reference's plain "
                             "SGD 1e-4; momentum/adamw give --zero real "
                             "state to shard)")
    parser.add_argument("--zero", action="store_true",
                        help="ZeRO-1: shard optimizer state over the data "
                             "axis (same math, 1/N the optimizer memory)")
    parser.add_argument("--plan",
                        choices=["auto", "s2dt", "s2d", "plain"],
                        default="auto",
                        help="ConvNet execution plan: s2dt = transposed "
                             "space-to-depth (models/convnet_s2d_t.py), "
                             "s2d = NHWC space-to-depth "
                             "(models/convnet_s2d.py) - same function as "
                             "the plain net either way, tested; auto "
                             "picks s2dt on TPU when the image "
                             "size allows")
    parser.add_argument("--dtype", choices=["bf16", "fp32"], default="bf16")
    add_checkpoint_cli(parser)
    add_grad_compress_cli(parser)
    add_prefetch_cli(parser)
    parser.add_argument("--force-cpu", action="store_true",
                        help="use virtual CPU devices even if an accelerator is present")
    parser.add_argument("--multiprocess", action="store_true",
                        help="one OS process per rank over jax.distributed + "
                             "Gloo (the reference's actual topology)")
    add_elastic_cli(parser)
    parser.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--elastic-worker", action="store_true",
                        help=argparse.SUPPRESS)
    parser.add_argument("--rank", type=int, default=0, help=argparse.SUPPRESS)
    parser.add_argument("--port", type=str, default="", help=argparse.SUPPRESS)
    parser.add_argument("--kv-port", type=str, default="",
                        help=argparse.SUPPRESS)
    return parser


def main():
    from tpu_sandbox.runtime.bootstrap import configure_compile_cache

    configure_compile_cache()
    args = build_parser().parse_args()
    world_size = args.gpus * args.nodes  # reference :123
    if args.worker:
        train_multiprocess_worker(args, world_size)
    elif args.elastic_worker:
        train_elastic_worker(args, world_size)
    elif args.agent_id is not None:
        run_host_agent(args, world_size)
    elif args.elastic and args.pool:
        run_cluster_pool(args, world_size)
    elif args.elastic and args.agents:
        spawn_elastic_agents(args, world_size)
    elif args.elastic:
        spawn_elastic(args, world_size)
    elif args.multiprocess:
        spawn_multiprocess(args, world_size)
    else:
        train(args, world_size)


if __name__ == "__main__":
    main()
