"""Single-device big-image MNIST training — TPU-native rebuild of the
reference ``mnist_onegpu.py`` (same flags, same log lines, same experiment).

Reference behavior (mnist_onegpu.py:34-96): seed 0, ConvNet with a lazily
materialized ~180M-param head at 3000x3000, batch size 5 (bs=10 OOMs a 24GB
A5000 — the README's whole point), CE + SGD(1e-4), loss print every 100
steps, wall-clock total. Data is MNIST resized 28->3000 per image on the
host by PIL.

TPU-native shape: one jit'd train step does resize (on device), forward,
loss, backward, and SGD apply; there is no .cuda() staging, no dummy
forward (Flax init-by-tracing sizes the head), and the host feeds raw
28x28 bytes. Without local MNIST IDX files a deterministic synthetic
MNIST stands in (zero egress — see tpu_sandbox/data/mnist.py).
"""

import argparse

IMAGE_SHAPE = [3000, 3000]


def build(args):
    """Everything ``train`` runs, built but not run: (model, initial state,
    jitted train step, batch loader) for ``args``. ``chip_smoke.py`` drives
    these same objects, so the smoke and the script cannot drift apart."""
    import jax
    import jax.numpy as jnp
    import optax

    from tpu_sandbox.data import BatchLoader, load_mnist, synthetic_mnist
    from tpu_sandbox.data.mnist import normalize
    from tpu_sandbox.models import pick_convnet
    from tpu_sandbox.train import TrainState, make_train_step

    from tpu_sandbox.obs import get_recorder

    rec = get_recorder()
    if args.accum_steps < 1:
        raise SystemExit(f"--accum-steps must be >= 1, got {args.accum_steps}")
    if args.batch_size % args.accum_steps:
        raise SystemExit(
            f"--batch-size {args.batch_size} must be divisible by "
            f"--accum-steps {args.accum_steps}"
        )
    # the launch from inside: ``setup:build`` is the root, ``setup:data`` and
    # ``TrainState.create``'s ``setup:model_init`` / ``setup:opt_init`` are
    # its children, and every program traced, lowered or compiled on the way
    # is recorded under the innermost of them (``runtime/bootstrap.py``)
    with rec.span("setup:build", loop=True):
        rng = jax.random.key(0)  # parity: torch.manual_seed(0), reference :35
        image_shape = [args.image_size, args.image_size]
        dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
        model = pick_convnet(args.image_size, plan=args.plan,
                             num_classes=10, dtype=dtype)
        tx = optax.sgd(learning_rate=1e-4)  # reference :49, no momentum

        with rec.span("setup:data", hist="setup.data_s", loop=True):
            try:
                images, labels = load_mnist("train", args.data_dir)
            except FileNotFoundError:
                print("MNIST IDX files not found; using deterministic "
                      "synthetic MNIST")
                images, labels = synthetic_mnist(n=args.synthetic_n, seed=0)
            if args.limit_steps:
                images = images[: args.limit_steps * args.batch_size]
                labels = labels[: args.limit_steps * args.batch_size]

            # reference :55-59: shuffle=True, num_workers=0. --native-loader
            # swaps in the C++ worker-pool loader (gather+normalize off the
            # Python thread). accumulation needs every batch divisible into
            # microbatches: drop the ragged tail instead of crashing on it
            # at the end of an epoch
            drop_last = args.accum_steps > 1
            if args.native_loader:
                from tpu_sandbox.data.native_loader import NativeBatchLoader

                loader = NativeBatchLoader(
                    images, labels, args.batch_size, shuffle=True, seed=0,
                    threads=2, drop_last=drop_last,
                )
            else:
                loader = BatchLoader(
                    normalize(images), labels.astype("int32"),
                    args.batch_size, shuffle=True, seed=0,
                    drop_last=drop_last,
                )

        state = TrainState.create(
            model, rng, jnp.zeros([1, *image_shape, 1], dtype), tx
        )
        step = make_train_step(model, tx, image_size=tuple(image_shape),
                               accum_steps=args.accum_steps)
    return model, state, step, loader


def train(device_index, args):
    if args.force_cpu:
        from tpu_sandbox.utils.cli import ensure_devices

        ensure_devices(1, force_cpu=True)
    from tpu_sandbox.train import Trainer

    image_shape = [args.image_size, args.image_size]
    model, state, step, loader = build(args)
    if args.ckpt_dir and args.resume:
        from tpu_sandbox.train import checkpoint as ckpt

        if ckpt.latest_step(args.ckpt_dir) is not None:
            state = ckpt.restore(args.ckpt_dir, state)
            print(f"resumed from step {int(state.step)}")
    trainer = Trainer(step, log_every=args.log_every,
                      ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    import contextlib

    if args.profile:
        from tpu_sandbox.utils.profiling import trace

        profile_ctx = trace(args.profile)
    else:
        profile_ctx = contextlib.nullcontext()
    with profile_ctx:
        state = trainer.fit(state, loader, args.epochs)
    if args.profile:
        print(f"profiler trace written to {args.profile}")
    if args.eval:
        from tpu_sandbox.data import load_mnist, synthetic_mnist
        from tpu_sandbox.data.mnist import normalize
        from tpu_sandbox.train.trainer import make_eval_step

        try:
            eval_images, eval_labels = load_mnist("test", args.data_dir)
        except FileNotFoundError:
            eval_images, eval_labels = synthetic_mnist(n=2000, seed=1)
        eval_images = normalize(eval_images)
        eval_labels = eval_labels.astype("int32")
        eval_step = make_eval_step(model, image_size=tuple(image_shape))
        ebs = min(args.batch_size, len(eval_images))
        correct = total = batches = 0
        loss_sum = 0.0
        for i in range(0, len(eval_images) - ebs + 1, ebs):
            c, l = eval_step(state, eval_images[i:i + ebs],
                             eval_labels[i:i + ebs])
            correct += int(c)
            loss_sum += float(l)
            total += ebs
            batches += 1
        if total:
            print(f"Eval: accuracy {correct}/{total} = {correct / total:.4f}, "
                  f"mean loss {loss_sum / batches:.4f}")
        else:
            print("Eval: no test data available, skipped")
    if args.ckpt_dir:
        from tpu_sandbox.train import checkpoint as ckpt

        print(f"saved checkpoint at step {ckpt.save(args.ckpt_dir, state)}")


def build_parser():
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", type=int, default=2, help="number of epochs")
    parser.add_argument("--batch-size", type=int, default=5,
                        help="reference :45 — bs=10 OOMs one 24GB GPU")
    parser.add_argument("--image-size", type=int, default=IMAGE_SHAPE[0])
    parser.add_argument("--data-dir", type=str, default=None,
                        help="directory with MNIST IDX files; synthetic fallback otherwise")
    parser.add_argument("--synthetic-n", type=int, default=60000)
    parser.add_argument("--limit-steps", type=int, default=None,
                        help="cap steps per epoch (quick runs)")
    parser.add_argument("--log-every", type=int, default=100)
    parser.add_argument("--accum-steps", type=int, default=1,
                        help="gradient accumulation: split each batch into k "
                             "sequential microbatches (OOM workaround on ONE "
                             "device — the counterpart of the reference's "
                             "DDP batch split, README OOM experiment)")
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
    parser.add_argument("--dtype", choices=["bf16", "fp32"], default="bf16",
                        help="compute dtype; params and loss stay fp32")
    parser.add_argument("--native-loader", action="store_true",
                        help="use the C++ prefetching data loader")
    parser.add_argument("--ckpt-every", type=int, default=0, metavar="N",
                        help="with --ckpt-dir: also save every N steps "
                             "(crash recovery), not just at the end")
    parser.add_argument("--ckpt-dir", type=str, default=None,
                        help="save a checkpoint here after training")
    parser.add_argument("--profile", type=str, default=None, metavar="DIR",
                        help="capture a jax.profiler trace of training into "
                             "DIR (view in TensorBoard/Perfetto): device ops "
                             "on the /device:TPU:N planes and, on the same "
                             "clock, the program's own spans (train:next_batch"
                             ", train:dispatch, train:sync; setup:build with "
                             "setup:data, setup:model_init, setup:opt_init; "
                             "trace:kernel at every Pallas call site) on "
                             "/host:CPU. With TPU_SANDBOX_TRACE_DIR set the "
                             "same spans, every program's compile:trace / "
                             "compile:lower / compile:backend and any "
                             "compile:in_loop go to a JSONL log")
    parser.add_argument("--eval", action="store_true",
                        help="report test-set accuracy after training")
    parser.add_argument("--resume", action="store_true",
                        help="restore the latest checkpoint from --ckpt-dir first")
    parser.add_argument("--force-cpu", action="store_true",
                        help="run on the CPU backend even if an accelerator is present")
    return parser


def main():
    from tpu_sandbox.runtime.bootstrap import configure_compile_cache

    configure_compile_cache()
    train(0, build_parser().parse_args())


if __name__ == "__main__":
    main()
