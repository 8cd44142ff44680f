"""Transformer-LM training across every parallelism the framework ships.

The reference stops at a CNN + data parallelism (SURVEY §2.2: TP/PP/SP/EP
and attention "ABSENT"); this entry script is the showcase for the
capabilities the TPU build adds on top — the same decoder-only LM trained
under any of:

  dp    — DataParallel-equivalent via PjitEngine (batch sharded on 'data')
  tp    — tensor parallel: qkv/mlp kernels sharded on 'model'
  sp    — sequence parallel: ring attention over 'sp' (long context)
  pp    — pipeline parallel: GPipe microbatches over 'pipe'
  pp_sp — pipeline stages with the sequence sharded over 'sp' (ring or
          flash-ring attention inside every stage block)
  ep    — expert parallel: switch-MoE, expert weights sharded on 'expert'

Data is a deterministic synthetic character stream (zero egress): the task
is modular next-token prediction, which a small LM drives to near-zero loss
in a few hundred steps — enough signal to watch convergence per
parallelism. ``--flash`` swaps in the Pallas flash-attention kernel
(ops/pallas_attention.py); ``--remat`` wraps each block in jax.checkpoint
to trade FLOPs for activation memory at long sequence lengths.

Examples::

    python lm_train.py --parallelism dp --devices 4 --force-cpu
    python lm_train.py --parallelism sp --devices 8 --seq-len 1024
    python lm_train.py --parallelism tp --devices 4 --steps 100 --flash
"""

import argparse


def make_batches(vocab: int, batch: int, seq_len: int, steps: int, seed: int):
    """Deterministic synthetic LM stream: targets = (tokens + k) % vocab with
    position-dependent k — learnable by position embeddings + mixing."""
    import numpy as np

    rng = np.random.default_rng(seed)
    for _ in range(steps):
        tokens = rng.integers(0, vocab, size=(batch, seq_len), dtype=np.int32)
        shift = (np.arange(seq_len, dtype=np.int32) % 3) + 1
        targets = ((tokens + shift[None, :]) % vocab).astype(np.int32)
        yield tokens, targets


def _xing4(config: dict, **how):
    from tpu_sandbox.models import xing4

    return (xing4.Xing4LM(xing4.Xing4Config.from_dict(config, **how)),
            xing4.MTP_LOSS_WEIGHT)


def _nemotron_h(config: dict, **how):
    from tpu_sandbox.models import nemotron_h

    return (nemotron_h.NemotronHLM(
        nemotron_h.NemotronHConfig.from_dict(config, **how)),
        nemotron_h.MTP_LOSS_WEIGHT)


def _olmo_hybrid(config: dict, **how):
    from tpu_sandbox.models import olmo_hybrid

    return (olmo_hybrid.OlmoHybridLM(
        olmo_hybrid.OlmoHybridConfig.from_dict(config, **how)), 0.0)


#: ``--model`` name -> builder of the models described by a ``--config`` file
#: of published keys: ``(config, tokens_per_step=, dtype=, remat=, flash=)
#: -> (model, weight of the MTP loss)``. ``gpt2`` is ``TransformerLM`` from
#: the size flags, under every parallelism.
CONFIG_MODELS = {"xing4": _xing4, "nemotron_h": _nemotron_h,
                 "olmo_hybrid": _olmo_hybrid}


def build(args, devices):
    """Model, optimizer, state (on the mesh) and engine for ``args`` (a
    namespace of ``build_parser``) over ``devices``: everything ``train``
    needs before its loop, and what the benchmark's runner drives, so that
    a cell measures this construction and not a copy of it. ``--model
    <name> --config <json>`` builds a model of ``CONFIG_MODELS`` from the
    published keys of the file (dp only); the size flags then do not apply.
    A caller that has read the file already sets ``args.config`` to its
    content.

    The whole of it runs under the span ``setup:build``, the root of the
    launch's record: ``setup:model_init``, ``setup:opt_init`` and
    ``place:state`` are its children, and every program traced, lowered or
    compiled on the way is recorded under the innermost of them
    (``runtime/bootstrap.py``). The token stream is made by ``train``, so
    there is no ``setup:data`` here.
    """
    from tpu_sandbox.obs import get_recorder

    with get_recorder().span("setup:build", loop=True):
        return _build(args, devices)


def _build(args, devices):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
    from tpu_sandbox.parallel import (
        MoeMlp,
        PipelineParallel,
        PjitEngine,
        SeqParallel,
        megatron_rules,
    )
    from tpu_sandbox.runtime import bootstrap
    from tpu_sandbox.runtime.mesh import make_mesh
    from tpu_sandbox.train import TrainState

    bootstrap.init()
    n = len(devices)
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    attention_fn = None
    if args.flash:
        from tpu_sandbox.ops.pallas_attention import flash_attention_fn

        attention_fn = flash_attention_fn()

    from tpu_sandbox.ops.losses import _FUSED_CE_MIN_CLASSES

    cfg = TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff, max_len=args.seq_len,
        dtype=dtype, remat=args.remat,
        remat_policy=args.remat_policy,
        n_experts=(n if args.parallelism == "ep" else 0),
        router_top_k=args.router_top_k,
        # when the loss will run the fused Pallas CE (LM-scale vocab),
        # skip the fp32 logits round-trip — the kernel upcasts in VMEM
        fp32_logits=args.vocab < _FUSED_CE_MIN_CLASSES,
    )
    # schedule + clipping: the standard LM training kit. Cosine decay
    # warms up linearly for --warmup steps then decays to 10% of --lr over
    # the run; --clip-norm prepends global-norm clipping.
    if args.schedule == "cosine":
        if args.warmup > 0:
            lr = optax.warmup_cosine_decay_schedule(
                0.0, args.lr, warmup_steps=args.warmup,
                decay_steps=max(args.steps, args.warmup + 1),
                end_value=args.lr * 0.1,
            )
        else:  # no warmup: start at peak (a forced 1-step warmup would
            # make the first update run at lr == 0)
            lr = optax.cosine_decay_schedule(
                args.lr, decay_steps=max(args.steps, 1), alpha=0.1
            )
    else:
        lr = args.lr
    tx = optax.adam(lr)
    if args.clip_norm < 0:
        raise SystemExit(f"--clip-norm must be >= 0, got {args.clip_norm} "
                         "(negative max_norm would sign-flip every update)")
    if args.clip_norm:
        if args.parallelism in ("pp", "pp_sp", "3d"):
            # inside the pipeline's shard_map the 'stages' grads are
            # rank-local, so clip_by_global_norm would compute a DIFFERENT
            # norm per pipe rank and scale the replicated embed/head grads
            # inconsistently — silent divergence. Refuse until the engine
            # clips with a psum'd global norm.
            raise SystemExit(
                "--clip-norm is not supported with --parallelism "
                "pp/pp_sp/3d "
                "(per-stage norms would diverge); clip under dp/tp/sp/ep"
            )
        tx = optax.chain(optax.clip_by_global_norm(args.clip_norm), tx)
    rng = jax.random.key(args.seed)
    sample = jnp.zeros((1, args.seq_len), jnp.int32)

    p = args.parallelism
    model = None  # the sp / pp engines build theirs from ``cfg``
    if args.model in CONFIG_MODELS:
        if p != "dp" or not args.config:
            raise SystemExit(f"--model {args.model} needs --config <json> "
                             "and --parallelism dp")
        import json
        import types

        config = args.config
        if not isinstance(config, dict):
            with open(config) as f:
                config = json.load(f)
        model, mtp_weight = CONFIG_MODELS[args.model](
            config, tokens_per_step=args.batch * args.seq_len, dtype=dtype,
            remat=args.remat, flash=args.flash)
        mesh = make_mesh({"data": n}, devices=devices)
        # ``create`` runs ``init`` op by op, and these models have hundreds
        # of distinct operations: hand it the init as one compiled program.
        # Parameter shapes do not depend on the length: a short sample.
        state = TrainState.create(
            types.SimpleNamespace(init=jax.jit(model.init)), rng,
            sample[:, :min(args.seq_len, 128)], tx)
        eng = PjitEngine(model, tx, mesh, task="lm", mtp_weight=mtp_weight)
    elif p == "dp":
        mesh = make_mesh({"data": n}, devices=devices)
        model = TransformerLM(cfg, attention_fn=attention_fn)
        state = TrainState.create(model, rng, sample, tx)
        eng = PjitEngine(model, tx, mesh, task="lm")
    elif p == "tp":
        if args.dp < 1 or n % args.dp:
            raise SystemExit(f"--dp {args.dp} must be >= 1 and divide {n} devices")
        dp, m = args.dp, n // args.dp
        if args.n_heads % m or args.d_ff % m or args.vocab % m or args.d_model % m:
            raise SystemExit(
                f"tp shards heads, d_ff, vocab and d_model: --n-heads "
                f"{args.n_heads}, --d-ff {args.d_ff}, --vocab {args.vocab}, "
                f"--d-model {args.d_model} must be divisible by {m} "
                "model-parallel ranks"
            )
        # composes with data parallelism: batch sharded on 'data', kernels
        # (full Megatron set incl. out-proj, lm_head, embeddings) on 'model'
        mesh = make_mesh({"data": dp, "model": m}, devices=devices)
        model = TransformerLM(cfg, attention_fn=attention_fn)
        state = TrainState.create(model, rng, sample, tx)
        eng = PjitEngine(model, tx, mesh, task="lm", rules=megatron_rules())
    elif p == "sp":
        if n % 2:
            raise SystemExit("sp needs an even device count (data=2 x sp=n/2)")
        mesh = make_mesh({"data": 2, "sp": n // 2}, devices=devices)
        eng = SeqParallel(
            lambda attn: TransformerLM(cfg, attention_fn=attn), tx, mesh,
            attn=args.attn,
        )
        state = eng.init_state(rng, sample)
    elif p == "pp":
        if cfg.n_layers % n:
            raise SystemExit(f"pp needs n_layers divisible by {n} devices")
        mesh = make_mesh({"data": 1, "pipe": n}, devices=devices)
        eng = PipelineParallel(cfg, tx, mesh, microbatches=args.microbatches,
                               circular_chunks=args.circular_chunks,
                               attention_fn=attention_fn)
        state = eng.init_state(rng, sample)
    elif p == "pp_sp":
        # pipeline stages with the sequence sharded over 'sp' — ring (or
        # flash-ring) attention inside each stage block; the long-context
        # composition (activations ride the pipe as [mb, S/sp, D])
        if n % 4:
            raise SystemExit("pp_sp wants devices divisible by 4 "
                             "(mesh data=2 x pipe=2 x sp=n/4)")
        mesh = make_mesh({"data": 2, "pipe": 2, "sp": n // 4},
                         devices=devices)
        if cfg.n_layers % 2:
            raise SystemExit("pp_sp needs even n_layers (2 stages)")
        eng = PipelineParallel(
            cfg, tx, mesh, microbatches=args.microbatches,
            circular_chunks=args.circular_chunks, seq_axis="sp",
            seq_attn="flash_ring" if args.flash else "ring",
        )
        state = eng.init_state(rng, sample)
    elif p == "3d":
        # data x model x pipe: DP batch sharding, Megatron TP inside each
        # pipeline stage, GPipe microbatching across stages
        if n % 8:
            raise SystemExit("3d wants devices divisible by 8 (2x2x2 mesh)")
        shape = {"data": 2, "model": 2, "pipe": n // 4}
        if cfg.n_layers % shape["pipe"] or args.n_heads % 2 or args.d_ff % 2:
            raise SystemExit(
                f"3d at {n} devices needs n_layers % {shape['pipe']} == 0 "
                "and even --n-heads/--d-ff"
            )
        mesh = make_mesh(shape, devices=devices)
        eng = PipelineParallel(
            cfg, tx, mesh, microbatches=args.microbatches,
            model_axis="model", circular_chunks=args.circular_chunks,
            attention_fn=attention_fn,
        )
        state = eng.init_state(rng, sample)
    elif p == "ep":
        mesh = make_mesh({"data": 1, "expert": n}, devices=devices)
        model = TransformerLM(cfg, mlp_cls=MoeMlp, attention_fn=attention_fn)
        state = TrainState.create(model, rng, sample, tx)
        eng = PjitEngine(
            model, tx, mesh, task="lm",
            rules=[(r"w_(up|down)", P("expert", None, None))],
        )
    else:
        raise SystemExit(f"unknown parallelism {p!r}")

    return model, tx, eng.shard_state(state), eng


def train(args):
    from tpu_sandbox.utils.cli import ensure_devices

    devices = ensure_devices(args.devices, force_cpu=args.force_cpu)

    import datetime

    import numpy as np

    from tpu_sandbox.runtime import bootstrap
    from tpu_sandbox.train.trainer import LoopSpans

    model, _, state, eng = build(args, devices)
    p = args.parallelism
    vocab = (model.config.vocab_size if args.model in CONFIG_MODELS
             else args.vocab)
    start = datetime.datetime.now()
    losses = []
    spans = LoopSpans()  # the same four spans as Trainer's loop
    try:
        for step, (tokens, targets) in enumerate(spans.batches(
            make_batches(vocab, args.batch, args.seq_len, args.steps, 0)), 1
        ):
            with spans.dispatch():
                state, loss = eng.train_step(
                    state, *eng.shard_batch(tokens, targets))
            spans.returned(step)
            if step % args.log_every == 0 or step == args.steps:
                with spans.sync("log"):
                    loss_v = float(np.ravel(np.asarray(loss))[0])
                losses.append(loss_v)
                print(f"[{p}] Step [{step}/{args.steps}], Loss: {loss_v:.4f}",
                      flush=True)
    finally:
        spans.ended()
    spans.rec.flush()
    print(f"Training complete in: {datetime.datetime.now() - start}")
    if len(losses) >= 2 and not losses[-1] < losses[0]:
        raise SystemExit(f"loss did not decrease: {losses[0]} -> {losses[-1]}")
    bootstrap.cleanup()


def build_parser() -> argparse.ArgumentParser:
    """Single source of the CLI; tests parse_args([]) for complete
    defaulted Namespaces instead of hand-building partial ones."""
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--parallelism",
                        choices=["dp", "tp", "sp", "pp", "pp_sp", "ep", "3d"],
                        default="dp")
    parser.add_argument("--model", choices=["gpt2", *CONFIG_MODELS],
                        default="gpt2",
                        help="gpt2: TransformerLM from the size flags; the "
                             "others: the model of that name from --config")
    parser.add_argument("--config", default=None,
                        help="the published config.json keys of a --model "
                             "other than gpt2 (benchmark/configs/*.json)")
    parser.add_argument("--seed", type=int, default=0,
                        help="key of the weights' init")
    parser.add_argument("--dp", type=int, default=1,
                        help="tp only: data-parallel axis size composed "
                             "with model parallelism (devices = dp x tp)")
    parser.add_argument("--devices", type=int, default=1)
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--vocab", type=int, default=64)
    parser.add_argument("--d-model", type=int, default=64)
    parser.add_argument("--n-heads", type=int, default=4)
    parser.add_argument("--n-layers", type=int, default=2)
    parser.add_argument("--d-ff", type=int, default=128)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--schedule", choices=["const", "cosine"],
                        default="const")
    parser.add_argument("--warmup", type=int, default=0,
                        help="linear warmup steps (cosine schedule)")
    parser.add_argument("--clip-norm", type=float, default=0.0,
                        help="global-norm gradient clipping (0 = off)")
    parser.add_argument("--microbatches", type=int, default=2,
                        help="pp only: GPipe microbatches per step")
    parser.add_argument("--circular-chunks", type=int, default=1,
                        help="pp/3d: layer chunks per stage (v>1 = circular "
                             "schedule, bubble ~v x smaller)")
    parser.add_argument("--router-top-k", type=int, default=1,
                        help="ep only: 1 = Switch top-1, 2 = GShard top-2")
    parser.add_argument("--log-every", type=int, default=10)
    parser.add_argument("--dtype", choices=["bf16", "fp32"], default="fp32")
    parser.add_argument("--attn", choices=["ring", "ulysses", "flash_ring"],
                        default="ring",
                        help="sp only: jnp K/V ring, Ulysses all-to-all "
                             "head/seq swap, or the Pallas flash-ring")
    parser.add_argument("--flash", action="store_true",
                        help="use the Pallas flash-attention kernel")
    parser.add_argument("--remat-policy", choices=["full", "dots"],
                        default="full",
                        help="remat=full recomputes whole blocks (with "
                             "--flash the forward kernel runs twice); dots "
                             "saves matmul outputs (checkpoint_dots) and "
                             "the flash kernel's output and logsumexp, so "
                             "backward pays no extra MXU FLOPs")
    parser.add_argument("--remat", action="store_true",
                        help="jax.checkpoint each block (memory for FLOPs)")
    parser.add_argument("--force-cpu", action="store_true")
    return parser


def main():
    from tpu_sandbox.runtime.bootstrap import configure_compile_cache

    configure_compile_cache()
    train(build_parser().parse_args())


if __name__ == "__main__":
    main()
