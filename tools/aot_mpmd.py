"""Chipless AOT receipt for the MPMD pipeline: per-stage executables.

The SPMD pipeline compiles ONE program that every pipe rank executes.
The MPMD claim is the opposite — each stage gang compiles ONLY its own
program — and this tool is the receipt: it AOT-compiles every stage's
train programs for a v5e topology (no TPU needed,
jax.experimental.topologies) and reads XLA's own numbers per stage:

- stage 0's executables carry the embedding table and no LM head; the
  last stage's the reverse; interior stages carry neither — visible in
  per-stage ``param_bytes`` and the has_embedding/has_head flags;
- per-program argument/output/temp bytes and FLOPs, which is what a
  per-stage mesh actually holds and executes (the whole point of MPMD:
  no stage pays memory or compile time for another stage's layers).

Usage:
  python tools/aot_mpmd.py                        # default geometry
  python tools/aot_mpmd.py --n-stages 8 --n-layers 32
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.aot_v5e import make_topology  # noqa: E402


def mpmd_aot_report(*, n_stages: int = 4, microbatches: int = 4,
                    vocab_size: int = 8192, d_model: int = 256,
                    n_layers: int = 8, n_heads: int = 8, d_ff: int = 1024,
                    batch: int = 32, seqlen: int = 128,
                    layer_split: list[int] | None = None,
                    zb: bool = False) -> dict:
    """Compile every stage's programs chiplessly; returns the receipt.

    ``layer_split`` compiles an uneven pipeline (per-stage layer counts);
    ``zb`` lowers the ZB-H1 split backward (bwd_input / bwd_weight as
    separate executables) instead of the fused one, so the receipt shows
    what each half actually costs — the numbers ``schedule.autotune_plan``
    trades against."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
    from tpu_sandbox.mpmd.program import (
        StageProgram,
        check_layer_split,
        stage_params,
    )
    from tpu_sandbox.mpmd.schedule import bubble_fraction

    topo = make_topology()
    cfg = TransformerConfig(vocab_size=vocab_size, d_model=d_model,
                            n_heads=n_heads, n_layers=n_layers, d_ff=d_ff,
                            max_len=max(seqlen, 128))
    # a real (tiny, CPU) init supplies the per-stage param trees; only
    # shapes reach the chipless compile below
    flat = jax.tree.map(
        np.asarray,
        TransformerLM(cfg).init(jax.random.key(0),
                                jnp.zeros((1, seqlen), jnp.int32))["params"])
    tx = optax.sgd(0.1)
    mb_rows = max(1, batch // microbatches)
    # one single-chip mesh PER STAGE — the chipless twin of one mesh per
    # stage gang; every stage's programs are compiled against its own
    mesh = Mesh(np.array(topo.devices), ("stage",))
    sh = NamedSharding(mesh, P())

    def sharded_like(x):
        return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                    sharding=sh)

    split = check_layer_split(n_layers, n_stages, layer_split)
    stages = []
    for s in range(n_stages):
        prog = StageProgram(cfg, tx, s, n_stages, microbatches,
                            layer_split=layer_split)
        sp = stage_params(flat, s, n_stages, layer_split=layer_split)
        absp = jax.tree.map(sharded_like, sp)
        if prog.is_first:
            x = jax.ShapeDtypeStruct((mb_rows, seqlen), jnp.int32,
                                     sharding=sh)
        else:
            x = jax.ShapeDtypeStruct((mb_rows, seqlen, d_model), cfg.dtype,
                                     sharding=sh)
        targets = jax.ShapeDtypeStruct((mb_rows, seqlen), jnp.int32,
                                       sharding=sh)
        lowered = prog.lower_train_programs(
            absp, x, targets if prog.is_last else None, zb=zb)
        programs = {}
        for name, low in lowered.items():
            compiled = low.compile()
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis()
            programs[name] = {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "flops": ca.get("flops"),
            }
        param_bytes = sum(
            int(np.asarray(leaf).nbytes) for leaf in jax.tree.leaves(sp))
        stages.append({
            "stage": s,
            "layers_local": split[s],
            "param_bytes": param_bytes,
            "has_embedding": "pre" in sp,
            "has_head": "post" in sp,
            "programs": programs,
        })

    return {
        "metric": "mpmd_aot_stages",
        "geometry": {
            "n_stages": n_stages, "microbatches": microbatches,
            "vocab_size": vocab_size, "d_model": d_model,
            "n_layers": n_layers, "n_heads": n_heads, "d_ff": d_ff,
            "batch": batch, "seqlen": seqlen,
            "layer_split": split, "zb": zb,
        },
        "bubble_fraction": bubble_fraction(n_stages, microbatches),
        "stages": stages,
        # the MPMD claim, checked from XLA's own accounting: embedding
        # weight lives in stage 0's executable only, the head in the
        # last stage's only — no stage compiles another stage's program
        "only_first_stage_has_embedding": all(
            r["has_embedding"] == (r["stage"] == 0) for r in stages),
        "only_last_stage_has_head": all(
            r["has_head"] == (r["stage"] == n_stages - 1) for r in stages),
        "source": "chipless v5e AOT compile of each stage's own programs "
                  "(XLA estimates, not measurements)",
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--n-stages", type=int, default=4)
    p.add_argument("--microbatches", type=int, default=4)
    p.add_argument("--vocab-size", type=int, default=8192)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-layers", type=int, default=8)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--d-ff", type=int, default=1024)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--seqlen", type=int, default=128)
    p.add_argument("--layer-split", default="",
                   help="json list of per-stage layer counts, e.g. [3,3,2]")
    p.add_argument("--zb", action="store_true",
                   help="lower the ZB-H1 split backward "
                   "(bwd_input/bwd_weight) instead of the fused one")
    args = p.parse_args()
    print(json.dumps(mpmd_aot_report(
        n_stages=args.n_stages, microbatches=args.microbatches,
        vocab_size=args.vocab_size, d_model=args.d_model,
        n_layers=args.n_layers, n_heads=args.n_heads, d_ff=args.d_ff,
        batch=args.batch, seqlen=args.seqlen,
        layer_split=(json.loads(args.layer_split)
                     if args.layer_split else None),
        zb=args.zb)))


if __name__ == "__main__":
    main()
