"""Chipless rehearsal of a config-built LM's train step: compile the step
that ``lm_train.build`` would run (``PjitEngine(task="lm")``, bf16, ``remat``,
flash, ``adam``) for one described v5e chip with the local libtpu, and print
the compiler's memory count. What decides whether a cut fits the chip's
15.75 GB before any chip time is spent (PR 38: 17.16 GB -> 14.50 GB in four
rehearsals).

    python tools/aot_lm_step.py --model olmo_hybrid \\
        --config benchmark/configs/olmo-hybrid-7b.json --seq-len 8192

The numbers are COMPILER estimates, labelled as such; a time, a rate or a
utilisation comes only from a run on the chip. Run one AOT tool at a time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--model", required=True)
    parser.add_argument("--config", required=True)
    parser.add_argument("--batch", type=int, default=1)
    parser.add_argument("--seq-len", type=int, default=8192)
    parser.add_argument("--hlo", default=None,
                        help="write the compiled program's text here")
    args = parser.parse_args()

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from tools.aot_v5e import make_topology

    topo = make_topology()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import lm_train
    from tpu_sandbox.parallel import PjitEngine
    from tpu_sandbox.train import TrainState

    with open(args.config) as f:
        config = json.load(f)
    model, mtp_weight = lm_train.CONFIG_MODELS[args.model](
        config, tokens_per_step=args.batch * args.seq_len, dtype=jnp.bfloat16,
        remat=True, flash=True)
    tx = optax.adam(3e-4)
    mesh = Mesh(np.array(topo.devices).reshape(1), ("data",))

    def create(key):
        variables = model.init(key, jnp.zeros((1, 128), jnp.int32))
        return TrainState(step=jnp.zeros((), jnp.int32),
                          params=variables["params"],
                          batch_stats=variables.get("batch_stats", {}),
                          opt_state=tx.init(variables["params"]))

    abstract = jax.eval_shape(create, jax.random.key(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(abstract.params))
    whole = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=whole),
        abstract)
    tokens = jax.ShapeDtypeStruct((args.batch, args.seq_len), jnp.int32,
                                  sharding=NamedSharding(mesh, P("data")))
    t0 = time.perf_counter()
    eng = PjitEngine(model, tx, mesh, task="lm", mtp_weight=mtp_weight)
    compiled = eng.lower_step(state, tokens, tokens).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(compiled.as_text())
    print(json.dumps({
        "model": args.model, "parameters": count,
        "state_gb_at_12_bytes": count * 12 / 1e9,
        "compiler_estimate": {
            "arguments_gb": mem.argument_size_in_bytes / 1e9,
            "temporaries_gb": mem.temp_size_in_bytes / 1e9,
            "total_gb": total / 1e9, "chip_gb": 15.75},
        "compile_s": round(time.perf_counter() - t0, 1)}))


if __name__ == "__main__":
    main()
