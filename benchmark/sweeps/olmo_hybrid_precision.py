"""The second reading of the limits in ``reference/olmo_hybrid.py::TOLERANCE``:
the plain reference computed one precision below the program's bf16 --
every product's operands rounded to float8 (e4m3), products and everything
else in float32 -- against the same reference in float32, at the cell's
shapes, through the very comparison that decides ``correct``. It has to
come out as not correct. Outside the benchmark; needs the chip.

    python3 benchmark/sweeps/olmo_hybrid_precision.py --seed 11

Writes ``chiprun_out/olmo_hybrid_precision.json``.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CELL = "olmoh_train_s8192"


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    args = parser.parse_args()

    from benchmark.lib import manifest, traffic
    from tpu_sandbox.runtime.bootstrap import configure_compile_cache

    configure_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    cell = manifest.cell(CELL)
    runner = manifest.module("runners", cell["runner"])
    reference = manifest.module("reference", cell["reference"])
    model, _, state, _ = runner.build(cell, args.seed, jax.devices()[:1])
    jax.tree.map(lambda x: x.delete(), state.opt_state)
    tokens, targets = next(traffic.token_batches(
        cell["traffic"], args.seed + 1, cell["config"]["vocab_size"]))
    tree = reference.from_program_tree(state.params)
    wanted = list(runner.gradients(model.config.layer_types))
    results = {}
    for name, dtype in (("float32", None), ("float8_e4m3", jnp.float8_e4m3fn)):
        loss, logits, grads = reference.loss_and_grads(
            tree, tokens, targets, {**cell["config"], "matmul_dtype": dtype},
            wanted, **runner.reference_hooks(cell["deployment"]))
        results[name] = {"logits": np.asarray(logits), "loss": float(loss),
                         "grads": {k: np.asarray(v) for k, v in grads.items()}}
        del logits, grads
    dev, bad = reference.compare(results["float8_e4m3"], results["float32"])
    out = {"cell": CELL, "seed": args.seed, "deviation": dev, "broken": bad,
           "tolerance": reference.TOLERANCE}
    path = ROOT / "chiprun_out" / "olmo_hybrid_precision.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
