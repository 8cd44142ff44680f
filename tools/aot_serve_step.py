"""Chipless rehearsal of a recurrent model's serving programs: compile the
decode step and every prefill bucket that ``serve.build_decode_step`` would
build for a ``jamba_serve_replay`` cell, for one described v5e chip with the
local libtpu, and print the compiler's memory count of each beside what the
engine keeps live (weights, pages, slot state). What decides whether the
cell fits the chip's 15.75 GB before any chip time is spent
(``tools/aot_lm_step.py`` does the same for a training step).

    python tools/aot_serve_step.py --workload jamba2_serve_decode_replay \\
        [--programs decode 512] [--hlo DIR]

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
    parser.add_argument("--workload", required=True)
    parser.add_argument("--programs", nargs="*", default=None,
                        help="'decode' and bucket lengths; default: all")
    parser.add_argument("--hlo", default=None,
                        help="write each compiled program's text into this "
                        "directory")
    args = parser.parse_args()

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from tools.aot_v5e import make_topology

    topo = make_topology()
    import jax
    import jax.numpy as jnp
    from jax.experimental.serialize_executable import serialize
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import manifest
    from tpu_sandbox.models.jamba import JambaLM
    from tpu_sandbox.serve.decode import (jamba_buffer_shapes,
                                          make_jamba_decode_fn,
                                          make_jamba_prefill_fn)

    cell = manifest.cell(args.workload)
    runner = manifest.module("runners", cell["runner"])
    scfg = runner.serve_config(cell["config"], cell["deployment"])
    mcfg, ccfg = scfg.model, scfg.cache
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    params = on_chip(jax.eval_shape(
        lambda: JambaLM(mcfg).init(jax.random.key(0),
                                   jnp.zeros((1, 8), jnp.int32))["params"]))
    held = on_chip(jamba_buffer_shapes(mcfg, ccfg, scfg.max_batch,
                                       scfg.cache_dtype))

    def gb(tree) -> float:
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(tree)) / 1e9

    live = {"weights_gb": gb(params), "pages_gb": gb(held[:2]),
            "slot_state_gb": gb(held[2]),
            "parameters": sum(x.size for x in jax.tree.leaves(params))}
    live["live_gb"] = (live["weights_gb"] + live["pages_gb"]
                       + live["slot_state_gb"])
    print(json.dumps({"workload": args.workload, **live}), flush=True)

    wanted = args.programs or ["decode", *map(str, scfg.buckets)]
    for name in wanted:
        t0 = time.perf_counter()
        if name == "decode":
            lowered = make_jamba_decode_fn(mcfg, ccfg).lower(
                params, *held, ints(scfg.max_batch, 1), ints(scfg.max_batch),
                ints(scfg.max_batch, ccfg.max_blocks_per_seq))
        else:
            b = int(name)
            lowered = make_jamba_prefill_fn(mcfg).lower(
                params, *held, ints(1, b), ints(b), ints(), ints())
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        text = compiled.as_text()
        # what the persistent compile cache would keep of it
        executable_mb = len(serialize(compiled)[0]) / 1e6
        if args.hlo:
            os.makedirs(args.hlo, exist_ok=True)
            with open(os.path.join(args.hlo, f"{name}.hlo.txt"), "w") as f:
                f.write(text)
        print(json.dumps({
            "program": name,
            "compiler_estimate": {
                "arguments_gb": mem.argument_size_in_bytes / 1e9,
                "temporaries_gb": mem.temp_size_in_bytes / 1e9,
                "aliased_gb": mem.alias_size_in_bytes / 1e9,
                "total_gb": total / 1e9, "chip_gb": 15.75},
            "program_text_mb": len(text) / 1e6,
            "executable_mb": executable_mb,
            "compile_s": round(time.perf_counter() - t0, 1)}), flush=True)


if __name__ == "__main__":
    main()
