"""Chipless rehearsal of a serving cell's programs: compile the decode step
and every prefill bucket that ``serve.build_decode_step`` would build for a
``*_serve_decode_replay`` cell (any of the four families), for one described v5e chip
with the local libtpu, and print the compiler's memory count of each beside
what the engine keeps live (weights, pages, slot state), how many
instructions of the compiled program copy a layer's pages or more
(``count_page_copies``), how many Pallas kernels it calls (``pallas_calls``:
the decode program's paged attention, one a layer where the shape rule of
``serve/decode.py::pages_per_step`` takes the pages, and which form each
site took, ``paged_attn``; and what the sites of a program's trace counted
of their choices, label for label: ``paged_attn.kernel_choice``,
``mla.cache_layout`` -- the latent cache's row: its lanes and the padding
among them --, ``moe.share_table`` -- an expert share's table, buffer rows
and row tile a call size), its temporaries and its serialized size. What
decides, before any chip time is spent, whether the cell fits the chip's
15.75 GB, whether the page buffers are laid out unpadded (the arguments
beside the live bytes), whether a step moves the pool or only the rows it
touches, and whether the kernel engaged (``tools/aot_lm_step.py`` does the
first for a training step).

    python tools/aot_serve_step.py --workload gpt2m_serve_decode_replay \\
        [--programs decode 512] [--hlo DIR]

The numbers are COMPILER estimates, labelled as such; a time, a rate or a
utilisation comes only from a run on the chip. Run one AOT tool at a time.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def count_page_copies(text: str, layer_elements: int,
                      pool_elements: int) -> dict:
    """Of a compiled program's ``text``, the instructions that move pages
    wholesale: the halves of rematerialisation's compression by name
    (``remat_compressed``, ``remat_uncompressed``: a buffer re-laid out and
    back), the ``copy`` instructions (and fusions the compiler named for
    the copy they hold) whose result has a layer's pages' elements
    (``layer_elements``) or more, and every instruction whose result has
    the whole K or V pool's (``pool_elements``) or more. A program that
    updates its donated pages in place reads 0 in all four."""
    from tools.hlo_traffic import _NO_TRAFFIC, _SHAPE, instructions

    found = {"remat_compressed": 0, "remat_uncompressed": 0,
             "page_copies": 0, "pool_sized": 0}
    for name, shape, op, _, _ in instructions(text):
        if op in _NO_TRAFFIC:  # these pass their operands' buffers on
            continue
        largest = max((math.prod(int(d) for d in dims.split(",") if d)
                       for _, dims, _ in _SHAPE.findall(shape)), default=0)
        for half in ("remat_compressed", "remat_uncompressed"):
            found[half] += half in name.split(".")
        found["page_copies"] += largest >= layer_elements and (
            op == "copy" or name.startswith("copy"))
        found["pool_sized"] += largest >= pool_elements
    return found


def gpt2_serve_config(config: dict, dep: dict):
    """A GPT-2 serving cell's ``ServeConfig`` from its own files, as
    ``benchmark/runners/lm_serve.py::build`` makes it (that runner has no
    function that gives it without the engine)."""
    import jax.numpy as jnp

    from tpu_sandbox.models.transformer import TransformerConfig
    from tpu_sandbox.serve import CacheConfig, ServeConfig

    types = {"bf16": jnp.bfloat16}
    mcfg = TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_len=config["n_positions"],
        dtype=types.get(dep["dtype"], jnp.float32))
    cache = CacheConfig(num_blocks=dep["num_blocks"],
                        block_size=dep["block_size"],
                        max_blocks_per_seq=dep["max_blocks_per_seq"])
    return ServeConfig(model=mcfg, cache=cache, max_batch=dep["max_batch"],
                       buckets=tuple(dep["prefill_buckets"]),
                       cache_dtype=types.get(dep["cache_dtype"], jnp.float32),
                       eos_token=None)


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
    from jax.experimental.serialize_executable import serialize
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import manifest
    from tpu_sandbox.serve.decode import lower_step

    cell = manifest.cell(args.workload)
    runner = manifest.module("runners", cell["runner"])
    scfg = getattr(runner, "serve_config", gpt2_serve_config)(
        cell["config"], cell["deployment"])
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    params, held, lower = lower_step(
        scfg.model, scfg.cache, max_batch=scfg.max_batch,
        cache_dtype=scfg.cache_dtype, placed=on_chip)

    def gb(tree) -> float:
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(tree)) / 1e9

    k_pages = held[0]
    live = {"weights_gb": gb(params), "pages_gb": gb(held[:2]),
            "slot_state_gb": gb(held[2:]),
            "page_buffers": [len(k_pages), list(k_pages[0].shape),
                             str(k_pages[0].dtype)],
            "parameters": sum(x.size for x in jax.tree.leaves(params))}
    live["live_gb"] = (live["weights_gb"] + live["pages_gb"]
                       + live["slot_state_gb"])
    print(json.dumps({"workload": args.workload, **live}), flush=True)

    def choices() -> dict:
        """Every count so far of the choice counters a serving program's
        sites keep, by its labelled key."""
        from tpu_sandbox.obs import get_registry

        return {key: n for key, n in
                get_registry().snapshot()["counters"].items()
                if key.startswith(("paged_attn.kernel_choice",
                                   "mla.cache_layout", "moe.share_table"))}

    wanted = args.programs or ["decode", *map(str, scfg.buckets)]
    for name in wanted:
        t0 = time.perf_counter()
        chosen_before = choices()
        compiled = lower(None if name == "decode" else int(name)).compile()
        chosen = {key: n - chosen_before.get(key, 0)
                  for key, n in choices().items()
                  if n != chosen_before.get(key, 0)}
        # the form every attention site of this program's trace took
        sites: dict = {}
        for key, n in chosen.items():
            if key.startswith("paged_attn.kernel_choice"):
                impl = re.search(r"impl=(\w+)", key).group(1)
                sites[impl] = sites.get(impl, 0) + n
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
                # what the arguments' layouts add to the live bytes (the
                # few small inputs aside): a page buffer padded to the
                # chip's tiles shows here
                "arguments_over_live_gb":
                    mem.argument_size_in_bytes / 1e9 - live["live_gb"],
                "temporaries_gb": mem.temp_size_in_bytes / 1e9,
                "aliased_gb": mem.alias_size_in_bytes / 1e9,
                "total_gb": total / 1e9, "chip_gb": 15.75},
            # (the smallest layer's: a window layer's pool is the smaller)
            "moves_of_pages": count_page_copies(
                text, min(p.size for p in k_pages),
                sum(p.size for p in k_pages)),
            # the Mosaic kernels the program calls, and the form every
            # attention site of this program's trace took
            "pallas_calls": text.count('custom_call_target="tpu_custom_call"'),
            "paged_attn": sites,
            "choices": chosen,
            "program_text_mb": len(text) / 1e6,
            "executable_mb": executable_mb,
            "compile_s": round(time.perf_counter() - t0, 1)}), flush=True)


if __name__ == "__main__":
    main()
