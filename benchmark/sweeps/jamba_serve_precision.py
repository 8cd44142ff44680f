"""Both readings of the two limits of ``reference/jamba.py::TOLERANCE`` that
decide ``correct`` in ``jamba2_serve_decode_replay`` (``chosen_gap_rel``,
``chosen_logprob_abs``), in one process, a seed after another:

- the **program**: the cell's own run (``runners/jamba_serve_replay``: 128
  sessions prefilled, the window's steps, ``verify``), whose deviations are
  the lower reading;
- two **controls**, each the plain reference put in the program's place one
  precision below what the configuration states, over the same prompts and
  served tokens -- ``float8``: both operands of every matrix product
  rounded to float8 (e4m3) where the program multiplies in bfloat16;
  ``state_bf16``: the scan's state rounded to bfloat16 after every token
  where the configuration states float32 -- all else float32. A control
  does not decode: at every position that chose a served token it puts its
  own first choice, whose gap under the float32 reference's best, and whose
  log-probability against the float32 reference's, go through the very
  comparison that decides ``correct``, as do its own slow states against
  the float32 reference's. Each has to come out as not correct.
- ``--program-state bf16`` runs the **program itself** with its slot state
  (prefill's scan and decode's step) in bfloat16 on the seeds that follow
  the others: what the cell's own ``correct`` says of a deployment that
  keeps the state narrower than the configuration states.

    python3 benchmark/sweeps/jamba_serve_precision.py --seeds 11 12 13 \\
        --control 2 --program-state-seeds 14

Outside the benchmark; needs the chip. Writes
``chiprun_out/jamba_serve_precision.json``. ``--tiny`` rehearses on the CPU
at ``tests/benchmark``'s tiny size.
"""

import argparse
import copy
import functools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CELL = "jamba2_serve_decode_replay"
TINY = {"config": {"vocab_size": 256, "hidden_size": 64,
                   "num_hidden_layers": 6, "attn_layer_offset": 1,
                   "attn_layer_period": 3, "intermediate_size": 128,
                   "num_attention_heads": 4, "num_key_value_heads": 1,
                   "mamba_dt_rank": 8},
        "deployment": {"dtype": "fp32", "param_dtype": "fp32",
                       "cache_dtype": "fp32", "max_batch": 4, "block_size": 4,
                       "max_blocks_per_seq": 16, "num_blocks": 65,
                       "prefill_buckets": [8, 16, 32, 48], "flash": False,
                       "scan_chunk": 8, "reference_sessions": 3,
                       "reference_pad": 16},
        "traffic": {"sessions": 4, "max_new_tokens": 16,
                    "prompt_len": {"dist": "uniform", "min": 5, "max": 40}}}


def controls():
    import jax.numpy as jnp

    return {"float8": {"matmul_dtype": jnp.float8_e4m3fn},
            "state_bf16": {"state_dtype": jnp.bfloat16}}


def control(reference, runner, tree, batch: dict, config: dict, pad: int,
            **precision):
    """The reference at a lower precision in the program's place:
    ``(deviations, limits broken)`` as ``reference.compare_served`` gives
    them."""
    import numpy as np

    low = runner.reference_rows(reference, tree, batch, config, pad=pad,
                                **precision)
    first = low["argmax"].astype(np.int32)
    ref = runner.reference_rows(reference, tree, batch, config, chosen=first,
                                pad=pad)
    valid = np.arange(first.shape[1])[None, :] < batch["counts"][:, None]
    system = (np.where(valid, low["argmax_logprob"], 0.0).sum(axis=1)
              / batch["counts"])
    return reference.compare_served(ref["gap_rel"], ref["logprob"],
                                    batch["counts"], system,
                                    ref["slow_state"], low["slow_state"])


def run_cell(cell: dict, seed: int, seconds: float):
    """One run of the cell as ``run.py`` drives it, without its trace."""
    import jax

    from benchmark.lib import manifest
    from benchmark.lib.observe import Observations

    obs = Observations(cell=cell, seed=seed, seconds=seconds, traced=False,
                       device_kind=jax.devices()[0].device_kind)
    runner = manifest.module("runners", cell["runner"])
    session = runner.setup(obs)
    runner.measure(obs, session, seconds)
    runner.finish(obs, session)
    runner.verify(obs, session)
    return obs, session


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seeds", type=int, nargs="*", default=[])
    parser.add_argument("--control", type=int, default=2,
                        help="run both controls on the first N seeds")
    parser.add_argument("--program-state-seeds", type=int, nargs="*",
                        default=[], help="seeds of the program's own run "
                        "with a bfloat16 slot state")
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()

    from benchmark.lib import manifest
    from tpu_sandbox.runtime.bootstrap import configure_compile_cache
    from tpu_sandbox.serve import decode

    configure_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu" and not args.tiny:
        raise SystemExit("needs a TPU")
    # one set of compiled programs serves every seed of a state type
    decode.build_decode_step = functools.cache(decode.build_decode_step)
    cell = manifest.cell(CELL)
    if args.tiny:
        for part, values in TINY.items():
            cell[part].update(values)
    pad = int(cell["deployment"].get("reference_pad", 1024))
    runner = manifest.module("runners", cell["runner"])
    reference = manifest.module("reference", cell["reference"])
    rows = []
    for k, seed in enumerate(args.seeds):
        obs, session = run_cell(cell, seed, args.seconds)
        row = {"seed": seed, "steps": session.steps,
               "compared_tokens": obs.notes.get("compared_tokens"),
               "program": obs.notes.get("reference_deviation"),
               "problems": obs.problems}
        if k < args.control and session.batch is not None:
            tree = reference.from_program_tree(session.params, cell["config"])
            for name, precision in controls().items():
                dev, bad = control(reference, runner, tree, session.batch,
                                   cell["config"], pad, **precision)
                row[f"control_{name}"] = dev
                row[f"control_{name}_broken"] = bad
            del tree
        del session  # it holds the seed's weights: the next needs the room
        rows.append(row)
        print(json.dumps(row), flush=True)
    narrow = copy.deepcopy(cell)
    narrow["deployment"]["state_dtype"] = "bf16"
    for seed in args.program_state_seeds:
        obs, session = run_cell(narrow, seed, args.seconds)
        row = {"seed": seed, "steps": session.steps,
               "program_state_bf16": obs.notes.get("reference_deviation"),
               "correct": not obs.problems, "problems": obs.problems}
        del session
        rows.append(row)
        print(json.dumps(row), flush=True)
    readings = {}
    for name in reference.TOLERANCE:
        def least(key):
            return min((r[key][name] for r in rows if r.get(key)),
                       default=None)
        readings[name] = {
            "program_largest": max((r["program"][name] for r in rows
                                    if r.get("program")), default=None),
            "control_float8_smallest": least("control_float8"),
            "control_state_bf16_smallest": least("control_state_bf16"),
            "program_state_bf16_smallest": least("program_state_bf16"),
            "limit": reference.TOLERANCE[name]}
    out = {"cell": CELL, "tiny": args.tiny, "seconds": args.seconds,
           "rows": rows, "readings": readings}
    path = ROOT / "chiprun_out" / "jamba_serve_precision.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    print(json.dumps(readings))


if __name__ == "__main__":
    main()
