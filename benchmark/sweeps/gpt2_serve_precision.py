"""Both readings of the two limits of ``reference/gpt2.py::TOLERANCE`` that
decide ``correct`` in ``gpt2m_serve_decode_replay`` (``chosen_gap_rel``,
``chosen_logprob_abs``), in one process, a seed after another:

- the **program**: the cell's own run (``runners/lm_serve_replay``: 64
  sessions prefilled, the window's steps, ``verify``), whose deviations are
  the lower reading;
- the **control**: the plain reference put in the program's place one
  precision below its bf16 -- both operands of every matrix product rounded
  to float8 (e4m3), all else float32 -- over the same prompts and served
  tokens. It does not decode: at every position that chose a served token
  it puts its own first choice, whose gap under the float32 reference's
  best, and whose log-probability against the float32 reference's, go
  through the very comparison that decides ``correct``. It has to come out
  as not correct.

    python3 benchmark/sweeps/gpt2_serve_precision.py --seeds 11 12 13 --control 3

Outside the benchmark; needs the chip. Writes
``chiprun_out/gpt2_serve_precision.json``. ``--tiny`` rehearses on the CPU
at ``tests/benchmark``'s tiny size.
"""

import argparse
import functools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CELL = "gpt2m_serve_decode_replay"
TINY = {"config": {"n_layer": 2, "n_embd": 32, "n_head": 4, "n_inner": 64,
                   "vocab_size": 97, "n_positions": 64},
        "deployment": {"dtype": "fp32", "cache_dtype": "fp32", "max_batch": 4,
                       "block_size": 4, "max_blocks_per_seq": 16,
                       "num_blocks": 65, "prefill_buckets": [8, 16, 32, 48]},
        "traffic": {"sessions": 4, "max_new_tokens": 16,
                    "prompt_len": {"dist": "uniform", "min": 5, "max": 40}}}


def control(reference, runner, tree, batch: dict, config: dict):
    """The float8 reference in the program's place: ``(deviations, limits
    broken)`` as ``reference.compare_served`` gives them."""
    import jax.numpy as jnp
    import numpy as np

    n = batch["n"]
    low = runner.reference_rows(reference, tree, batch, config,
                                matmul_dtype=jnp.float8_e4m3fn)
    first = low["argmax"].astype(np.int32)
    ref = runner.reference_rows(reference, tree, batch, config, chosen=first)
    valid = np.arange(first.shape[1])[None, :] < batch["counts"][:, None]
    system = (np.where(valid, low["argmax_logprob"], 0.0).sum(axis=1)
              / batch["counts"])
    return reference.compare_served(ref["gap_rel"][:n], ref["logprob"][:n],
                                    batch["counts"][:n], system[:n])


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--control", type=int, default=3,
                        help="run the control on the first N seeds")
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()

    from benchmark.lib import manifest
    from benchmark.lib.observe import Observations
    from tpu_sandbox.runtime.bootstrap import configure_compile_cache
    from tpu_sandbox.serve import decode

    configure_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu" and not args.tiny:
        raise SystemExit("needs a TPU")
    # one set of compiled programs serves every seed
    decode.build_decode_step = functools.cache(decode.build_decode_step)
    cell = manifest.cell(CELL)
    if args.tiny:
        for part, values in TINY.items():
            cell[part].update(values)
    runner = manifest.module("runners", cell["runner"])
    reference = manifest.module("reference", cell["reference"])
    rows = []
    for k, seed in enumerate(args.seeds):
        obs = Observations(cell=cell, seed=seed, seconds=args.seconds,
                           traced=False,
                           device_kind=jax.devices()[0].device_kind)
        session = runner.setup(obs)
        runner.measure(obs, session, args.seconds)
        runner.finish(obs, session)
        runner.verify(obs, session)
        row = {"seed": seed, "steps": session.steps,
               "compared_tokens": obs.notes.get("compared_tokens"),
               "program": obs.notes.get("reference_deviation"),
               "problems": obs.problems}
        if k < args.control:
            tree = reference.from_program_tree(session.params,
                                               cell["config"]["n_layer"])
            dev, bad = control(reference, runner, tree, session.batch,
                               cell["config"])
            row["control_float8_e4m3"] = dev
            row["control_broken"] = bad
            del tree  # it holds the seed's weights: the next seed needs the room
        del session
        rows.append(row)
        print(json.dumps(row), flush=True)
    readings = {}
    for name in ("chosen_gap_rel", "chosen_logprob_abs"):
        program = [r["program"][name] for r in rows if r["program"]]
        low = [r["control_float8_e4m3"][name] for r in rows
               if "control_float8_e4m3" in r]
        readings[name] = {
            "program_largest": max(program, default=None),
            "control_smallest": min(low, default=None),
            "limit": reference.TOLERANCE[name]}
    out = {"cell": CELL, "tiny": args.tiny, "seconds": args.seconds,
           "rows": rows, "readings": readings}
    path = ROOT / "chiprun_out" / "gpt2_serve_precision.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    print(json.dumps(readings))


if __name__ == "__main__":
    main()
