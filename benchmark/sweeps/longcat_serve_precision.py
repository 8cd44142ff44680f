"""Both readings of the limits of ``reference/longcat_flash.py::TOLERANCE``
that decide ``correct`` in ``longcat_serve_decode_replay``
(``chosen_gap_rel``, ``chosen_logprob_mean_abs``), in one process, a seed after
another:

- the **program**: the cell's own run (``runners/longcat_serve_replay``: 128
  sessions prefilled, the window's steps, ``verify``), whose deviations are
  the lower reading;
- three **controls**, each the plain reference with a fault put in the
  program's place over the same prompts and served tokens -- ``latent_f8``:
  the row a position leaves in the cache (``[c | k_pe]`` behind norm, scale
  and rotation) rounded to float8 (e4m3) where the configuration states
  bfloat16, one precision below; ``no_scale_q`` / ``no_scale_kv``:
  ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` left out -- all else float32.
  A control does not decode: at every position that chose a served token it
  puts its own first choice, whose gap under the float32 reference's best,
  and whose log-probability against the float32 reference's, go through the
  very comparison that decides ``correct``. Each has to come out as not
  correct.

    python3 benchmark/sweeps/longcat_serve_precision.py --seeds 11 12 13 \\
        --control 1

Outside the benchmark; needs the chip. Writes
``chiprun_out/longcat_serve_precision.json``. ``--tiny`` rehearses on the CPU
at ``tests/benchmark``'s tiny size.
"""

import argparse
import functools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CELL = "longcat_serve_decode_replay"
TINY = {"config": {"vocab_size": 96, "hidden_size": 64, "ffn_hidden_size": 96,
                   "expert_ffn_hidden_size": 32, "num_layers": 2,
                   "num_attention_heads": 4, "kv_lora_rank": 32,
                   "q_lora_rank": 24, "qk_rope_head_dim": 8,
                   "v_head_dim": 16, "qk_nope_head_dim": 16,
                   "n_routed_experts": 4, "zero_expert_num": 4,
                   "moe_topk": 3},
        "deployment": {"dtype": "fp32", "param_dtype": "fp32",
                       "cache_dtype": "fp32", "routed_experts_total": 8,
                       "held": [0, 1, 2, 3], "max_batch": 4, "block_size": 4,
                       "max_blocks_per_seq": 16, "num_blocks": 65,
                       "prefill_buckets": [8, 16, 32, 48], "flash": False,
                       "reference_sessions": 3, "reference_pad": 16},
        "traffic": {"sessions": 4, "max_new_tokens": 16,
                    "prompt_len": {"dist": "uniform", "min": 5, "max": 40}}}


def controls():
    import jax.numpy as jnp

    return {"latent_f8": {"latent_dtype": jnp.float8_e4m3fn},
            "no_scale_q": {"scale_q": False},
            "no_scale_kv": {"scale_kv": False}}


def by_session(ref: dict, counts, system) -> dict:
    """What ``compare_served`` reduces, a session at a time: the widest gap
    of its tokens and its mean log-probability by system and reference."""
    import numpy as np

    counts = np.asarray(counts)
    valid = np.arange(ref["gap_rel"].shape[1])[None, :] < counts[:, None]
    return {"counts": counts.tolist(),
            "gap_rel": np.where(valid, ref["gap_rel"], 0).max(axis=1).tolist(),
            "system": np.asarray(system, np.float64).tolist(),
            "reference": (np.where(valid, ref["logprob"], 0.0).sum(axis=1)
                          / counts).tolist()}


def control(reference, runner, tree, batch: dict, config: dict, pad: int,
            **fault):
    """The reference with a fault in the program's place: ``(deviations,
    limits broken, by_session)``, the first two as
    ``reference.compare_served`` gives them."""
    import numpy as np

    low = runner.reference_rows(reference, tree, batch, config, pad=pad,
                                **fault)
    first = low["argmax"].astype(np.int32)
    ref = runner.reference_rows(reference, tree, batch, config, chosen=first,
                                pad=pad)
    valid = np.arange(first.shape[1])[None, :] < batch["counts"][:, None]
    system = (np.where(valid, low["argmax_logprob"], 0.0).sum(axis=1)
              / batch["counts"])
    return (*reference.compare_served(ref["gap_rel"], ref["logprob"],
                                      batch["counts"], system),
            by_session(ref, batch["counts"], system))


def run_cell(cell: dict, seed: int, seconds: float):
    """One run of the cell as ``run.py`` drives it, without its trace."""
    import jax

    from benchmark.lib import manifest
    from benchmark.lib.observe import Observations

    obs = Observations(cell=cell, seed=seed, seconds=seconds, traced=False,
                       device_kind=jax.devices()[0].device_kind)
    runner = manifest.module("runners", cell["runner"])
    session = runner.setup(obs)
    obs.in_window = True
    runner.measure(obs, session, seconds)
    obs.in_window = False
    runner.finish(obs, session)
    runner.verify(obs, session)
    return obs, session


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seeds", type=int, nargs="*", default=[])
    parser.add_argument("--control", type=int, default=1,
                        help="run the controls on the first N seeds")
    parser.add_argument("--f8-control", type=int, default=0,
                        help="... and the float8 latent alone on the first "
                        "N seeds (where N is the larger)")
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
    # one set of compiled programs serves every seed
    decode.build_decode_step = functools.cache(decode.build_decode_step)
    cell = manifest.cell(CELL)
    if args.tiny:
        for part, values in TINY.items():
            cell[part].update(values)
    pad = int(cell["deployment"].get("reference_pad", 1024))
    config = {**cell["config"], "deployment": cell["deployment"]}
    runner = manifest.module("runners", cell["runner"])
    reference = manifest.module("reference", cell["reference"])
    rows = []
    for k, seed in enumerate(args.seeds):
        obs, session = run_cell(cell, seed, args.seconds)
        row = {"seed": seed, "steps": session.steps,
               "decode_step_ms": 1e3 * session.window_s / max(1, session.steps),
               "compared_tokens": obs.notes.get("compared_tokens"),
               "program": obs.notes.get("reference_deviation"),
               "share_counters": obs.notes.get("share_counters"),
               "problems": obs.problems}
        faults = (controls() if k < args.control else
                  {"latent_f8": controls()["latent_f8"]}
                  if k < args.f8_control else {})
        if session.batch is not None:
            row["program_by_session"] = by_session(
                session.reference_rows, session.batch["counts"],
                session.batch["system"])
            tree = reference.from_program_tree(session.params, config)
            for name, fault in faults.items():
                dev, bad, sessions = control(reference, runner, tree,
                                             session.batch, config, pad,
                                             **fault)
                row[f"control_{name}"] = dev
                row[f"control_{name}_broken"] = bad
                row[f"control_{name}_by_session"] = sessions
            del tree
        del session  # it holds the seed's weights: the next needs the room
        rows.append(row)
        print(json.dumps(row), flush=True)
    readings = {}
    for name in reference.TOLERANCE:
        readings[name] = {
            "program_largest": max((r["program"][name] for r in rows
                                    if r.get("program")), default=None),
            **{f"control_{c}_smallest": min(
                (r[f"control_{c}"][name] for r in rows
                 if r.get(f"control_{c}")), default=None)
               for c in controls()},
            "limit": reference.TOLERANCE[name]}
    out = {"cell": CELL, "tiny": args.tiny, "seconds": args.seconds,
           "rows": rows, "readings": readings}
    path = ROOT / "chiprun_out" / "longcat_serve_precision.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    print(json.dumps(readings))


if __name__ == "__main__":
    main()
