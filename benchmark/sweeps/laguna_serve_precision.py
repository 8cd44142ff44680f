"""Both readings of the limits of ``reference/laguna.py::TOLERANCE`` that
decide ``correct`` in ``laguna_serve_decode_replay`` (``chosen_gap_rel``,
``chosen_logprob_mean_abs``), in one process, a seed after another:

- the **program**: the cell's own run (``runners/laguna_serve_replay``: 64
  sessions prefilled, the window's steps, ``verify``), whose deviations are
  the lower reading;
- four **controls**, each the plain reference with a fault put in the
  program's place over the same prompts and served tokens -- ``no_window``:
  the window layers read every key; ``no_gate``: the per-head output gate
  left out; ``window_rope_full``: the window layers given the full layers'
  rotary rule; ``cache_f8``: every key (behind its rotation) and value
  rounded to float8 (e4m3) where the configuration states bfloat16, one
  precision below -- all else float32. A control does not decode: at every
  position that chose a served token it puts its own first choice, whose gap
  under the float32 reference's best, and whose log-probability against the
  float32 reference's, go through the very comparison that decides
  ``correct``. Each has to come out as not correct.

    python3 benchmark/sweeps/laguna_serve_precision.py --seeds 11 12 13 \\
        --control 1

Outside the benchmark; needs the chip. Writes
``chiprun_out/laguna_serve_precision.json``. ``--tiny`` rehearses on the CPU
at ``tests/benchmark``'s tiny size. ``--sessions N`` holds the controls to
the first N of the run's reference sessions (a control costs two reference
forwards a session; the longest session's take most of a minute each);
``--only cache_f8`` runs that control alone.
"""

import argparse
import functools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CELL = "laguna_serve_decode_replay"
TINY = {"config": {"vocab_size": 96, "hidden_size": 64,
                   "intermediate_size": 96, "num_hidden_layers": 4,
                   "num_key_value_heads": 2, "head_dim": 16,
                   "num_experts": 4, "num_experts_per_tok": 2,
                   "moe_intermediate_size": 32,
                   "shared_expert_intermediate_size": 32, "sliding_window": 8,
                   "layer_types": ["full_attention"]
                   + 3 * ["sliding_attention"],
                   "mlp_layer_types": ["dense"] + 3 * ["sparse"],
                   "num_attention_heads_per_layer": [6, 8, 8, 8]},
        "rope_original_max": 16,
        "deployment": {"dtype": "fp32", "param_dtype": "fp32",
                       "cache_dtype": "fp32", "routed_experts_total": 8,
                       "held": [0, 1, 2, 3], "max_batch": 4, "block_size": 4,
                       "max_blocks_per_seq": 16, "num_blocks": 65,
                       "window_blocks": 13,
                       "prefill_buckets": [8, 16, 32, 48], "flash": False,
                       "reference_sessions": 3, "reference_pad": 4},
        "traffic": {"sessions": 4, "max_new_tokens": 16,
                    "prompt_len": {"dist": "uniform", "min": 5, "max": 40}}}


def tiny(cell: dict) -> dict:
    """``cell`` at the tiny size: the configuration's keys of ``TINY``, the
    full layers' YaRN over 16 positions, and the reference's blocks of
    queries of 4."""
    import copy

    cell = copy.deepcopy(cell)
    for part in ("config", "deployment", "traffic"):
        cell[part].update(TINY[part])
    rules = cell["config"]["rope_parameters"]
    rules["full_attention"]["original_max_position_embeddings"] = \
        rules["original_max_position_embeddings"] = TINY["rope_original_max"]
    return cell


def controls():
    import jax.numpy as jnp

    return {"no_window": {"window": False}, "no_gate": {"gate": False},
            "window_rope_full": {"window_rope": "full"},
            "cache_f8": {"cache_dtype": jnp.float8_e4m3fn}}


def first_sessions(batch: dict, n: int | None) -> dict:
    """``batch`` held to its first ``n`` sessions (the shortest and the
    longest lead it: ``reference_sessions``)."""
    if not n or n >= batch["n"]:
        return batch
    return {k: (n if k == "n" else v[:n]) for k, v in batch.items()}


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
    parser.add_argument("--only", nargs="*", default=None,
                        help="of the controls, these alone (their names)")
    parser.add_argument("--sessions", type=int, default=0,
                        help="hold the controls to the first N reference "
                        "sessions of a run (0: all)")
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
    reference = manifest.module("reference", cell["reference"])
    if args.tiny:
        cell = tiny(cell)
        reference.QUERY_BLOCK = 4
    pad = int(cell["deployment"].get("reference_pad", 1024))
    config = {**cell["config"], "deployment": cell["deployment"]}
    runner = manifest.module("runners", cell["runner"])
    rows = []
    for k, seed in enumerate(args.seeds):
        obs, session = run_cell(cell, seed, args.seconds)
        row = {"seed": seed, "steps": session.steps,
               "decode_step_ms": 1e3 * session.window_s / max(1, session.steps),
               "compared_tokens": obs.notes.get("compared_tokens"),
               "program": obs.notes.get("reference_deviation"),
               "share_counters": obs.notes.get("share_counters"),
               "window_blocks": obs.notes.get("window_blocks"),
               "check_s": obs.facts.get("after_window_check_s"),
               "prefill_s": obs.facts.get("session_prefill_s"),
               "problems": obs.problems}
        if session.batch is not None:
            row["program_by_session"] = by_session(
                session.reference_rows, session.batch["counts"],
                session.batch["system"])
            tree = reference.from_program_tree(session.params, config)
            batch = first_sessions(session.batch, args.sessions)
            faults = {name: fault for name, fault in controls().items()
                      if k < args.control
                      and (args.only is None or name in args.only)}
            for name, fault in faults.items():
                dev, bad, sessions = control(reference, runner, tree, batch,
                                             config, pad, **fault)
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
    path = ROOT / "chiprun_out" / "laguna_serve_precision.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    print(json.dumps(readings))


if __name__ == "__main__":
    main()
