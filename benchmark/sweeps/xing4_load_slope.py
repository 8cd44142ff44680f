"""Is the step's time the expert layer's, or the seed's? Outside the
benchmark: the cell's own program (``runners/xing4_train.build``, the same
compiled step), with the routers' bias ``e_score_correction_bias`` set on
the held experts so that their load is about 0.5 x, 1 x and 1.5 x the mean
share, and the step timed at each. A layer whose device work is a function
of shapes alone reads the same time at all three (ISSUE 27: within 0.1 %).

    python3 benchmark/sweeps/xing4_load_slope.py --seed 7 [--steps 8]

Writes ``chiprun_out/xing4_load_slope.json``. Needs the chip.
"""

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CELL = "xing4_train_s4096"
#: candidate biases on the held experts; scores are sigmoids, so a few
#: hundredths move a held expert across many tokens' fourth place
DELTAS = (-0.3, -0.1, -0.05, -0.02, 0.0, 0.02, 0.05, 0.1, 0.3)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--steps", type=int, default=8)
    args = parser.parse_args()

    from benchmark.lib import manifest, traffic
    from tpu_sandbox.runtime.bootstrap import configure_compile_cache

    configure_compile_cache()
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    cell = manifest.cell(CELL)
    runner = manifest.module("runners", cell["runner"])
    model, _, state, eng = runner.build(cell, args.seed, jax.devices()[:1])
    held = jnp.asarray(model.config.held)
    batches = traffic.token_batches(cell["traffic"], args.seed,
                                    cell["config"]["vocab_size"])
    step = eng.lower_step(state, *eng.shard_batch(*next(batches))).compile()

    def with_bias(state, delta):
        stats = jax.tree.map(lambda x: x, state.batch_stats)
        for block in stats.values():
            bias = block["moe"]["e_score_correction_bias"]
            block["moe"]["e_score_correction_bias"] = jax.device_put(
                jnp.zeros_like(bias).at[held].set(delta), bias.sharding)
        return state.replace(batch_stats=stats)

    def run(state, delta, steps):
        state = with_bias(state, delta)
        before = runner.moe_counters(state)
        times = []
        for _ in range(steps):
            placed = eng.shard_batch(*next(batches))
            t0 = time.perf_counter()
            state, loss = step(state, *placed)
            jax.block_until_ready(loss)
            times.append(1e3 * (time.perf_counter() - t0))
        after = runner.moe_counters(state)
        held_rows = ((after["rows_held"] - before["rows_held"])
                     / (after["steps"] - before["steps"]))
        return state, times, held_rows, after["rows_dropped"] - before["rows_dropped"]

    state, _, _, _ = run(state, 0.0, 2)                       # warm-up
    mean_rows = model.config.local_rows / cell["deployment"]["local_rows_factor"]
    probe = {}
    for delta in DELTAS:                                      # one step each
        state, _, rows, _ = run(state, delta, 1)
        probe[delta] = rows / mean_rows
    picks = {want: min(probe, key=lambda d: abs(probe[d] - want))
             for want in (0.5, 1.0, 1.5)}
    rows_out = []
    for want, delta in picks.items():
        state, times, rows, dropped = run(state, delta, args.steps)
        rows_out.append({"wanted_load": want, "bias": delta,
                         "load": rows / mean_rows, "rows_held_per_layer": rows,
                         "rows_dropped": dropped,
                         "step_ms_median": statistics.median(times),
                         "step_ms": times})
    base = next(r for r in rows_out if r["wanted_load"] == 1.0)["step_ms_median"]
    out = {"cell": CELL, "seed": args.seed, "device": jax.devices()[0].device_kind,
           "local_rows": model.config.local_rows, "probe_load_by_bias": {
               str(k): v for k, v in probe.items()},
           "runs": rows_out,
           "step_ms_rel_to_mean_load": {
               str(r["wanted_load"]): r["step_ms_median"] / base - 1.0
               for r in rows_out}}
    path = ROOT / "chiprun_out" / "xing4_load_slope.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    print(json.dumps({k: out[k] for k in ("probe_load_by_bias",
                                          "step_ms_rel_to_mean_load")}))
    for r in rows_out:
        print(r["wanted_load"], r["bias"], round(r["load"], 3),
              r["rows_dropped"], r["step_ms_median"])


if __name__ == "__main__":
    main()
