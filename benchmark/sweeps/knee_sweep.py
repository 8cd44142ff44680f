"""The chat mix at a few fixed rates on the chip, one engine, one process:
what a serving cell's rate is chosen from. Not part of a run; its output is
kept beside it and the chosen rate is a number in the cell's traffic file.

    python3 benchmark/sweeps/knee_sweep.py --workload gpt2m_serve_chat \
        --rates 0.4,0.8,1.6 --seconds 30 --seeds 11 --out chiprun_out/sweeps/knee_sweep.json

Every row is one window of the cell's mix at one rate (``--warmup-s`` of the
same mix first): failures, backlog, how the waiting queue grew from the
window's first third to its last, tokens per second, time to first token,
gaps between tokens, occupancy. Several ``--seeds`` and ``--seconds`` give
the spread of one rate without paying the set-up again. The script names no
knee: whoever reads the rows does (NOTES.md says how PR 22 read them, and
why a 30 s window could not show a backlog at any rate).

The workload must be a cell of ``BENCHMARK.json``; PR 22 ran it while the
serving cell was still listed there.
"""

import argparse
import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def thirds(values):
    n = len(values) // 3
    if not n:
        return None, None
    return sum(values[:n]) / n, sum(values[-n:]) / n


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", default="20")
    parser.add_argument("--seeds", default="11")
    parser.add_argument("--warmup-s", type=float, default=3.0)
    parser.add_argument("--out", required=True)
    args = parser.parse_args()

    from benchmark.lib import manifest, stats, traffic
    from benchmark.lib.observe import Observations
    from tpu_sandbox.runtime.bootstrap import configure_compile_cache

    configure_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"knee_sweep: needs a TPU, found {dev.platform!r}")
    cell = manifest.cell(args.workload)
    runner = manifest.module("runners", cell["runner"])
    seeds = [int(x) for x in args.seeds.split(",")]
    first = Observations(cell=cell, seed=seeds[0], seconds=1.0,
                         traced=False, device_kind=dev.device_kind)
    session = runner.setup(first)
    rows = []
    for rate, seconds, seed in [
            (float(r), float(s), seed) for r in args.rates.split(",")
            for s in args.seconds.split(",") for seed in seeds]:
        this = copy.deepcopy(cell)
        this["traffic"].update(rate_per_s=rate, warmup_s=args.warmup_s)
        obs = Observations(cell=this, seed=seed, seconds=seconds,
                           traced=False, device_kind=dev.device_kind)
        session.arrivals = traffic.requests(
            this["traffic"], seed, seconds, this["config"]["vocab_size"])
        t0 = time.perf_counter()
        runner.measure(obs, session, seconds)
        runner.finish(obs, session)
        session.eng.results.clear()
        session.eng.cache.flush_prefix_cache()
        e2e = runner.end_to_end(obs)
        early, late = thirds(obs.series.get("waiting", []))
        row = {
            "rate_per_s": rate, "seconds": seconds, "seed": seed,
            "attempted": obs.attempted, "backlog": obs.notes["backlog"],
            "failed": obs.failed, "finished": obs.notes["finished"],
            "serve_tok_per_s": e2e["serve_tok_per_s"],
            "ttft_p50_ms": 1e3 * (stats.median(obs.series["ttft_s"]) or 0),
            "ttft_p90_ms": e2e.get("ttft_p90_ms"),
            "itl_p50_ms": 1e3 * (stats.median(obs.series["itl_s"]) or 0),
            "itl_p99_ms": e2e["itl_p99_ms"],
            "waiting_first_third": early, "waiting_last_third": late,
            "occupancy_pct_p50": stats.median(obs.series.get("occupancy_pct", [])),
            "decode_step_ms_p50": 1e3 * (stats.median(
                obs.series.get("decode_step_s", [])) or 0),
            "preemptions": obs.facts["preemptions"],
            "wall_s": time.perf_counter() - t0,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = {"workload": args.workload, "seeds": seeds,
           "seconds": args.seconds, "warmup_s": args.warmup_s,
           "device": {"platform": dev.platform, "kind": dev.device_kind},
           "rows": rows, "problems": first.problems}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1) + "\n")


if __name__ == "__main__":
    main()
