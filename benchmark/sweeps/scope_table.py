"""Device milliseconds a step by scope, for any list of patterns: one traced
run of a cell through the benchmark's own functions (``run.py``'s ``measure``,
the cell's runner, ``readers.scope_ms``), then a regex a row. What PERF.md
section 5's by-scope lines are made with, where ``breakdown.device_scopes``'
twenty rows do not reach. Outside the benchmark; needs the chip.

    python3 benchmark/sweeps/scope_table.py olmoh_train_s8192 11 '/gdn/conv' '/mlp/'

Writes ``chiprun_out/scope_table_<cell>.json``; without patterns, the rows
of the Olmo-Hybrid cell.
"""

import json
import runpy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

OLMO_HYBRID = (
    r"/gdn/", r"/gdn/in_proj", r"/gdn/in_proj/q/", r"/gdn/in_proj/k/",
    r"/gdn/in_proj/v/", r"/gdn/in_proj/g/", r"/gdn/in_proj/(a|b)/",
    r"/gdn/conv", r"/gdn/gates", r"/gdn/delta_rule", r"/gdn/delta_rule/while",
    r"/gdn/delta_rule/.*(ij,\.\.\.jk,\.\.\.kl|ji,\.\.\.jk,\.\.\.lk)",
    r"/gdn/norm", r"/gdn/out_proj", r"/gdn/post_norm", r"/attn/",
    r"/attn/pallas_call", r"/attn/(q|k|v)/", r"/attn/o/", r"/attn/(q|k)_norm",
    r"/mlp/", r"/mlp/gate", r"/mlp/up", r"/mlp/down", r"lm_head", r"tok_emb",
    r"norm_f", r"(^|/)optimizer(/|$)", r"block\d")


def main() -> None:
    name, seed, *patterns = sys.argv[1:]
    bench = runpy.run_path(str(ROOT / "benchmark/run.py"), run_name="bench_run")

    from benchmark.lib import manifest, readers
    from benchmark.lib.observe import Observations
    from tpu_sandbox.obs import get_registry
    from tpu_sandbox.runtime.bootstrap import configure_compile_cache

    cell = manifest.cell(name)
    configure_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("needs a TPU")
    obs = Observations(cell=cell, seed=int(seed), seconds=10.0, traced=True,
                       device_kind=dev.device_kind)
    bench["watch_compiles"](obs)
    runner = manifest.module("runners", cell["runner"])
    session = runner.setup(obs)
    bench["measure"](runner, obs, session, 10.0)
    runner.finish(obs, session)
    snap = get_registry().snapshot()
    out = {"cell": name, "seed": int(seed),
           "device_step_ms": manifest.module(
               "layer_metrics", "device_step_ms").read(obs),
           "scope_ms": {p: readers.scope_ms(obs, p)
                        for p in patterns or OLMO_HYBRID},
           "choices": {k: v for k, v in snap["counters"].items()
                       if "_choice{" in k},
           "kernel_sites": {k: v for k, v in snap["histograms"].items()
                            if k.startswith("trace.kernel_s{")},
           "problems": obs.problems}
    path = ROOT / "chiprun_out" / f"scope_table_{name}.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
