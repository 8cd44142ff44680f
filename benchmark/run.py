"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's system through the program's own construction path, warms
every shape, checks it against the plain float32 reference on the chip
(all of that is set-up), measures for ``--seconds``, and prints one JSON
object as the last line of stdout: the cell's end-to-end metrics
(``--trace 0``) or its per-layer metrics and a breakdown of the device
trace (``--trace 1``). Without a TPU holding the chips the cell asks for it
exits 1 and prints no result; there is no CPU fallback and no flag for one.
"""

import time

T_START = time.perf_counter()  # process start, as near as Python can see it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

#: Trace-time switches of the program that swap a Pallas kernel for a
#: reference or for the interpreter: with one set, the run would measure
#: another program (copied from ``chip_smoke.KILL_SWITCHES``).
KILL_SWITCHES = (
    "TPU_SANDBOX_NO_SPARSE_CONV1", "TPU_SANDBOX_NO_PALLAS_FC",
    "TPU_SANDBOX_NO_FUSED_CONV1_BWD", "TPU_SANDBOX_WGRAD_RESTAGE",
    "TPU_SANDBOX_FORCE_COMPILED_KERNELS",
)
#: A traced run measures at most this long: traces are large, and the
#: per-layer numbers need a steady stretch, not the whole window.
TRACE_SECONDS = 10.0
TRACE_DIR = ROOT / ".bench_trace"
#: the result line's contract: a list of ``breakdown`` holds at most ten
#: rows; the reduction's twenty go into ``notes``, which the driver ignores
LINE_ROWS = 10


def die(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def watch_compiles(obs) -> None:
    """Nothing may compile inside the measured window: count what does.
    Outside it, add up jax's own clocks of tracing, lowering and compiling
    (``jax_trace_s``, ``jax_lower_s``, ``jax_compile_s``) for a runner whose
    program lowers and compiles in one call."""
    import jax.monitoring

    names = {"jaxpr_trace_duration": "jax_trace_s",
             "jaxpr_to_mlir_module_duration": "jax_lower_s",
             "backend_compile_duration": "jax_compile_s"}

    def on_event(event: str, duration: float, **kw) -> None:
        fact = names.get(event.rsplit("/", 1)[-1])
        if fact is None:
            return
        if obs.in_window:
            obs.compiles_in_window += fact == "jax_compile_s"
        else:
            obs.facts[fact] = obs.facts.get(fact, 0.0) + duration

    jax.monitoring.register_event_duration_secs_listener(on_event)


def measure(runner, obs, session, seconds: float) -> None:
    """The measured window, under the profiler in a traced run."""
    if not obs.traced:
        obs.in_window = True
        runner.measure(obs, session, seconds)
        obs.in_window = False
        return
    import jax

    from benchmark.lib import trace_reduce
    from benchmark.lib.observe import PREFIX, WINDOW

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the benchmark's annotations name the
    options.host_tracer_level = 1    # host's work; Python frames only bloat
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    try:
        obs.in_window = True
        with jax.profiler.TraceAnnotation(WINDOW):
            runner.measure(obs, session, seconds)
        obs.in_window = False
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(TRACE_DIR)
    obs.trace = trace_reduce.reduce(
        trace_reduce.load(path, host_prefix=PREFIX), marker=WINDOW,
        prefix=PREFIX, scopes=obs.scopes)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    if obs.trace is None:
        obs.problem("the traced window holds no device operation")


def memory_peak(obs, used) -> int:
    """Peak bytes on the fullest chip, from ``memory_stats()`` alone. On
    this platform ``peak_bytes_in_use`` counts live buffers only; what a
    running program allocates for its temporaries shows as
    ``bytes_reserved`` (PR 22: 3,780,804,608 reserved beside a ConvNet step
    whose compiled program declares 3,782,153,728 bytes of temporaries). So
    the peak is the larger of the live peak (set-up included) and what is
    live right after the window plus the peak reservation."""
    def peak(s: dict) -> int:
        return max(s["peak_bytes_in_use"],
                   s["bytes_in_use"] + s.get("peak_bytes_reserved", 0))

    stats = max((d.memory_stats() for d in used), key=peak)
    obs.notes["memory_stats"] = {k: stats.get(k) for k in (
        "peak_bytes_in_use", "bytes_in_use", "peak_bytes_reserved",
        "bytes_limit")}
    return peak(stats)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args()

    set_switches = [k for k in KILL_SWITCHES if k in os.environ]
    if set_switches:
        die(f"kernel kill-switch(es) in the environment: {set_switches}")

    from benchmark.lib import manifest, peaks
    from benchmark.lib.observe import Observations

    cell = manifest.cell(args.workload)

    from tpu_sandbox.runtime.bootstrap import configure_compile_cache

    cache_dir = configure_compile_cache()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        die(f"needs a TPU; jax.devices()[0].platform is {dev.platform!r}")
    if len(devices) < cell["chips"]:
        die(f"cell {cell['name']} needs {cell['chips']} chips; "
            f"jax sees {len(devices)}")
    peaks.peak(dev.device_kind)  # an unknown kind raises here
    say(f"cell {cell['name']} seed {args.seed} on {len(devices)} x "
        f"{dev.device_kind}; compile cache {cache_dir}")

    traced = bool(args.trace)
    seconds = min(args.seconds, TRACE_SECONDS) if traced else args.seconds
    obs = Observations(cell=cell, seed=args.seed, seconds=seconds,
                       traced=traced, device_kind=dev.device_kind)
    watch_compiles(obs)
    runner = manifest.module("runners", cell["runner"])
    session = runner.setup(obs)
    setup_s = time.perf_counter() - T_START
    say(f"set-up {setup_s:.2f} s: {obs.facts}")

    measure(runner, obs, session, seconds)
    runner.finish(obs, session)
    if obs.compiles_in_window:
        obs.problem(f"{obs.compiles_in_window} compilation(s) inside the "
                    "measured window")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": memory_peak(obs, devices[:cell["chips"]])}
    # a runner whose reference runs on what the window produced runs it
    # here: the peak is read, so the reference's memory is not the cell's
    if hasattr(runner, "verify"):
        runner.verify(obs, session)
    metrics: dict = {}
    breakdown = None
    if traced:
        for m in cell["per_layer"]:
            value = manifest.module("layer_metrics", m["name"]).read(obs)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if obs.trace is not None:
            device["busy_s"] = obs.trace["busy_s"]
            device["window_s"] = obs.trace["window_s"]
            from benchmark.lib.trace_reduce import cut

            breakdown = {
                "device_ops": cut(obs.trace["device_ops"], LINE_ROWS),
                "idle_gaps": cut(obs.trace["idle_gaps"], LINE_ROWS)}
            if obs.trace["device_scopes"]:
                breakdown["device_scopes"] = cut(
                    obs.trace["device_scopes"], LINE_ROWS, "(other)")
            obs.notes["breakdown_top20"] = {
                key: obs.trace[key] for key in ("device_ops", "device_scopes")}
    else:
        values = dict(runner.end_to_end(obs), setup_s=setup_s)
        for m in cell["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    # after the readers: one that cannot trust its source says so
    result = {"correct": not obs.problems, "attempted": obs.attempted,
              "failed": obs.failed, "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    # each number compared beside its limit: the line's last key, and the
    # last lines on stderr
    compared = obs.notes.pop("compared", None)
    result["notes"] = dict(obs.notes, problems=obs.problems,
                           compiles_in_window=obs.compiles_in_window,
                           setup_phases_s=obs.facts)
    if compared:
        result["compared"] = compared
        for name, pair in compared.items():
            say(f"compared {name}: {pair['value']:.6g} (limit {pair['limit']})")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
