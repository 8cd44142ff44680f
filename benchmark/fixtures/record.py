"""Record the small trace the reducer's test reads, on the chip(s):

    python3 benchmark/fixtures/record.py chiprun_out/fixture

A jitted step over all local chips (a matmul chain, a gradient-sized
``psum``, more matmuls) run six times under the benchmark's annotations,
with the host asleep for 3 ms in ``bench:next_batch`` before each step so
that the device has idle gaps with a known name. The newest
``.xplane.pb`` is copied to ``<out>/tiny.xplane.pb`` and, beside it,
``tiny.expect.json`` says what was run.
"""

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

STEPS = 6
SLEEP_S = 0.003


def main() -> None:
    out = Path(sys.argv[1])
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.lib import trace_reduce
    from benchmark.lib.observe import PREFIX, WINDOW

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("record.py: needs a TPU")
    mesh = Mesh(np.array(devices), ("data",))

    def body(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        g = jax.lax.psum(x, "data") if len(devices) > 1 else x
        for _ in range(4):
            g = jnp.tanh(g @ w)
        return g

    step = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P()),
                                 out_specs=P("data"), check_vma=False))
    x = jax.device_put(jnp.ones((len(devices) * 2048, 2048), jnp.bfloat16),
                       NamedSharding(mesh, P("data")))
    w = jax.device_put(jnp.eye(2048, dtype=jnp.bfloat16),
                       NamedSharding(mesh, P()))
    jax.block_until_ready(step(x, w))  # compiled and warm

    log = out / "log"
    shutil.rmtree(log, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(log), profiler_options=options)
    with jax.profiler.TraceAnnotation(WINDOW):
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation(PREFIX + "next_batch"):
                time.sleep(SLEEP_S)
            with jax.profiler.TraceAnnotation(PREFIX + "step_dispatch"):
                y = step(x, w)
            with jax.profiler.TraceAnnotation(PREFIX + "wait_device"):
                jax.block_until_ready(y)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(log)
    shutil.copy(path, out / "tiny.xplane.pb")
    (out / "tiny.expect.json").write_text(json.dumps({
        "devices": len(devices), "kind": devices[0].device_kind,
        "steps": STEPS, "sleep_s": SLEEP_S,
        "collective": len(devices) > 1}) + "\n")
    (out / "tiny.describe.txt").write_text(trace_reduce.describe(path) + "\n")
    shutil.rmtree(log, ignore_errors=True)
    print(json.dumps({k: v for k, v in trace_reduce.reduce(
        trace_reduce.load(out / "tiny.xplane.pb", host_prefix=PREFIX)).items()
        if k != "devices"}))


if __name__ == "__main__":
    main()
