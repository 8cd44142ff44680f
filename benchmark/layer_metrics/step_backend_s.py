"""Seconds in the backend for the window's programs (the train step): an XLA
compile, or a load from the persistent cache (``compile.program_s{phase=
backend, cache=hit|miss|none}`` of the program's own record; ``compile_s``
clocks the same call from outside). The value is the seconds; which of the
three it was goes to ``notes["step_backend_cache"]`` (``none``: compiled,
and too fast for jax to cache)."""

from benchmark.lib import manifest


def read(obs):
    found = manifest.module("layer_metrics", "step_trace_s").step_phase_s(
        obs, "backend")
    if found is None:
        return None
    obs.notes["step_backend_cache"] = found
    return sum(found.values())
