"""Seconds jax spent lowering the window's programs (the train step) from
jaxpr to an MLIR module, Pallas kernels to Mosaic included:
``compile.program_s{phase=lower}`` of the program's own record, read as
``step_trace_s`` reads the trace phase. Which of the two holds
``trace_lower_s`` is what sizes ``ROADMAP.md`` A8(b): jitting a kernel's call
(``_traced_once``) saves traces, not necessarily lowerings."""

from benchmark.lib import manifest


def read(obs):
    found = manifest.module("layer_metrics", "step_trace_s").step_phase_s(
        obs, "lower")
    return None if found is None else sum(found.values())
