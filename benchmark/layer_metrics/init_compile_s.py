"""Seconds of tracing, lowering and backend (compile or cache load) spent
while the train state was made and placed: the sums of the program's
histograms ``compile.trace_s``, ``compile.lower_s``, ``compile.backend_s``
under ``setup:model_init``, ``setup:opt_init`` and ``place:state``. Each
observes a phase less the phases inside it, so the sum is wall time, counted
once. Against ``model_init_s`` + ``opt_init_s`` + ``state_place_s`` it says
how much of the init is getting programs ready and how much is running them.
``notes["init_compile_phases"]`` has the three apart."""

from benchmark.lib import manifest


def read(obs):
    phases = manifest.module("layer_metrics", "init_programs").phases_under()
    if phases is None:
        return None
    obs.notes["init_compile_phases"] = phases
    return sum(p["sum"] for p in phases.values())
