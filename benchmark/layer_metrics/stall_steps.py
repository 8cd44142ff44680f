"""Stalled steps in the window: steps whose wall time (less admissions, a
decode call) passed 1.5 x the median of the 64 before them, as the engine
itself counted them (``engine.stalls{phase}``); 0 in a healthy window."""

from benchmark.lib.engine_steps import stalls


def read(obs):
    found = stalls(obs)
    return None if found is None else len(found)
