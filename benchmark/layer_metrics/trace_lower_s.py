"""Seconds tracing and lowering the cell's programs (Python; no cache helps),
host clock around ``.lower()``."""


def read(obs):
    return obs.facts.get("trace_lower_s")
