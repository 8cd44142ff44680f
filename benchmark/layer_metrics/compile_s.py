"""Seconds in ``.compile()`` of the cell's programs: XLA compile when cold, a
load from the persistent cache when warm. Host clock."""


def read(obs):
    return obs.facts.get("compile_s")
