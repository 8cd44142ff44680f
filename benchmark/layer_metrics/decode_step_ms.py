"""Median milliseconds of an engine step that admitted nothing: one decode
over the running batch plus the host work per token. Host clock around
``eng.step()``."""

from benchmark.lib.readers import series


def read(obs):
    value = series(obs, "decode_step_s")
    return None if value is None else 1e3 * value
