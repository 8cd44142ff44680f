"""Median milliseconds of a measured engine step, host clock around
``eng.step()``: the steadier statistic beside the end-to-end
``decode_step_ms``, which is the window's whole time over its steps (in the
open-loop runner: of the steps that admitted nothing)."""

from benchmark.lib.readers import series


def read(obs):
    value = series(obs, "decode_step_s")
    return None if value is None else 1e3 * value
