"""Median share of the engine's decode slots that produced a token in a
step, in percent of ``max_batch``."""

from benchmark.lib.readers import series


def read(obs):
    return series(obs, "occupancy_pct")
