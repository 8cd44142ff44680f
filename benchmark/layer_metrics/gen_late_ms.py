"""99th percentile of how late the load generator submitted a request after
its due time, in ms. The generator shares the engine's thread, so this is
bounded by one engine step; a request is timed from its due time whatever
this says."""

from benchmark.lib.readers import series


def read(obs):
    value = series(obs, "gen_late_s", 99)
    return None if value is None else 1e3 * value
