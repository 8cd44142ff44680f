"""Median gap between consecutive tokens of one request, in ms, stamped when
the step that produced them returned: the decode step as a caller feels
it."""

from benchmark.lib.readers import series


def read(obs):
    value = series(obs, "itl_s")
    return None if value is None else 1e3 * value
