"""90th percentile of the time from a request's due time to the start of the
step that admitted it, in ms."""

from benchmark.lib.readers import series


def read(obs):
    value = series(obs, "queue_wait_s", 90)
    return None if value is None else 1e3 * value
