"""Median milliseconds a step waited for the program's loader: host clock
around every ``next()`` of the batch iterator the epoch loop consumes."""

from benchmark.lib.readers import span_ms


def read(obs):
    return span_ms(obs, "next_batch")
