"""Milliseconds the collector ran inside the window's engine steps (any
generation, any thread), from the step log's differences of the one
``gc.callbacks`` hook's totals."""

from benchmark.lib.engine_steps import sum_ms


def read(obs):
    return sum_ms(obs, "gc_s")
