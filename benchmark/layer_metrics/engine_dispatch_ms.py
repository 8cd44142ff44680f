"""Milliseconds of a measured step inside the engine's span
``engine:dispatch``: preparing and enqueueing a decode call (the numpy
lengths and tables, their host-to-device copies, the jit call; it waits for
nothing), mean over the window's records of the engine's step log."""

from benchmark.lib.engine_steps import mean_ms


def read(obs):
    return mean_ms(obs, "dispatch_s")
