"""Milliseconds of a measured step in the scheduler's and the allocator's
phases: the engine's spans ``engine:shed`` + ``engine:admit`` +
``engine:grow`` (deadlines, the waiting queue, the capacity sweep over every
slot with ``cache.grow``), mean over the window's records of the engine's
step log."""

from benchmark.lib.engine_steps import mean_ms


def read(obs):
    return mean_ms(obs, "shed_s", "admit_s", "grow_s")
