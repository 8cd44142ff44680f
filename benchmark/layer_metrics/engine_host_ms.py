"""Milliseconds of a measured step that the engine spent outside
``engine:wait``: the host's own work a step (shedding, admission, the
capacity sweep, preparing and enqueueing the next call, emission, the loop's
glue), mean over the window's records of the engine's step log, wall less
wait. What has to stay under ``decode_device_ms`` for the step to be the
device's."""

from benchmark.lib.engine_steps import mean_ms


def read(obs):
    wall = mean_ms(obs, "wall_s")
    return None if wall is None else wall - mean_ms(obs, "wait_s")
