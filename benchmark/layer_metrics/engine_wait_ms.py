"""Milliseconds of a measured step inside the engine's span ``engine:wait``
(the blocking read of a call's picks): the slack the host has behind the
device, mean over the window's records of the engine's step log. It falls
when the device gets faster *and* when the host gets slower: read it beside
``engine_host_ms``."""

from benchmark.lib.engine_steps import mean_ms


def read(obs):
    return mean_ms(obs, "wait_s")
