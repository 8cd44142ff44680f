"""Device milliseconds per optimizer step in which a collective ran and no
other operation did: the part of the gradient all-reduce that no compute
hides. At most ``allreduce_ms``."""

from benchmark.lib.readers import device_ms_per_step


def read(obs):
    return device_ms_per_step(obs, "collective_exposed_ns")
