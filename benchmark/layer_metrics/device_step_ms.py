"""Device-busy milliseconds per optimizer step: the union of the intervals
in which an operation ran on the device, from the trace, over the steps of
the traced window, averaged over the chips."""

from benchmark.lib.readers import device_ms_per_step


def read(obs):
    return device_ms_per_step(obs, "busy_ns")
