"""Device milliseconds per optimizer step inside collectives (the gradient
all-reduce of the data-parallel step): the union of the trace's events whose
instruction is a collective opcode, averaged over the chips. The four-chip
cell alone has any."""

from benchmark.lib.readers import device_ms_per_step


def read(obs):
    return device_ms_per_step(obs, "collective_ns")
