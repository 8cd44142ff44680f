"""Median device milliseconds of one execution of the compiled decode
program, from the trace's ``XLA Modules`` line: what is left of
``decode_step_ms`` is the host's."""

from benchmark.lib.readers import module_ms


def read(obs):
    return module_ms(obs, "decode")
