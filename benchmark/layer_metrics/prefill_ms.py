"""Median device milliseconds of one execution of a compiled prefill
program, from the trace's ``XLA Modules`` line. (The program's own ``prefill``
span closes when the dispatch returns, before the device finishes, so it is
not read.)"""

from benchmark.lib.readers import module_ms


def read(obs):
    return module_ms(obs, "prefill")
