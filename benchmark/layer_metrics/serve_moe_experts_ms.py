"""Device milliseconds a decode step under ``block{i}/moe/experts``: the
held experts' three grouped products over the share's row buffer, every
tile multiplied whatever it holds."""

from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs):
    return scope_ms_a_step(obs, r"/moe/experts(/|$)")
