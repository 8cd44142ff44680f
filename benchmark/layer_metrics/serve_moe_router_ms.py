"""Device milliseconds a decode step under ``block{i}/moe/router``: the
float32 product over all 768 outputs at ``HIGHEST``, the softmax, the
top-k of probability + bias, the chosen probabilities and the counts."""

from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs):
    return scope_ms_a_step(obs, r"/moe/router(/|$)")
