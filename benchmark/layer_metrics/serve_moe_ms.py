"""Device milliseconds a decode step under the served expert share
(``block{i}/moe/``: router, dispatch, experts, combine, zero)."""

from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs):
    return scope_ms_a_step(obs, r"/moe/")
