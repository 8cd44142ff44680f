"""Device milliseconds per step in the routers (``block{i}/moe/router``):
the float32 scores over all experts, the top-k, the chosen scores and the
per-expert counts; forward, recomputation and backward."""

from benchmark.lib.readers import scope_ms


def read(obs):
    return scope_ms(obs, r"/moe/router")
