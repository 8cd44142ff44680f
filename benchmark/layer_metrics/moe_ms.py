"""Device milliseconds per step under the expert layers (``block{i}/moe``):
router, dispatch, the experts' products, combine and the shared expert."""

from benchmark.lib.readers import scope_ms


def read(obs):
    return scope_ms(obs, r"/moe/")
