"""Device milliseconds per step in the routed experts' products
(``block{i}/moe/experts``): the grouped matrix products over the whole row
buffer with the gate's activation between them."""

from benchmark.lib.readers import scope_ms


def read(obs):
    return scope_ms(obs, r"/moe/experts")
