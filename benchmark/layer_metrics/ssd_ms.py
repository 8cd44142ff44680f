"""Device milliseconds per step in the state-space scan itself (the scope
``ssd`` that ``ops/ssd.py`` opens, whatever implements it): forward,
recomputation and backward."""

from benchmark.lib.readers import scope_ms


def read(obs):
    return scope_ms(obs, r"/mamba/ssd")
