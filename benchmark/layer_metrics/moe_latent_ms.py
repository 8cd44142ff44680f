"""Device milliseconds per step in the projections into and out of the
experts' latent (``block{i}/moe/latent_down``, ``latent_up``)."""

from benchmark.lib.readers import scope_ms


def read(obs):
    return scope_ms(obs, r"/moe/latent_(down|up)")
