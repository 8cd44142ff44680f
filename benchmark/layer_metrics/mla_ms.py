"""Device milliseconds per step under the latent-attention modules
(``block{i}/mla``): the low-rank projections, RoPE, the flash kernels and
the output projection, forward, recomputation and backward."""

from benchmark.lib.readers import scope_ms


def read(obs):
    return scope_ms(obs, r"/mla(/|$)")
