"""Device milliseconds per step under the state-space mixers
(``block{i}/mamba``): input and output projections, the causal convolution,
the scan and the gated norm, forward, recomputation and backward."""

from benchmark.lib.readers import scope_ms


def read(obs):
    return scope_ms(obs, r"/mamba/")
