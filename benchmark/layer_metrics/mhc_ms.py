"""Device milliseconds per step under the hyper-connection modules
(``block{i}/mhc_attn``, ``block{i}/mhc_ffn``): the token-wide norm, the
coefficients with their Sinkhorn iterations, and both mixes."""

from benchmark.lib.readers import scope_ms


def read(obs):
    return scope_ms(obs, r"/mhc_")
