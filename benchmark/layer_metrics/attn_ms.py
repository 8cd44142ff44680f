"""Device milliseconds per step under the attention modules
(``block{i}/attn``): the flash-attention kernels and the qkv / out
projections of every block, forward, recomputation and backward."""

from benchmark.lib.readers import scope_ms


def read(obs):
    return scope_ms(obs, r"/attn/")
