"""Device milliseconds a decode step under the latent-attention sub-layers
(``block{i}/mla0`` and ``block{i}/mla1``, both whole): the low-rank query
and key/value paths with their norms and scales, RoPE at the row's position,
the absorbed query, the latent row's write, the read of the cached rows with
both products and the softmax (``gather_ctx``), the unabsorbed output and
the output projection."""

from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs):
    return scope_ms_a_step(obs, r"/mla[01](/|$)")
