"""Device milliseconds a decode step under the dense gated MLPs
(``block{i}/mlp0/`` and ``block{i}/mlp1/``: gate, up, down): weight
streaming, 453 MB a double layer."""

from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs):
    return scope_ms_a_step(obs, r"/mlp[01]/")
