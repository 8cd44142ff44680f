"""Device milliseconds a decode step in operations of the compiled decode
program that belong to no module of the model (no ``TransformerLM/`` in
their ``op_name``, or no ``op_name`` at all): what the compiler put between
the model's operations. Today that is whole copies of the page buffers --
the arguments' copies and, twice a layer, the pair of copies
rematerialisation's compression adds under memory pressure
(``fusion.N.remat_uncompressed``) -- and most of the step (PERF.md section
5); it grows with the pool, not with the live tokens."""

from benchmark.lib.serve_readers import outside_ms_a_step


def read(obs):
    return outside_ms_a_step(obs, r"/TransformerLM(/|$)")
