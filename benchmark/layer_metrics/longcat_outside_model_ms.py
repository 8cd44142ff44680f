"""Device milliseconds a decode step in operations of the compiled decode
program that belong to no module of the model (no ``LongcatFlashLM/`` in
their ``op_name``, or no ``op_name`` at all): what the compiler put between
the model's operations -- copies of the latent pages, if a sub-layer's
update made any (``jamba_outside_model_ms`` is the same reading of
``JambaLM``'s program)."""

from benchmark.lib.serve_readers import outside_ms_a_step


def read(obs):
    return outside_ms_a_step(obs, r"/LongcatFlashLM(/|$)")
