"""Device milliseconds a decode step under the ``mamba`` scopes of
``models/jamba.py``: every Mamba mixer whole -- its projections, the
convolution's step, the three inner norms, the state's update, the gate --
without the layer's MLP and norms (a fusion counts under its root's
scope)."""

from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs):
    return scope_ms_a_step(obs, r"/mamba(/|$)")
