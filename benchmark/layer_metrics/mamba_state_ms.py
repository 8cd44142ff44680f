"""Device milliseconds a decode step under the ``ssm_step`` scopes of
``models/jamba.py``: the scan's one-token update of every live slot's
state in every Mamba layer (a fusion counts under its root's scope)."""

from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs):
    return scope_ms_a_step(obs, r"/ssm_step(/|$)")
