"""Device milliseconds a decode step under the ``gather_ctx`` scopes of
``serve/decode.py``: every layer's gather of each slot's keys and values
out of the pages, at the cache's ``max_context`` whatever the live lengths
(a fusion counts under its root's scope)."""

from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs):
    return scope_ms_a_step(obs, r"/gather_ctx(/|$)")
