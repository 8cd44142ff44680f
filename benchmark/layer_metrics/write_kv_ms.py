"""Device milliseconds a decode step under the ``write_kv`` scopes of
``serve/decode.py``: every layer's scatter of the new token's key and value
into the pages."""

from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs):
    return scope_ms_a_step(obs, r"/write_kv(/|$)")
