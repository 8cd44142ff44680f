"""Milliseconds a measured step in the engine's own span
``engine:decode_call`` (registry histogram ``engine.decode_call_s``): from
the dispatch of the compiled decode program to its logits on the host."""

from benchmark.lib.serve_readers import span_ms_a_step


def read(obs):
    return span_ms_a_step(obs, "decode_call_s")
