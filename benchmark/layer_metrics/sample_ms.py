"""Milliseconds a measured step in the engine's own span ``engine:sample``
(registry histogram ``engine.sample_s``): the host's work a token after the
logits arrive -- the choice, its log-probability, the slot's bookkeeping."""

from benchmark.lib.serve_readers import span_ms_a_step


def read(obs):
    return span_ms_a_step(obs, "sample_s")
