"""Device milliseconds a decode step under ``gather_ctx/full``: the full
attention layers' read of every session's whole cached context through the
block tables (``serve/decode.py::_attend``, ``kind="full"``)."""

from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs):
    if "full_ctx_bytes_per_step" not in obs.facts:
        return None   # a program without layer kinds has no such scope
    return scope_ms_a_step(obs, r"/gather_ctx/full(/|$)")
