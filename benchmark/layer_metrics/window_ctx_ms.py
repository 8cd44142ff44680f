"""Device milliseconds a decode step under ``gather_ctx/window``: the window
attention layers' read of every session's last ``sliding_window`` positions
through the rings (``serve/decode.py::_attend``, ``kind="window"``)."""

from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs):
    if "window_ctx_bytes_per_step" not in obs.facts:
        return None   # a program without layer kinds has no such scope
    return scope_ms_a_step(obs, r"/gather_ctx/window(/|$)")
