"""The held experts' products as a share of their roofline: the least time
the chip could take for the held experts' matrices read once and the rows
the router gave them in and out
(``longcat_serve_counts.experts_bytes`` / ``experts_flops``: bytes bind, an
expert's 75 MB against two rows) over ``serve_moe_experts_ms``. A tile of
zero rows, or an expert's matrices read for a second tile, count in the
time and not in the need, so it cannot pass 100 %."""

from benchmark.lib import gpt2_serve_counts, peaks
from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs):
    nbytes = obs.facts.get("moe_experts_bytes_per_step")
    ms = None if nbytes is None else scope_ms_a_step(
        obs, r"/moe/experts(/|$)")
    if not ms:
        return None
    least_s = gpt2_serve_counts.roofline_s(
        obs.facts["moe_experts_flops_per_step"], nbytes,
        peaks.peak(obs.device_kind))
    return 100.0 * least_s / (ms / 1e3)
