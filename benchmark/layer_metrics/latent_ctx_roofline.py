"""The read of the latent cache as a share of its roofline: the least time
the chip could take for what one step's attention over the cached rows
needs -- the larger of the live latent rows' bytes
(``longcat_serve_counts.latent_ctx_bytes``: 1152 B a token a sub-layer,
once; not the lanes a layout pads a row to) over the memory's peak and the
absorbed core's FLOPs (``latent_ctx_flops``: 2 x 64 x (576 + 512) a token a
sub-layer) over the bf16 peak -- over the device time under ``/gather_ctx``.
Read on the scope, not on a ``pallas_call``: the same work whatever reads
the cache, so a later kernel is judged by the same count and none can pass
100 % unless the scope misses part of the read."""

from benchmark.lib import gpt2_serve_counts, peaks
from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs):
    nbytes = obs.facts.get("latent_ctx_bytes_per_step")
    ms = None if nbytes is None else scope_ms_a_step(obs, r"/gather_ctx(/|$)")
    if not ms:
        return None
    least_s = gpt2_serve_counts.roofline_s(
        obs.facts["latent_ctx_flops_per_step"], nbytes,
        peaks.peak(obs.device_kind))
    return 100.0 * least_s / (ms / 1e3)
