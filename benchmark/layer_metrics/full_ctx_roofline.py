"""The full layers' read of the cache as a share of its roofline: the least
time the chip could take for what one step's attention over the cached rows
needs -- the larger of the live rows' bytes
(``laguna_serve_counts.ctx_bytes``: ``length`` rows of 4096 B a session a
full layer, once) over the memory's peak and the core's FLOPs
(``ctx_flops``: 4 x 48 x 128 a row a layer) over the bf16 peak -- over the
device time under ``/gather_ctx/full``. Read on the scope, not on a
``pallas_call``, as ``latent_ctx_roofline`` is: the same work whatever reads
the cache, so a later kernel is judged by the same count and none can pass
100 % unless the scope misses part of the read."""

from benchmark.lib import gpt2_serve_counts, peaks
from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs, kind: str = "full"):
    nbytes = obs.facts.get(f"{kind}_ctx_bytes_per_step")
    ms = None if nbytes is None else scope_ms_a_step(
        obs, rf"/gather_ctx/{kind}(/|$)")
    if not ms:
        return None
    least_s = gpt2_serve_counts.roofline_s(
        obs.facts[f"{kind}_ctx_flops_per_step"], nbytes,
        peaks.peak(obs.device_kind))
    return 100.0 * least_s / (ms / 1e3)
