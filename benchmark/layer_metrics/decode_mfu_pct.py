"""The whole decode step's share of the chip's peak: the least time the
chip could take for what the mean measured step **needs**
(``lib/gpt2_serve_counts.py``: the larger of its FLOPs over the bf16 peak
and its bytes over the memory's, counted on the live contexts the traffic
drew) over the time the step took end to end (``decode_step_ms``, host
clock). Bytes bind at this size. It bounds every kernel's claim: a later
PR that takes the gathers off the path leaves ``gather_ctx_ms`` silent and
can claim a gain only while this share rises."""

from benchmark.lib import gpt2_serve_counts, peaks
from benchmark.lib.serve_readers import step_s


def read(obs):
    took = step_s(obs)
    flops = obs.facts.get("decode_flops_per_step")
    if took is None or flops is None:
        return None
    least = gpt2_serve_counts.roofline_s(
        flops, obs.facts["decode_bytes_per_step"], peaks.peak(obs.device_kind))
    return 100.0 * least / took
