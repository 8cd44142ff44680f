"""The state-space scan's share of its roofline: the least time the chip
could take for what the scan needs -- the larger of its FLOPs
(``nemotron_h_counts.ssd_flops``) over the bf16 peak and its bytes
(``ssd_bytes``: x, B, C, dt read and y written forward, twice that backward)
over the HBM bandwidth; at this cut the bytes bind -- over ``ssd_ms``. Read on
the scope, not on a ``pallas_call``: the same work whatever implements it.
Recomputation is not counted, so it cannot pass 100 %."""

from benchmark.lib import peaks
from benchmark.lib.readers import scope_ms


def read(obs):
    flops = obs.facts.get("ssd_flops_per_step")
    needed = obs.facts.get("ssd_bytes_per_step")
    ms = None if flops is None or needed is None else scope_ms(obs, r"/mamba/ssd")
    if ms is None:
        return None
    peak, chips = peaks.peak(obs.device_kind), obs.cell["chips"]
    least_s = max(flops / (chips * peak["bf16_flops_per_s"]),
                  needed / (chips * peak["hbm_bytes_per_s"]))
    return 100.0 * least_s / (ms / 1e3)
