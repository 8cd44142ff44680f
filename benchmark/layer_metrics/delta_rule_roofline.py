"""The delta rule's share of its roofline: the least time the chip could
take for what the rule needs -- the larger of its FLOPs
(``olmo_hybrid_counts.delta_rule_flops``: the recurrence's own 7 d_k d_v a
token a head, three times with the backward) over the bf16 peak and its
bytes (``delta_rule_bytes``: q, k, v, g, beta read and o written forward,
twice that backward) over the HBM bandwidth; at this cut the bytes bind --
over ``delta_rule_ms``. Read on the scope, not on a ``pallas_call``: the same
work whatever implements it. What a chunked form computes beyond the
recurrence, and recomputation, are not counted, so it cannot pass 100 %."""

from benchmark.lib import peaks
from benchmark.lib.readers import scope_ms


def read(obs):
    flops = obs.facts.get("delta_rule_flops_per_step")
    needed = obs.facts.get("delta_rule_bytes_per_step")
    ms = (None if flops is None or needed is None
          else scope_ms(obs, r"/gdn/delta_rule"))
    if ms is None:
        return None
    peak, chips = peaks.peak(obs.device_kind), obs.cell["chips"]
    least_s = max(flops / (chips * peak["bf16_flops_per_s"]),
                  needed / (chips * peak["hbm_bytes_per_s"]))
    return 100.0 * least_s / (ms / 1e3)
