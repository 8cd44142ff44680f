"""The routed experts' share of their roofline: three products of
2 x 3584 x 1024 FLOPs a row over the R rows the share's buffer stands for,
forward and twice that backward (``xing4_counts.expert_flops``; the FLOPs
bind: a weight tile is reused by 256 rows), over ``moe_experts_ms`` and the
bf16 peak. Counted on R, not on the rows a seed happens to route here nor
on the alignment tiles, so it cannot pass 100 %."""

from benchmark.lib import peaks
from benchmark.lib.readers import scope_ms


def read(obs):
    flops = obs.facts.get("moe_expert_flops_per_step")
    ms = None if flops is None else scope_ms(obs, r"/moe/experts")
    if ms is None:
        return None
    return peaks.mfu_pct(flops, ms / 1e3, obs.device_kind, obs.cell["chips"])
