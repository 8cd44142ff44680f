"""The flash-attention kernels' share of their roofline under latent
attention: the causal FLOPs a step needs at head sizes 192 (q.k) and 128
(v) (``xing4_counts.causal_attention_train_flops``: forward once, backward
2.5 times, recomputation and masked blocks not counted; the FLOPs bind)
over the device time of the Pallas calls under ``/mla/`` and the bf16 peak."""

from benchmark.lib import peaks
from benchmark.lib.readers import scope_ms


def read(obs):
    flops = obs.facts.get("mla_core_flops_per_step")
    kernel_ms = None if flops is None else scope_ms(obs, r"/mla/pallas_call")
    if kernel_ms is None:
        return None
    return peaks.mfu_pct(flops, kernel_ms / 1e3, obs.device_kind,
                         obs.cell["chips"])
