"""The flash-attention kernels' share of their roofline: the FLOPs of the
causal attention a step needs (``peaks.causal_attention_train_flops``:
forward once, backward 2.5 times, recomputation and masked blocks not
counted; the FLOPs bind at this size, not the bytes) over the device time
of the Pallas calls under ``/attn/`` and the chips' bf16 peak."""

from benchmark.lib import peaks
from benchmark.lib.readers import scope_ms


def read(obs):
    flops = obs.facts.get("attn_flops_per_step")
    kernel_ms = None if flops is None else scope_ms(obs, r"/attn/pallas_call")
    if kernel_ms is None:
        return None
    return peaks.mfu_pct(flops, kernel_ms / 1e3, obs.device_kind,
                         obs.cell["chips"])
