"""Model FLOP/s utilisation: the FLOPs the forward and backward passes need
for a step (``lib/peaks.py``; recomputation not counted) times steps per
second, over chips times the bf16 peak of the exact ``device_kind``. It is
an end-to-end utilisation, not a kernel's roofline share."""

from benchmark.lib import peaks
from benchmark.lib.train_window import train_step_ms


def read(obs):
    step_ms = train_step_ms(obs)
    flops = obs.facts.get("flops_per_step")
    if step_ms is None or flops is None:
        return None
    return peaks.mfu_pct(flops, step_ms / 1e3, obs.device_kind,
                         obs.cell["chips"])
