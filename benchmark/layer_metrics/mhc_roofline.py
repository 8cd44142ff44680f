"""The hyper-connections' share of their roofline: the bytes their mixes
need (``xing4_counts.mhc_bytes``: 3 n + 2 stream-wide rows a token a
sub-layer, forward and as many backward, recomputation not counted; the
bytes bind: a mix does a few FLOPs a byte) over ``mhc_ms`` and the chips'
HBM bandwidth."""

from benchmark.lib import peaks
from benchmark.lib.readers import scope_ms


def read(obs):
    needed = obs.facts.get("mhc_bytes_per_step")
    ms = None if needed is None else scope_ms(obs, r"/mhc_")
    if ms is None:
        return None
    return 100.0 * needed / (ms / 1e3) / (
        obs.cell["chips"] * peaks.peak(obs.device_kind)["hbm_bytes_per_s"])
