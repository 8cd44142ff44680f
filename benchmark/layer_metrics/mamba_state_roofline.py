"""The recurrent state's update as a share of its roofline: the least time
the memory could take for the bytes one step's update needs
(``jamba_serve_counts.state_update_bytes``: every live slot's scan state
read and written once in every Mamba layer, its x, dt, B, C read, y
written; the runner's fact ``mamba_state_bytes_per_step``) over
``mamba_state_ms``. Bytes bind: the update is about 7 operations a state
element of 8 bytes moved. Read on the scope, not on a kernel: the same work
whatever implements it. A copy of the state counts in the time and not in
the bytes, so it cannot pass 100 % unless the scope misses part of the
update."""

from benchmark.lib import peaks
from benchmark.lib.serve_readers import scope_ms_a_step


def read(obs):
    needed = obs.facts.get("mamba_state_bytes_per_step")
    ms = None if needed is None else scope_ms_a_step(obs, r"/ssm_step(/|$)")
    if not ms:
        return None
    least_s = needed / peaks.peak(obs.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
