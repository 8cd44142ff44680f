"""Milliseconds per step inside Pallas kernels: device time of the trace's
``custom-call`` events whose instruction the compiled program marks as a
``pallas_call`` (``observe.pallas_instructions``), averaged over the chips.
If the trace names none of the marked instructions there is nothing to
read: the metric is left out and the run says why."""


def read(obs):
    if obs.trace is None or not obs.op_scopes or not obs.attempted:
        return None
    devices = obs.trace["devices"]
    kernels = {n for d in devices for n in d["custom_calls"]} & set(obs.op_scopes)
    if not kernels:
        obs.problem("pallas_ms: the trace's custom-call names and the "
                    "compiled program's pallas_call instructions share none")
        return None
    ns = sum(t for d in devices for name, t in d["by_op_ns"].items()
             if name in kernels)
    return ns / len(devices) / obs.attempted / 1e6
