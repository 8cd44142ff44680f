"""Device milliseconds per optimizer step that a chip spends on the gradient
sync and on nothing else, in whichever form the compiler gave the
collective. A synchronous ``all-reduce`` is what ``allreduce_exposed_ms``
reads, and that part is taken from the same numbers. An asynchronous one is
a pair of ``fusion`` events, ``async-collective-start`` (the issue) and
``async-collective-done`` (the wait for what the kernels between them did
not hide), whose names ``trace_reduce.COLLECTIVE`` does not know: their
time is added here. Operations on a chip's ``XLA Ops`` line run one after
another, so a start's or a done's own time is time nothing else ran in.
Where a step holds no such pair this is ``allreduce_exposed_ms``. A later
``benchmark`` PR that teaches ``COLLECTIVE`` the pair's names retires this
reader; with both, the pair would count twice."""

import re

from benchmark.lib.readers import device_ms_per_step

ASYNC_PAIR = re.compile(r"^async-collective-(start|done)(\.\d+)*$")


def read(obs):
    exposed = device_ms_per_step(obs, "collective_exposed_ns")
    if exposed is None:
        return None
    devices = obs.trace["devices"]
    pair_ns = sum(ns for d in devices for name, ns in d["by_op_ns"].items()
                  if ASYNC_PAIR.match(name))
    return exposed + pair_ns / len(devices) / obs.attempted / 1e6
