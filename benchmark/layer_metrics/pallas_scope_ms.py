"""Milliseconds per step in the operations the compiled program scopes
under a ``pallas_call``, averaged over the chips: the Pallas kernels,
whether the trace shows one as its own ``custom-call`` event (what
``pallas_ms`` counts) or as the ``fusion`` event that wraps it with a share
of an asynchronous collective and takes its name and its ``op_name`` (an
``async_collective_fusion``: the kernel inside is renamed and no event
names it). With the kernels come the reductions of their per-channel
outputs, a few microseconds each, which carry the same scope. Where no
kernel is wrapped this is ``pallas_ms`` plus those."""

from benchmark.lib.readers import scope_ms


def read(obs):
    return scope_ms(obs, r"/pallas_call$")
