"""Kernel call sites traced outside the model's init: the count of the
program's ``trace:kernel`` spans that ``kernel_trace_s`` sums. A plain
kernel fires once a site, a kernel whose call is a jitted function
(``pallas_mhc``'s ``_traced_once``) once a shape: against the compiled
step's ``pallas_calls`` that is what jitting the calls saves."""

from benchmark.lib import manifest


def read(obs):
    reader = manifest.module("layer_metrics", "kernel_trace_s")
    kernels = reader.by_kernel()
    if not kernels and not reader.by_kernel(in_init=True):
        return None
    return sum(k["sites"] for k in kernels.values())
