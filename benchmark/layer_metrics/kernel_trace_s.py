"""Seconds of tracing spent at kernel call sites outside the model's init:
the sum of the program's ``trace:kernel`` spans (registry histograms
``trace.kernel_s{kernel=..., under=...}``,
``ops/pallas_common.py::kernel_site``), each round the place where a
kernel's ``pl.pallas_call`` is built and applied — the kernel body's jaxpr
trace, its specs, the tile rule — and ended before the kernel is lowered.
The train step's sites, and those of the check's programs.

Sites ``under=setup:model_init`` are left out and go to
``notes["kernel_sites_in_init"]``: where the init runs op by op each is an
eager call whose span also holds the kernel's compile-or-load and dispatch,
and either way their seconds are inside ``model_init_s`` (their compile
phases inside ``init_compile_s``). ``notes["kernel_sites"]`` has sites and
seconds by kernel for the rest. ``None`` where the program has no such
span."""

import re

_SERIES = re.compile(r"^trace\.kernel_s\{kernel=([^,]+),under=([^}]+)\}$")
INIT = "setup:model_init"


def by_kernel(in_init: bool = False) -> dict[str, dict]:
    """kernel -> ``{"sites", "seconds"}`` over the sites outside the init
    (inside it with ``in_init``)."""
    from tpu_sandbox.obs import get_registry

    out: dict[str, dict] = {}
    for key, h in get_registry().snapshot()["histograms"].items():
        m = _SERIES.match(key)
        if m and h["count"] and (m.group(2) == INIT) == in_init:
            row = out.setdefault(m.group(1), {"sites": 0, "seconds": 0.0})
            row["sites"] += h["count"]
            row["seconds"] += h["sum"]
    return out


def read(obs):
    kernels, in_init = by_kernel(), by_kernel(in_init=True)
    if not kernels and not in_init:
        return None
    obs.notes["kernel_sites"] = kernels
    obs.notes["kernel_sites_in_init"] = in_init
    return sum(k["seconds"] for k in kernels.values())
