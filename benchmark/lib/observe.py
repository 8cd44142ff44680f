"""What one run observed: host-clock spans the benchmark put around its
calls into each layer, counters, and (traced runs) the reduced device
trace. Per-layer metric readers (``benchmark/layer_metrics/<name>.py``)
take everything from here."""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

#: prefix of the benchmark's own ``TraceAnnotation`` s in the profiler trace
PREFIX = "bench:"
WINDOW = PREFIX + "window"


@dataclass
class Observations:
    cell: dict
    seed: int
    seconds: float
    traced: bool
    device_kind: str = ""
    #: name -> durations in seconds, host clock, in the measured window
    spans: dict[str, list[float]] = field(default_factory=dict)
    #: name -> one number (set-up phases in seconds, counts, sizes)
    facts: dict[str, float] = field(default_factory=dict)
    #: name -> samples that are not durations (occupancy per step, ...)
    series: dict[str, list[float]] = field(default_factory=dict)
    #: ``trace_reduce.reduce`` of the traced window, or None
    trace: dict | None = None
    #: HLO instruction name -> ``op_name`` scope, from the compiled programs
    op_scopes: dict[str, str] = field(default_factory=dict)
    problems: list[str] = field(default_factory=list)
    notes: dict = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    in_window: bool = False
    compiles_in_window: int = 0

    @contextlib.contextmanager
    def span(self, name: str, *, record: bool = True):
        """Host clock plus a ``TraceAnnotation`` of the same name, so an
        idle gap on the device can be named by what the host was doing."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield
        if record:
            self.spans.setdefault(name, []).append(time.perf_counter() - t0)

    def add(self, name: str, value: float) -> None:
        self.series.setdefault(name, []).append(value)

    def problem(self, text: str) -> None:
        """A failed correctness check: the run goes on, ``correct`` is false."""
        self.problems.append(text)


def pallas_instructions(hlo_text: str) -> dict[str, str]:
    """Instruction name -> scope for the Pallas kernels of a compiled
    module: custom calls whose ``op_name`` ends in ``pallas_call`` (copied
    from ``tpu_sandbox.utils.flops.pallas_call_paths``)."""
    import re

    out = {}
    for line in hlo_text.splitlines():
        if not re.search(r"= [^=]*custom-call\(", line):
            continue
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        n = re.search(r'op_name="([^"]*)"', line)
        if m and n and "/pallas_call" in n.group(1):
            out[m.group(1)] = n.group(1)
    return out
