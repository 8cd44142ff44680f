"""Small helpers the per-layer metric readers share. A reader is
``read(obs) -> float | None``; ``None`` means there was nothing to read and
the harness leaves the metric out of the line."""

from __future__ import annotations

import re

from benchmark.lib import stats
from benchmark.lib.observe import Observations


def span_ms(obs: Observations, name: str, q: float | None = None) -> float | None:
    """Median (or ``q``-th percentile) of a host span, in ms."""
    values = obs.spans.get(name, [])
    value = stats.median(values) if q is None else stats.percentile(values, q)
    return None if value is None else 1e3 * value


def series(obs: Observations, name: str, q: float | None = None) -> float | None:
    values = obs.series.get(name, [])
    return stats.median(values) if q is None else stats.percentile(values, q)


def device_ms_per_step(obs: Observations, key: str) -> float | None:
    """A per-device time of the trace (``busy_ns``, ``collective_ns``, ...)
    averaged over the devices and divided by the steps of the window: every
    step dispatched in the window also ended in it (the loop ends with a
    wait for the device)."""
    if obs.trace is None or not obs.attempted:
        return None
    devices = obs.trace["devices"]
    return (sum(d[key] for d in devices) / len(devices) / obs.attempted) / 1e6


def idle_pct(obs: Observations) -> float | None:
    """Share of the traced window in which no operation ran on the device,
    in percent; on several chips the worst one."""
    return None if obs.trace is None else obs.trace["idle_pct_worst"]


def module_ms(obs: Observations, pattern: str, q: float | None = None) -> float | None:
    """Median device time of the executions of the programs whose name
    holds ``pattern`` (the trace's ``XLA Modules`` line), in ms."""
    if obs.trace is None:
        return None
    values = [ns for d in obs.trace["devices"]
              for name, runs in d["modules_ns"].items() if pattern in name
              for ns in runs]
    value = stats.median(values) if q is None else stats.percentile(values, q)
    return None if value is None else value / 1e6


def _scope_sum(obs: Observations, pattern: str, column: int) -> float | None:
    """Per step and per chip, the nanoseconds (``column`` 1) or the calls
    (``column`` 2) of the trace's operations whose scope matches ``pattern``.
    An untraced run has nothing to read; a traced one whose runner noted no
    program, or whose programs hold no such scope, says which."""
    from benchmark.lib.trace_reduce import joined

    if obs.trace is None or not obs.attempted:
        return None
    if not obs.scopes:
        obs.problem(f"scope {pattern!r}: the runner noted no compiled "
                    "program (Observations.note_program)")
        return None
    match = re.compile(pattern).search
    rows = [row for row in joined(obs.trace["devices"], obs.scopes)
            if row[0] and match(row[0])]
    if not rows:
        obs.problem(f"scope {pattern!r}: no operation of the trace runs "
                    "under a scope it matches")
        return None
    return (sum(row[column] for row in rows) / len(obs.trace["devices"])
            / obs.attempted)


def scope_ms(obs: Observations, pattern: str) -> float | None:
    """Device milliseconds a step in the operations whose ``op_name`` (the
    compiled program's, joined by ``trace_reduce.joined``) the regular
    expression ``pattern`` is found in: forward, recomputation and backward
    of a module together, averaged over the chips, as ``pallas_ms``. A
    fusion counts under its root's scope (``trace_reduce``'s docstring)."""
    ns = _scope_sum(obs, pattern, 1)
    return None if ns is None else ns / 1e6


def scope_calls(obs: Observations, pattern: str) -> float | None:
    """How many such operations a step runs on a chip."""
    return _scope_sum(obs, pattern, 2)
