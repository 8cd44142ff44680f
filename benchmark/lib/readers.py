"""Small helpers the per-layer metric readers share. A reader is
``read(obs) -> float | None``; ``None`` means there was nothing to read and
the harness leaves the metric out of the line."""

from __future__ import annotations

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
