"""What the window's stalled steps cost it, in milliseconds: the sum of
their excess over the reference they were judged against. To set beside the
trace's idle gap ``bench:eng.step`` and ``serve_device_idle_pct`` x the
window."""

from benchmark.lib.engine_steps import stalls


def read(obs):
    found = stalls(obs)
    return None if found is None else 1e3 * sum(s["excess_s"] for s in found)
