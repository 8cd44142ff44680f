"""The window's slowest engine step in milliseconds, by the engine's own
step log; ``notes["slowest_steps_ms"]`` gets the five slowest with the phase
that held each."""

from benchmark.lib.engine_steps import slowest


def read(obs):
    rows = slowest(obs)
    if not rows:
        return None
    obs.notes["slowest_steps_ms"] = rows
    return rows[0]["ms"]
