"""Of the window's stalled steps, the milliseconds the engine's thread spent
off the CPU outside ``engine:wait`` -- descheduled or blocked, not busy
(wall less wait, less the thread's CPU time over the step). 0.0 without a
stall; ``notes["stalls"]`` gets every stall record of the window."""

from benchmark.lib.engine_steps import stalls


def read(obs):
    found = stalls(obs)
    if found is None:
        return None
    obs.notes["stalls"] = found
    return 1e3 * sum(s["offcpu_outside_wait_s"] for s in found)
