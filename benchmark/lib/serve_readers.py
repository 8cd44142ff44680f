"""What the readers of a serving cell's per-layer metrics share: the
measured steps of the window (the runner's facts ``window_steps`` and
``window_s``) under the readers of ``lib/readers.py``, which divide by
``obs.attempted`` -- steps in a training cell, sessions or requests here."""

from __future__ import annotations

from benchmark.lib import readers
from benchmark.lib.observe import Observations


def step_s(obs: Observations) -> float | None:
    """Seconds a measured engine step: all the window's time over all its
    steps, host clock."""
    steps = obs.facts.get("window_steps")
    return obs.facts["window_s"] / steps if steps else None


def scope_ms_a_step(obs: Observations, pattern: str) -> float | None:
    """``readers.scope_ms`` a measured engine step."""
    steps = obs.facts.get("window_steps")
    value = readers.scope_ms(obs, pattern) if steps else None
    return None if value is None else value * obs.attempted / steps


def span_ms_a_step(obs: Observations, fact: str) -> float | None:
    """Milliseconds a measured step in one of the program's own spans: the
    window's share of its registry histogram's sum (the runner's fact
    ``fact``, in seconds) over the steps. ``None`` where the program keeps
    no such span."""
    steps = obs.facts.get("window_steps")
    seconds = obs.facts.get(fact)
    return 1e3 * seconds / steps if steps and seconds else None


def outside_ms_a_step(obs: Observations, inside: str) -> float | None:
    """Device milliseconds a measured step in the operations of the noted
    programs whose ``op_name`` the regular expression ``inside`` is **not**
    found in, those with no ``op_name`` among them: what the compiler put
    between the model's own operations (copies, relayouts, what
    rematerialisation adds). Averaged over the chips."""
    import re

    from benchmark.lib.trace_reduce import OTHER_PROGRAM, joined

    steps = obs.facts.get("window_steps")
    if obs.trace is None or not steps:
        return None
    if not obs.scopes:
        obs.problem("outside the scope: the runner noted no compiled "
                    "program (Observations.note_program)")
        return None
    found = re.compile(inside).search
    ns = sum(ns for scope, ns, _ in joined(obs.trace["devices"], obs.scopes)
             if scope != OTHER_PROGRAM and not (scope and found(scope)))
    return ns / len(obs.trace["devices"]) / steps / 1e6
