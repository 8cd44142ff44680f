"""What the readers of the serve engine's step log share (PR 50).

The engine keeps one record a ``step()`` in memory (``eng.step_log``,
``tpu_sandbox/serve/steplog.py``): wall and thread-CPU seconds, the seconds
of the six phases that tile the step, the collector's runs, and a second
ring of the steps that stalled. The window's steps are the **last
``obs.facts["window_steps"]`` records**: after the window the runners call
``settle()`` and ``drain_to_requests()``, never ``step()``. That is checked
step by step: the benchmark's own span round ``eng.step()`` contains the
engine's span, so a record may not be longer than the span it is laid
beside; where more than ``LONGER`` of them are, the records are other steps
than the window's and the run is not ``correct``. (A rule on the two sums
cannot do this: a shift by one step among steps that are alike moves a sum
by nothing, and the two clocks lie a constant apart -- the benchmark's span
holds the log's own ``begin`` and ``end`` -- that was 113 us a step on the
chip's host, 1.5 % of GPT-2's step: my chip run, PR 50.) A program that
keeps no log (the parent of PR 50) gives ``None`` to every reader.
"""

from __future__ import annotations

import statistics

from benchmark.lib.observe import Observations

#: the share of the window's records that may read longer than the
#: benchmark's span beside them (the two clocks' own noise; aligned, none is)
LONGER = 0.05


def _engine():
    """The process's one engine that stepped, through the accessor that
    does not ask whether it is busy (a cell's engine is drained by then)."""
    from tpu_sandbox.serve import engine

    found = getattr(engine, "engines", None)
    logged = [e for e in (found() if found else [])
              if getattr(e, "step_log", None) is not None
              and e.step_log.logged]
    return max(logged, key=lambda e: e.step_log.logged, default=None)


def window(obs: Observations) -> dict | None:
    """``{"records": [...], "stalls": [...]}`` of the measured steps, or
    ``None`` where there is no log to read. Read once a run."""
    if "_engine_window" not in vars(obs):
        obs._engine_window = _read_window(obs)
    return obs._engine_window


def _read_window(obs: Observations) -> dict | None:
    steps = int(obs.facts.get("window_steps") or 0)
    eng = _engine()
    if eng is None or not steps:
        return None
    records = list(eng.step_log.steps)[-steps:]
    if len(records) < steps:
        obs.problem(f"the engine's step log holds {len(records)} records "
                    f"for a window of {steps} steps")
        return None
    spans = obs.spans.get("eng.step", [])
    longer = sum(r.wall_s > span for r, span in zip(records, spans))
    if len(spans) != steps or longer > LONGER * steps:
        obs.problem(f"the step log's last {steps} records are not the "
                    f"window's steps: {longer} of them are longer than the "
                    f"benchmark's own span round eng.step() ({len(spans)} "
                    "spans)")
    first = records[0].step
    return {"records": records,
            "stalls": [s for s in eng.step_log.stalls if s["step"] >= first]}


def mean_ms(obs: Observations, *fields: str) -> float | None:
    """Mean over the window's steps of the sum of ``fields``, in ms."""
    found = window(obs)
    if found is None:
        return None
    return 1e3 * statistics.fmean(
        sum(getattr(r, f) for f in fields) for r in found["records"])


def sum_ms(obs: Observations, field: str) -> float | None:
    found = window(obs)
    if found is None:
        return None
    return 1e3 * sum(getattr(r, field) for r in found["records"])


def slowest(obs: Observations, count: int = 5) -> list[dict] | None:
    """The window's ``count`` slowest steps, slowest first: the step's place
    in the window, its milliseconds, and the phase that lies furthest over
    that phase's median in the window (a stalled step's: the stall's)."""
    found = window(obs)
    if found is None:
        return None
    from tpu_sandbox.serve.steplog import PHASES   # there is a log: it exists

    records = found["records"]
    medians = {p: statistics.median(getattr(r, f"{p}_s") for r in records)
               for p in PHASES}
    named = {s["step"]: s["phase"] for s in found["stalls"]}
    first = records[0].step
    rows = []
    for r in sorted(records, key=lambda r: -r.wall_s)[:count]:
        over = {p: getattr(r, f"{p}_s") - medians[p] for p in PHASES}
        rows.append({"step": r.step - first, "ms": 1e3 * r.wall_s,
                     "phase": named.get(r.step, max(over, key=over.get))})
    return rows


def stalls(obs: Observations) -> list[dict] | None:
    """Every stall record of the window (``[]`` in a healthy one)."""
    found = window(obs)
    return None if found is None else found["stalls"]
