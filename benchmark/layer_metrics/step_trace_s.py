"""Seconds jax spent tracing the programs the window runs (the train step):
the program's own record of its launch, gauge
``compile.program_s{program, phase=trace, cache}`` of
``runtime/bootstrap.py``'s compile listener, summed over the programs whose
name is one of ``obs.scopes`` (the trace's ``jit_train_step`` is jax's
``fun_name`` ``jit(train_step)``). The step is the outermost trace, so the
``jit`` s and kernel bodies traced inside it are in this number once. With
``step_lower_s`` it is what ``trace_lower_s`` clocks from outside.

``None`` where the program keeps no such record (an older checkout), where
the runner noted no program, or where the step is not among the 32 programs
with the most seconds (it is the largest of every cell)."""

import re

_SERIES = re.compile(
    r"^compile\.program_s\{cache=(\w+),phase=(\w+),program=(.*)\}$")
_CALL = re.compile(r"^(\w+)\((.*)\)$")
_NOT_A_NAME = re.compile(r"[^\w.-]")  # jax's ``mlir.sanitize_name``


def module_name(program: str) -> str:
    """``jit(train_step)`` -> ``jit_train_step``: the name jax gives the
    compiled module, which the trace and ``obs.scopes`` use."""
    return _NOT_A_NAME.sub("_", _CALL.sub(r"\1_\2", program))


def step_phase_s(obs, phase: str) -> dict[str, float] | None:
    """``cache`` label -> seconds of ``phase`` over the window's programs."""
    from tpu_sandbox.obs import get_registry

    found: dict[str, float] = {}
    for key, seconds in get_registry().snapshot()["gauges"].items():
        m = _SERIES.match(key)
        if m and m.group(2) == phase and module_name(m.group(3)) in obs.scopes:
            found[m.group(1)] = found.get(m.group(1), 0.0) + seconds
    return found or None


def read(obs):
    found = step_phase_s(obs, "trace")
    return None if found is None else sum(found.values())
