"""What one run observed: host-clock spans the benchmark put around its
calls into each layer, counters, and (traced runs) the reduced device
trace. Per-layer metric readers (``benchmark/layer_metrics/<name>.py``)
take everything from here."""

from __future__ import annotations

import contextlib
import re
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
    #: program -> instruction name -> ``op_name``, of every compiled program
    #: the window runs (``note_program``): what the trace's events are
    #: joined with for time by scope
    scopes: dict[str, dict[str, str]] = field(default_factory=dict)
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

    def note_program(self, hlo_text: str) -> None:
        """A compiled program the window runs, by its text
        (``compiled.as_text()``), in one pass: its instructions' scopes, under
        the name the trace's ``XLA Modules`` line gives its executions, and
        its Pallas kernels into ``op_scopes``. Two programs of one name (a
        prefill program per bucket) share an entry, and an instruction name
        they give different scopes is marked, not guessed. What the pass
        costs set-up is the fact ``note_program_s``."""
        t0 = time.perf_counter()
        mine = self.scopes.setdefault(program_name(hlo_text), {})
        for name, scope, line in _instructions(hlo_text):
            if mine.setdefault(name, scope) != scope:
                mine[name] = AMBIGUOUS
            if _is_pallas_call(scope, line):
                self.op_scopes[name] = scope
        self.facts["note_program_s"] = (self.facts.get("note_program_s", 0.0)
                                        + time.perf_counter() - t0)

    def problem(self, text: str) -> None:
        """A failed correctness check: the run goes on, ``correct`` is false."""
        self.problems.append(text)


#: the scope of an instruction name that two programs of one name use
AMBIGUOUS = "(two programs of one name)"

_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CUSTOM_CALL = re.compile(r"= [^=]*custom-call\(")


def _instructions(hlo_text: str):
    """``(name, op_name, line)`` of every instruction that carries an
    ``op_name``, in one pass over a compiled module's text."""
    for line in hlo_text.splitlines():
        scope = _OP_NAME.search(line)
        name = _INSTRUCTION.match(line) if scope else None
        if name:
            yield name.group(1), scope.group(1), line


def _is_pallas_call(scope: str, line: str) -> bool:
    return "/pallas_call" in scope and bool(_CUSTOM_CALL.search(line))


def program_name(hlo_text: str) -> str:
    """``HloModule jit_train_step, is_scheduled=...`` -> ``jit_train_step``:
    what the trace calls the program's executions, before ``(<id>)``."""
    m = re.match(r"\s*HloModule ([^\s,]+)", hlo_text)
    return m.group(1) if m else ""


def instruction_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` (jax's name stack: the program, the
    transformations, flax's module path and ``jax.named_scope`` s, the
    primitive) for every instruction of a compiled module that carries one:
    the entry computation and the bodies of ``while`` / ``call`` /
    ``conditional``, whose instructions the trace shows as events. A fusion
    is one event and carries the ``op_name`` of its root; the instructions
    inside fused computations are in the table too and no event names them
    (names are unique within a module). What the compiler inserted (copies,
    async pairs) has no ``op_name`` and no entry."""
    return {name: scope for name, scope, _ in _instructions(hlo_text)}


def pallas_instructions(hlo_text: str) -> dict[str, str]:
    """The Pallas kernels among ``instruction_scopes``: custom calls whose
    ``op_name`` ends in ``pallas_call`` (copied from
    ``tpu_sandbox.utils.flops.pallas_call_paths``)."""
    return {name: scope for name, scope, line in _instructions(hlo_text)
            if _is_pallas_call(scope, line)}
