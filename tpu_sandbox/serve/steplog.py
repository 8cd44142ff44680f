"""The serve engine's record of its own steps, in memory.

``_EngineBase.step`` opens a step here (``StepLog.begin``), adds each
phase span's seconds as it closes, and closes the step (``StepLog.end``):
one :class:`StepRecord` a step in a ring of the last ``STEPS_KEPT``. The
phases are the spans that tile ``engine:step`` (``PHASES``); beside their
wall seconds a record holds the engine thread's CPU seconds since the step
before it closed and the collector's runs inside the step. The CPU clock is
read once a step, at its end (``time.thread_time()``: the record's one
system call, 5 us on the chip's host), so a record's ``cpu_s`` also holds
what the caller's loop burnt between the two steps; on a host whose clock
counts nanoseconds that is every step's own, on one that counts it in ticks
of 10 ms (the chip's: my chip runs, PR 50) it reads 0 or a tick a step, true
over a window's sum and telling for a stall of many ticks, which is what it
is kept for. The wait burns no CPU to speak of (0.04 ms of a 4.9 ms wait:
my chip run, PR 50), so a step's CPU time is the host's own, and time off
the CPU *outside* the wait (descheduled, or blocked in a copy, a lock, a
write) is the step's wall less its wait less its CPU time -- told from time
off the CPU *inside* the wait, which is expected: the thread is blocked on
the device.

A step is **stalled** when its wall time less its admissions, a decode
call (``judged_s``), passes ``STALL_FACTOR`` times the median of the same
over the ``REFERENCE_STEPS`` steps before it (none is judged before
``REFERENCE_EVERY`` steps are logged; the median is taken anew every
``REFERENCE_EVERY`` steps, not every step). ``engine:admit`` is left out
of the rule, and a step of two calls is allowed two calls' time: a prefill
is a step's work, as long as its prompt and told by ``engine.admit_s`` and
the prefill's own spans, and the step that admits decodes the newcomer in
a call of its own beside the one that was ahead (as a step between two
weight versions does); under steady arrivals either would fill the ring
and push the true stalls out. A stalled step is counted once
(``engine.stalls{phase}``), kept whole in a second ring (``StepLog.stalls``)
with the phase -- of the five that are judged -- that exceeded its own
median by most, and written as the instant ``engine:stall``, which the
recorder flushes at once. The constants are literals: no option selects
them.

The collector is one more span of the same record: ``watch_collector``
installs one ``gc.callbacks`` hook, when the first engine of a process is
built, that keeps the running totals a step differences. The hook runs
inside a collection, on whichever thread allocated and under whatever lock
that thread holds, so it takes no lock and touches no registry; ``end``
and ``report`` publish what it noted (``gc.collections{generation}``,
``gc.pause_s{generation}``).
"""

from __future__ import annotations

import gc
import statistics
import time
from collections import deque
from itertools import islice
from typing import NamedTuple

from tpu_sandbox.obs import get_recorder, get_registry

STEPS_KEPT = 4096
STALLS_KEPT = 64
#: a step is judged against the median wall time of this many before it
REFERENCE_STEPS = 64
#: ... taken anew every this many steps, and first when this many exist
REFERENCE_EVERY = 16
STALL_FACTOR = 1.5
#: the spans that tile ``engine:step``, in the order a step runs them
PHASES = ("shed", "admit", "grow", "dispatch", "wait", "sample")
#: ... and those a stall is judged on and named by
JUDGED = tuple(p for p in PHASES if p != "admit")
#: collections the hook keeps for the next ``publish`` (a process whose
#: engines stand still publishes none)
UNPUBLISHED_KEPT = 1024


class StepRecord(NamedTuple):
    step: int           # index since the engine was built
    t0: float           # start, time.monotonic()
    wall_s: float       # the ``engine:step`` span
    cpu_s: float        # the thread's CPU time since the step before closed
    shed_s: float
    admit_s: float
    grow_s: float
    dispatch_s: float
    wait_s: float
    sample_s: float
    rows: int           # decode rows that gained a token
    calls: int          # decode calls whose result the step read
    gc_n: int           # collections inside the step, any thread's
    gc_s: float


def judged_s(record: StepRecord) -> float:
    """What the stall rule holds a step to: its wall seconds less its
    admissions, a decode call."""
    return (record.wall_s - record.admit_s) / max(1, record.calls)


def offcpu_outside_wait_s(record: StepRecord) -> float:
    """Seconds of a step that the engine's thread was neither inside
    ``engine:wait`` nor on the CPU."""
    return max(0.0, record.wall_s - record.wait_s - record.cpu_s)


def format_stall(stall: dict) -> str:
    """A stall's record (the args of an ``engine:stall`` instant) on one
    line: the phase that held the step, by how much, and whether the
    engine's thread was on the CPU meanwhile."""
    def ms(key: str) -> str:
        return f"{1e3 * float(stall.get(key) or 0.0):.3f}ms"

    phase = stall.get("phase")
    return (f"step={stall.get('step')} phase={phase} "
            f"excess={ms('excess_s')} wall={ms('wall_s')} "
            f"{phase}={ms(f'{phase}_s')} cpu={ms('cpu_s')} "
            f"offcpu_outside_wait={ms('offcpu_outside_wait_s')} "
            f"gc={stall.get('gc_n')}/{ms('gc_s')} "
            f"compiles={stall.get('compiles')} flushed={stall.get('flushed')}")


class _Collector:
    """Running totals of the process's collections (``watch_collector``)."""

    def __init__(self) -> None:
        self.collections = 0
        self.seconds = 0.0
        #: (generation, seconds) of the collections not yet in the registry
        self.unpublished: deque[tuple[int, float]] = deque(
            maxlen=UNPUBLISHED_KEPT)
        self._t0: float | None = None

    def __call__(self, phase: str, info: dict) -> None:
        # inside a collection: the thread may hold the registry's lock or a
        # histogram's (a collection starts at an allocation, and both
        # allocate under their locks), so nothing here takes one
        if phase == "start":
            self._t0 = time.monotonic()
            return
        t0, self._t0 = self._t0, None
        if t0 is None:  # installed between a collection's two calls
            return
        seconds = time.monotonic() - t0
        self.collections += 1
        self.seconds += seconds
        self.unpublished.append((min(info["generation"], 2), seconds))

    def publish(self) -> None:
        """Count and observe what the hook noted, outside any collection."""
        reg = get_registry()
        while self.unpublished:
            try:
                generation, seconds = self.unpublished.popleft()
            except IndexError:  # another thread's publish took it
                return
            labels = _GENERATION[generation]
            reg.counter("gc.collections", labels=labels).inc()
            reg.histogram("gc.pause_s", labels=labels).observe(seconds)


_GENERATION = tuple({"generation": str(g)} for g in range(3))
_COLLECTOR = _Collector()


def watch_collector() -> None:
    """Install the one ``gc.callbacks`` hook of the process, once."""
    if _COLLECTOR not in gc.callbacks:
        gc.callbacks.append(_COLLECTOR)


class StepLog:
    """One engine's ring of step records and ring of stalls."""

    def __init__(self) -> None:
        watch_collector()
        self.steps: deque[StepRecord] = deque(maxlen=STEPS_KEPT)
        self.stalls: deque[dict] = deque(maxlen=STALLS_KEPT)
        self.logged = 0     # steps since the engine was built
        self.stalled = 0    # ... of them stalled
        self._reference: float | None = None
        self._cpu = time.thread_time()  # where the last step closed
        self.begin()

    def begin(self) -> None:
        """Open a step: zero what the phases add to, note where the totals
        stand. What a phase adds outside a step (``settle``) is dropped by
        the next call."""
        self.shed_s = self.admit_s = self.grow_s = 0.0
        self.dispatch_s = self.wait_s = self.sample_s = 0.0
        self.rows = self.calls = 0
        rec = get_recorder()
        self._compiles0, self._flushes0 = rec.compiles, rec.flushes
        self._gc0 = (_COLLECTOR.collections, _COLLECTOR.seconds)
        self._t0 = time.monotonic()

    def end(self, wall_s: float) -> None:
        """Close the step whose ``engine:step`` span took ``wall_s``."""
        cpu0, self._cpu = self._cpu, time.thread_time()
        record = StepRecord(
            self.logged, self._t0, wall_s, self._cpu - cpu0,
            self.shed_s, self.admit_s, self.grow_s, self.dispatch_s,
            self.wait_s, self.sample_s, self.rows, self.calls,
            _COLLECTOR.collections - self._gc0[0],
            _COLLECTOR.seconds - self._gc0[1])
        reference = self._reference
        if reference is not None \
                and judged_s(record) > STALL_FACTOR * reference:
            self._stall(record, reference)
        self.steps.append(record)
        self.logged += 1
        if self.logged % REFERENCE_EVERY == 0:
            self._reference = statistics.median(
                judged_s(r) for r in self.last(REFERENCE_STEPS))
        if _COLLECTOR.unpublished:
            _COLLECTOR.publish()

    def last(self, n: int) -> list[StepRecord]:
        """The last ``n`` records (fewer where fewer exist), newest first."""
        return list(islice(reversed(self.steps), n))

    def _stall(self, record: StepRecord, reference: float) -> None:
        before = self.last(REFERENCE_STEPS)  # ``record`` is not among them
        over = [getattr(record, f"{phase}_s") - statistics.median(
            getattr(r, f"{phase}_s") for r in before) for phase in JUDGED]
        phase = JUDGED[over.index(max(over))]
        rec = get_recorder()
        stall = dict(record._asdict(), phase=phase,
                     excess_s=record.wall_s - record.admit_s
                     - max(1, record.calls) * reference,
                     offcpu_outside_wait_s=offcpu_outside_wait_s(record),
                     compiles=rec.compiles - self._compiles0,
                     flushed=rec.flushes > self._flushes0)
        self.stalls.append(stall)
        self.stalled += 1
        get_registry().counter("engine.stalls", labels={"phase": phase}).inc()
        rec.instant("engine:stall", args=stall)

    def report(self) -> dict:
        """What ``load_report`` says of the steps: the host's own
        milliseconds a step and its wait for the device (medians over the
        last ``REFERENCE_STEPS`` steps), and the stalls so far with the last
        one's phase and excess."""
        _COLLECTOR.publish()    # a replica that stands still reports too
        recent = self.last(REFERENCE_STEPS)
        last = self.stalls[-1] if self.stalls else None
        return {
            "host_ms": 1e3 * statistics.median(
                r.wall_s - r.wait_s for r in recent) if recent else None,
            "wait_ms": 1e3 * statistics.median(
                r.wait_s for r in recent) if recent else None,
            "stalls": {"count": self.stalled,
                       "phase": None if last is None else last["phase"],
                       "ms": None if last is None
                       else 1e3 * last["excess_s"]},
        }
