"""The span/event recorder: per-process append-only JSONL, cheap enough
to leave on.

One :class:`Recorder` per process, obtained via :func:`get_recorder`. It
is **disabled unless** ``TPU_SANDBOX_TRACE_DIR`` is set in the
environment — on a disabled recorder a span costs its histogram observe,
a profiler flag test and a push and pop on its thread's stack of open
spans (a few microseconds; PERF.md gives the measured cost), every other
emit a couple of attribute reads, so instrumentation stays in the hot
paths unconditionally.

Record forms (one JSON object per line, all timestamps are THIS
process's ``time.monotonic()`` seconds — never wall clock, never another
host's clock):

    {"ph":"P", ...}   preamble: proc name, pid, a coarse (mono, wall)
                      pair — the fallback clock anchor
    {"ph":"X", ...}   complete span: ts + dur, trace/span/parent ids
    {"ph":"i", ...}   instant event (fault injections, verdicts, job
                      lifecycle); flushed immediately so it survives a
                      SIGKILL issued on the next line
    {"ph":"C", ...}   clock-calibration sample: (kv-sequencer value,
                      mono midpoint, rtt, wall) — the collector derives
                      per-process offsets from these (see
                      ``obs/collect.py::clock_offsets``)
    {"ph":"m", ...}   metric sample: (series name, numeric value) —
                      rendered by the collector as a Chrome/Perfetto
                      counter track (``ph:"C"`` in the Chrome JSON; the
                      recorder's own "C" phase was already taken by
                      calibration) so time-series and spans share one
                      timeline

Causality is carried by :class:`TraceContext` — ``(trace_id, span_id)``
pairs serialized as ``{"t":…,"s":…}`` wherever a request body crosses a
process boundary (gateway wire frames, ``serve/req/<rid>`` bodies). A
disabled recorder *passes contexts through* unchanged, so one dark
process does not sever the chain between two instrumented ones.

One span, three sinks. ``with rec.span(name, hist="layer.what_s")`` (a)
enters a ``jax.profiler.TraceAnnotation(name)`` — the profiler's clock,
the one the device planes are on, so under ``--profile DIR`` the program's
spans lie over the device ops; outside a profiler session that is a flag
test, and a process that never imported ``jax`` (gateway, scheduler, KV)
skips it and stays jax-free; (b) observes the duration in seconds into the
always-on registry histogram ``hist`` (a static literal at the call site,
GL-O402), recorder enabled or not; (c) writes the JSONL ``X`` record, only
when enabled. A span names work and ends when that work has ended: a span
around a device dispatch measures the enqueue and must say so in its name
(``train:dispatch``); one named for device work ends after a wait.

Who caused a span. A request span names its parent explicitly (a
:class:`TraceContext`, whose span id is the record's ``parent``). A
``loop=True`` span has no request to belong to: the recorder keeps, per
thread, a stack of the spans that are open, and such a span's ``parent``
in the JSONL is the NAME of the innermost span open on its thread when it
was opened (``setup:model_init`` under ``setup:build``, a ``compile:trace``
record under ``setup:model_init``), or null at the top. Self time is a
span's duration less what its children cover. The same stack answers
:meth:`Recorder.innermost`, which is how the compile listener
(``runtime/bootstrap.py``) labels a program's phases with the span they
ran ``under``; it is kept whether the recorder is enabled or not.

Span discipline: ``with rec.span(name) as sp`` is the sanctioned form;
``begin_span`` exists for the rare span that cannot be a ``with`` block
and MUST be closed in a ``try/finally`` (graftlint GL-O401 polices
this — a leaked open span never emits and corrupts the merged timeline).
Spans whose start time predates the call (claim/admit/decode latencies
measured around existing control flow) use :meth:`Recorder.complete`,
which emits retrospectively and cannot leak.
"""

from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
from dataclasses import dataclass

from tpu_sandbox.obs.metrics import get_registry

ENV_TRACE_DIR = "TPU_SANDBOX_TRACE_DIR"
ENV_PROC_NAME = "TPU_SANDBOX_OBS_PROC"

#: the KV store's shared sequencer for clock calibration: every
#: ``kv.add`` on this key is serialized by the single-threaded server,
#: so the returned values give a TOTAL order across hosts that the
#: collector can pin each host's monotonic clock against
CLOCK_SEQ_KEY = "obs/clock/seq"


@dataclass(frozen=True)
class TraceContext:
    """One request's position in the causal chain: which trace it
    belongs to and which span is the current parent."""

    trace_id: str
    span_id: str

    def to_wire(self) -> dict:
        return {"t": self.trace_id, "s": self.span_id}

    @classmethod
    def from_wire(cls, obj) -> "TraceContext | None":
        """Tolerant decode: None, a wire dict, or an existing context.
        Anything malformed reads as 'no context' — tracing must never
        fail a request."""
        if obj is None:
            return None
        if isinstance(obj, TraceContext):
            return obj
        if isinstance(obj, dict) and "t" in obj and "s" in obj:
            return cls(trace_id=str(obj["t"]), span_id=str(obj["s"]))
        return None


_ANNOTATION = None


def _annotation(name: str):
    """An entered ``jax.profiler.TraceAnnotation``, or None in a process
    that has not imported ``jax``: this module never pays that import."""
    global _ANNOTATION
    if _ANNOTATION is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    ann = _ANNOTATION(name)
    ann.__enter__()
    return ann


def _observe(hist: str | None, seconds: float,
             labels: dict | None = None) -> None:
    if hist is not None:
        get_registry().histogram(hist, labels=labels).observe(seconds)


class Span:
    """A live span handle, and the ``with`` form itself. ``ctx`` is the
    context CHILDREN of this span should carry; on a disabled recorder it
    passes the parent through."""

    __slots__ = ("_rec", "name", "ctx", "parent", "args", "_t0", "_closed",
                 "_hist", "_hist_labels", "_ann", "_under", "_stack", "dur")

    def __init__(self, rec: "Recorder", name: str,
                 ctx: TraceContext | None, parent: TraceContext | None,
                 args: dict | None, hist: str | None,
                 hist_labels: dict | None = None, loop: bool = False):
        self._rec = rec
        self.name = name
        self.ctx = ctx
        self.parent = parent
        self.args = args if args is not None else {}
        self._hist = hist
        self._hist_labels = hist_labels
        self._closed = False
        #: seconds from open to close, once closed: for an opener that keeps
        #: its own record of the span
        self.dur = 0.0
        # the opener's stack, kept so that a close from another thread, or
        # out of order, still takes this span off the stack it is on
        self._stack = stack = rec._open_spans()
        self._under = stack[-1].name if loop and parent is None and stack \
            else None
        stack.append(self)
        self._ann = _annotation(name)
        self._t0 = time.monotonic()

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        dur = self.dur = time.monotonic() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = self._stack
        if stack and stack[-1] is self:
            stack.pop()
        else:  # closed out of order: leave by identity, not by position
            for i in range(len(stack) - 1, -1, -1):
                if stack[i] is self:
                    del stack[i]
                    break
        _observe(self._hist, dur, self._hist_labels)
        if not self._rec.enabled:
            return
        self._rec._emit({
            "ph": "X", "name": self.name, "ts": self._t0, "dur": dur,
            "trace": None if self.ctx is None else self.ctx.trace_id,
            "span": None if self.ctx is None else self.ctx.span_id,
            "parent": self._under if self.parent is None
            else self.parent.span_id,
            "args": self.args,
        })


class Recorder:
    """Bounded-buffer JSONL event sink. Thread-safe; one per process.

    ``flush_every`` > 0 flushes the buffer to disk whenever it reaches
    that many records (and on every instant — instants mark faults and
    verdicts, which must survive an immediate SIGKILL). Between flushes a
    span costs no file I/O; the default is wide enough that a training
    loop of four spans a step writes once in 256 steps, while the device
    still holds queued work, and ``Trainer.fit`` flushes at its end.
    ``flush_every``
    == 0 means fully manual flushing, which is how the backpressure path
    is exercised: once the buffer holds ``max_buffered`` records, new
    ones are DROPPED and counted — the recorder prefers losing its own
    data to growing without bound inside a serving process. The drop
    count rides the engine load reports (satellite: a silently-dropping
    recorder is visible, not invisible)."""

    def __init__(self, path: str | None, *, proc: str | None = None,
                 flush_every: int = 1024, max_buffered: int = 4096):
        self.path = path
        self.enabled = path is not None
        self.pid = os.getpid()
        self.proc = proc or os.environ.get(ENV_PROC_NAME) \
            or f"proc-{self.pid}"
        self.flush_every = flush_every
        self.max_buffered = max_buffered
        self.events = 0
        self.dropped = 0
        self._buf: list[dict] = []
        self._lock = threading.Lock()
        self._next_span = 0
        self._tls = threading.local()
        #: the optimizer step a training loop has last returned from, while
        #: one runs (``train/trainer.py::LoopSpans``); None outside a loop.
        #: The compile listener reads it to count a compile inside the loop
        self.loop_step: int | None = None
        #: backend compiles the compile listener has seen, and writes of the
        #: buffer to the file: plain totals, read without a lock, for a loop
        #: that asks whether either fell inside one of its iterations
        self.compiles = 0
        self.flushes = 0
        self._fh = None
        if self.enabled:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            self._fh = open(path, "a", encoding="utf-8")
            self._emit({"ph": "P", "mono": time.monotonic(),
                        "wall": time.time()}, flush=True)

    # -- emission ------------------------------------------------------------

    def _emit(self, rec: dict, *, flush: bool = False) -> None:
        if not self.enabled:
            return
        rec.setdefault("pid", self.pid)
        rec.setdefault("proc", self.proc)
        rec.setdefault("tid", threading.get_ident())
        with self._lock:
            if len(self._buf) >= self.max_buffered:
                self.dropped += 1
                return
            self._buf.append(rec)
            self.events += 1
            if flush or (self.flush_every
                         and len(self._buf) >= self.flush_every):
                self._flush_locked()

    def _flush_locked(self) -> None:
        if not self._buf or self._fh is None:
            return
        lines = "".join(json.dumps(r) + "\n" for r in self._buf)
        self._buf.clear()
        self._fh.write(lines)
        self._fh.flush()
        self.flushes += 1

    def flush(self) -> None:
        with self._lock:
            self._flush_locked()

    def close(self) -> None:
        with self._lock:
            self._flush_locked()
            if self._fh is not None:
                self._fh.close()
                self._fh = None
        self.enabled = False

    def stats(self) -> dict:
        """The load-report rider: emitted vs dropped-on-backpressure."""
        return {"events": self.events, "dropped": self.dropped}

    # -- ids -----------------------------------------------------------------

    def _mint_span_id(self) -> str:
        self._next_span += 1
        return f"{self.pid:x}.{self._next_span}"

    def _mint_trace_id(self) -> str:
        return os.urandom(8).hex()

    def _child_ctx(self, parent: TraceContext | None) -> TraceContext:
        if parent is None:
            return TraceContext(self._mint_trace_id(), self._mint_span_id())
        return TraceContext(parent.trace_id, self._mint_span_id())

    # -- spans / events ------------------------------------------------------

    def _open_spans(self) -> list:
        """This thread's stack of open spans, innermost last."""
        try:
            return self._tls.open
        except AttributeError:
            stack = self._tls.open = []
            return stack

    def innermost(self, skip: str = "") -> str | None:
        """The name of the innermost span open on this thread, or None;
        ``skip`` passes over names that start with it (the compile listener
        skips ``trace:``: a kernel site lies inside a program's trace and is
        no phase of the launch)."""
        for sp in reversed(self._open_spans()):
            if not (skip and sp.name.startswith(skip)):
                return sp.name
        return None

    def _span_ctx(self, parent: TraceContext | None,
                  loop: bool) -> TraceContext | None:
        if not self.enabled or (loop and parent is None):
            return parent
        return self._child_ctx(parent)

    def begin_span(self, name: str, parent=None, args: dict | None = None,
                   hist: str | None = None, loop: bool = False,
                   hist_labels: dict | None = None) -> Span:
        """Open a span the caller MUST close in a try/finally (GL-O401).
        Prefer ``with rec.span(...)``; use this only when the span's
        lifetime cannot be a lexical block."""
        parent = TraceContext.from_wire(parent)
        return Span(self, name, self._span_ctx(parent, loop), parent, args,
                    hist, hist_labels, loop)

    def span(self, name: str, parent=None, args: dict | None = None,
             hist: str | None = None, loop: bool = False,
             hist_labels: dict | None = None) -> Span:
        """The sanctioned span form, ``with rec.span(...)``: closes on
        every path, and feeds all three sinks (module docstring).

        A span without a parent starts a new trace — a request's root.
        ``loop=True`` marks what belongs to no request: an iteration of a
        training or serving loop, a set-up phase. Such a span carries no
        trace id, so the collector's request chains leave it out, and it
        sits on the merged timeline by its time alone; its ``parent`` is
        the name of the span it was opened inside (module docstring).
        ``hist_labels`` are the labels of the ``hist`` series: a bounded
        set, as every registry label (``trace.kernel_s{kernel=...}``)."""
        return self.begin_span(name, parent=parent, args=args, hist=hist,
                               loop=loop, hist_labels=hist_labels)

    def complete(self, name: str, start_mono: float, parent=None,
                 args: dict | None = None, hist: str | None = None,
                 loop: bool = False) -> TraceContext | None:
        """Emit a span retrospectively: started at ``start_mono`` (this
        process's monotonic clock), ended now. Returns the context
        children should parent to (parent pass-through when disabled).
        Feeds the registry histogram ``hist`` and the JSONL, not the
        profiler's timeline: an annotation cannot start in the past. A
        ``loop=True`` record's ``parent`` is the innermost span open now."""
        dur = time.monotonic() - start_mono
        _observe(hist, dur)
        parent = TraceContext.from_wire(parent)
        ctx = self._span_ctx(parent, loop)
        if not self.enabled:
            return ctx
        if parent is not None:
            caused_by = parent.span_id
        else:
            caused_by = self.innermost() if loop else None
        self._emit({
            "ph": "X", "name": name, "ts": start_mono, "dur": dur,
            "trace": None if ctx is None else ctx.trace_id,
            "span": None if ctx is None else ctx.span_id,
            "parent": caused_by,
            "args": args or {},
        })
        return ctx

    def instant(self, name: str, parent=None,
                args: dict | None = None) -> TraceContext | None:
        """Point event — flushed immediately (auto-flush mode) so a
        fault injection's record survives the SIGKILL it announces."""
        parent = TraceContext.from_wire(parent)
        if not self.enabled:
            return parent
        ctx = self._child_ctx(parent)
        self._emit({
            "ph": "i", "name": name, "ts": time.monotonic(),
            "trace": ctx.trace_id, "span": ctx.span_id,
            "parent": None if parent is None else parent.span_id,
            "args": args or {},
        }, flush=bool(self.flush_every))
        return ctx

    def metric(self, name: str, value: float) -> None:
        """Sample a metric series onto the timeline. Buffered like spans
        (metrics are periodic, not fault markers — losing the tail on
        SIGKILL is acceptable); the collector turns these into Perfetto
        counter tracks."""
        if not self.enabled:
            return
        self._emit({"ph": "m", "name": name, "ts": time.monotonic(),
                    "value": float(value)})

    # -- clock calibration ---------------------------------------------------

    def calibrate(self, kv, rounds: int = 5) -> int:
        """Pin this process's monotonic clock against the KV server's
        shared sequencer. Each round brackets one ``kv.add`` round trip
        with monotonic reads; the sequencer value is a server-serialized
        total order, so the collector can (a) offset each process by its
        own (wall - mono) median and (b) enforce that calibration points
        appear in sequencer order on the merged timeline — no raw
        cross-host wall-clock arithmetic anywhere (GL-R302). Returns the
        last sequencer value observed (0 when disabled)."""
        if not self.enabled:
            return 0
        seq = 0
        for _ in range(rounds):
            m0 = time.monotonic()
            seq = kv.add(CLOCK_SEQ_KEY)
            m1 = time.monotonic()
            self._emit({
                "ph": "C", "seq": int(seq), "mono": (m0 + m1) / 2.0,
                "rtt": m1 - m0, "wall": time.time(),
            })
        self.flush()
        return int(seq)


# -- process-global recorder --------------------------------------------------

_RECORDER: Recorder | None = None
_RECORDER_LOCK = threading.Lock()


def get_recorder() -> Recorder:
    """The process-wide recorder, built once from the environment:
    enabled iff ``TPU_SANDBOX_TRACE_DIR`` is set (log file
    ``<dir>/<proc>-<pid>.jsonl``)."""
    global _RECORDER
    rec = _RECORDER
    if rec is not None:
        return rec
    with _RECORDER_LOCK:
        if _RECORDER is None:
            trace_dir = os.environ.get(ENV_TRACE_DIR)
            if trace_dir:
                proc = os.environ.get(ENV_PROC_NAME) \
                    or f"proc-{os.getpid()}"
                path = os.path.join(trace_dir, f"{proc}-{os.getpid()}.jsonl")
                _RECORDER = Recorder(path, proc=proc)
                # spans are buffered: what a clean exit still holds is
                # written then (a SIGKILL loses it, instants excepted)
                atexit.register(_RECORDER.flush)
            else:
                _RECORDER = Recorder(None)
        return _RECORDER


def reset_recorder() -> None:
    """Close and forget the global recorder so the next
    :func:`get_recorder` re-reads the environment (tests flipping
    tracing on and off inside one process)."""
    global _RECORDER
    with _RECORDER_LOCK:
        if _RECORDER is not None:
            _RECORDER.close()
        _RECORDER = None
