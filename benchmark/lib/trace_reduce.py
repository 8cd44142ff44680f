"""From a profiler trace (``.xplane.pb``) to numbers: which planes are
devices, when each was busy, time by operation, collective time and the
part of it no compute hides, and what the host was doing in the longest
idle gaps. Reads the file with ``jax.profiler.ProfileData`` and nothing
else.

What a v5e trace looks like (looked at by hand, PR 22): one plane per chip
named ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
executed HLO instruction, named by the instruction's whole text
(``%fusion.12 = f32[...] fusion(...)``; a Pallas kernel is a
``custom-call`` named after the kernel, ``%conv2.3``), and whose line
``XLA Modules`` holds one event per executed program
(``jit_train_step(<hash>)``). The body of a ``while`` appears as events of
its own *inside* the ``while`` event, so times by operation nest; async
copies live on a line of their own (``Async XLA Ops``) and are not counted
as busy. Host threads are lines of the plane ``/host:CPU``; the
``TraceAnnotation`` s are on its line ``python3``, on the same clock. All
times are nanoseconds.

**Time by scope** (PR 25). The trace has no name-scope line and an ``XLA
Ops`` event carries no ``op_name``, only the instruction's text. So time
by scope is a join: the event's instruction name, looked up in the
``op_name`` s of the compiled program it ran in (``observe.
instruction_scopes`` of ``compiled.as_text()``, kept by program name in
``Observations.scopes``). The program is the ``XLA Modules`` event that
holds the operation's start, its name cut before ``(<id>)``; instruction
names are unique within a module, not across modules, so a window that
runs two programs is joined program by program, and two programs of one
name (a prefill program per bucket) are told apart only where their
instruction names differ: an instruction they scope differently counts as
``observe.AMBIGUOUS``. What the join is: exact for custom calls (a Pallas
kernel is one instruction under one module) and for collectives. What it
is not: a fusion is one event under the ``op_name`` of its *root*, so a
fusion that spans two modules is all under one of them (the ConvNet
head's weight gradient fused into the SGD update counts under ``fc``, not
under ``optimizer``); an instruction the compiler inserted (a copy, an
async pair) has no ``op_name`` and counts as ``(no scope)``; an operation
of a program nobody noted counts as ``(other program)``.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: HLO opcodes that move data between chips. The trace names an event by
#: the instruction's whole text, and jax names the instruction after its own
#: primitive (``%psum.79 = f32[...] all-reduce(...)``), so the opcode is
#: looked for in the text; a bare event name is matched from its start.
_COLLECTIVE_OPS = (r"(all-reduce|all-gather|reduce-scatter|"
                   r"collective-permute|all-to-all)(-start|-done)?")
COLLECTIVE = re.compile(rf"^{_COLLECTIVE_OPS}|\s{_COLLECTIVE_OPS}\(")

#: instructions that only wrap others: their time is their children's
WRAPPERS = re.compile(r"^(while|conditional|call)([.\d]|$)")

#: scope segments that are jax's own (checkpoint, shard_map), not the model's
_JAX_MARKERS = {"checkpoint", "rematted_computation", "shard_map"}
_TRANSFORMED = re.compile(r"^\w+\((.*)\)$")
_NUMBERED = re.compile(r"^(.*?)(\d+)$")
NO_SCOPE = "(no scope)"
OTHER_PROGRAM = "(other program)"
#: rows of ``device_scopes`` name at most this many scopes from the top
SCOPE_DEPTH = 4
#: more numbered siblings than this (block0 .. block23) are one row
MAX_SIBLINGS = 4

Event = tuple[str, int, int]  # name, start_ns, duration_ns


@dataclass
class Plane:
    name: str
    lines: dict[str, list[Event]] = field(default_factory=dict)
    #: names of device events whose instruction is a ``custom-call``
    custom_calls: set[str] = field(default_factory=set)
    #: names of device events whose instruction is a cross-chip collective
    collectives: set[str] = field(default_factory=set)


def instruction_name(text: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def program_of(module_event: str) -> str:
    """``jit_train_step(2742204733650636828)`` -> ``jit_train_step``."""
    return module_event.split("(", 1)[0]


def scope_path(op_name: str | None) -> str:
    """The module path of an ``op_name``, forward and backward together:
    ``jit(step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/
    rematted_computation/block3/attn/qkv/add`` -> ``TransformerLM/block3/
    attn/qkv``. The program's own ``jit(...)`` and the primitive at the end
    go; a scope under a transformation (``jvp(loss)``) is that scope;
    jax's own markers (``checkpoint``, ``shard_map``) and repeats go; an
    inner ``jit(...)`` (a
    library function: ``jit(_take)``) ends the path; at most
    ``SCOPE_DEPTH`` segments stay. Of several ``op_name`` s joined by ``;``
    the first counts."""
    if not op_name:
        return NO_SCOPE
    if op_name.startswith("("):  # OTHER_PROGRAM, observe.AMBIGUOUS
        return op_name
    parts = op_name.split(";", 1)[0].split("/")
    if parts and parts[0].startswith(("jit(", "pjit(")):
        parts = parts[1:]
    path: list[str] = []
    for part in parts[:-1]:
        if part.startswith(("jit(", "pjit(")):
            break
        while (m := _TRANSFORMED.match(part)):
            part = m.group(1)
        if part in _JAX_MARKERS or (path and path[-1] == part):
            continue
        path.append(part)
    return "/".join(path[:SCOPE_DEPTH]) or NO_SCOPE


def fold_siblings(table: dict[str, int]) -> dict[str, int]:
    """Rows that differ only in the number at the end of one segment
    (``block0/attn`` .. ``block23/attn``) as one row ``block*/attn``, where
    there are more than ``MAX_SIBLINGS`` of them; ``conv1`` and ``conv2``
    stay two rows."""
    def split(path: str, at: int):
        """``(family, folded path)`` of a path whose segment ``at`` ends in
        a number; the family is the path up to that segment."""
        parts = path.split("/")
        m = _NUMBERED.match(parts[at]) if at < len(parts) else None
        if not m:
            return None, path
        head = parts[:at] + [m.group(1) + "*"]
        return "/".join(head), "/".join(head + parts[at + 1:])

    for at in range(SCOPE_DEPTH):
        members: dict[str, set[str]] = {}
        for path in table:
            family, _ = split(path, at)
            if family is not None:
                members.setdefault(family, set()).add(path.split("/")[at])
        out: dict[str, int] = {}
        for path, ns in table.items():
            family, folded = split(path, at)
            if family is None or len(members[family]) <= MAX_SIBLINGS:
                folded = path
            out[folded] = out.get(folded, 0) + ns
        table = out
    return table


def joined(devices: list[dict], scopes: dict[str, dict[str, str]]):
    """``(op_name | None, ns, calls)`` of every operation of the reduced
    ``devices``, wrappers left out (their bodies' operations are there),
    each joined through the program it ran in (module docstring)."""
    for d in devices:
        for program, ops in d["by_program"].items():
            names = scopes.get(program)
            for op, (ns, calls) in ops.items():
                if WRAPPERS.match(op):
                    continue
                yield (OTHER_PROGRAM if names is None else names.get(op),
                       ns, calls)


def find_xplane(trace_dir) -> Path:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path, *, host_prefix: str | None = None) -> list[Plane]:
    """``planes_of`` the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    return planes_of(ProfileData.from_file(str(path)), host_prefix=host_prefix)


def planes_of(profile, *, host_prefix: str | None = None) -> list[Plane]:
    """Planes, lines and events of a ``ProfileData`` as plain tuples.
    Device planes are read whole. Of the host plane only events whose name
    starts with ``host_prefix`` are kept (the benchmark's own annotations):
    the host plane of a long trace holds every runtime call."""
    planes = []
    for p in profile.planes:
        is_device = bool(DEVICE_PLANE.match(p.name))
        if not is_device and p.name != HOST_PLANE:
            continue
        plane = Plane(p.name)
        for line in p.lines:
            events = []
            for e in line.events:
                name = e.name
                if is_device:
                    short = instruction_name(name)
                    if " custom-call(" in name:
                        plane.custom_calls.add(short)
                    if COLLECTIVE.search(name):
                        plane.collectives.add(short)
                    name = short
                elif host_prefix is not None and not name.startswith(
                        host_prefix):
                    continue
                events.append((name, int(e.start_ns), int(e.duration_ns)))
            if events:
                plane.lines.setdefault(line.name, []).extend(events)
        planes.append(plane)
    return planes


def describe(path) -> str:
    """Planes, lines, event counts and a few names: for looking at a trace
    by hand before trusting the reduction."""
    from collections import Counter

    from jax.profiler import ProfileData

    out = []
    for p in ProfileData.from_file(str(path)).planes:
        out.append(f"plane {p.name!r}")
        for line in p.lines:
            events = list(line.events)
            names = Counter(e.name for e in events).most_common(6)
            span = (f"{min(e.start_ns for e in events):.0f}.."
                    f"{max(e.start_ns + e.duration_ns for e in events):.0f}"
                    if events else "-")
            out.append(f"  line {line.name!r}: {len(events)} events, "
                       f"ns {span}, top {names}")
    return "\n".join(out)


def merge(intervals) -> list[tuple[int, int]]:
    """Union of ``(start, end)`` intervals as sorted disjoint intervals."""
    out: list[list[int]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[int, int]]:
    """Parts of merged intervals ``a`` not covered by merged intervals ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(events, window) -> list[Event]:
    if window is None:
        return list(events)
    w0, w1 = window
    out = []
    for name, s, d in events:
        s2, e2 = max(s, w0), min(s + d, w1)
        if e2 > s2:
            out.append((name, s2, e2 - s2))
    return out


def window_of(planes, marker: str) -> tuple[int, int] | None:
    """The traced window: the host annotation ``marker`` if the trace holds
    it, else from the first to the last device operation."""
    for p in planes:
        if p.name == HOST_PLANE:
            for events in p.lines.values():
                for name, s, d in events:
                    if name == marker:
                        return (s, s + d)
    spans = [(s, s + d) for p in planes if DEVICE_PLANE.match(p.name)
             for _, s, d in p.lines.get(OPS_LINE, [])]
    if not spans:
        return None
    return (min(s for s, _ in spans), max(e for _, e in spans))


def host_annotations(planes, prefix: str, marker: str) -> list[Event]:
    return sorted(
        ((n, s, d) for p in planes if p.name == HOST_PLANE
         for events in p.lines.values() for n, s, d in events
         if n.startswith(prefix) and n != marker),
        key=lambda e: e[1])


def name_gap(gap: tuple[int, int], annotations: list[Event]) -> str:
    """What the host was doing in an idle gap: the annotation that covers
    most of it, or ``(no annotation)``."""
    best, best_ns = "(no annotation)", 0
    for name, s, d in annotations:
        if s >= gap[1]:
            break
        cover = min(s + d, gap[1]) - max(s, gap[0])
        if cover > best_ns:
            best, best_ns = name, cover
    return best


def reduce_device(plane: Plane, window) -> dict:
    ops = clip(plane.lines.get(OPS_LINE, []), window)
    busy = merge((s, s + d) for _, s, d in ops)
    moves = plane.collectives
    coll = merge((s, s + d) for n, s, d in ops if n in moves)
    compute = merge((s, s + d) for n, s, d in ops
                    if n not in moves and not WRAPPERS.match(n))
    by_op: dict[str, int] = {}
    # program -> op -> [ns, calls]: an operation belongs to the program
    # whose execution (unclipped) holds its start; "" where none does
    by_program: dict[str, dict[str, list[int]]] = {}
    runs = sorted((s, s + d, program_of(n))
                  for n, s, d in plane.lines.get(MODULES_LINE, []))
    starts = [r[0] for r in runs]
    for n, s, d in ops:
        by_op[n] = by_op.get(n, 0) + d
        i = bisect.bisect_right(starts, s) - 1
        program = runs[i][2] if i >= 0 and s < runs[i][1] else ""
        cell = by_program.setdefault(program, {}).setdefault(n, [0, 0])
        cell[0] += d
        cell[1] += 1
    modules: dict[str, list[int]] = {}
    for n, s, d in plane.lines.get(MODULES_LINE, []):
        # whole executions only: a clipped one is not a step time
        if window is None or (s >= window[0] and s + d <= window[1]):
            modules.setdefault(n, []).append(d)
    w0, w1 = window if window else (busy[0][0], busy[-1][1]) if busy else (0, 0)
    return {
        "name": plane.name,
        "window_ns": w1 - w0,
        "busy_ns": total(busy),
        "collective_ns": total(coll),
        "collective_exposed_ns": total(subtract(coll, compute)),
        "by_op_ns": by_op,
        "by_program": by_program,
        "custom_calls": sorted(plane.custom_calls),
        "modules_ns": modules,
        "gaps": subtract([(w0, w1)], busy),
        "n_ops": len(ops),
    }


def cut(rows: list, top: int, rest: str | None = None) -> list:
    """The first ``top`` rows of a ranked table; with ``rest``, the last of
    them is the sum of all that was cut, under that name, so that the
    table's sum stays."""
    if rest is None or len(rows) <= top:
        return rows[:top]
    return rows[:top - 1] + [[rest, sum(v for _, v in rows[top - 1:])]]


def reduce(planes, *, marker: str = "bench:window", prefix: str = "bench:",
           scopes: dict[str, dict[str, str]] | None = None,
           top: int = 20) -> dict | None:
    """The whole trace as one summary; ``None`` when no operation ran on a
    device (a reader that finds nothing to read returns nothing).
    ``device_scopes`` is ``device_ops`` regrouped by ``scope_path`` through
    ``scopes`` (``Observations.scopes``; empty without them): the same
    operations and the same seconds, so both tables have one sum; what does
    not fit ``top`` rows is its last row, ``(other)``."""
    window = window_of(planes, marker)
    devices = [reduce_device(p, window) for p in planes
               if DEVICE_PLANE.match(p.name)]
    devices = [d for d in devices if d["n_ops"]]
    if not devices or window is None:
        return None
    annotations = host_annotations(planes, prefix, marker)
    by_op: dict[str, int] = {}
    for d in devices:
        for n, ns in d["by_op_ns"].items():
            if not WRAPPERS.match(n):  # its body's operations are listed
                by_op[n] = by_op.get(n, 0) + ns
    n_dev = len(devices)
    # idle gaps are named on the busiest-idle device: the others of a
    # data-parallel step mirror it
    idlest = max(devices, key=lambda d: d["window_ns"] - d["busy_ns"])
    gap_ns: dict[str, int] = {}
    for gap in idlest["gaps"]:
        label = name_gap(gap, annotations)
        gap_ns[label] = gap_ns.get(label, 0) + (gap[1] - gap[0])

    by_scope: dict[str, int] = {}
    for scope, ns, _ in joined(devices, scopes or {}):
        path = scope_path(scope)
        by_scope[path] = by_scope.get(path, 0) + ns
    by_scope = fold_siblings(by_scope)

    def ranked(table, scale=1.0, rest=None):
        rows = sorted(table.items(), key=lambda kv: -kv[1])
        return cut([[n, ns * scale / 1e9] for n, ns in rows], top, rest)

    return {
        "devices": devices,
        "n_devices": n_dev,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(d["busy_ns"] for d in devices) / n_dev / 1e9,
        "idle_pct_worst": 100.0 * max(
            1.0 - d["busy_ns"] / d["window_ns"] for d in devices),
        "device_ops": ranked(by_op, 1.0 / n_dev),
        "device_scopes": (ranked(by_scope, 1.0 / n_dev, rest="(other)")
                          if scopes else []),
        "idle_gaps": ranked(gap_ns),
    }
