"""Process bootstrap / rendezvous — the TPU-native runtime layer.

Capability parity with the reference's L4/L3 bootstrap glue
(reference: test_init.py:45-100, allreduce_toy.py:10-18,52-58,
mnist_distributed.py:15-23,124-125): ``find_free_port`` + MASTER_ADDR/
MASTER_PORT env vars + ``dist.init_process_group('nccl'|'gloo')`` become a
coordinator address + ``jax.distributed.initialize()``.

Key design differences from the reference (TPU-first, not a port):

- **One process per host, not per chip.** The reference forks one process per
  GPU with ``mp.spawn`` (test_init.py:116). On TPU, all local chips belong to
  one process (``jax.local_devices()``), and multi-*host* jobs run one process
  per host. The entire mp.spawn layer collapses; rank arithmetic
  (``rank = nr * gpus + gpu``, mnist_distributed.py:49) becomes
  ``jax.process_index()``.
- **Rendezvous is a coordinator service, not a TCPStore.** The reference sets
  MASTER_ADDR/MASTER_PORT and lets torch's env:// TCPStore handle the
  KV-store rendezvous. Here ``jax.distributed.initialize(coordinator_address,
  num_processes, process_id)`` does the same job over DCN. For familiarity we
  honor MASTER_ADDR/MASTER_PORT env vars when building the default
  coordinator address.
- **Backend selection is automatic.** The reference picks ``'nccl'`` iff CUDA
  is available, else ``'gloo'`` (test_init.py:84-88). JAX picks TPU/CPU the
  same way; :func:`backend_name` reports the choice with the same
  role ("which collective fabric will be used").

The reference's ``rank == -1`` "serial mode, skip init" sentinel
(test_init.py:73) is preserved: ``init(process_id=-1)`` is a no-op.
"""

from __future__ import annotations

import os
import socket
import threading
import time
from dataclasses import dataclass
from pathlib import Path

import jax

SERIAL_RANK = -1

#: Where compiled programs persist when nobody placed the cache from
#: outside: a fixed path inside the checkout. The directory is part of the
#: cache key, so it must never come from tempfile, a pid or the clock.
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Give JAX a persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it by itself, and no code
    may point the cache anywhere else (a machine that comes with the
    variable set keeps the cache across runs only if the program writes
    there). Unset, the cache lives at ``DEFAULT_COMPILE_CACHE``. Entry
    scripts call this first, before anything compiles.

    Also starts the program's record of its own launch in the always-on
    registry (``_count_cache_events``): the cache's hits and misses, and
    every program's trace, lower and compile-or-cache-load by name and by
    the span it ran under.
    """
    _count_cache_events()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


#: jax's three duration events of a program on its way to the device, by the
#: phase they clock. Each arrives with ``fun_name=``: the bare function name
#: for a trace (``train_step``), ``jit(train_step)`` for the other two.
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
#: rows of ``compile.program_s``: the programs with the most seconds in all
PROGRAM_TABLE = 32
#: intervals a thread keeps to tell an outer phase from those inside it. A
#: step's trace holds one for every ``jit`` it calls directly (every
#: ``jnp`` function is one): thousands, of two floats each.
_OPEN_INTERVALS = 1 << 16

_cache_listener_on = False
_launch_lock = threading.Lock()
_launch_tls = threading.local()
_programs: dict[str, dict] = {}


def reset_launch_record() -> None:
    """Stop listening and forget the program table: for a test, so that no
    later test of its process finds series it did not make.
    ``_count_cache_events`` starts the record again."""
    global _cache_listener_on
    if _cache_listener_on:
        jax.monitoring.unregister_event_listener(_on_event)
        jax.monitoring.unregister_event_duration_listener(_on_duration)
        _cache_listener_on = False
    with _launch_lock:
        _programs.clear()


def _self_seconds(start: float, duration: float) -> float:
    """``duration`` less what the phases inside it cover. jax reports an
    inner ``jit``'s trace (and an eager operation's three phases) before,
    and within, the trace of the function that called it: an interval that
    starts at or after ``start`` is this one's child, and leaves the list as
    this one joins it, so the list holds outermost intervals only and every
    second is observed once."""
    try:
        open_ = _launch_tls.open
    except AttributeError:
        open_ = _launch_tls.open = []
    inside = 0.0
    while open_ and open_[-1][0] >= start:
        inside += open_.pop()[1]
    open_.append((start, duration))
    if len(open_) > _OPEN_INTERVALS:
        del open_[:_OPEN_INTERVALS // 2]
    return max(duration - inside, 0.0)


def _note_program(program: str, phase: str, cache: str,
                  duration: float) -> None:
    """``compile.program_s{program, phase, cache}``: seconds in all, for
    the ``PROGRAM_TABLE`` programs with the most. A row is a program with
    all its phases, so the 0.15 s cache load of a step that took 10 s to
    trace stays; the smallest row leaves when a larger program arrives."""
    from tpu_sandbox.obs import get_registry

    reg = get_registry()
    with _launch_lock:
        row = _programs.get(program)
        if row is None:
            if len(_programs) >= PROGRAM_TABLE:
                least = min(_programs, key=lambda p: _programs[p]["total"])
                if _programs[least]["total"] >= duration:
                    return
                for ph, ca in _programs.pop(least)["seconds"]:
                    reg.drop_gauge("compile.program_s", labels={
                        "program": least, "phase": ph, "cache": ca})
            row = _programs[program] = {"total": 0.0, "seconds": {}}
        row["total"] += duration
        seconds = row["seconds"][phase, cache] = (
            row["seconds"].get((phase, cache), 0.0) + duration)
    reg.gauge("compile.program_s", labels={
        "program": program, "phase": phase, "cache": cache}).set(seconds)


def _on_event(event: str, **kwargs) -> None:
    from tpu_sandbox.obs import get_registry

    if event == "/jax/compilation_cache/cache_hits":
        get_registry().counter("compile.cache_hits").inc()
        _launch_tls.cache = "hit"
    elif event == "/jax/compilation_cache/cache_misses":
        get_registry().counter("compile.cache_misses").inc()
        _launch_tls.cache = "miss"


def _on_duration(event: str, duration: float, **kwargs) -> None:
    from tpu_sandbox.obs import get_recorder, get_registry

    phase = _PHASES.get(event)
    if phase is None:
        return
    reg, rec = get_registry(), get_recorder()
    start = time.monotonic() - duration
    seconds = _self_seconds(start, duration)
    under = rec.innermost(skip="trace:") or "none"
    labels = {"under": under}
    cache = "none"
    if phase == "trace":
        reg.histogram("compile.trace_s", labels=labels).observe(seconds)
    elif phase == "lower":
        reg.histogram("compile.lower_s", labels=labels).observe(seconds)
    else:
        reg.histogram("compile.backend_s", labels=labels).observe(seconds)
        rec.compiles += 1
        cache = getattr(_launch_tls, "cache", "none")
        _launch_tls.cache = "none"
    name = kwargs.get("fun_name", "?")
    program = name if "(" in name else f"jit({name})"
    _note_program(program, phase, cache, duration)
    step = rec.loop_step
    if step is not None and phase == "backend":
        reg.counter("compile.in_loop").inc()
        rec.instant("compile:in_loop",
                    args={"program": program, "step": step})
    if rec.enabled:
        args = {"program": program, "under": under, "cache": cache}
        if step is not None:
            args["step"] = step
        rec.complete(f"compile:{phase}", start, args=args, loop=True)


def _count_cache_events() -> None:
    """The program's record of its own launch, in the always-on registry;
    registers, once, the two ``jax.monitoring`` listeners behind it
    (``_on_event``, ``_on_duration``).

    - ``compile.cache_hits`` (a program loaded instead of compiled) and
      ``compile.cache_misses`` (a program compiled and written: jax counts
      a miss only where it writes the entry, i.e. a compile above its
      ``jax_persistent_cache_min_compile_time_secs``), and
      ``compile.in_loop``. All three exist from this call on, so a warm run
      reads a true 0, not nothing.
    - histograms ``compile.trace_s``, ``compile.lower_s``,
      ``compile.backend_s`` with the label ``under``: the innermost span
      open on the thread (``setup:model_init``, ``compile:lower_step``, ...;
      ``trace:`` kernel sites passed over), or ``none``. Each observes a
      phase's seconds *less the phases inside it* (``_self_seconds``), so a
      ``jit`` inside a ``jit`` counts once and the three sums add up to wall
      time spent.
    - gauge ``compile.program_s{program, phase, cache}`` (``_note_program``):
      whole seconds by program, ``program`` as ``jit(<name>)`` for every
      phase. ``cache`` is ``hit`` or ``miss`` for a backend phase inside
      which jax's cache event fell (on jax 0.9.0 it arrives before the
      duration event that encloses it, on the same thread), ``none`` for a
      compile too short for jax to cache and for the other phases.
    - with the JSONL on, a retrospective ``compile:<phase>`` record with
      ``program``, ``under``, ``cache`` and, while a loop runs, ``step``.
    - a backend phase while a training loop runs (``Recorder.loop_step``)
      raises ``compile.in_loop`` and writes the instant ``compile:in_loop``
      with ``program`` and ``step``: a recompile traces and lowers too, but
      jax reports one trace for every ``jit`` inside the program and one
      backend phase for the program, so the backend phase is what is
      counted. Nothing waits, nothing is read from the device.
    """
    global _cache_listener_on
    from tpu_sandbox.obs import get_registry

    get_registry().counter("compile.cache_hits")
    get_registry().counter("compile.cache_misses")
    get_registry().counter("compile.in_loop")
    if _cache_listener_on:
        return
    _cache_listener_on = True
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


# Module state: records what init() decided, so entry scripts and tests can
# query topology without re-deriving it.
_state: dict = {"initialized": False, "serial": False, "multiprocess": False}


def find_free_port() -> str:
    """Bind to port 0 and return the OS-assigned free port as a string.

    String (not int) return matches the reference helper, whose result feeds
    an env var (reference: test_init.py:45-53 and two duplicate copies).
    """
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("", 0))
        return str(s.getsockname()[1])


def coordinator_address(host: str | None = None, port: str | int | None = None) -> str:
    """Build the coordinator address, honoring MASTER_ADDR/MASTER_PORT.

    The reference exports MASTER_ADDR=127.0.0.1 and a fresh free port before
    every launch (mnist_distributed.py:124-125). We honor the same env vars
    so launch environments carry over, defaulting to loopback + free port.
    """
    host = host or os.environ.get("MASTER_ADDR", "127.0.0.1")
    port = port or os.environ.get("MASTER_PORT") or find_free_port()
    return f"{host}:{port}"


@dataclass
class Topology:
    """What this process can see after init."""

    process_id: int
    num_processes: int
    local_devices: int
    global_devices: int
    backend: str

    def summary(self) -> str:
        return (
            f"process {self.process_id}/{self.num_processes}: "
            f"{self.local_devices} local / {self.global_devices} global "
            f"{self.backend} device(s)"
        )


def init(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> Topology:
    """Join the distributed job (or no-op for single-process / serial runs).

    Parity with ``setup_process`` (reference: test_init.py:55-94):

    - ``process_id == -1``: serial sentinel — skip initialization entirely.
    - single process (num_processes in (None, 1)): nothing to rendezvous;
      local devices are the world.
    - multi-process: ``jax.distributed.initialize`` against the coordinator.
    """
    global _state
    if process_id == SERIAL_RANK:
        _state = {"initialized": True, "serial": True, "multiprocess": False}
        return topology()

    if _state.get("initialized"):
        return topology()

    num_processes = num_processes or int(os.environ.get("NUM_PROCESSES", "1"))
    if num_processes > 1:
        # Every process must dial the SAME coordinator: require an explicit
        # address or a shared MASTER_ADDR/MASTER_PORT environment. Falling
        # back to a locally-generated free port would give each process a
        # different address and the rendezvous could never complete.
        if coordinator is None:
            if "MASTER_PORT" not in os.environ:
                raise ValueError(
                    "multi-process init needs a shared coordinator: pass "
                    "coordinator='host:port' or export MASTER_ADDR/MASTER_PORT "
                    "identically on every process"
                )
            coordinator = coordinator_address()
        if process_id is None:
            if "PROCESS_ID" not in os.environ:
                raise ValueError(
                    "multi-process init needs process_id (or PROCESS_ID env); "
                    "defaulting it would make every process claim id 0"
                )
            process_id = int(os.environ["PROCESS_ID"])
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
        _state = {"initialized": True, "serial": False, "multiprocess": True}
    else:
        _state = {"initialized": True, "serial": False, "multiprocess": False}
    return topology()


def cleanup() -> None:
    """Tear down the process group (reference: ``cleanup``, test_init.py:96-100).

    Unlike the reference — which defines this but never calls it — the entry
    scripts here do call it.  Serial mode skips, same sentinel semantics.
    """
    global _state
    if _state.get("multiprocess"):
        jax.distributed.shutdown()
    _state = {"initialized": False, "serial": False, "multiprocess": False}


def is_initialized() -> bool:
    return bool(_state.get("initialized"))


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def backend_name() -> str:
    """The collective fabric in use — role parity with backend selection at
    reference test_init.py:84-88 ('nccl' iff CUDA else 'gloo')."""
    return jax.default_backend()


def topology() -> Topology:
    return Topology(
        process_id=jax.process_index(),
        num_processes=jax.process_count(),
        local_devices=jax.local_device_count(),
        global_devices=jax.device_count(),
        backend=backend_name(),
    )


def topology_summary() -> str:
    return topology().summary()
