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

    Also counts what the cache does into the always-on registry:
    ``compile.cache_hits`` (a program loaded instead of compiled) and
    ``compile.cache_misses`` (a program compiled and written: jax counts a
    miss only where it writes the entry, i.e. a compile above its
    ``jax_persistent_cache_min_compile_time_secs``). Both exist from this
    call on, so a warm run reads a true 0 misses.
    """
    _count_cache_events()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


_cache_listener_on = False


def _count_cache_events() -> None:
    """Create the two compile-cache counters (so that they read 0, not
    nothing) and register, once, the ``jax.monitoring`` listener behind
    them."""
    global _cache_listener_on
    from tpu_sandbox.obs import get_registry

    get_registry().counter("compile.cache_hits")
    get_registry().counter("compile.cache_misses")
    if _cache_listener_on:
        return
    _cache_listener_on = True

    def on_event(event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            get_registry().counter("compile.cache_hits").inc()
        elif event == "/jax/compilation_cache/cache_misses":
            get_registry().counter("compile.cache_misses").inc()

    jax.monitoring.register_event_listener(on_event)


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
