"""Stage transport: device-buffer shipment of activations/grads between
meshes.

Three wires behind one narrow interface (put / get / poll / claim /
release_step / stats):

- :class:`KVTransport` — the KV store (control plane) + host staging: a
  producer stages its device buffer to host bytes, chunks them under the
  store's read cap, and publishes a seq-numbered slot; the consumer
  reads chunk-pipelined (each chunk is fetched as soon as it lands, not
  after the slot completes) and reassembles through memoryviews, so the
  only full-payload copy on the read side is the final join.
- :class:`LocalTransport` — the in-process host wire (dict + condvar),
  same delivery contract, no sockets. Tier-1's workhorse.
- :class:`DeviceTransport` — the fast path for stages colocated in one
  process on separate meshes: ``put`` hands the producer's device
  arrays straight to the consumer (which ``jax.device_put``-s them onto
  its own mesh), while a durable *journal* transport underneath records
  the same slot for recovery. The journal owns produce-once commits and
  claim-once consumption, so the fault matrix semantics are identical
  to the host wires — the device buffer is just a cache in front of it.

Slots are *durable until acknowledged* — a stage that dies mid-step
relaunches from its checkpoint and replays, and every slot its peers
already produced is still there to re-read, so recovery never recomputes
a neighbor's work. The interface is deliberately narrow so a faster wire
— real DCN send/recv, or ICI once jax grows cross-mesh transfer — can
replace these without touching the schedule or the per-stage programs.

Delivery discipline:

- **Produce once.** ``put`` claims the slot's commit counter with an
  atomic fetch-add; only the first claimant writes. A replaying stage
  (same step re-run after a crash) re-puts the same slot, loses the
  claim, sees the slot complete, and skips — so a slot's payload is
  written exactly once even when the producer runs the step twice.
  If the first claimant died *mid-write* (commit claimed, meta never
  landed), the replayer detects the incomplete slot and finishes it:
  replay is deterministic, so the bytes it writes are the bytes the
  dead writer would have written.
- **Claim-once consume.** ``claim`` is a per-generation fetch-add on
  the slot's claim counter: within one generation a slot feeds exactly
  one consumer op (the duplicate-delivery audit), while a relaunched
  generation claims afresh — replay re-reads are legitimate, double
  consumption inside a live schedule is a bug.
- **TTL hygiene.** Claim markers carry a TTL so a dead generation's
  claims cannot satisfy (or poison) a later one forever. Slot payloads
  are TTL'd only if asked — durability until ``release_step`` is what
  makes crash replay cheap.
- ``release_step`` garbage-collects every slot of an edge up to a step
  the whole pipeline has applied; the leader calls it once per step.
"""

from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from tpu_sandbox.obs import get_recorder, get_registry

SLOT_PREFIX = "mpmd/slot"
CLAIM_PREFIX = "mpmd/claim"


def _account(stats: TransportStats) -> None:
    """Mirror per-transport stats into the process metrics registry so a
    live OP_METRICS scrape sees wire traffic without reaching into every
    Transport instance."""
    reg = get_registry()
    reg.gauge("transport.puts").set(stats.puts)
    reg.gauge("transport.gets").set(stats.gets)
    reg.gauge("transport.bytes_out").set(stats.bytes_out)
    reg.gauge("transport.bytes_in").set(stats.bytes_in)


def pack_views(arrays) -> tuple[dict, list[memoryview]]:
    """[arrays] -> (meta, per-array memoryviews). Raw little-endian
    bytes, no pickling: the payload crosses trust and process
    boundaries, and bitwise replay parity needs the exact bits, not a
    codec's idea of them. The views alias the (contiguous) host arrays —
    zero staging copies until bytes actually hit a wire."""
    meta_arrays = []
    views = []
    for a in arrays:
        a = np.asarray(a)
        shape = list(a.shape)  # before ascontiguousarray: it 1-d's 0-d
        if not a.flags["C_CONTIGUOUS"]:
            a = np.ascontiguousarray(a)
        meta_arrays.append({"shape": shape, "dtype": a.dtype.str})
        views.append(memoryview(a).cast("B") if a.nbytes
                     else memoryview(b""))
    return {"arrays": meta_arrays}, views


def pack_arrays(arrays) -> tuple[dict, bytes]:
    """[arrays] -> (meta, joined payload); the one-copy variant for
    wires that want a single buffer (LocalTransport's slot dict)."""
    meta, views = pack_views(arrays)
    return meta, b"".join(views)


def iter_chunks(views: list[memoryview], chunk_bytes: int):
    """Yield ``chunk_bytes``-sized bytes across the concatenation of
    ``views`` without ever materialising the joined payload — each chunk
    is assembled straight from the array views it overlaps."""
    pending: list[memoryview] = []
    size = 0
    for v in views:
        off = 0
        while off < len(v):
            take = min(chunk_bytes - size, len(v) - off)
            pending.append(v[off:off + take])
            size += take
            off += take
            if size == chunk_bytes:
                yield pending[0].tobytes() if len(pending) == 1 \
                    else b"".join(pending)
                pending, size = [], 0
    if size:
        yield pending[0].tobytes() if len(pending) == 1 \
            else b"".join(pending)


def unpack_arrays(meta: dict, payload) -> list[np.ndarray]:
    """(meta, payload bytes-like) -> [arrays]. Slices through a
    memoryview, so each array aliases the payload buffer instead of
    copying its range out (``bytes`` slicing copies; this path is the
    read side of every wire)."""
    view = memoryview(payload)
    out = []
    off = 0
    for spec in meta["arrays"]:
        dt = np.dtype(spec["dtype"])
        n = int(np.prod(spec["shape"], dtype=np.int64)) * dt.itemsize
        out.append(
            np.frombuffer(view[off:off + n], dt).reshape(spec["shape"]))
        off += n
    if off != len(view):
        raise ValueError(
            f"payload is {len(view)} bytes, meta describes {off}")
    return out


@dataclass
class TransportStats:
    """Wire accounting. Latencies are whole-op wall
    times (staging + chunk puts / blocking wait + reassembly)."""

    puts: int = 0
    gets: int = 0
    bytes_out: int = 0
    bytes_in: int = 0
    put_seconds: float = 0.0
    get_seconds: float = 0.0       # retrieval work only (wait excluded)
    get_wait_seconds: float = 0.0  # time blocked on a slot not yet produced
    device_hits: int = 0           # gets served from the device buffer
    journal_fallbacks: int = 0     # gets that fell back to the journal

    def snapshot(self) -> dict:
        out = {
            "puts": self.puts, "gets": self.gets,
            "bytes_out": self.bytes_out, "bytes_in": self.bytes_in,
            "put_seconds": round(self.put_seconds, 6),
            "get_seconds": round(self.get_seconds, 6),
            "get_wait_seconds": round(self.get_wait_seconds, 6),
        }
        if self.device_hits or self.journal_fallbacks:
            out["device_hits"] = self.device_hits
            out["journal_fallbacks"] = self.journal_fallbacks
        return out


class Transport:
    """Interface contract; see the module docstring for the semantics."""

    stats: TransportStats

    def put(self, edge: str, step: int, mb: int, arrays) -> bool:
        """Publish a slot. True if this call won the produce claim, False
        when the slot was already complete (idempotent replay)."""
        raise NotImplementedError

    def get(self, edge: str, step: int, mb: int, *,
            timeout: float = 60.0) -> list[np.ndarray]:
        """Block until the slot exists; TimeoutError past ``timeout``."""
        raise NotImplementedError

    def poll(self, edge: str, step: int, mb: int) -> bool:
        raise NotImplementedError

    def claim(self, edge: str, step: int, mb: int, generation: int) -> bool:
        """Claim-once consume marker; True exactly once per generation."""
        raise NotImplementedError

    def release_step(self, edge: str, step: int) -> None:
        """Drop every slot of ``edge`` at ``step`` (pipeline has applied)."""
        raise NotImplementedError


class LocalTransport(Transport):
    """In-process transport over a dict + condition variable. Same
    produce-once/claim-once contract as the KV wire (a slot survives its
    producer; replays re-put idempotently), so the tier-1 schedule and
    recovery tests exercise the exact delivery discipline the distributed
    path relies on — without sockets."""

    def __init__(self):
        self._slots: dict[tuple, tuple[dict, bytes]] = {}
        self._commits: dict[tuple, int] = {}
        self._claims: dict[tuple, int] = {}
        self._cond = threading.Condition()
        self.stats = TransportStats()

    def put(self, edge, step, mb, arrays) -> bool:
        t0 = time.perf_counter()
        meta, payload = pack_arrays(arrays)
        key = (edge, step, mb)
        with self._cond:
            self._commits[key] = self._commits.get(key, 0) + 1
            first = self._commits[key] == 1
            if not first and key in self._slots:
                return False
            self._slots[key] = (meta, payload)
            self._cond.notify_all()
        self.stats.puts += 1
        self.stats.bytes_out += len(payload)
        self.stats.put_seconds += time.perf_counter() - t0
        _account(self.stats)
        get_recorder().instant(
            "slot:put", args={"edge": edge, "step": step, "mb": mb,
                              "bytes": len(payload), "first": first})
        return first

    def get(self, edge, step, mb, *, timeout: float = 60.0):
        t0 = time.perf_counter()
        t_mono = time.monotonic()
        key = (edge, step, mb)
        deadline = t0 + timeout
        waited = 0.0
        with self._cond:
            while key not in self._slots:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    raise TimeoutError(f"transport slot {key} never arrived")
                w0 = time.perf_counter()
                self._cond.wait(min(remaining, 0.05))
                waited += time.perf_counter() - w0
            meta, payload = self._slots[key]
        out = unpack_arrays(meta, payload)
        self.stats.gets += 1
        self.stats.bytes_in += len(payload)
        # blocked-on-producer time is the schedule's, not the wire's:
        # it lands in get_wait_seconds and the span starts after it, so
        # get_seconds / slot:get durs measure retrieval work only
        self.stats.get_wait_seconds += waited
        self.stats.get_seconds += time.perf_counter() - t0 - waited
        _account(self.stats)
        get_recorder().complete(
            "slot:get", t_mono + waited,
            args={"edge": edge, "step": step, "mb": mb,
                  "bytes": len(payload), "tier": "local"})
        return out

    def poll(self, edge, step, mb) -> bool:
        with self._cond:
            return (edge, step, mb) in self._slots

    def claim(self, edge, step, mb, generation) -> bool:
        key = (edge, step, mb, generation)
        with self._cond:
            self._claims[key] = self._claims.get(key, 0) + 1
            won = self._claims[key] == 1
        if won:
            get_recorder().instant(
                "slot:claim", args={"edge": edge, "step": step, "mb": mb,
                                    "gen": generation})
        return won

    def release_step(self, edge, step) -> None:
        with self._cond:
            for key in [k for k in self._slots if k[0] == edge
                        and k[1] == step]:
                del self._slots[key]

    # -- audit (tier-1 delivery tests) --------------------------------------

    def audit(self) -> dict:
        """Counters for the zero-dup/zero-loss audit: commit attempts per
        slot and claims per (slot, generation)."""
        with self._cond:
            return {
                "commits": {"/".join(map(str, k)): v
                            for k, v in self._commits.items()},
                "claims": {"/".join(map(str, k)): v
                           for k, v in self._claims.items()},
            }


class KVTransport(Transport):
    """The KV-store wire. Chunked puts sized under the client's 1 MiB
    read cap; meta is written LAST so its presence is the slot-complete
    signal; commit/claim counters give produce-once / claim-once.

    ``kv`` may be namespaced or raw — stages of one pipeline must share
    the SAME namespace view (the transport plane is cross-job state when
    stages run as separate scheduler jobs, so it lives under a pipeline
    prefix, not under either job's ``job/<id>/``).
    """

    def __init__(self, kv, *, prefix: str = "", chunk_bytes: int = 256 << 10,
                 claim_ttl: float = 600.0, slot_ttl: float | None = None,
                 poll_interval: float = 0.005):
        if chunk_bytes < 1 or chunk_bytes > (1 << 20) - 4096:
            raise ValueError(
                f"chunk_bytes {chunk_bytes} must fit the KV read cap (1MiB)")
        self.kv = kv
        self.prefix = prefix.rstrip("/") + "/" if prefix else ""
        self.chunk_bytes = chunk_bytes
        self.claim_ttl = claim_ttl
        self.slot_ttl = slot_ttl
        self.poll_interval = poll_interval
        self.stats = TransportStats()

    def _slot(self, edge: str, step: int, mb: int) -> str:
        return f"{self.prefix}{SLOT_PREFIX}/{edge}/{step}/{mb}"

    def _set(self, key: str, val: bytes) -> None:
        if self.slot_ttl is not None:
            self.kv.set_ttl(key, val, self.slot_ttl)
        else:
            self.kv.set(key, val)

    def put(self, edge, step, mb, arrays) -> bool:
        t0 = time.perf_counter()
        meta, views = pack_views(arrays)
        nbytes = sum(len(v) for v in views)
        slot = self._slot(edge, step, mb)
        first = self.kv.add(f"{slot}/commit", 1) == 1
        if not first and self.kv.try_get(f"{slot}/meta") is not None:
            return False  # complete slot: replay no-op
        # not first but incomplete: the claimant died mid-write — finish
        # its slot (deterministic replay writes the identical bytes).
        # Chunks stream straight off the array views (iter_chunks) — the
        # joined payload never exists on the put side.
        nchunks = 0
        for i, chunk in enumerate(iter_chunks(views, self.chunk_bytes)):
            self._set(f"{slot}/chunk/{i}", chunk)
            nchunks = i + 1
        meta = dict(meta, nchunks=nchunks, bytes=nbytes, seq=(step, mb))
        self._set(f"{slot}/meta", json.dumps(meta).encode())
        self.stats.puts += 1
        self.stats.bytes_out += nbytes
        self.stats.put_seconds += time.perf_counter() - t0
        _account(self.stats)
        get_recorder().instant(
            "slot:put", args={"edge": edge, "step": step, "mb": mb,
                              "bytes": nbytes, "first": first})
        return first

    def get(self, edge, step, mb, *, timeout: float = 60.0):
        """Chunk-pipelined read: chunks are written before the slot's
        meta, so the consumer fetches chunk ``i`` as soon as it appears
        and overlaps its reads with the producer's remaining writes —
        the wait for a slot "in flight" shrinks to the tail chunk plus
        meta instead of the whole staging pass."""
        t0 = time.perf_counter()
        t_mono = time.monotonic()
        slot = self._slot(edge, step, mb)
        deadline = t0 + timeout
        meta = None
        parts = []
        i = 0
        waited = 0.0
        while True:
            chunk = self.kv.try_get(f"{slot}/chunk/{i}")
            if chunk is not None:
                parts.append(chunk)
                i += 1
                continue
            if meta is None:
                raw = self.kv.try_get(f"{slot}/meta")
                if raw is not None:
                    meta = json.loads(raw)
                    # the producer may have landed chunk i AND the meta
                    # between our two probes — re-try the chunk before
                    # judging it missing
                    continue
            if meta is not None:
                if i >= meta["nchunks"]:
                    break
                # chunks land before meta, so a chunk probed AFTER the
                # meta was seen complete can only be missing if deleted
                raise RuntimeError(
                    f"slot {slot} chunk {i} missing under a complete meta "
                    "(released early, or TTL expired mid-read)")
            if time.perf_counter() >= deadline:
                raise TimeoutError(
                    f"transport slot {slot} never arrived ({timeout}s)")
            w0 = time.perf_counter()
            time.sleep(self.poll_interval)
            waited += time.perf_counter() - w0
        payload = parts[0] if len(parts) == 1 else b"".join(parts)
        if len(payload) != meta["bytes"]:
            raise RuntimeError(
                f"slot {slot}: reassembled {len(payload)} bytes, "
                f"meta says {meta['bytes']}")
        out = unpack_arrays(meta, payload)
        self.stats.gets += 1
        self.stats.bytes_in += len(payload)
        # sleeps waiting on the producer are the schedule's share; the
        # chunk fetches interleaved between them are the wire's
        self.stats.get_wait_seconds += waited
        self.stats.get_seconds += time.perf_counter() - t0 - waited
        _account(self.stats)
        get_recorder().complete(
            "slot:get", t_mono + waited,
            args={"edge": edge, "step": step, "mb": mb,
                  "bytes": len(payload), "tier": "kv"})
        return out

    def poll(self, edge, step, mb) -> bool:
        return self.kv.try_get(f"{self._slot(edge, step, mb)}/meta") is not None

    def claim(self, edge, step, mb, generation) -> bool:
        key = (f"{self.prefix}{CLAIM_PREFIX}/{generation}/{edge}/{step}/{mb}")
        n = self.kv.add(key, 1)
        if n == 1:
            # fetch-add created a plain counter; re-arm it as TTL'd so a
            # dead generation's claims expire (value no longer needs to
            # count past "claimed at least twice" for the audit)
            self.kv.set_ttl(key, str(n), self.claim_ttl)
            get_recorder().instant(
                "slot:claim", args={"edge": edge, "step": step, "mb": mb,
                                    "gen": generation})
        return n == 1

    def release_step(self, edge, step) -> None:
        self.kv.delete_prefix(f"{self.prefix}{SLOT_PREFIX}/{edge}/{step}/")

    # -- audit --------------------------------------------------------------

    def audit(self) -> dict:
        """Commit counters per live slot and claim counters per generation
        (released slots drop out of ``commits``; claims persist until
        their TTL, which is what the post-mortem audit reads)."""
        commits, claims = {}, {}
        for key in self.kv.keys(f"{self.prefix}{SLOT_PREFIX}/"):
            if key.endswith("/commit"):
                commits[key[len(self.prefix) + len(SLOT_PREFIX) + 1:
                            -len("/commit")]] = int(self.kv.get(key))
        for key in self.kv.keys(f"{self.prefix}{CLAIM_PREFIX}/"):
            raw = self.kv.try_get(key)
            if raw is not None:
                claims[key[len(self.prefix) + len(CLAIM_PREFIX) + 1:]] = (
                    int(raw))
        return {"commits": commits, "claims": claims}


class DeviceTransport(Transport):
    """The fast path for stages colocated in one process on separate
    meshes: ``put`` publishes the producer's device arrays as-is (no
    host staging on the data path — the consumer ``jax.device_put``-s
    them onto its own mesh), and a durable *journal* transport
    underneath records the identical slot bytes for recovery.

    Division of labour: the journal is authoritative for produce-once
    commits, claim-once consumption, and the post-mortem audit — this
    class adds only a device-buffer cache in front of it. The buffer is
    published before the journal write, so a consumer never waits on
    host staging; a ``get`` that finds no buffer (a transport rebuilt
    over a persistent journal after a driver crash) falls back to the
    journal's bytes, which deterministic replay guarantees are the bits
    the buffer held.
    """

    def __init__(self, journal: Transport | None = None):
        self.journal = LocalTransport() if journal is None else journal
        self._bufs: dict[tuple, list] = {}
        self._cond = threading.Condition()
        self.stats = TransportStats()

    @staticmethod
    def _nbytes(arrays) -> int:
        return sum(int(getattr(a, "nbytes", 0) or np.asarray(a).nbytes)
                   for a in arrays)

    def put(self, edge, step, mb, arrays) -> bool:
        t0 = time.perf_counter()
        arrays = list(arrays)
        key = (edge, step, mb)
        with self._cond:
            if key not in self._bufs:
                self._bufs[key] = arrays
                self._cond.notify_all()
        # the journal owns the produce-once verdict; a replayed put loses
        # the commit there and leaves the published buffer untouched
        first = self.journal.put(edge, step, mb, arrays)
        self.stats.puts += 1
        self.stats.bytes_out += self._nbytes(arrays)
        self.stats.put_seconds += time.perf_counter() - t0
        _account(self.stats)
        return first

    def get(self, edge, step, mb, *, timeout: float = 60.0):
        t0 = time.perf_counter()
        t_mono = time.monotonic()
        key = (edge, step, mb)
        deadline = t0 + timeout
        waited = 0.0
        with self._cond:
            while key not in self._bufs:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    raise TimeoutError(
                        f"transport slot {key} never arrived ({timeout}s)")
                if self.journal.poll(edge, step, mb):
                    break  # journal has it but no buffer: recovery read
                w0 = time.perf_counter()
                self._cond.wait(min(remaining, 0.01))
                waited += time.perf_counter() - w0
            arrays = self._bufs.get(key)
        if arrays is None:
            out = self.journal.get(
                edge, step, mb,
                timeout=max(0.001, deadline - time.perf_counter()))
            self.stats.journal_fallbacks += 1
            tier = "journal"
        else:
            out = list(arrays)
            self.stats.device_hits += 1
            tier = "device"
        nbytes = self._nbytes(out)
        self.stats.gets += 1
        self.stats.bytes_in += nbytes
        # same split as the staged tiers: blocked-on-producer time goes
        # to get_wait_seconds, get_seconds is the handoff itself
        self.stats.get_wait_seconds += waited
        self.stats.get_seconds += time.perf_counter() - t0 - waited
        _account(self.stats)
        get_recorder().complete(
            "slot:get", t_mono + waited,
            args={"edge": edge, "step": step, "mb": mb,
                  "bytes": nbytes, "tier": tier})
        return out

    def poll(self, edge, step, mb) -> bool:
        with self._cond:
            if (edge, step, mb) in self._bufs:
                return True
        return self.journal.poll(edge, step, mb)

    def claim(self, edge, step, mb, generation) -> bool:
        return self.journal.claim(edge, step, mb, generation)

    def release_step(self, edge, step) -> None:
        with self._cond:
            for key in [k for k in self._bufs if k[0] == edge
                        and k[1] == step]:
                del self._bufs[key]
        self.journal.release_step(edge, step)

    def audit(self) -> dict:
        return self.journal.audit()


@dataclass
class EdgeNames:
    """The two directed edges between adjacent stages s and s+1."""

    stage: int
    act: str = field(init=False)   # activations s -> s+1
    grad: str = field(init=False)  # cotangents  s+1 -> s

    def __post_init__(self):
        self.act = f"act{self.stage}"
        self.grad = f"grad{self.stage}"
