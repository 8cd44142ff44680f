"""Continuous-batching serve engine (plus the static-batch baseline).

``ContinuousEngine`` owns the compiled steps, the page buffers, and the
paged allocator, and advances the whole replica one decode step at a time:

- **admit at step granularity** — every step first fills free decode slots
  from the waiting queue (prefill runs per request, one compiled bucket);
- **evict at step granularity** — sequences that finish release their
  blocks immediately, and the freed slots/blocks are available to the very
  next admit, no batch barrier;
- **preempt-to-requeue** — when a sequence crosses a block boundary and no
  block can be allocated, the newest-admitted sequence is evicted and its
  request goes back to the waiting queue intact (greedy decode + bitwise
  steps make the replay identical).

``StaticEngine`` is the control: admit a full batch, decode until *all* of
it finishes, then admit the next batch. Same compiled steps, same
allocator — the two differ in scheduling policy only.

Decoding is greedy argmax over fp32 logits by default — deterministic,
which is what makes requeue/replay and the replica zero-loss story exact
rather than probabilistic. Every family's programs give that pick
themselves (``DecodeStep.picks``): the engine takes it (``_choose``) and the logits stay on the device; for a
sampled request, and under a program-less stub step that gives logits
alone, the choice is made here on the host (``_pick_token``). With the pick
on the device the next step's call needs nothing of the host's but lengths
and block tables, which the host knows beforehand: it is **dispatched
ahead** (``_decode_ahead``), before this step's picks are read, so the
device goes from one step into the next while the host reads, emits and
prepares, and a step takes the device's time, not the device's plus the
host's. Whether a step did, or what it saw in its input that kept it from
it, is counted once a step (``engine.decode_ahead{outcome}``). Sampled
decode (``temperature``/``top_k`` on the
request) keeps the same guarantee: the sampler key is derived from the
request seed folded with the decode-step index, so a replayed request
re-draws identical tokens (see ``serve/decode.py:sample_token``).

A step tells its own host side. Six phases tile the span ``engine:step``
(``engine:shed``, ``engine:admit``, ``engine:grow``, then a decode call's
``engine:dispatch`` -- prepare and enqueue, waits for nothing -- and
``engine:wait`` -- the blocking read of its result -- and ``engine:sample``),
and ``step_log`` (``serve/steplog.py``) keeps the last steps in memory, one
record each: the phases' seconds, wall and thread-CPU time, the collector's
runs; a step of 1.5 x its neighbours is a **stall**, counted and kept with
the phase that held it. ``load_report()`` says what they add up to
(``host_ms``, ``wait_ms``, ``stalls``).

SLO guardrails live here too:

- requests may carry an absolute **deadline** (engine clock); waiting or
  active requests past their deadline are **shed** — removed from the
  system with an explicit :class:`ShedRecord` instead of silently rotting
  in the queue;
- ``ServeConfig.max_waiting`` bounds the admission queue — on overload
  ``submit`` first sheds oldest-past-deadline waiters, then sheds the
  incoming request if the queue is still full (the caller learns from the
  ``False`` return and the shed record);
- ``load_report()`` exposes the backpressure signals (queue depth,
  block-pool pressure, decode-step lag) replicas publish to the KV store.
"""

from __future__ import annotations

import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from tpu_sandbox.models.transformer import TransformerConfig
from tpu_sandbox.obs import get_recorder, get_registry
from tpu_sandbox.serve.cache import CacheConfig, PagedKVCache, SeqAlloc
from tpu_sandbox.serve.decode import (DecodeStep, Pages, build_decode_step,
                                      init_buffers, sample_token)
from tpu_sandbox.serve.steplog import StepLog

if TYPE_CHECKING:
    from tpu_sandbox.models.jamba import JambaConfig
    from tpu_sandbox.models.laguna import LagunaConfig
    from tpu_sandbox.models.longcat_flash import LongcatFlashConfig

# engines with a live decode loop / replica thread, for the conftest leak
# fixture (mirrors kvstore.live_servers())
_LIVE_ENGINES: "weakref.WeakSet" = weakref.WeakSet()


def live_engines() -> list:
    return [e for e in _LIVE_ENGINES if e.active_requests or e.waiting]


def engines() -> list:
    """Every engine of this process that is still referenced, busy or not:
    how a reader finds an engine's ``step_log`` after the engine was
    drained."""
    return list(_LIVE_ENGINES)


@dataclass(frozen=True)
class ServeConfig:
    # the model family's configuration: ``build_decode_step`` picks the
    # family's step builder by its type
    model: TransformerConfig | JambaConfig | LongcatFlashConfig \
        | LagunaConfig = field(
        default_factory=TransformerConfig)
    cache: CacheConfig = field(default_factory=CacheConfig)
    max_batch: int = 4
    buckets: tuple[int, ...] = (16, 32, 64)
    cache_dtype: Any = jnp.float32
    eos_token: int | None = None  # None -> run to max_new_tokens
    max_waiting: int = 0          # admission-queue bound; 0 = unbounded


@dataclass
class Request:
    rid: str
    prompt: list[int]
    max_new_tokens: int
    arrival: float = 0.0  # engine clock time the request became visible
    preemptions: int = 0  # times evicted-to-requeue so far
    deadline: float | None = None  # engine clock; past it -> shed, not served
    temperature: float = 0.0       # 0 -> greedy argmax
    top_k: int = 0                 # 0 -> full vocab
    seed: int = 0                  # sampler key; folded with the step index
    ver: int | None = None         # pinned weight version; None -> pin to the
                                   # engine's current version at admit
    tc: dict | None = None         # trace context (wire form); never affects
                                   # tokens, only the flight recorder


@dataclass
class RequestResult:
    rid: str
    tokens: list[int]             # generated tokens only
    ttft: float                   # first-token latency (s, engine clock)
    itl: list[float]              # inter-token latencies (s)
    finished_at: float = 0.0
    preemptions: int = 0
    ver: int = 0                  # weight version every token was decoded on
    tc: dict | None = None        # decode span context; parents the verdict


@dataclass
class ShedRecord:
    """Terminal verdict for a request the engine refused or gave up on.
    A shed request never also produces a RequestResult."""
    rid: str
    reason: str       # "queue_full" | "deadline" | explicit shed reason
    shed_at: float
    preemptions: int = 0
    tc: dict | None = None  # shed-instant context; parents the verdict


@dataclass
class _Slot:
    request: Request
    alloc: SeqAlloc
    tokens: list[int]             # prompt + generated
    generated: list[int] = field(default_factory=list)
    first_token_at: float | None = None
    last_token_at: float | None = None
    itl: list[float] = field(default_factory=list)
    preemptions: int = 0
    ver: int = 0                      # weight version this slot decodes on
    logprob_sum: float = 0.0          # sum of chosen-token logprobs
    tc: dict | None = None            # admit span context
    admitted_mono: float | None = None  # real monotonic time of admission
                                        # (the engine clock may be a fake)


@dataclass
class _Ahead:
    """A decode call dispatched before the picks of the call before it
    were read (``_decode_ahead``)."""
    picks: Any                  # its rows' picks, still on the device
    slots: dict[int, _Slot]     # row -> the slot it decodes for
    ver: int                    # the weight version it runs on


#: "this version is not resident" — distinct from None, which is a valid
#: params value for stub-step engines that never touch weights
_MISSING = object()


def _token_logprob(logits_row: np.ndarray, token: int,
                   logsumexp: float | None = None) -> float:
    """Logprob of ``token`` under fp32 ``logits_row`` (stable logsumexp;
    ``logsumexp`` where the program gave the row's own: nothing of the
    vocabulary is then exponentiated here).
    Fed into the ``engine.logprob`` series the canary analysis compares —
    a weight regression shows up as the model scoring its own chosen
    tokens lower, with no reference labels needed."""
    if logsumexp is not None:
        return float(logits_row[int(token)]) - float(logsumexp)
    row = np.asarray(logits_row, np.float64)
    m = float(row.max())
    return float(row[int(token)] - m - np.log(np.exp(row - m).sum()))


class _EngineBase:
    def __init__(self, params, config: ServeConfig,
                 step: DecodeStep | None = None,
                 clock: Callable[[], float] = time.monotonic,
                 version: int = 0, loader: Callable | None = None):
        self.config = config
        # weights are versioned: requests pin the version they started on
        # and decode on it to the last token, even across a swap (grouped
        # decode below). The boot version is retained forever — it is the
        # rollback target when nothing was ever promoted.
        self.version = int(version)
        self._boot_version = int(version)
        self._params_by_ver: dict[int, Any] = {int(version): params}
        self.loader = loader  # optional: ver -> params | None, for pinned
                              # versions this process never held (post-respawn)
        self.step_fns = step or build_decode_step(
            config.model, config.cache, max_batch=config.max_batch,
            buckets=config.buckets, cache_dtype=config.cache_dtype)
        # the device state both programs take and give back, donated: the
        # pages and a third buffer where the family has one (a model with
        # recurrent layers: every slot's state; one with routed experts:
        # the shares' counters)
        self.recurrent = bool(getattr(self.step_fns, "recurrent", False))
        self.picks = bool(getattr(self.step_fns, "picks", False))
        self.cache = PagedKVCache(config.cache, recurrent=self.recurrent)
        # a stub step (no ``buffers``) carries empty pages and never reads
        # them
        self.k_pages, self.v_pages, *state = init_buffers(self.step_fns) \
            if getattr(self.step_fns, "buffers", ()) else (Pages(), Pages())
        self.state = state[0] if state else None
        if self.recurrent:
            get_registry().gauge("serve.state_bytes").set(sum(
                x.nbytes for x in jax.tree.leaves(self.state)))
        if self.k_pages and not self.v_pages:  # a latent cache: one row a
            # position, no separate V (``serve/decode.py::page_shapes``)
            get_registry().gauge("serve.latent_bytes").set(sum(
                x.nbytes for x in self.k_pages))
        self.clock = clock
        self.waiting: deque[Request] = deque()
        self.slots: list[_Slot | None] = [None] * config.max_batch
        self.results: dict[str, RequestResult] = {}
        self.shed: dict[str, ShedRecord] = {}
        self.steps = 0
        self.last_step_at: float | None = None
        self._ahead: _Ahead | None = None
        #: the last steps, one record each, and the stalls among them
        #: (``serve/steplog.py``)
        self.step_log = StepLog()
        _LIVE_ENGINES.add(self)

    # -- public --------------------------------------------------------------

    @property
    def params(self):
        """The *current* version's weights (the long-standing single-version
        API; versioned access goes through ``_params_for``)."""
        return self._params_by_ver[self.version]

    @params.setter
    def params(self, value) -> None:
        self._params_by_ver[self.version] = value

    @property
    def active_requests(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def swap_params(self, params, version: int) -> int:
        """Install ``params`` as weight ``version`` and make it current,
        between decode steps. Resident paged-KV state is NOT drained: live
        slots keep decoding on the version they pinned at admit (grouped
        decode), only the prefix cache is flushed — its K/V was computed
        under other weights. Returns the number of cache entries flushed."""
        t_swap = time.monotonic()
        self._params_by_ver[int(version)] = params
        self.version = int(version)
        flushed = self.cache.flush_prefix_cache()
        self._gc_params()
        get_registry().counter("engine.swap").inc()
        # process-level span (no request parent): the critpath analyzer
        # overlaps it against resident requests' gaps — a swap stalls
        # every request on this engine, and that stall should be blamed
        # on the swap, not on "queue_wait"
        get_recorder().complete("swap:pause", t_swap,
                                args={"ver": int(version),
                                      "flushed": int(flushed)})
        return flushed

    def has_version(self, ver: int) -> bool:
        return int(ver) in self._params_by_ver

    def _params_for(self, ver: int):
        """Weights for ``ver``, or the ``_MISSING`` sentinel (None is a
        valid params value — stub engines run weightless)."""
        ver = int(ver)
        if ver in self._params_by_ver:
            return self._params_by_ver[ver]
        if self.loader is not None:
            params = self.loader(ver)
            if params is not None:
                self._params_by_ver[ver] = params
                return params
        return _MISSING

    def _gc_params(self) -> None:
        keep = {self.version, self._boot_version}
        keep.update(s.ver for s in self.slots if s is not None)
        keep.update(int(r.ver) for r in self.waiting if r.ver is not None)
        for ver in [v for v in self._params_by_ver if v not in keep]:
            del self._params_by_ver[ver]

    def submit(self, request: Request) -> bool:
        """Admit ``request`` to the waiting queue. Returns False when the
        request was shed instead (bounded queue full even after expired
        waiters were swept) — a ShedRecord is written either way, so every
        submitted request reaches exactly one terminal verdict."""
        if self.cache.blocks_needed(request.prompt, request.max_new_tokens) \
                > self.config.cache.max_blocks_per_seq:
            raise ValueError(f"request {request.rid} exceeds max context")
        limit = self.config.max_waiting
        if limit and len(self.waiting) >= limit:
            # shed-on-overload: oldest-past-deadline first, then the arrival
            self.shed_expired()
            if len(self.waiting) >= limit:
                self._record_shed(request, "queue_full")
                return False
        self.waiting.append(request)
        return True

    @property
    def idle(self) -> bool:
        return not self.waiting and self.active_requests == 0

    def run_until_idle(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if self.idle:
                return
            self.step()
        raise RuntimeError("serve engine failed to drain")

    def drain_to_requests(self) -> list[Request]:
        """Evict everything in flight back to request form (original prompt,
        arrival preserved) — the replica's SIGTERM path."""
        self._ahead = None  # its tokens go with the slots they were for
        out = []
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            self.cache.free(slot.alloc, cache_prefix=False)
            out.append(slot.request)
            self.slots[i] = None
        out.extend(self.waiting)
        self.waiting.clear()
        return out

    # -- SLO guardrails ------------------------------------------------------

    def _record_shed(self, request: Request, reason: str,
                     preemptions: int | None = None,
                     tc: dict | None = None) -> None:
        # the shed instant is the trace's terminal node for this request;
        # its context rides the ShedRecord so the replica's verdict
        # instant stays chained
        ctx = get_recorder().instant(
            f"shed:{reason}", parent=tc if tc is not None else request.tc,
            args={"rid": request.rid})
        get_registry().counter("engine.shed", labels={"reason": reason}).inc()
        self.shed[request.rid] = ShedRecord(
            rid=request.rid, reason=reason, shed_at=self.clock(),
            preemptions=request.preemptions if preemptions is None
            else preemptions,
            tc=None if ctx is None else ctx.to_wire())

    def shed_expired(self) -> int:
        """Shed every waiting or active request whose deadline has passed,
        oldest (queue head / earliest-admitted slot) first. Runs at submit
        overload and at the top of every step, so a request past its
        deadline can never be admitted or produce a late result."""
        now = self.clock()
        n = 0
        keep: deque[Request] = deque()
        while self.waiting:
            req = self.waiting.popleft()
            if req.deadline is not None and now > req.deadline:
                self._record_shed(req, "deadline")
                n += 1
            else:
                keep.append(req)
        self.waiting = keep
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            dl = slot.request.deadline
            if dl is not None and now > dl:
                self.cache.free(slot.alloc, cache_prefix=False)
                self.slots[i] = None
                self._record_shed(slot.request, "deadline",
                                  preemptions=slot.preemptions)
                n += 1
        return n

    def shed_waiting(self, reason: str) -> int:
        """Shed the entire waiting queue (the ``shed_storm`` fault)."""
        n = len(self.waiting)
        while self.waiting:
            self._record_shed(self.waiting.popleft(), reason)
        return n

    def load_report(self) -> dict:
        """Backpressure signals a replica publishes to the KV store.

        ``prefix_digest`` rides along so the gateway can route by prefix
        affinity from the load reports alone — no extra KV round trips per
        request (see ``cache.PagedKVCache.resident_prefix_digest``)."""
        now = self.clock()
        cache = self.cache
        rec_stats = get_recorder().stats()
        return {
            "queue_depth": len(self.waiting),
            "active": self.active_requests,
            "ver": self.version,  # the swap ack the deploy controller reads
            "max_batch": self.config.max_batch,
            "free_block_frac": cache.free_blocks / cache.config.num_blocks,
            "steps": self.steps,
            "step_age": None if self.last_step_at is None
            else now - self.last_step_at,
            "shed": len(self.shed),
            "done": len(self.results),
            "prefix_digest": cache.resident_prefix_digest(),
            "recorder": rec_stats,
            # a silently-dropping recorder must be visible at the top
            # level of every load report, not buried in a nested dict
            "dropped_events": rec_stats["dropped"],
            # the host's own share of a step, its slack behind the device,
            # and the steps that stalled: ``host_ms``, ``wait_ms``, ``stalls``
            **self.step_log.report(),
        }

    # -- shared mechanics ----------------------------------------------------

    def _run(self, program: Callable, params, *args):
        """A compiled step over the donated device state (the pages and,
        where the model has one, the slot state): its logits, still on the
        device, and the rows' greedy picks where the program gives them
        (``DecodeStep.picks``), else None."""
        held = (self.k_pages, self.v_pages) + (
            () if self.state is None else (self.state,))
        logits, *rest = program(params, *held, *args)
        picks = rest.pop(0) if self.picks else None
        self.k_pages, self.v_pages, *state = rest
        if state:
            self.state = state[0]
        return logits, picks

    def _choose(self, slot: _Slot, row, pick) -> None:
        """The slot's next token and its log-probability: the program's own
        pick (``[token, logprob, logsumexp]`` of the row) for a greedy
        request, else ``_pick_token`` over the logits ``row`` on the
        host."""
        if pick is not None and slot.request.temperature <= 0.0:
            token, logprob = int(pick[0]), float(pick[1])
        else:
            token = self._pick_token(slot, row)
            logprob = _token_logprob(
                row, token, None if pick is None else pick[2])
        slot.logprob_sum += logprob
        self._emit_token(slot, token)

    def _admit_from_waiting(self) -> bool:
        """Admit (or resolve) the queue head. True = the head was consumed
        (admitted, or shed because its pinned version is gone); False = the
        head is blocked on capacity and the loop should stop."""
        req = self.waiting[0]
        ver = self.version if req.ver is None else int(req.ver)
        if self._params_for(ver) is _MISSING:
            # the pinned weights no longer exist in this process (respawn
            # after a swap, no loader): an explicit shed verdict, so the
            # client restarts a fresh single-version lifecycle — never a
            # silent decode on different weights than the pin
            self.waiting.popleft()
            self._record_shed(req, "stale_version")
            return True
        if not self._try_admit(req):
            return False
        self.waiting.popleft()
        return True

    def _try_admit(self, request: Request) -> bool:
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free:
            return False
        # reserve the prompt's blocks only; decode grows the allocation one
        # block at a time, so block pressure shows up as preempt-to-requeue
        # rather than refused admission
        alloc = self.cache.alloc(request.prompt, 0)
        if alloc is None:
            return False
        self._prefill(request, alloc, free[0])
        return True

    def _prefill(self, request: Request, alloc: SeqAlloc, slot_idx: int):
        rec = get_recorder()
        ver = self.version if request.ver is None else int(request.ver)
        request.ver = ver  # pin sticks to the request: preempt-to-requeue
                           # and drain replay on these weights, swap or not
        # the admit span covers admission bookkeeping plus the prefill
        # compute; the prefill child span carves the compute out so the
        # critpath analyzer can tell "slow admission" from "big prompt",
        # and ends when the logits are on the host — the device has
        # finished — not when the program was enqueued. The decode span
        # that follows is emitted retrospectively at retire time,
        # anchored at the admit span's end
        with rec.span("admit", parent=request.tc,
                      args={"rid": request.rid}) as admit:
            params = self._params_for(ver)
            if params is _MISSING:
                raise KeyError(
                    f"request {request.rid} pinned to version {ver} but no "
                    f"such params are resident (admit through the queue, "
                    f"which sheds stale pins, or provide a loader)")
            plen = len(request.prompt)
            bucket = self.step_fns.pick_bucket(plen)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :plen] = request.prompt
            dest = self.cache.dest_indices(alloc, bucket).astype(np.int32)
            # beside window layers: where they store the prompt's last window
            more = (jnp.asarray(self.cache.window_dest_indices(
                alloc, bucket, plen).astype(np.int32)),) \
                if self.config.cache.window else ()
            with rec.span("prefill", parent=admit.ctx,
                          args={"rid": request.rid, "plen": plen},
                          hist="engine.prefill_s"):
                # a recurrent model's prefill replaces the slot's state
                # with the prompt's: the slot's reset at admission (and at
                # the replay of a preempted request)
                slot_arg = (jnp.asarray(slot_idx, jnp.int32),) \
                    if self.recurrent else ()
                next_logits, pick = self._run(
                    self.step_fns.prefill[bucket], params,
                    jnp.asarray(toks), jnp.asarray(dest),
                    jnp.asarray(plen - 1, jnp.int32), *slot_arg, *more)
                # the logits come to the host only for a request that
                # samples from them
                if pick is not None:
                    pick = np.asarray(pick)
                row = None if pick is not None and request.temperature <= 0.0 \
                    else np.asarray(next_logits).reshape(-1)
            if self.recurrent:
                get_registry().counter("serve.state_resets").inc()
            alloc.length = plen
            self.cache.commit_prefix(alloc)
            slot = _Slot(request=request, alloc=alloc,
                         tokens=list(request.prompt),
                         preemptions=request.preemptions, ver=ver)
        slot.tc = None if admit.ctx is None else admit.ctx.to_wire()
        slot.admitted_mono = time.monotonic()
        self.slots[slot_idx] = slot
        self._choose(slot, row, pick)
        get_registry().counter("engine.tokens").inc()
        if self._finished(slot):
            self._retire(slot_idx)

    def _pick_token(self, slot: _Slot, logits_row: np.ndarray) -> int:
        """Greedy argmax, or sampled via a key derived from (request seed,
        decode-step index). The step index is ``len(slot.generated)`` — on
        requeue the request replays from its original prompt, so every
        re-draw folds the same index into the same key and the sampled
        trajectory is bitwise identical to the unfaulted run."""
        req = slot.request
        if req.temperature <= 0.0:
            return int(logits_row.argmax())
        return sample_token(logits_row, seed=req.seed,
                            step_index=len(slot.generated),
                            temperature=req.temperature, top_k=req.top_k)

    def _emit_token(self, slot: _Slot, token: int) -> None:
        now = self.clock()
        if slot.first_token_at is None:
            slot.first_token_at = now
        elif slot.last_token_at is not None:
            slot.itl.append(now - slot.last_token_at)
        slot.last_token_at = now
        slot.generated.append(token)
        slot.tokens.append(token)

    def _finished(self, slot: _Slot) -> bool:
        if len(slot.generated) >= slot.request.max_new_tokens:
            return True
        eos = self.config.eos_token
        return eos is not None and slot.generated and slot.generated[-1] == eos

    def _retire(self, i: int) -> None:
        slot = self.slots[i]
        self.slots[i] = None
        self.cache.free(slot.alloc)
        req = slot.request
        ctx = get_recorder().complete(
            "decode",
            slot.admitted_mono if slot.admitted_mono is not None
            else time.monotonic(),
            parent=slot.tc,
            args={"rid": req.rid, "tokens": len(slot.generated)})
        tc = None if ctx is None else ctx.to_wire()
        if req.deadline is not None and self.clock() > req.deadline:
            # finished, but past the promise: the verdict is SHED, never a
            # late result
            self._record_shed(req, "deadline", preemptions=slot.preemptions,
                              tc=tc)
            return
        get_registry().counter("engine.done").inc()
        get_registry().histogram("engine.ttft").observe(
            slot.first_token_at - req.arrival)
        get_registry().histogram("engine.logprob").observe(
            slot.logprob_sum / max(1, len(slot.generated)))
        self.results[req.rid] = RequestResult(
            rid=req.rid, tokens=list(slot.generated),
            ttft=slot.first_token_at - req.arrival,
            itl=list(slot.itl), finished_at=self.clock(),
            preemptions=slot.preemptions, ver=slot.ver, tc=tc)

    def _preempt(self, i: int) -> None:
        """Evict slot i back to the waiting queue (front: it has seniority)."""
        slot = self.slots[i]
        self.slots[i] = None
        self.cache.free(slot.alloc, cache_prefix=False)
        req = slot.request
        req.preemptions = slot.preemptions + 1
        self.waiting.appendleft(req)

    def _ensure_capacity(self, i: int) -> bool:
        """Grow slot i's allocation for its next token; on block pressure
        preempt the newest other slot and retry. False = slot i itself must
        be preempted (nothing left to evict)."""
        slot = self.slots[i]
        need_block = slot.alloc.length % self.config.cache.block_size == 0 \
            and slot.alloc.length // self.config.cache.block_size \
            >= len(slot.alloc.block_ids)
        if not need_block:
            return True
        while not self.cache.grow(slot.alloc):
            victims = [j for j, s in enumerate(self.slots)
                       if s is not None and j != i]
            if not victims:
                return False
            self._preempt(max(victims, key=lambda j: self.slots[j].alloc.seq_id))
        return True

    def _decode_active(self) -> None:
        """One compiled decode step over every occupied slot. Around a
        weight swap the batch can hold slots pinned to different versions:
        one decode call runs per resident version, with the other
        versions' rows zeroed out (length 0 masks their reads, table 0
        scatters their writes to the null block, and a recurrent layer
        leaves such a row's state as it was — exactly the treatment empty
        slots already get), so every sequence decodes every token on
        the weights it pinned at admit, never a blend.

        A call dispatched ahead by the step before (``_decode_ahead``) is
        this step's call for the slots that still hold the row it decoded
        for them; the rows of slots that left since (retired, shed,
        preempted) are dropped, and slots it does not hold (admitted since)
        get a call of their own, the others' rows zeroed."""
        B = self.config.max_batch
        rec = get_recorder()
        log = self.step_log
        # resolve capacity for every slot first: growing one slot may
        # preempt another that was already swept, so the batch is built
        # only from the survivors
        with rec.span("engine:grow", loop=True) as sp:
            for i in range(B):
                if self.slots[i] is not None and not self._ensure_capacity(i):
                    self._preempt(i)
        log.grow_s = sp.dur
        ahead, self._ahead = self._ahead, None
        covered = {} if ahead is None else {
            i: s for i, s in ahead.slots.items() if self.slots[i] is s}
        by_ver: dict[int, list[int]] = {}
        for i, slot in enumerate(self.slots):
            if slot is not None and i not in covered:
                by_ver.setdefault(slot.ver, []).append(i)
        if not by_ver and not covered:
            return
        # one call decodes every occupied slot: the next step's can follow
        # it on the device before its picks are read here
        alone = len(by_ver) + bool(covered) == 1
        if not alone:  # several calls this step: none follows them ahead
            versions = set(by_ver) | ({ahead.ver} if covered else set())
            self._ahead_outcome(
                "versions" if len(versions) > 1 else "admitted")
        rows: dict[int, tuple] = {}  # slot -> (logits row, program's pick)
        # a decode call is two phases: ``engine:dispatch`` prepares and
        # enqueues (this step's call, and the next step's ahead of it) and
        # waits for nothing; ``engine:wait`` is the blocking read
        if covered:
            with rec.span("engine:decode_call",
                          hist="engine.decode_call_s", loop=True):
                if alone:
                    with rec.span("engine:dispatch", loop=True) as sp:
                        self._ahead = self._decode_ahead(
                            ahead.picks, ahead.ver)
                    log.dispatch_s += sp.dur
                _, picks = self._fetch(None, ahead.picks)
            for i in covered:
                rows[i] = (None, picks[i])
        for ver in sorted(by_ver):
            members = by_ver[ver]
            # dispatch to logits on the host: device time plus the D2H
            with rec.span("engine:decode_call",
                          hist="engine.decode_call_s", loop=True):
                with rec.span("engine:dispatch", loop=True) as sp:
                    tokens = np.zeros((B, 1), np.int32)
                    lengths = np.zeros((B,), np.int32)
                    for i in members:
                        slot = self.slots[i]
                        tokens[i, 0] = slot.tokens[-1]
                        lengths[i] = len(slot.tokens)
                    logits, picks = self._run(
                        self.step_fns.decode, self._params_by_ver[ver],
                        jnp.asarray(tokens), jnp.asarray(lengths),
                        *self._tables({i: self.slots[i] for i in members}))
                    if alone:
                        self._ahead = self._decode_ahead(picks, ver)
                log.dispatch_s += sp.dur
                # [B, vocab] logits come to the host only if the program
                # picks nothing or a member samples from them
                if picks is not None and not any(
                        self.slots[i].request.temperature > 0.0
                        for i in members):
                    logits = None
                logits, picks = self._fetch(logits, picks)
            for i in members:
                rows[i] = (None if logits is None else logits[i],
                           None if picks is None else picks[i])
        self._emit_rows(rows)

    def _fetch(self, logits, picks) -> tuple:
        """The blocking read of a call's results (``None`` stays ``None``):
        the phase ``engine:wait``."""
        with get_recorder().span("engine:wait", loop=True) as sp:
            if logits is not None:
                logits = np.asarray(logits)
            if picks is not None:
                picks = np.asarray(picks)
        self.step_log.wait_s += sp.dur
        self.step_log.calls += 1
        return logits, picks

    def _emit_rows(self, rows: dict[int, tuple]) -> None:
        """A step's tokens: slot -> (logits row, program's pick)."""
        self.steps += 1
        self.last_step_at = self.clock()
        with get_recorder().span("engine:sample", hist="engine.sample_s",
                                 loop=True) as sp:
            for i in sorted(rows):
                slot = self.slots[i]
                slot.alloc.length = len(slot.tokens)
                self._choose(slot, *rows[i])
                if self._finished(slot):
                    self._retire(i)
            get_registry().counter("engine.tokens").inc(len(rows))
        self.step_log.sample_s += sp.dur
        self.step_log.rows += len(rows)

    def _decode_ahead(self, picks, ver: int) -> _Ahead | None:
        """The **next** step's decode call, dispatched now: ``picks`` are
        this step's, of the one call that decoded every occupied slot, and
        are still on the device, where the call takes its tokens from them
        (``DecodeStep.next_tokens``); its lengths and block tables are what
        this step's emission will make them. The device then runs on into
        the next step while the host reads this one's picks, emits them
        and comes round again.

        None — the next step dispatches as it always did — unless the
        programs pick on the device and every slot is greedy and on
        ``ver``; and where a slot would need a block the pool cannot give:
        preempting is the next step's business. Either way the step counts
        what came of it (``_ahead_outcome``). A slot whose last token
        this step emits by count rides the call as an empty row; one that
        ends on ``eos_token`` cannot be known here, and its row is dropped
        by the next step (what the call wrote for it lies behind its
        sequence's end in blocks it held, and in a slot state the next
        admission replaces)."""
        if not self.picks:
            return self._ahead_outcome("no_picks")
        B = self.config.max_batch
        cfg = self.config.cache
        members: dict[int, _Slot] = {}
        for i, slot in enumerate(self.slots):
            if slot is None or \
                    len(slot.generated) + 1 >= slot.request.max_new_tokens:
                continue
            if slot.ver != ver:
                return self._ahead_outcome("versions")
            if slot.request.temperature > 0.0:
                return self._ahead_outcome("sampled")
            members[i] = slot
        if not members:
            return self._ahead_outcome("no_rows")
        lengths = np.zeros((B,), np.int32)
        for i, slot in members.items():
            at = len(slot.tokens)  # where the call writes: this step's token
            if at % cfg.block_size == 0 \
                    and at // cfg.block_size >= len(slot.alloc.block_ids) \
                    and not self.cache.grow(slot.alloc):
                return self._ahead_outcome("no_blocks")
            lengths[i] = at + 1
        _, picks = self._run(
            self.step_fns.decode, self._params_by_ver[ver],
            self.step_fns.next_tokens(picks), jnp.asarray(lengths),
            *self._tables(members))
        return self._ahead_outcome(
            "dispatched", _Ahead(picks=picks, slots=members, ver=ver))

    def _tables(self, rows: dict[int, _Slot]) -> tuple:
        """The tables of a decode call that decodes ``rows`` (row -> slot;
        the null block for every other row): the block tables ``[B,
        max_blocks]`` and, beside window layers, the rings ``[B,
        ring_blocks]`` (``cache.window_table``)."""
        cfg = self.config.cache
        B = self.config.max_batch
        tables = np.zeros((B, cfg.max_blocks_per_seq), np.int32)
        rings = np.zeros((B, cfg.ring_blocks), np.int32)
        for i, slot in rows.items():
            tables[i] = self.cache.block_table(slot.alloc)
            if cfg.window:
                rings[i] = self.cache.window_table(slot.alloc)
        return (jnp.asarray(tables),) + (
            (jnp.asarray(rings),) if cfg.window else ())

    @staticmethod
    def _ahead_outcome(outcome: str, ahead: _Ahead | None = None):
        """Count what a step that decoded made of the call ahead, once:
        ``dispatched``, or what it saw that kept it from it -- ``sampled``
        (a member samples), ``versions`` (slots on more than one weight
        version), ``admitted`` (slots admitted since the call before took a
        call of their own), ``no_blocks`` (the pool gives none: the next
        step preempts), ``no_rows`` (every slot on its last token),
        ``no_picks`` (a stub step)."""
        get_registry().counter("engine.decode_ahead",
                               labels={"outcome": outcome}).inc()
        return ahead

    def settle(self) -> None:
        """Resolve the call dispatched ahead, if there is one: its tokens
        are emitted as a step's, and no call follows. Tokens and device
        state then agree as they do after a step that dispatched nothing
        ahead: every token of a sequence but its last has passed through
        the pages and the slot state."""
        ahead, self._ahead = self._ahead, None
        if ahead is None:
            return
        picks = np.asarray(ahead.picks)
        self._emit_rows({i: (None, picks[i]) for i, s in ahead.slots.items()
                         if self.slots[i] is s})

    def _admit_waiting(self) -> None:
        """Fill free slots from the queue head until it blocks."""
        with get_recorder().span("engine:admit",
                                 hist="engine.admit_s", loop=True) as sp:
            while self.waiting:
                if not self._admit_from_waiting():
                    break
        self.step_log.admit_s = sp.dur

    def _admits(self) -> bool:
        """Whether this step fills free slots before it decodes: the two
        engines' one difference."""
        raise NotImplementedError

    def step(self) -> None:
        """One step of the replica: shed, admit, decode. Its phases tile
        the span ``engine:step`` (``engine:shed``, ``engine:admit``,
        ``engine:grow``, then ``engine:dispatch`` and ``engine:wait`` a
        decode call, ``engine:sample``), and the step log keeps the record
        (``serve/steplog.py``)."""
        rec = get_recorder()
        log = self.step_log
        log.begin()
        with rec.span("engine:step", hist="engine.step_s",
                      loop=True) as step:
            with rec.span("engine:shed", loop=True) as sp:
                self.shed_expired()
            log.shed_s = sp.dur
            if self._admits():
                self._admit_waiting()
            self._decode_active()
        log.end(step.dur)


class ContinuousEngine(_EngineBase):
    """Admit/evict at decode-step granularity — freed slots refill before
    the next step, nothing waits for a batch to finish."""

    def _admits(self) -> bool:
        return True


class StaticEngine(_EngineBase):
    """Batch-barrier control: fill the batch once, then decode until every
    member finishes before admitting again."""

    def _admits(self) -> bool:
        return self.active_requests == 0
