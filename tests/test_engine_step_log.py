"""The serve engine's record of its own steps (``serve/steplog.py``, PR 50),
on the CPU with stub steps: the six phases tile ``engine:step`` on every
path of ``_decode_active``; ``engine:dispatch`` ends before a call's result
exists; a stall planted in one phase is named by that phase and told on the
CPU from off it; the collector's runs land in the step that held them; the
rings stay bounded; ``load_report`` carries the operator's three keys.

A stub's "device" is a sleep of ``DEVICE_S`` inside the result's
``__array__``: steps of a steady few milliseconds, so that what the host's
scheduler adds to one of them stays well under the stall rule's 1.5 x."""

import gc
import json
import threading
import time

import numpy as np
import pytest

from tests.helpers import StubStep, counters, label
from tpu_sandbox.models.transformer import TransformerConfig
from tpu_sandbox.obs import get_recorder, get_registry, reset_recorder
from tpu_sandbox.serve import engine as serve_engine
from tpu_sandbox.serve import steplog
from tpu_sandbox.serve.cache import CacheConfig
from tpu_sandbox.serve.engine import (ContinuousEngine, Request, ServeConfig,
                                      StaticEngine)
from tpu_sandbox.serve.steplog import (PHASES, StepLog, format_stall,
                                       offcpu_outside_wait_s)

MCFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                         d_ff=64, max_len=128)
CCFG = CacheConfig(num_blocks=64, block_size=4, max_blocks_per_seq=24)
DEVICE_S = 0.004
#: a planted fault, ten ticks of a thread-CPU clock that counts in 10 ms: what
#: the tests below ask of ``cpu_s`` leaves two ticks of room
PLANTED_S = 0.1


class _Result:
    """A call's result still "on the device": reading it waits."""

    def __init__(self, owner, array):
        self.owner, self.array = owner, np.asarray(array)

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.owner.device_s)
        self.owner.on_read()
        return np.asarray(self.array, dtype)


class PickingStub(StubStep):
    """``StubStep`` whose programs pick on the "device" as the real families'
    do (``DecodeStep.picks``): the engine dispatches its calls ahead. The
    hooks ``on_call`` / ``on_read`` run inside a decode call's enqueue and
    inside the read of its result."""

    picks = True

    def __init__(self, device_s: float = 0.0, prefill_s: float = 0.0, **kw):
        super().__init__(**kw)
        self.device_s, self.prefill_s = device_s, prefill_s
        self.on_call = self.on_read = lambda: None
        self.prefill = {b: self._prefill_picking for b in self.buckets}

    @staticmethod
    def _pick(logits):
        logits = np.atleast_2d(logits)
        return np.stack([logits.argmax(-1), np.zeros(len(logits)),
                         np.zeros(len(logits))], -1).astype(np.float32)

    def _prefill_picking(self, params, k, v, toks, dest, last):
        time.sleep(self.prefill_s)
        logits, k, v = self._prefill(params, k, v, toks, dest, last)
        return logits, self._pick(logits)[0], k, v

    def decode(self, params, k, v, tokens, lengths, tables):
        self.on_call()
        logits, k, v = super().decode(params, k, v, tokens, lengths, tables)
        return (_Result(self, logits), _Result(self, self._pick(logits)),
                k, v)

    def next_tokens(self, picks):
        return picks.array[:, :1].astype(np.int32)


class SlowStub(StubStep):
    """A stub without picks whose logits take ``device_s`` to read."""

    def __init__(self, device_s: float = 0.0, **kw):
        super().__init__(**kw)
        self.device_s = device_s
        self.on_read = lambda: None

    def decode(self, params, k, v, tokens, lengths, tables):
        logits, k, v = super().decode(params, k, v, tokens, lengths, tables)
        return _Result(self, logits), k, v


@pytest.fixture
def registry():
    reg = get_registry()
    reg.reset()
    yield reg
    reg.reset()


def engine(step, cls=ContinuousEngine, max_batch=3):
    cfg = ServeConfig(model=MCFG, cache=CCFG, max_batch=max_batch,
                      buckets=(8, 16))
    return cls(None, cfg, step=step)


def request(rid, new, **kw):
    return Request(rid=rid, prompt=[1, 2, 3], max_new_tokens=new, **kw)


def once_at(eng, at_step: int, what):
    """A hook that runs ``what`` in the step with index ``at_step``."""
    def hook():
        if eng.step_log.logged == at_step:
            what()
    return hook


# -- the phases tile the step ------------------------------------------------


def drive(path: str):
    """An engine run to idle along one path of ``_decode_active``, and the
    ``engine.decode_ahead`` outcome that names the path."""
    if path == "stub without picks":
        eng = engine(SlowStub(DEVICE_S))
        outcome = "no_picks"
    else:
        eng = engine(PickingStub(DEVICE_S))
        outcome = {"ahead covered": "dispatched", "not covered": "admitted",
                   "two versions": "versions", "sampled": "sampled"}[path]
    sampling = {"temperature": 0.7, "seed": 2} if path == "sampled" else {}
    eng.submit(request("a", 12))
    eng.submit(request("b", 12, **sampling))
    since = counters("engine.decode_ahead")
    for n in range(40):
        if eng.idle:
            break
        if n == 4 and path == "not covered":
            eng.submit(request("late", 4))     # a call of its own beside
        if n == 4 and path == "two versions":  # the one that was ahead
            eng.swap_params(None, 1)
            eng.submit(request("v1", 4))
        eng.step()
    assert eng.idle
    gained = {label(k, "outcome") for k in counters("engine.decode_ahead",
                                                    since=since)}
    assert outcome in gained, (path, gained)
    return eng


@pytest.mark.parametrize("path", ["ahead covered", "not covered",
                                  "two versions", "sampled",
                                  "stub without picks"])
def test_the_six_phases_tile_the_step(registry, path):
    eng = drive(path)
    records = list(eng.step_log.steps)
    assert len(records) == eng.step_log.logged >= 11
    uncovered = [r.wall_s - sum(getattr(r, f"{p}_s") for p in PHASES)
                 for r in records]
    assert min(uncovered) > -1e-6  # the phases do not overlap
    # a step: within 5 % or 50 us (one in ten may have lost the CPU in the
    # glue on a busy machine); the run: within 5 %
    loose = [u for u, r in zip(uncovered, records)
             if u > max(0.05 * r.wall_s, 50e-6)]
    assert len(loose) <= len(records) // 10, (loose, path)
    hist = registry.snapshot()["histograms"]
    whole = hist["engine.step_s"]["sum"]
    assert whole == pytest.approx(sum(r.wall_s for r in records))
    assert 0.0 <= sum(uncovered) < 0.05 * whole + 50e-6 * len(loose)
    # the two phases that had a histogram keep it, and the log reads the same
    for phase in ("admit", "sample"):
        assert hist[f"engine.{phase}_s"]["sum"] == pytest.approx(
            sum(getattr(r, f"{phase}_s") for r in records))
    # a decode call is its two phases
    assert sum(r.dispatch_s + r.wait_s for r in records) \
        <= hist["engine.decode_call_s"]["sum"]
    # the four new phases and the derived series have the log for a sink,
    # not a histogram each beside it
    assert not {"engine.shed_s", "engine.grow_s", "engine.dispatch_s",
                "engine.wait_s", "engine.host_s", "engine.step_cpu_s",
                "engine.occupancy"} & set(hist)
    assert 0.0 < sum(r.cpu_s for r in records) < whole  # blocked in the wait
    assert [r.step for r in records] == list(range(len(records)))
    assert sum(r.rows for r in records) == registry.snapshot()["counters"][
        "engine.tokens"] - len(eng.results)  # a prefill's token is no row


def test_the_static_engine_keeps_the_same_record(registry):
    eng = engine(PickingStub(), cls=StaticEngine, max_batch=2)
    for n in range(3):
        eng.submit(request(f"r{n}", 5))
    eng.run_until_idle()
    records = list(eng.step_log.steps)
    assert len(records) == eng.step_log.logged
    # it admits only into an empty batch: the third request waits
    assert sum(r.admit_s > 0 for r in records) == 2
    assert all(r.wall_s >= sum(getattr(r, f"{p}_s") for p in PHASES) - 1e-6
               for r in records)


def test_dispatch_ends_before_the_result_exists(registry):
    """A program whose result blocks: the block lands in ``engine:wait``,
    on the path that dispatches ahead and on the one that does not."""
    for stub in (PickingStub(0.02), SlowStub(0.02)):
        eng = engine(stub)
        eng.submit(request("r", 8))
        eng.run_until_idle()
        decoded = [r for r in eng.step_log.steps if r.rows]
        assert len(decoded) == 7
        assert all(r.wait_s >= 0.02 for r in decoded)
        for r in decoded[1:]:   # blocked, not busy (the first step admits,
            assert r.cpu_s < 0.25 * r.wait_s, r     # and may warm things up)
        assert sorted(r.dispatch_s for r in decoded)[3] < 0.01


def test_settle_logs_no_step(registry):
    eng = engine(PickingStub())
    eng.submit(request("r", 6))
    for _ in range(3):
        eng.step()
    assert eng._ahead is not None
    logged, tokens = eng.step_log.logged, len(eng.slots[0].generated)
    eng.settle()
    assert len(eng.slots[0].generated) == tokens + 1
    assert eng.step_log.logged == logged == len(eng.step_log.steps)
    eng.drain_to_requests()
    # a drained engine is no live one, and its log can still be found
    assert eng not in serve_engine.live_engines()
    assert eng in serve_engine.engines()


# -- planted stalls are named right -------------------------------------------


def busy(seconds: float) -> None:
    """Burn ``seconds`` of this thread's CPU time."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def planted(where: str, at_step: int = 40, steps: int = 60):
    """Sixty steps of one request with one fault of ``PLANTED_S``: a sleep
    in the program's enqueue, a sleep in the read of its result, or a busy
    loop in emission."""
    return run_planted(engine(PickingStub(DEVICE_S)), where, at_step, steps)


def run_planted(eng, where: str, at_step: int, steps: int):
    stub = eng.step_fns
    if where == "dispatch":
        stub.on_call = once_at(eng, at_step, lambda: time.sleep(PLANTED_S))
    elif where == "wait":
        stub.on_read = once_at(eng, at_step, lambda: time.sleep(PLANTED_S))
    else:
        emit = eng._emit_token
        fault = once_at(eng, at_step, lambda: busy(PLANTED_S))

        def emitting(slot, token):
            fault()
            return emit(slot, token)

        eng._emit_token = emitting
    eng.submit(request("r", steps + 1))
    eng.run_until_idle()
    assert eng.step_log.logged == steps
    return eng


def stall_at(eng, step: int) -> dict:
    """The one record of the step a fault was planted in. (A shared
    machine adds stalls of its own to other steps, which are true records
    and not this file's business.)"""
    (stall,) = [s for s in eng.step_log.stalls if s["step"] == step]
    return stall


@pytest.mark.parametrize("where, offcpu", [
    ("dispatch", "most"),   # asleep outside the wait: off the CPU
    ("wait", "none"),       # asleep inside it: where a wait is expected
    ("sample", "none"),     # busy: on the CPU
])
def test_a_planted_stall_is_named_by_its_phase(registry, where, offcpu):
    eng = planted(where)
    stall = stall_at(eng, 40)
    assert stall["phase"] == where
    assert stall["excess_s"] >= 0.8 * PLANTED_S
    assert stall[f"{where}_s"] >= PLANTED_S
    assert stall["offcpu_outside_wait_s"] == offcpu_outside_wait_s(
        eng.step_log.steps[40])
    if offcpu == "most":
        assert stall["offcpu_outside_wait_s"] >= 0.8 * PLANTED_S, stall
    else:   # what the machine's other work took from this thread aside
        assert stall["offcpu_outside_wait_s"] < 0.6 * stall["excess_s"], stall
    if where == "wait":     # off the CPU all the same, where that is expected
        assert stall["wait_s"] - stall["cpu_s"] >= 0.8 * PLANTED_S
    if where == "sample":
        assert stall["cpu_s"] >= 0.8 * PLANTED_S
    assert stall["compiles"] == 0 and stall["flushed"] is False
    assert eng.step_log.steps[40]._asdict().items() <= stall.items()
    # counted once, under its phase
    snap = registry.snapshot()
    assert snap["counters"][f"engine.stalls{{phase={where}}}"] >= 1
    assert sum(n for k, n in snap["counters"].items()
               if k.startswith("engine.stalls")) == eng.step_log.stalled \
        == len(eng.step_log.stalls)
    report = eng.load_report()
    last = eng.step_log.stalls[-1]
    assert report["stalls"] == {"count": eng.step_log.stalled,
                                "phase": last["phase"],
                                "ms": 1e3 * last["excess_s"]}


def test_an_admission_is_no_stall_and_hides_none(registry):
    """Steady arrivals: a prefill of five decode steps' length every fourth
    step. A step that admits is judged on its wall time less the admission,
    so none of them is a stall, the one planted in the wait among them is
    the record the ring and the load report hold, and the step after an
    admission (a call of its own, nothing ahead) is none either."""
    device_s = 0.01     # (steps long enough that a busy machine adds no half)
    stub = PickingStub(device_s, prefill_s=5 * device_s)
    eng = engine(stub, max_batch=4)
    arrived = set()

    def arrive():   # inside a call's enqueue, once a step
        now = eng.step_log.logged
        if now % 4 == 1 and now < 56 and now not in arrived:
            arrived.add(now)
            eng.submit(request(f"late{now}", 6))

    stub.on_call = arrive
    run_planted(eng, "wait", at_step=40, steps=60)
    records = list(eng.step_log.steps)
    admitting = [r for r in records if r.admit_s >= 5 * device_s]
    assert len(admitting) >= 14     # the first request's, then the arrivals'
    assert {r.step for r in admitting if r.step >= 16} \
        >= {n for n in range(18, 58, 4)}
    assert all(r.wall_s > 1.5 * eng.step_log._reference for r in admitting)
    # (by the old rule every one of them was a stall; a busy machine may
    # still add a stall of its own to one)
    stalled = {s["step"] for s in eng.step_log.stalls}
    assert len(stalled & {r.step for r in admitting}) <= 1
    assert len(stalled & {r.step + 1 for r in admitting}) <= 1
    # the newcomer's first token comes from a call of its own, beside the one
    # that was ahead: two calls in the step, and two calls' time allowed
    assert {r.calls for r in admitting if r.step >= 16} == {2}
    assert {r.calls for r in records} == {1, 2}
    stall = stall_at(eng, 40)
    assert stall["phase"] == "wait"
    assert stall["calls"] == 1 and stall["excess_s"] == pytest.approx(
        stall["wall_s"] - stall["admit_s"] - device_s, abs=0.5 * device_s)
    assert not counters("engine.stalls").get("engine.stalls{phase=admit}")
    if stalled == {40}:     # (a shared machine may add stalls of its own)
        assert eng.load_report()["stalls"] == {
            "count": 1, "phase": "wait", "ms": 1e3 * stall["excess_s"]}


def test_no_step_is_judged_before_sixteen_are_logged(registry):
    eng = planted("dispatch", at_step=9, steps=16)
    assert eng.step_log.stalled == 0 and not eng.step_log.stalls
    assert eng.step_log.steps[9].dispatch_s >= PLANTED_S
    assert not counters("engine.stalls")


def test_a_stall_is_written_as_an_instant_at_once(registry, tmp_path,
                                                  monkeypatch, capsys):
    """With the JSONL on, the stall's whole record is on disk when the step
    returns (an instant is flushed at once), and ``tracecat`` prints it on
    one readable line."""
    monkeypatch.setenv("TPU_SANDBOX_TRACE_DIR", str(tmp_path))
    reset_recorder()
    try:
        eng = planted("wait")
        stall = stall_at(eng, 40)
        (log,) = tmp_path.glob("*.jsonl")   # not flushed by the test
        on_disk = [json.loads(line) for line in open(log)]
        instants = [r for r in on_disk if r.get("name") == "engine:stall"]
        assert stall in [r["args"] for r in instants]
        get_recorder().flush()
    finally:
        reset_recorder()
    records = [json.loads(line) for line in open(log)]
    # the phases are the step's children, dispatch and wait the call's
    parents = {r["name"]: r["parent"] for r in records if r["ph"] == "X"
               and r["name"].startswith("engine:")}
    assert parents == {
        "engine:step": None, "engine:shed": "engine:step",
        "engine:admit": "engine:step", "engine:grow": "engine:step",
        "engine:decode_call": "engine:step",
        "engine:dispatch": "engine:decode_call",
        "engine:wait": "engine:decode_call", "engine:sample": "engine:step"}
    line = format_stall(stall)
    assert "phase=wait" in line and f"step={stall['step']}" in line
    assert f"excess={1e3 * stall['excess_s']:.3f}ms" in line
    assert f"wait={1e3 * stall['wait_s']:.3f}ms" in line
    assert "offcpu_outside_wait=" in line and "t0" not in line
    from tools import tracecat
    assert tracecat.main([str(tmp_path)]) == 0
    listed = [ln for ln in capsys.readouterr().out.splitlines()
              if "engine:stall" in ln]
    assert line in [ln.split("engine:stall  ")[1] for ln in listed]


def test_a_write_of_the_recorders_buffer_inside_a_step_is_noted(
        registry, tmp_path, monkeypatch):
    monkeypatch.setenv("TPU_SANDBOX_TRACE_DIR", str(tmp_path))
    reset_recorder()
    try:
        stub = PickingStub(DEVICE_S)
        eng = engine(stub)

        def write():    # file I/O on the engine's thread, as a full buffer's
            get_recorder().flush()
            time.sleep(PLANTED_S)

        stub.on_call = once_at(eng, 30, write)
        eng.submit(request("r", 41))
        eng.run_until_idle()
    finally:
        reset_recorder()
    stall = stall_at(eng, 30)
    assert stall["flushed"] is True and stall["phase"] == "dispatch"


def test_a_collection_inside_a_step_is_in_that_steps_record(registry):
    stub = PickingStub()
    eng = engine(stub)
    heap = [[i] for i in range(200_000)]    # something to walk

    stub.on_call = once_at(eng, 5, gc.collect)
    eng.submit(request("r", 12))
    eng.run_until_idle()
    del heap
    record = eng.step_log.steps[5]
    assert record.gc_n >= 1 and record.gc_s > 0.0
    assert record.gc_s <= record.dispatch_s
    others = [r for r in eng.step_log.steps if r.step != 5]
    assert sum(r.gc_s for r in others) < record.gc_s
    snap = registry.snapshot()
    assert snap["counters"]["gc.collections{generation=2}"] >= 1
    assert snap["histograms"]["gc.pause_s{generation=2}"]["max"] \
        >= 0.9 * record.gc_s / record.gc_n
    assert gc.callbacks.count(steplog._COLLECTOR) == 1  # one hook a process
    assert not steplog._COLLECTOR.unpublished   # the step's end published it


@pytest.mark.parametrize("held", ["the registry's", "a histogram's"])
def test_the_collectors_hook_takes_no_lock(registry, held):
    """A collection starts at an allocation, on the thread that made it and
    under whatever lock that thread holds -- the registry allocates a new
    series under its lock, a histogram sorts under its own. The hook runs
    right there: it may wait for neither, and the series are published
    later, outside any collection."""
    pause = registry.histogram("gc.pause_s", labels={"generation": "0"})
    lock = registry._lock if held == "the registry's" else pause._lock
    hook = steplog._COLLECTOR
    before = hook.collections
    with lock:
        caller = threading.Thread(target=lambda: (
            hook("start", {"generation": 0}),
            hook("stop", {"generation": 0, "collected": 0})), daemon=True)
        caller.start()
        caller.join(timeout=10.0)
        assert not caller.is_alive(), f"the hook waits for {held} lock"
    assert hook.collections == before + 1
    assert (0, pytest.approx(0.0, abs=0.1)) in list(hook.unpublished)
    since = counters("gc.collections")
    StepLog().report()      # a load report publishes, as a step's end does
    assert not hook.unpublished
    assert counters("gc.collections", since=since)[
        "gc.collections{generation=0}"] >= 1
    assert pause.snapshot()["count"] >= 1


# -- the rings, the load report -------------------------------------------------


def test_the_rings_stay_bounded(registry):
    log = StepLog()
    for n in range(steplog.STEPS_KEPT + 904):
        log.begin()
        log.end(10.0 if n % 3 == 2 else 1.0)
    assert log.logged == steplog.STEPS_KEPT + 904
    assert len(log.steps) == steplog.STEPS_KEPT
    assert log.steps[-1].step == log.logged - 1
    assert log.stalled > 1500 and len(log.stalls) == steplog.STALLS_KEPT
    assert log.stalls[-1]["excess_s"] == pytest.approx(9.0)
    # the reference is the median of the steps before, taken every 16 steps
    assert log._reference == 1.0


def test_the_reference_follows_the_steps_every_sixteen(registry):
    log = StepLog()
    for wall in [1.0] * 16 + [2.0] * 15:
        log.begin()
        log.end(wall)
    # fifteen steps of twice the time: each is judged against the old median
    assert log.stalled == 15
    log.begin()
    log.end(2.0)    # the 32nd: judged, then the median is taken anew
    assert log.stalled == 16 and log._reference == 1.5
    for _ in range(16):
        log.begin()
        log.end(2.0)
    assert log.stalled == 16 and log._reference == 2.0


def test_the_load_report_carries_host_wait_and_stalls(registry):
    eng = engine(PickingStub(DEVICE_S))
    report = eng.load_report()
    assert report["host_ms"] is None and report["wait_ms"] is None
    assert report["stalls"] == {"count": 0, "phase": None, "ms": None}
    eng.submit(request("r", 10))
    eng.run_until_idle()
    report = eng.load_report()
    assert report["wait_ms"] >= 1e3 * DEVICE_S
    assert 0.0 < report["host_ms"] < report["wait_ms"]
    assert json.loads(json.dumps(report)) == report
    assert {"step_age", "dropped_events", "active"} <= set(report)
