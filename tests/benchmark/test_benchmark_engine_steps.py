"""The readers of the serve engine's step log (PR 50;
``benchmark/lib/engine_steps.py`` and nine ``layer_metrics/`` modules)
without a chip: on a log of known records (the last-N rule, each reader's
arithmetic, the stall's notes), on a stub engine driven as a replay runner
drives the real one, with a step after the window (the cross-check makes the
run not correct), and with no log at all (every reader gives ``None``, as on
the parent). The nine ``per_layer`` entries are not in ``BENCHMARK.json``
yet -- each serving cell's own test holds its cell to an exact set of
metrics, and those files are a ``benchmark`` PR's to edit -- so the last
test appends them in a copy and holds the copy to the benchmark's
invariants. Counts and arithmetic only: no CPU time stands for a chip's."""

import json
import sys
import time
import weakref
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import engine_steps, manifest  # noqa: E402
from benchmark.lib.observe import Observations  # noqa: E402
from test_benchmark_harness import (assert_benchmark_invariants,  # noqa: E402
                                    copy_benchmark)
from tests.test_engine_step_log import (PickingStub, engine,  # noqa: E402,F401
                                        registry, request)
from tpu_sandbox.serve import engine as serve_engine  # noqa: E402
from tpu_sandbox.serve.steplog import StepLog  # noqa: E402

READERS = ("engine_host_ms", "engine_wait_ms", "engine_dispatch_ms",
           "engine_admit_grow_ms", "step_max_ms", "stall_steps", "stall_ms",
           "stall_offcpu_ms", "gc_pause_ms")
SERVING_CELLS = ("gpt2m_serve_decode_replay", "jamba2_serve_decode_replay",
                 "longcat_serve_decode_replay", "laguna_serve_decode_replay")
#: a healthy step of the synthetic log, seconds by phase
HEALTHY = {"shed_s": 0.001, "admit_s": 0.002, "grow_s": 0.003,
           "dispatch_s": 0.010, "wait_s": 0.070, "sample_s": 0.012}
WALL = 0.100    # the six and 0.002 of glue


def read(name: str, obs):
    return manifest.module("layer_metrics", name).read(obs)


def observations(steps: int, walls) -> Observations:
    obs = Observations(cell={}, seed=0, seconds=1.0, traced=True,
                       device_kind="TPU v5 lite")
    obs.facts["window_steps"] = float(steps)
    obs.spans["eng.step"] = list(walls)
    return obs


def log_of(*steps) -> StepLog:
    """A step log of ``steps``: each a wall time or ``(wall, phases)``."""
    log = StepLog()
    for step in steps:
        wall, phases = step if isinstance(step, tuple) else (step, HEALTHY)
        log.begin()
        for field, seconds in phases.items():
            setattr(log, field, seconds)
        log.end(wall)
    return log


class _Engine:
    def __init__(self, log):
        self.step_log = log


@pytest.fixture
def a_log(monkeypatch):
    """Give the readers a log of known records in place of an engine's."""
    def give(log):
        monkeypatch.setattr(engine_steps, "_engine", lambda: _Engine(log))
        return log
    return give


# -- a log of known records --------------------------------------------------


def test_the_readers_read_the_windows_last_records(registry, a_log):
    """Twenty steps of set-up (one of them the long first step) and a window
    of thirty, one of which stalled in dispatch."""
    stalled = dict(HEALTHY, dispatch_s=0.130)           # 0.120 over
    window = [WALL] * 24 + [(0.220, stalled)] + [WALL] * 5
    log = a_log(log_of(5.0, *[0.4] * 3, *[WALL] * 16, *window))
    walls = [w[0] if isinstance(w, tuple) else w for w in window]
    obs = observations(30, walls)
    assert read("engine_wait_ms", obs) == pytest.approx(70.0)
    assert read("engine_host_ms", obs) == pytest.approx(
        1e3 * (sum(walls) / 30 - 0.070))
    assert read("engine_dispatch_ms", obs) == pytest.approx(
        (10.0 * 29 + 130.0) / 30)
    assert read("engine_admit_grow_ms", obs) == pytest.approx(6.0)
    assert read("step_max_ms", obs) == pytest.approx(220.0)
    assert obs.notes["slowest_steps_ms"][0] == {
        "step": 24, "ms": pytest.approx(220.0), "phase": "dispatch"}
    assert len(obs.notes["slowest_steps_ms"]) == 5
    assert read("stall_steps", obs) == 1
    (stall,) = log.stalls   # set-up's long steps came before sixteen existed
    assert stall["phase"] == "dispatch" and stall["step"] == 20 + 24
    assert read("stall_ms", obs) == pytest.approx(1e3 * (0.220 - WALL))
    # the thread was neither waiting nor, by its own clock, on the CPU
    assert read("stall_offcpu_ms", obs) == pytest.approx(
        1e3 * stall["offcpu_outside_wait_s"])
    assert 140.0 < read("stall_offcpu_ms", obs) <= 150.0
    assert obs.notes["stalls"] == [stall]
    assert json.loads(json.dumps(obs.notes))["stalls"][0]["phase"] \
        == "dispatch"
    assert read("gc_pause_ms", obs) == pytest.approx(
        1e3 * sum(r.gc_s for r in list(log.steps)[-30:]))
    assert obs.problems == []


def test_a_healthy_window_reads_zero_not_nothing(registry, a_log):
    a_log(log_of(5.0, *[WALL] * 40))
    obs = observations(20, [WALL] * 20)
    assert read("stall_steps", obs) == 0
    assert read("stall_ms", obs) == 0.0
    assert read("stall_offcpu_ms", obs) == 0.0
    assert obs.notes["stalls"] == []
    assert read("step_max_ms", obs) == pytest.approx(1e3 * WALL)
    assert read("step_max_ms", obs) < 1.5 * 1e3 * WALL
    assert all(isinstance(read(name, obs), (int, float)) for name in READERS)
    assert obs.problems == []


def test_a_step_after_the_window_makes_the_run_not_correct(registry, a_log):
    """The last-N rule holds only while nothing steps after the window. The
    benchmark's span contains the engine's, so laid beside the window's own
    spans no record is the longer one; shifted by a step, about half are."""
    walls = [WALL + 0.001 * (n % 7) for n in range(40)]   # steps that differ
    spans = [w + 0.0001 for w in walls[-20:]]    # the benchmark's clock round
    a_log(log_of(5.0, *walls))
    obs = observations(20, spans)
    assert read("engine_host_ms", obs) is not None and obs.problems == []
    a_log(log_of(5.0, *walls, WALL))             # ... and one more step
    obs = observations(20, spans)
    values = {name: read(name, obs) for name in READERS}
    assert len(obs.problems) == 1            # said once, not by every reader
    assert "not the window's steps" in obs.problems[0]
    assert all(v is not None for v in values.values())
    # a span short of the window's steps is one too
    a_log(log_of(5.0, *walls))
    obs = observations(20, spans[:19])
    assert read("engine_host_ms", obs) is not None
    assert len(obs.problems) == 1


def test_a_window_longer_than_the_log_is_a_problem(registry, a_log):
    a_log(log_of(*[WALL] * 10))
    obs = observations(12, [WALL] * 12)
    assert [read(name, obs) for name in READERS] == [None] * 9
    assert len(obs.problems) == 1 and "holds 10 records" in obs.problems[0]


# -- no log: the parent, a training cell ---------------------------------------


def test_without_a_log_every_reader_gives_none(registry, monkeypatch):
    monkeypatch.setattr(serve_engine, "_LIVE_ENGINES", weakref.WeakSet())
    obs = observations(20, [WALL] * 20)
    assert [read(name, obs) for name in READERS] == [None] * 9
    eng = engine(PickingStub())     # an engine that never stepped
    obs = observations(20, [WALL] * 20)
    assert [read(name, obs) for name in READERS] == [None] * 9
    # the parent of PR 50: no accessor, no log
    monkeypatch.delattr(serve_engine, "engines")
    obs = observations(20, [WALL] * 20)
    assert [read(name, obs) for name in READERS] == [None] * 9
    assert obs.problems == [] and "stalls" not in obs.notes
    del eng


def test_a_run_without_measured_steps_gives_none(registry, a_log):
    a_log(log_of(*[WALL] * 20))
    obs = observations(0, [])
    assert [read(name, obs) for name in READERS] == [None] * 9
    assert obs.problems == []


# -- a stub engine, driven as a replay runner drives the real one --------------


def replay(steps: int, after: int = 0):
    """Set-up's steps, a window of ``steps`` under the benchmark's span,
    ``finish``'s facts, then what the runners do after the window
    (``settle``, ``drain_to_requests``) and ``after`` more steps, which
    they never do."""
    eng = engine(PickingStub(0.02))
    eng.submit(request("r", 3 + steps + after + 4))
    for _ in range(3):
        eng.step()
    obs = observations(steps, [])
    t0 = end = time.perf_counter()
    for _ in range(steps):
        with obs.span("eng.step"):
            eng.step()
        end = time.perf_counter()
    obs.facts["window_s"] = end - t0
    for _ in range(after):
        eng.step()
    eng.settle()
    eng.drain_to_requests()
    return eng, obs


def test_the_readers_find_the_cells_engine_after_it_was_drained(
        registry, monkeypatch):
    monkeypatch.setattr(serve_engine, "_LIVE_ENGINES", weakref.WeakSet())
    bystander = engine(PickingStub())   # built, never stepped
    eng, obs = replay(12)
    assert engine_steps._engine() is eng and eng.idle
    values = {name: read(name, obs) for name in READERS}
    assert obs.problems == []
    assert all(v is not None for v in values.values())
    assert values["engine_wait_ms"] >= 20.0
    assert 0.0 < values["engine_dispatch_ms"] < values["engine_host_ms"]
    assert values["engine_admit_grow_ms"] < values["engine_host_ms"]
    # wall = host + wait, and the benchmark's step is that plus its own
    # clock's share
    step_ms = 1e3 * obs.facts["window_s"] / 12
    assert values["engine_host_ms"] + values["engine_wait_ms"] \
        == pytest.approx(step_ms, abs=0.3)
    assert values["step_max_ms"] >= step_ms - 0.3
    records = list(eng.step_log.steps)[-12:]
    assert values["gc_pause_ms"] == pytest.approx(
        1e3 * sum(r.gc_s for r in records))
    # the registry's sums are the log's
    assert sum(r.wall_s for r in eng.step_log.steps) == pytest.approx(
        registry.snapshot()["histograms"]["engine.step_s"]["sum"])
    del bystander


def test_steps_after_the_window_are_caught_on_a_real_log(
        registry, monkeypatch):
    monkeypatch.setattr(serve_engine, "_LIVE_ENGINES", weakref.WeakSet())
    eng, obs = replay(12, after=1)
    # the stub's steps are alike to a few tens of microseconds, which no
    # comparison of clocks tells apart; the device's are not (5.9-9.2 ms
    # round 7.8 in GPT-2's cell): give the records that spread
    steps = eng.step_log.steps
    assert len(steps) == 3 + 12 + 1
    for n in range(3, 16):      # the window's twelve and the step after
        steps[n] = steps[n]._replace(wall_s=0.02 + 0.001 * (n % 5))
    obs.spans["eng.step"] = [steps[n].wall_s + 1e-4 for n in range(3, 15)]
    assert read("engine_host_ms", obs) is not None
    assert len(obs.problems) == 1


# -- the entries, for the benchmark PR that adds them ---------------------------


def test_the_nine_entries_fit_the_benchmark_as_appended(tmp_path):
    root = copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    m = manifest.load(root)
    listed = {x["name"] for x in m["per_layer"]}
    assert not listed & set(READERS)    # not in BENCHMARK.json yet
    for name in READERS:
        m["per_layer"].append({
            "name": name, "unit": "count" if name == "stall_steps" else "ms",
            "better": "lower",
            "source": "program_counter" if name == "stall_steps"
            else "program_span",
            "layer": "serve engine", "moves": "decode_step_ms",
            "workloads": list(SERVING_CELLS)})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    assert_benchmark_invariants(root)
    for name in SERVING_CELLS:
        cell = manifest.cell(name, root)
        assert [x["name"] for x in cell["per_layer"]][-9:] == list(READERS)
    for name in READERS:
        assert callable(manifest.module("layer_metrics", name, root).read)
    for w in m["workloads"]:
        if w["name"] not in SERVING_CELLS:
            assert not set(READERS) & {
                x["name"] for x in manifest.cell(w["name"], root)["per_layer"]}
    for path, content in before.items():
        assert path.read_bytes() == content
