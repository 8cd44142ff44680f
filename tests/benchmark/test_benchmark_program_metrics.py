"""The nine per-layer metrics of PR 23: seven read the program's always-on
registry (what its own spans and counters left there), two the reduction of
a device trace. Each reads its value where the source holds one and reads
nothing — ``None``, the metric is left out — where it holds none; none
invents a zero. The device trace is the four-chip recording under
``benchmark/fixtures/``."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import manifest  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402
from benchmark.lib.observe import Observations  # noqa: E402

FIXTURE = ROOT / "benchmark" / "fixtures" / "tiny.xplane.pb"
STEPS = 6  # of the recording (tiny.expect.json)


def _obs(trace=None, attempted=0) -> Observations:
    obs = Observations(cell={}, seed=0, seconds=1.0, traced=trace is not None)
    obs.trace, obs.attempted = trace, attempted
    return obs


@pytest.fixture
def registry():
    from tpu_sandbox.obs import get_registry

    reg = get_registry()
    reg.reset()
    yield reg
    reg.reset()


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce(tr.load(FIXTURE, host_prefix="bench:"))


def _fill(reg) -> None:
    """What a short run of the program leaves in the registry."""
    # a loop that runs ahead of the device and waits on every third step:
    # two dispatches, then three steps' worth of device time
    for s in (0.001, 0.001, 0.268, 0.001, 0.001, 0.268, 0.001, 0.269):
        reg.histogram("train.step_s").observe(s)
    reg.counter("train.steps").inc(9)
    for s in (4e-5, 5e-5, 6e-5):
        reg.histogram("train.next_batch_s").observe(s)
    for s in (0.002, 0.003, 0.004):
        reg.histogram("place.batch_s").observe(s)
    reg.histogram("setup.model_init_s").observe(12.5)
    reg.histogram("setup.model_init_s").observe(0.5)
    reg.histogram("setup.opt_init_s").observe(0.25)
    reg.histogram("place.state_s").observe(1.5)
    reg.counter("compile.cache_hits").inc(7)
    reg.counter("compile.cache_misses")  # created, never hit: a warm run


#: metric -> what it reads from the registry ``_fill`` left
FROM_REGISTRY = {
    "loop_step_ms": 90.0,           # sum over steps, in ms: not the median
    "loop_loader_wait_ms": 0.05,    # medians, in ms
    "place_batch_ms": 3.0,
    "model_init_s": 13.0,           # sums, in s
    "opt_init_s": 0.25,
    "state_place_s": 1.5,
    "compile_cache_misses": 0.0,    # a true zero: the counter exists
}
FROM_TRACE = ("allreduce_ms", "allreduce_exposed_ms")


@pytest.mark.parametrize("name", list(FROM_REGISTRY) + list(FROM_TRACE))
def test_reader_reads_its_source_and_nothing_from_an_empty_one(
        name, registry, recorded):
    read = manifest.module("layer_metrics", name).read
    # nothing observed, no trace: nothing to read, and reading creates no
    # series as a side effect
    assert read(_obs()) is None
    assert registry.snapshot() == {"counters": {}, "gauges": {},
                                   "histograms": {}}
    if name in FROM_REGISTRY:
        _fill(registry)
        assert read(_obs()) == pytest.approx(FROM_REGISTRY[name])
        return
    # the four-chip recording: six steps with one all-reduce each
    assert read(_obs(trace=recorded)) is None  # no step counted: no rate
    value = read(_obs(trace=recorded, attempted=STEPS))
    per_step = {key: sum(d[key] for d in recorded["devices"])
                / len(recorded["devices"]) / STEPS / 1e6
                for key in ("collective_ns", "collective_exposed_ns",
                            "busy_ns")}
    want = per_step["collective_ns" if name == "allreduce_ms"
                    else "collective_exposed_ns"]
    assert value == pytest.approx(want) and value > 0.0
    assert per_step["collective_exposed_ns"] <= per_step["collective_ns"] \
        < per_step["busy_ns"]


def test_the_new_metrics_are_entries_with_a_reader_each():
    """Each of PR 23's nine is listed at least in the cells it was added
    for, with its reader (``assert_benchmark_invariants`` holds the cells:
    a later cell appends itself), and reads the program, not the host."""
    from test_benchmark_harness import READ_IN, assert_benchmark_invariants

    assert set(FROM_REGISTRY) | set(FROM_TRACE) <= set(READ_IN)
    assert_benchmark_invariants(manifest.ROOT)
    per_layer = {m["name"]: m for m in manifest.load()["per_layer"]}
    sources = {per_layer[n]["source"] for n in FROM_REGISTRY}
    assert sources == {"program_span", "program_counter"}
