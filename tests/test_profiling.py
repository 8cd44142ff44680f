"""utils/profiling.py coverage: the trace() wrapper against the installed
``jax.profiler.start_trace`` signature (stubbed, and once for real), the
fetch-synced host_sync primitive and the differential per-step measurement
— all on CPU. (Regions are annotated by ``obs.record.Recorder.span``:
tests/test_program_spans.py.)"""

import jax.numpy as jnp
import pytest

from tpu_sandbox.utils import profiling


class _StubProfiler:
    """Records start/stop calls behind the installed ``start_trace``
    signature; ``ProfileOptions`` is the real class."""

    ProfileOptions = profiling.jax.profiler.ProfileOptions

    def __init__(self):
        self.calls = []

    def start_trace(self, log_dir, create_perfetto_link=False,
                    create_perfetto_trace=False, profiler_options=None):
        self.calls.append(("start", log_dir, profiler_options))

    def stop_trace(self):
        self.calls.append(("stop",))


def test_trace_passes_host_tracer_level_in_profile_options(
        monkeypatch, tmp_path):
    stub = _StubProfiler()
    monkeypatch.setattr(profiling.jax, "profiler", stub)
    with profiling.trace(str(tmp_path), host_tracer_level=3):
        pass
    (start, logdir, options), stop = stub.calls
    assert (start, logdir, stop) == ("start", str(tmp_path), ("stop",))
    assert options.host_tracer_level == 3


def test_trace_writes_an_xplane_with_the_installed_profiler(tmp_path):
    # no stub: the call must be one the installed jax accepts (the old
    # ``host_tracer_level=`` keyword was always rejected by it)
    with profiling.trace(str(tmp_path)):
        jnp.ones((8, 8)).sum().block_until_ready()
    assert list(tmp_path.rglob("*.xplane.pb"))


def test_trace_stops_profiler_on_body_exception(monkeypatch, tmp_path):
    stub = _StubProfiler()
    monkeypatch.setattr(profiling.jax, "profiler", stub)
    with pytest.raises(RuntimeError, match="boom"):
        with profiling.trace(str(tmp_path)):
            raise RuntimeError("boom")
    assert stub.calls[-1] == ("stop",)


def test_host_sync_fetches_a_data_dependent_scalar():
    x = jnp.arange(8, dtype=jnp.float32) + 1.0
    assert profiling.host_sync(x) == 1.0
    assert profiling.host_sync(jnp.zeros((2, 3))) == 0.0


class _FakeClock:
    """``time`` for ``profiling``: work advances it, nothing else does, so
    no assertion here depends on the machine's load."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now


def test_measure_per_step_cancels_fixed_costs(monkeypatch):
    clock = _FakeClock()
    monkeypatch.setattr(profiling, "time", clock)
    fixed, per_step = 0.004, 0.001

    def run_steps(k):
        clock.now += fixed + per_step * k
        return jnp.ones((1,))

    out = profiling.measure_per_step(run_steps, n=4)
    assert out["n"] == 4
    assert out["t_n_sec"] == pytest.approx(fixed + 4 * per_step)
    assert out["t_2n_sec"] == pytest.approx(fixed + 8 * per_step)
    # the constant cost cancels: the estimate is per_step, not
    # fixed + per_step
    assert out["sec_per_step"] == pytest.approx(per_step)
    assert "differential" in out["timing_method"]


def test_measure_per_step_repeated_publishes_spread(monkeypatch):
    clock = _FakeClock()
    monkeypatch.setattr(profiling, "time", clock)
    costs = iter([0.001, 0.001, 0.001, 0.002, 0.002, 0.002])  # a step, by call

    def run_steps(k):
        clock.now += next(costs) * k
        return jnp.ones((1,))

    out = profiling.measure_per_step_repeated(run_steps, n=2, repeats=2)
    assert out["repeats"] == 2
    assert out["sec_per_step_samples"] == [0.001, 0.002]
    assert out["sec_per_step"] == pytest.approx(0.001)  # the least contended
    assert out["spread_frac"] == 1.0
