"""utils/profiling.py coverage: the trace() wrapper against the installed
``jax.profiler.start_trace`` signature (stubbed, and once for real), the
fetch-synced host_sync primitive and the differential per-step measurement
— all on CPU. (Regions are annotated by ``obs.record.Recorder.span``:
tests/test_program_spans.py.)"""

import math
import time

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


def test_measure_per_step_cancels_fixed_costs():
    fixed, per_step = 0.004, 0.001

    def run_steps(k):
        time.sleep(fixed + per_step * k)
        return jnp.ones((1,))

    out = profiling.measure_per_step(run_steps, n=4)
    assert out["n"] == 4
    assert out["t_2n_sec"] > out["t_n_sec"]
    # the constant cost cancels: the estimate tracks per_step, not
    # fixed + per_step
    assert out["sec_per_step"] == pytest.approx(per_step, rel=0.75)
    assert "differential" in out["timing_method"]


def test_measure_per_step_repeated_publishes_spread():
    def run_steps(k):
        time.sleep(0.001 * k)
        return jnp.ones((1,))

    out = profiling.measure_per_step_repeated(run_steps, n=2, repeats=2)
    assert out["repeats"] == 2
    assert len(out["sec_per_step_samples"]) == 2
    assert out["sec_per_step"] > 0
    if out["spread_frac"] is not None:
        assert out["spread_frac"] >= 0
