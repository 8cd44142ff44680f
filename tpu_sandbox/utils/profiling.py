"""Tracing / profiling utilities.

The reference's only instrumentation is a wall-clock print
(``datetime.now() - start``, mnist_onegpu.py:61,83-84 — kept verbatim by
train.Trainer). SURVEY §5 calls a real profiler "a free idiomatic add" on
TPU, so: ``trace()`` wraps ``jax.profiler`` (XLA/TPU timeline viewable in
TensorBoard/Perfetto). Regions are named by the program's one span
primitive (``obs.record.Recorder.span``), which annotates the profiler's
timeline itself; there is no annotation helper here.
"""

from __future__ import annotations

import contextlib
import time

import jax


@contextlib.contextmanager
def trace(logdir: str, *, host_tracer_level: int = 2):
    """Capture an XLA profiler trace for the enclosed block
    (``mnist_onegpu.py --profile DIR``). The program's own spans
    (``train:next_batch`` / ``train:dispatch`` / ``train:sync``,
    ``place:*``, ``setup:*``, ``engine:*``) are ``TraceAnnotation``s on
    the trace's ``/host:CPU`` plane, on the same clock as the device ops
    of the ``/device:TPU:N`` planes: this is where an operator sees what
    the host was doing over each device gap."""
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_sync(x) -> float:
    """Host-side synchronization by fetching a scalar derived from ``x``.

    A device->host transfer of a value that data-depends on the computation
    cannot complete before the computation does, on any platform. The
    benchmarks sync this way; ``chip_smoke.py`` times the same steps with
    this and with ``jax.block_until_ready`` side by side (ROADMAP A2 picks
    one clock from that evidence).
    """
    import jax.numpy as jnp

    return float(jnp.ravel(x)[0])


def measure_per_step(run_steps, n: int) -> dict:
    """Fetch-synced *differential* step timing: per_step = (t(2n)-t(n)) / n.

    ``run_steps(k)`` must execute k steps whose final output data-depends on
    all k (e.g. a threaded train state) and return that output; we fetch a
    scalar from it (``host_sync``). Timing t(n) and t(2n) and differencing
    cancels the constant costs a single timed loop cannot escape — the
    device->host fetch round-trip and any fixed dispatch overhead — leaving
    the marginal cost of one step.

    Both loops are warmed (compile + queue drain) before timing. Returns
    seconds per step plus the raw t(n)/t(2n) for the benchmark record.
    """
    host_sync(run_steps(n))  # warm: compile, stage, drain queue
    t0 = time.perf_counter()
    host_sync(run_steps(n))
    t_n = time.perf_counter() - t0
    t0 = time.perf_counter()
    host_sync(run_steps(2 * n))
    t_2n = time.perf_counter() - t0
    return {
        "sec_per_step": (t_2n - t_n) / n,
        "t_n_sec": t_n,
        "t_2n_sec": t_2n,
        "n": n,
        "timing_method": "fetch-synced differential (t(2n)-t(n))/n",
    }


def measure_per_step_repeated(run_steps, n: int, repeats: int = 3) -> dict:
    """``measure_per_step`` run ``repeats`` times: publishes the MIN (the
    least-contended sample) plus every sample, so artifacts carry their own
    run-to-run spread (VERDICT r03 next-7: the same kernel differed 25-50%
    between single-shot r03 sweeps; single samples must not drive plan
    decisions)."""
    samples = [measure_per_step(run_steps, n) for _ in range(repeats)]
    times = [s["sec_per_step"] for s in samples]
    positive = [t for t in times if t > 0] or times
    best = samples[times.index(min(positive))]
    # spread is only a repeatability claim when EVERY repeat measured;
    # with noise-negative samples dropped it would report a lone noisy
    # sample as perfectly repeatable — publish None + the failure count
    all_ok = len(positive) == len(times) and min(positive) > 0
    spread = ((max(positive) - min(positive)) / min(positive)
              if all_ok else None)
    out = {
        **best,
        "sec_per_step": min(positive),
        "repeats": repeats,
        "sec_per_step_samples": [round(t, 6) for t in times],
        "spread_frac": round(spread, 3) if spread is not None else None,
        "timing_method": best["timing_method"] + f"; min of {repeats}",
    }
    bad = len(times) - len([t for t in times if t > 0])
    if bad:
        out["nonpositive_samples"] = bad
    return out
