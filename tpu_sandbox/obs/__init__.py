"""Flight recorder + health plane: causal tracing, fleet metrics,
merged timelines, durable time series, and alerting wired into control.

Five pieces, deliberately decoupled:

- :mod:`tpu_sandbox.obs.record` — the in-process recorder and the
  program's one span primitive: ``Recorder.span`` puts a span on the
  profiler's timeline (``jax.profiler.TraceAnnotation``), into a registry
  histogram (always) and into the append-only per-process JSONL
  (monotonic timestamps, propagated trace context). The JSONL is off by
  default; exporting ``TPU_SANDBOX_TRACE_DIR`` turns it on for every
  process that inherits the env (agents, replicas, the gateway).
- :mod:`tpu_sandbox.obs.metrics` — counters / gauges / streaming-quantile
  histograms. Always on (an increment is nanoseconds); scraped live via
  the gateway's METRICS wire op. Bounded dimensions ride ``labels=``;
  names are static ``snake.dotted`` literals (graftlint GL-O402).
- :mod:`tpu_sandbox.obs.tsdb` — the durable KV-backed time-series ring:
  each process flushes its registry (counter deltas, gauges, histogram
  digests) into TTL'd per-bucket windows any process can read back.
- :mod:`tpu_sandbox.obs.health` — the leader-elected ``HealthMonitor``:
  multi-window SLO burn-rate rules and anomaly detectors over the tsdb
  and durable control-plane state, raising claim-once alerts that the
  gateway, autoscaler, and scheduler consume (``tools/fleetop.py`` is
  the ops console).
- :mod:`tpu_sandbox.obs.collect` — the offline collector: merges per-host
  logs on a KV-sequencer-calibrated clock, emits Chrome trace-event JSON
  (spans + metric counter tracks), per-request waterfalls, and
  last-N-seconds postmortem timelines (``tools/tracecat.py`` is the CLI).
- :mod:`tpu_sandbox.obs.critpath` — the trace analytics plane over the
  merged timeline: per-request causal critical paths attributed to named
  segments (>= 95% of wall, residue reported as ``unattributed``), the
  run-level where-time-goes profile, blame for every shed/late request,
  offline MPMD bubble accounting, and the profile compare engine behind
  ``tools/tracediff.py`` regression gating.
- :mod:`tpu_sandbox.obs.workload` — the canonical replayable workload
  trace exported from a merged run (arrival offsets, tenant, prefix
  chain, token counts, outcome), schema-versioned and byte-stable so a
  saved workload round-trips and diffs cleanly.
"""

from tpu_sandbox.obs.record import (ENV_TRACE_DIR, Recorder, TraceContext,
                                    get_recorder, reset_recorder)
from tpu_sandbox.obs.metrics import MetricsRegistry, get_registry
from tpu_sandbox.obs.tsdb import TimeSeriesFlusher, list_series, read_series

__all__ = [
    "ENV_TRACE_DIR",
    "MetricsRegistry",
    "Recorder",
    "TimeSeriesFlusher",
    "TraceContext",
    "get_recorder",
    "get_registry",
    "list_series",
    "read_series",
    "reset_recorder",
]
