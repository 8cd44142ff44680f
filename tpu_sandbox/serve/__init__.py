"""Inference serving stack: AOT decode, paged KV cache, continuous batching.

Layers (bottom up):

- ``cache``   — host-side paged KV-cache allocator: fixed-size blocks, per-
  sequence block tables, free-list reuse, refcounted prefix sharing
  (declined for a model with recurrent layers, whose fixed-size state a
  decode slot lives beside the pages).
- ``decode``  — AOT-compiled static-shape prefill (bucketed lengths) and
  single-token decode step, a model family at a time over one page store
  (``Pages``: a buffer an attention layer at the key/value heads —
  ``models/transformer.py``: every layer; ``models/jamba.py``: the
  attention layers, and the Mamba layers' slot state beside them;
  ``models/longcat_flash.py``: a latent row a position an attention
  sub-layer, no separate V, and the expert shares' counters beside them),
  both donating the device buffers; replay-exact seeded sampling
  (``sample_token``).
- ``engine``  — continuous-batching engine: admits/evicts sequences at
  decode-step granularity, preempts-to-requeue under block pressure, plus a
  static-batch baseline as its control. SLO guardrails live here:
  per-request deadlines, a bounded admission queue with shed-on-overload,
  and the load-report backpressure signals.
- ``replica`` — replica processes behind the KV-backed request queue:
  claim-once queue entries, TTL leases, idempotent results, claim-once
  terminal verdicts (result or SHED), SIGTERM drain back to the queue,
  orphan scavenging, TTL'd load reports. Replicas run as ranks of a
  HostAgent gang so the elastic runtime relaunches them.
- ``client``  — producer-side SLO machinery: deadline submit, retry-on-shed
  with jittered backoff, straggler hedging over the idempotent verdicts.
- ``autoscale`` — leader-elected control loop sizing the replica gang from
  the load reports through the cluster scheduler (serve/train colocation).
"""

from tpu_sandbox.serve.cache import CacheConfig, PagedKVCache
from tpu_sandbox.serve.engine import (
    ContinuousEngine,
    Request,
    RequestResult,
    ServeConfig,
    ShedRecord,
    StaticEngine,
    live_engines,
)

__all__ = [
    "CacheConfig",
    "PagedKVCache",
    "ContinuousEngine",
    "Request",
    "RequestResult",
    "ServeConfig",
    "ShedRecord",
    "StaticEngine",
    "live_engines",
]
