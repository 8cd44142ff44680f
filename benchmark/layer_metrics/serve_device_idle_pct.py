"""``device_idle_pct`` for a serving cell (a per-layer metric names one
end-to-end metric it moves, and serving cells report no ``train_step_ms``)."""

from benchmark.lib.readers import idle_pct as read  # noqa: F401
