"""Share of the traced window in which no operation ran on the device, in
percent; on several chips the worst one."""

from benchmark.lib.readers import idle_pct as read  # noqa: F401
