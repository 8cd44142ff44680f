"""The window layers' read of the cache as a share of its roofline:
``full_ctx_roofline``'s count on the window layers -- ``min(length, 512)``
rows of 4096 B a session a window layer, 4 x 64 x 128 FLOPs a row -- over
the device time under ``/gather_ctx/window``. The rows of a window's first
block that lie before the window are read and do not count."""

from benchmark.layer_metrics.full_ctx_roofline import read as _read


def read(obs):
    return _read(obs, "window")
