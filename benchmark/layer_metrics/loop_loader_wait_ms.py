"""Median milliseconds ``Trainer``'s loop spent in ``next()`` on its
loader (span ``train:next_batch``, registry histogram
``train.next_batch_s``): ``loader_wait_ms`` clocked from inside the
program. ``None`` where no batch was drawn through ``Trainer``."""


def read(obs):
    from tpu_sandbox.obs import get_registry

    h = get_registry().snapshot()["histograms"].get("train.next_batch_s")
    return 1e3 * h["p50"] if h and h["count"] else None
