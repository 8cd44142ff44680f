"""Seconds in ``build()`` loading or synthesising the dataset and
constructing the loader (span ``setup:data``, registry histogram
``setup.data_s``), summed over the process. ``None`` where no ``build()``
with that span ran (the LM cells make their stream in the loop's caller)."""


def read(obs):
    from tpu_sandbox.obs import get_registry

    h = get_registry().snapshot()["histograms"].get("setup.data_s")
    return h["sum"] if h and h["count"] else None
