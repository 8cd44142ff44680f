"""Seconds in ``model.init`` inside ``TrainState.create``, ended by a wait
for the device (span ``setup:model_init``, registry histogram
``setup.model_init_s``), summed over the process: the larger part of
``init_s``, clocked where it happens."""


def read(obs):
    from tpu_sandbox.obs import get_registry

    h = get_registry().snapshot()["histograms"].get("setup.model_init_s")
    return h["sum"] if h and h["count"] else None
