"""Seconds in ``tx.init(params)`` inside ``TrainState.create``, ended by a
wait for the device (span ``setup:opt_init``, registry histogram
``setup.opt_init_s``), summed over the process."""


def read(obs):
    from tpu_sandbox.obs import get_registry

    h = get_registry().snapshot()["histograms"].get("setup.opt_init_s")
    return h["sum"] if h and h["count"] else None
