"""Median milliseconds the host spent handing one batch to the mesh in
``DataParallel.shard_batch`` / ``PjitEngine.shard_batch`` (span
``place:batch``, registry histogram ``place.batch_s``): the enqueue of the
copy, not its end. The histogram holds the whole process, the two or three
warm-up batches included; the median is read. ``None`` on one chip with no
engine, where nothing is placed."""


def read(obs):
    from tpu_sandbox.obs import get_registry

    h = get_registry().snapshot()["histograms"].get("place.batch_s")
    return 1e3 * h["p50"] if h and h["count"] else None
