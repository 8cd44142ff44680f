"""Seconds placing the train state on the mesh in ``shard_state`` of
``DataParallel`` / ``PjitEngine``, ended by a wait for the device (span
``place:state``, registry histogram ``place.state_s``), summed over the
process. ``None`` on one chip with no engine."""


def read(obs):
    from tpu_sandbox.obs import get_registry

    h = get_registry().snapshot()["histograms"].get("place.state_s")
    return h["sum"] if h and h["count"] else None
