"""Times a running request was evicted back to the queue for lack of cache
blocks, summed over finished requests (``RequestResult.preemptions``)."""


def read(obs):
    return obs.facts.get("preemptions")
