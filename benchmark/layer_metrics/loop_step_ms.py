"""Milliseconds a step, as ``Trainer``'s own loop clocks it: the sum of its
``train:step`` intervals (registry histogram ``train.step_s``: one return of
the step call to the next) over the steps it ran (counter ``train.steps``).

The mean, not the median: the step this benchmark hands to ``Trainer`` waits
for the device on every K-th call only, so K - 1 of K intervals are a
dispatch (0.6-0.8 ms on the chip, PR 23) and one is K steps long; the median
reads the dispatch, the sum reads the device. A fit's first step has no
interval of its own, but the device time of every step lies inside the later
ones, the loop ending with a wait. Both series hold the whole process;
``None`` where no step ran through ``Trainer`` or the program has no such
span."""


def read(obs):
    from tpu_sandbox.obs import get_registry

    snap = get_registry().snapshot()
    h = snap["histograms"].get("train.step_s")
    steps = snap["counters"].get("train.steps")
    return 1e3 * h["sum"] / steps if h and h["count"] and steps else None
