"""Device milliseconds per step under the ConvNet's ``fc`` module, the
18,000,000 -> 10 head: flatten, forward, dgrad and the weight gradient
(which XLA fuses into the SGD update on one chip: that fusion's root is the
gradient's contraction, so all of it counts here)."""

from benchmark.lib.readers import scope_ms


def read(obs):
    return scope_ms(obs, r"/fc(/|$)")
