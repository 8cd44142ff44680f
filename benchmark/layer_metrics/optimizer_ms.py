"""Device milliseconds per step under the train step's ``optimizer`` scope
(``tx.update`` and ``apply_updates``). It is the optimizer's pass only where
a collective stands between a gradient and its update, as in the
data-parallel step: without one XLA fuses the update into the gradient's
contraction, and that fusion counts under the gradient's module (PR 25, on
the chip: 0.0003 ms of 41 in the one-chip ConvNet step, 1.94 of 518 in the
LM's, where Adam over 406 M parameters alone needs 14). So only such cells
list it."""

from benchmark.lib.readers import scope_ms


def read(obs):
    return scope_ms(obs, r"(^|/)optimizer(/|$)")
