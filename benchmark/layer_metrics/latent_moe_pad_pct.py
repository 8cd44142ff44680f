"""``moe_pad_pct`` in a cell whose experts live in a latent: the same scopes,
facts and counters, read by ``layer_metrics/moe_pad_pct.py``. A metric of its own
because ``moe_pad_pct``'s list of cells is held to the cell it was made for
(``tests/benchmark/test_benchmark_xing4.py``, which a later PR may not edit)."""

from benchmark.lib import manifest


def read(obs):
    return manifest.module("layer_metrics", "moe_pad_pct").read(obs)
