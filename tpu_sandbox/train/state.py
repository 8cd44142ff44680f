"""Train state: params + BN batch stats + optimizer state, as one pytree.

The reference's equivalents are scattered across mutable objects (the torch
module's parameters/buffers and the SGD optimizer's state, reference
mnist_onegpu.py:36-49); here they are one immutable pytree so the whole
update is a pure function XLA can fuse, donate, and shard.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
import optax
from flax import struct

from tpu_sandbox.obs import get_recorder


@struct.dataclass
class TrainState:
    # grad_residual is the error-feedback buffer of the compressed gradient
    # sync (parallel/collectives.py::CompressedAllReduce): a param-shaped
    # fp32 pytree per rank, or None (an empty pytree node, so states built
    # before/without compression keep their leaf structure bit-for-bit).
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any
    grad_residual: Any = None

    @classmethod
    def create(cls, model, rng, sample_input, tx: optax.GradientTransformation):
        """Init by tracing (gives the reference's LazyLinear sizing without
        its CPU dummy-forward dance, mnist_onegpu.py:39).

        The two set-up spans end when the device has finished, not when the
        (eager, op-by-op) init has been enqueued: every caller waits for the
        state before it can do anything else."""
        rec = get_recorder()
        with rec.span("setup:model_init",
                      hist="setup.model_init_s", loop=True):
            try:
                variables = model.init(rng, sample_input, train=False)
            except TypeError:  # model without a train-mode switch (the LM)
                variables = model.init(rng, sample_input)
            jax.block_until_ready(variables)
        params = variables["params"]
        with rec.span("setup:opt_init", hist="setup.opt_init_s", loop=True):
            opt_state = jax.block_until_ready(tx.init(params))
        return cls(
            step=jax.numpy.zeros((), jax.numpy.int32),
            params=params,
            batch_stats=variables.get("batch_stats", {}),
            opt_state=opt_state,
        )

    def variables(self) -> dict:
        v = {"params": self.params}
        if self.batch_stats:
            v["batch_stats"] = self.batch_stats
        return v

    def host_view(self) -> "TrainState":
        """This process's host-local numpy copy of every leaf.

        For fully-addressable arrays that is the whole value; for
        multi-controller global arrays it is the first *addressable* shard
        — the full value for replicated leaves (params, opt state under
        plain DP), this process's block for sharded ones (its BN-stats
        replica). This is what elastic workers checkpoint: it needs no
        collective, so it still works while peer ranks are dead.
        """

        def to_host(leaf):
            if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
                return np.asarray(leaf.addressable_shards[0].data)
            return np.asarray(leaf)

        return jax.tree.map(to_host, self)
