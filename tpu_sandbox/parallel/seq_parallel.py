"""Sequence-parallel training: the full step under shard_map over
('data', 'sp') with ring attention.

Long sequences shard over 'sp': every device holds S/n_sp tokens of each
sequence (and B/n_dp sequences). Embeddings, LayerNorms and MLPs are
token-local so they need no communication; attention is the only op that
mixes positions and runs as a ring (parallel/ring_attention.py) over ICI
neighbors. Gradients psum over BOTH axes — data-parallel and
sequence-parallel reduce into the same mean because every token contributes
equally to the global-mean LM loss.

The reference has nothing like this (no sequence dimension at all, SURVEY
§5 "long-context: absent entirely"); it is the capability that makes the
framework long-context-ready, and it composes with the DP engine's design:
params replicated, batch (and here sequence) sharded, one jit'd step.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_sandbox.ops.losses import cross_entropy_loss
from tpu_sandbox.parallel.ring_attention import ring_attention
from tpu_sandbox.train.state import TrainState


class SeqParallel:
    """Train-step factory for TransformerLM over a ('data','sp') mesh."""

    def __init__(
        self,
        model_ctor: Callable[[Callable | None], "flax.linen.Module"],  # noqa: F821
        tx: optax.GradientTransformation,
        mesh: Mesh,
        *,
        data_axis: str = "data",
        seq_axis: str = "sp",
        attn: str = "ring",
        donate: bool = True,
    ):
        for ax in (data_axis, seq_axis):
            if ax not in mesh.axis_names:
                raise ValueError(f"axis {ax!r} not in mesh axes {mesh.axis_names}")
        self.mesh = mesh
        self.data_axis, self.seq_axis = data_axis, seq_axis
        self.tx = tx
        # the model used INSIDE shard_map: attention mixes positions across
        # the 'sp' shards — either K/V ring rotation or the Ulysses
        # all-to-all head/sequence swap (see parallel/ulysses.py for the
        # tradeoff between the two)
        if attn == "ring":
            sp_attn = partial(ring_attention, axis_name=seq_axis)
        elif attn == "ulysses":
            from tpu_sandbox.parallel.ulysses import ulysses_attention

            sp_attn = partial(ulysses_attention, axis_name=seq_axis)
        elif attn == "flash_ring":
            from tpu_sandbox.parallel.flash_ring import flash_ring_attention

            def sp_attn(q, k, v):
                return flash_ring_attention(q, k, v, seq_axis)
        else:
            raise ValueError(
                f"attn must be 'ring', 'ulysses' or 'flash_ring', got {attn!r}"
            )
        self.sp_model = model_ctor(sp_attn)
        # the same architecture with local attention (for init / eval)
        self.local_model = model_ctor(None)
        self._build(donate)

    def init_state(self, rng, sample_tokens) -> TrainState:
        """Init with the local-attention twin (identical params pytree)."""
        return TrainState.create(self.local_model, rng, sample_tokens, self.tx)

    def shard_state(self, state: TrainState) -> TrainState:
        return jax.device_put(state, NamedSharding(self.mesh, P()))

    def shard_batch(self, tokens, targets):
        """tokens/targets [B, S] -> sharded (tokens, targets, positions).

        Targets are the NEXT token (shift done on the host before sharding,
        so causality across shard boundaries is already correct).
        """
        b, s = tokens.shape
        positions = np.broadcast_to(np.arange(s, dtype=np.int32), (b, s))
        sh = NamedSharding(self.mesh, P(self.data_axis, self.seq_axis))
        return tuple(
            jax.device_put(jnp.asarray(x), sh) for x in (tokens, targets, positions)
        )

    def _build(self, donate: bool) -> None:
        model, tx = self.sp_model, self.tx
        daxis, saxis = self.data_axis, self.seq_axis

        def loss_fn(params, tokens, targets, positions):
            logits = model.apply({"params": params}, tokens, positions)
            return cross_entropy_loss(
                logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
            )

        def body(state: TrainState, tokens, targets, positions):
            loss, grads = jax.value_and_grad(loss_fn)(
                state.params, tokens, targets, positions
            )
            # mean over all shards: every token weighs equally (equal shard
            # sizes), so pmean over both axes == global-batch mean grad
            grads = lax.pmean(lax.pmean(grads, daxis), saxis)
            loss = lax.pmean(lax.pmean(loss, daxis), saxis)
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            return (
                state.replace(
                    step=state.step + 1,
                    params=optax.apply_updates(state.params, updates),
                    opt_state=new_opt,
                ),
                loss,
            )

        batch_spec = P(daxis, saxis)
        state_spec = TrainState(step=P(), params=P(), batch_stats=P(), opt_state=P())
        smapped = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(state_spec, batch_spec, batch_spec, batch_spec),
            out_specs=(state_spec, P()),
            check_vma=False,  # replicated-out params: pmean'd grads guarantee it
        )
        self._jitted = jax.jit(smapped, donate_argnums=(0,) if donate else ())

    def train_step(self, state: TrainState, tokens, targets, positions):
        return self._jitted(state, tokens, targets, positions)

    def eval_logits(self, state: TrainState, tokens) -> jax.Array:
        """Single-stream (local attention) logits for parity checks."""
        return self.local_model.apply({"params": state.params}, tokens)
