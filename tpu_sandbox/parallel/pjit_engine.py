"""Compiler-driven parallelism: rule-based sharding + jit (the pjit path).

The explicit engine (data_parallel.py) spells out its collectives with
``shard_map`` + ``lax.pmean`` — the "I am the DDP reducer" style. This module
is the complementary, fully XLA-driven style from the TPU playbook: pick a
``Mesh``, annotate parameter/batch shardings with ``PartitionSpec`` rules,
``jit`` the step, and let XLA *insert* the collectives (grad all-reduce over
the data axis, activation collectives around tensor-sharded matmuls) and
overlap them with compute.

This is how the reference's missing parallelisms become cheap mesh axes
(SURVEY §2.2: TP/PP/SP "absent, not required — mesh axis is cheap to add
later"): e.g. the 3000x3000 experiment's 18M x 10 classifier head
(mnist_onegpu.py:21-31's LazyLinear) tensor-shards with one rule,
``("fc/kernel", P("model", None))`` — an 18M-row matmul split across chips,
each holding 18M/n rows, with XLA adding the psum.

No DDP analogue exists for this file on purpose: torch needs separate
engines for DP (DistributedDataParallel) and TP (megatron-style layers);
on TPU they are the same jit with different specs.

Note BatchNorm semantics: under jit the batch axis is a *global* axis, so
BN reduces over the full global batch (SyncBN). The explicit engine keeps
per-replica BN for DDP loss-parity; this engine is the idiomatic-TPU
alternative. Pick per experiment.
"""

from __future__ import annotations

import re
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_sandbox.obs import get_recorder
from tpu_sandbox.ops.losses import cross_entropy_loss
from tpu_sandbox.train.state import TrainState

Rule = tuple[str, P]


def megatron_rules(model_axis: str = "model") -> list[Rule]:
    """The COMPLETE tensor-parallel ruleset for models.transformer (VERDICT
    r01 weak #5 flagged the partial qkv/mlp-only version): Megatron-style
    column-parallel qkv (heads) and mlp-up (d_ff), row-parallel attention
    out-projection and mlp-down, vocab-sharded token embedding and lm_head,
    d_model-sharded position embedding. Under jit, XLA inserts the psums
    after the row-parallel matmuls and the gather/psum around the sharded
    embedding lookups."""
    m = model_axis
    return [
        (r"attn/qkv/kernel", P(None, None, m, None)),
        (r"attn/qkv/bias", P(None, m, None)),
        (r"attn/out/kernel", P(m, None, None)),
        (r"mlp/up/kernel", P(None, m)),
        (r"mlp/up/bias", P(m)),
        (r"mlp/down/kernel", P(m, None)),
        (r"lm_head/kernel", P(None, m)),
        (r"lm_head/bias", P(m)),
        (r"tok_emb/embedding", P(m, None)),
        (r"pos_emb/embedding", P(None, m)),
    ]


def _match_rule(path: str, rules: Sequence[Rule]) -> P | None:
    """First rule whose regex matches the '/'-joined param path wins;
    None when no rule claims the path (callers decide the fallback)."""
    for pattern, spec in rules:
        if re.search(pattern, path):
            return spec
    return None


def spec_for_path(path: str, rules: Sequence[Rule]) -> P:
    """Rule-matched spec for a path, default replicated."""
    s = _match_rule(path, rules)
    return P() if s is None else s


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def param_specs(params, rules: Sequence[Rule],
                fsdp_axis: str | None = None, fsdp_axis_size: int = 1):
    """Map a params pytree to PartitionSpecs via path-regex rules.

    ``fsdp_axis``: fully-sharded data parallelism (ZeRO-3) as pure specs —
    leaves no rule claims are sharded on dim 0 over that axis when it
    divides; GSPMD then all-gathers each parameter just-in-time at its use
    and reduce-scatters its gradient, deriving the FSDP choreography from
    the sharding alone."""

    def spec(path, leaf):
        # explicit rules win outright — including an explicit P() pin; FSDP
        # only claims leaves NO rule matched
        s = _match_rule(_path_str(path), rules)
        if s is not None:
            return s
        if (
            fsdp_axis is not None
            and hasattr(leaf, "ndim") and leaf.ndim >= 1
            and leaf.shape[0] >= fsdp_axis_size
            and leaf.shape[0] % fsdp_axis_size == 0
        ):
            return P(fsdp_axis)
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


def state_specs(state: TrainState, rules: Sequence[Rule],
                zero_axis: str | None = None,
                zero_axis_size: int = 1,
                fsdp_axis: str | None = None,
                fsdp_axis_size: int = 1) -> TrainState:
    """Specs for a full TrainState: params by rules; optimizer state mirrors
    the params specs leaf-for-leaf where shapes match (optax state pytrees
    contain param-shaped leaves like momenta); BN stats replicated.

    ``zero_axis``: compiler-driven ZeRO-1 — optimizer-state leaves whose
    params carry NO rule (i.e. would be replicated) are instead sharded on
    dim 0 over that axis when it divides. The SPMD partitioner then derives
    the reduce-scatter/update/all-gather choreography from the sharding
    mismatch between gradients and moments, the pjit spelling of what
    DataParallel(zero=True) writes out by hand with shard_map."""
    if fsdp_axis is not None and zero_axis is None:
        # FSDP subsumes ZeRO-1 at THIS layer too (not just in the engine
        # constructor): params sharded without their moments would quietly
        # keep 2x replicated optimizer memory per device
        zero_axis, zero_axis_size = fsdp_axis, fsdp_axis_size
    pspecs = param_specs(state.params, rules, fsdp_axis=fsdp_axis,
                         fsdp_axis_size=fsdp_axis_size)

    def opt_spec(path, leaf):
        # param-shaped moment buffers share the param's spec; scalars/counters
        # are replicated. Match by trailing path against the params tree.
        s = _match_rule(_path_str(path), rules)
        if s is not None:
            return s
        if (
            zero_axis is not None and hasattr(leaf, "ndim") and leaf.ndim >= 1
            and leaf.shape[0] >= zero_axis_size
            and leaf.shape[0] % zero_axis_size == 0
        ):
            return P(zero_axis)
        return P()

    return TrainState(
        step=P(),
        params=pspecs,
        batch_stats=jax.tree.map(lambda _: P(), state.batch_stats),
        opt_state=jax.tree_util.tree_map_with_path(opt_spec, state.opt_state),
        # this engine syncs no compressed gradient and threads no
        # error-feedback residual; it mirrors the (normally empty) node
        # so a state that came from ``DataParallel`` keeps its structure
        grad_residual=jax.tree.map(lambda _: P(), state.grad_residual),
    )


class PjitEngine:
    """jit-with-shardings train-step factory.

    Usage::

        eng = PjitEngine(model, tx, mesh, rules=[("fc/kernel", P(None, "model"))])
        state = eng.shard_state(state)
        state, loss = eng.train_step(state, images, labels)  # global batch
    """

    def __init__(
        self,
        model,
        tx: optax.GradientTransformation,
        mesh: Mesh,
        *,
        rules: Sequence[Rule] = (),
        batch_axis: str = "data",
        input_spec: P | None = None,
        image_size: tuple[int, int] | None = None,
        task: str = "image",
        aux_weight: float = 0.01,
        mtp_weight: float = 0.0,
        zero_axis: str | None = None,
        fsdp_axis: str | None = None,
        donate: bool = True,
    ):
        if task not in ("image", "lm"):
            raise ValueError(f"task must be 'image' or 'lm', got {task!r}")
        if batch_axis not in mesh.axis_names:
            raise ValueError(
                f"batch axis {batch_axis!r} not in mesh axes {mesh.axis_names}"
            )
        self.model = model
        self.tx = tx
        self.mesh = mesh
        self.rules = list(rules)
        self.batch_axis = batch_axis
        # input_spec can additionally shard the image dims (spatial
        # partitioning — XLA inserts conv halo exchanges): e.g.
        # P('data', 'spatial') splits batch AND image height.
        self.input_spec = input_spec if input_spec is not None else P(batch_axis)
        self.image_size = image_size
        self.task = task
        # Weight on sown "aux_loss" values (MoE load-balance, Switch eq. 4,
        # parallel/expert.py:65): without it top-1 routing can collapse onto
        # one expert (VERDICT r01 weak #8). 0.01 is the Switch paper's alpha;
        # models that sow nothing are unaffected.
        self.aux_weight = aux_weight
        # Weight on the cross-entropy of sown "mtp_logits" (a multi-token-
        # prediction module's logits for the token after the next); the
        # model's own constant, handed over by whoever builds both.
        self.mtp_weight = mtp_weight
        if fsdp_axis is not None:
            if fsdp_axis not in mesh.axis_names:
                raise ValueError(
                    f"fsdp axis {fsdp_axis!r} not in mesh axes "
                    f"{mesh.axis_names}"
                )
            if zero_axis is not None and zero_axis != fsdp_axis:
                raise ValueError(
                    f"zero_axis {zero_axis!r} conflicts with fsdp_axis "
                    f"{fsdp_axis!r}: moments must shard with their params "
                    "(omit zero_axis — FSDP subsumes ZeRO-1)"
                )
            # FSDP subsumes ZeRO-1: moments follow their (sharded) params
            zero_axis = fsdp_axis
        if zero_axis is not None and zero_axis not in mesh.axis_names:
            raise ValueError(
                f"zero axis {zero_axis!r} not in mesh axes {mesh.axis_names}"
            )
        self.zero_axis = zero_axis
        self.fsdp_axis = fsdp_axis
        self.donate = donate
        self._jitted: Callable | None = None

    def _state_specs(self, state: TrainState) -> TrainState:
        """Single home for spec derivation so shard_state's placement and
        the jitted step's in/out shardings can never desynchronize."""
        return state_specs(
            state, self.rules, zero_axis=self.zero_axis,
            zero_axis_size=(
                self.mesh.shape[self.zero_axis] if self.zero_axis else 1
            ),
            fsdp_axis=self.fsdp_axis,
            fsdp_axis_size=(
                self.mesh.shape[self.fsdp_axis] if self.fsdp_axis else 1
            ),
        )

    def _sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def shard_state(self, state: TrainState) -> TrainState:
        # the span ends when the state is on the mesh, not when the copies
        # are enqueued: set-up, and the next consumer waits for it anyway
        with get_recorder().span("place:state",
                                 hist="place.state_s", loop=True):
            specs = self._state_specs(state)
            return jax.block_until_ready(jax.tree.map(
                lambda x, s: jax.device_put(x, self._sharding(s)), state, specs
            ))

    def shard_batch(self, images, labels):
        # what the loop pays to hand a batch over; the copy itself may
        # still be in flight when this returns (no wait is added per step)
        with get_recorder().span("place:batch",
                                 hist="place.batch_s", loop=True):
            images, labels = jnp.asarray(images), jnp.asarray(labels)
            return (
                jax.device_put(images, self._sharding(self.input_spec)),
                jax.device_put(labels, self._sharding(P(self.batch_axis))),
            )

    def _build(self, state: TrainState) -> Callable:
        model, tx, image_size = self.model, self.tx, self.image_size

        if self.task == "lm":
            aux_weight, mtp_weight = self.aux_weight, self.mtp_weight

            def loss_fn(params, batch_stats, tokens, targets):
                variables = {"params": params}
                if batch_stats:  # state no gradient moves (a router's bias)
                    variables["batch_stats"] = batch_stats
                logits, sown = model.apply(
                    variables, tokens,
                    mutable=["aux_loss", "mtp_logits", "batch_stats"]
                )

                def ce(logits, targets):
                    return cross_entropy_loss(
                        logits.reshape(-1, logits.shape[-1]),
                        targets.reshape(-1))

                with jax.named_scope("loss"):
                    loss = ce(logits, targets)
                    aux = jax.tree.leaves(sown.get("aux_loss", {}))
                    if aux:  # mean over layers: alpha independent of depth
                        loss = loss + aux_weight * sum(aux) / len(aux)
                    # multi-token prediction: position i's extra logits are
                    # held to the target of position i + 1
                    for extra in jax.tree.leaves(sown.get("mtp_logits", {})):
                        loss = loss + mtp_weight * ce(
                            extra[:, :-1], targets[:, 1:])
                return loss, sown.get("batch_stats", batch_stats)

        else:

            def loss_fn(params, batch_stats, images, labels):
                variables = {"params": params}
                if batch_stats:
                    variables["batch_stats"] = batch_stats
                logits, mutated = model.apply(
                    variables, images, train=True, mutable=["batch_stats"]
                )
                with jax.named_scope("loss"):
                    loss = cross_entropy_loss(logits, labels)
                return loss, mutated.get("batch_stats", {})

        def step(state: TrainState, images, labels):
            if image_size is not None and self.task == "image":
                from tpu_sandbox.train import prepare_inputs
                images = prepare_inputs(model, images, image_size)
            (loss, new_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params, state.batch_stats, images, labels)
            with jax.named_scope("optimizer"):
                updates, new_opt = tx.update(
                    grads, state.opt_state, state.params)
                new_params = optax.apply_updates(state.params, updates)
            return (
                state.replace(
                    step=state.step + 1,
                    params=new_params,
                    batch_stats=new_stats,
                    opt_state=new_opt,
                ),
                loss,
            )

        specs = self._state_specs(state)
        to_sh = lambda tree: jax.tree.map(self._sharding, tree)  # noqa: E731
        return jax.jit(
            step,
            in_shardings=(
                to_sh(specs),
                self._sharding(self.input_spec),
                self._sharding(P(self.batch_axis)),
            ),
            out_shardings=(to_sh(specs), self._sharding(P())),
            donate_argnums=(0,) if self.donate else (),
        )

    def train_step(self, state: TrainState, images, labels):
        if self._jitted is None:
            self._jitted = self._build(state)
        return self._jitted(state, images, labels)

    def lower_step(self, state: TrainState, images, labels):
        """AOT-lower the train step without executing it — same hook as
        ``DataParallel.lower_step`` so the HLO analysis tools (traffic,
        schedule, graftlint pass 2) can treat every engine uniformly."""
        # the step's trace and lower phases are recorded ``under`` this span
        # (``runtime/bootstrap.py``'s compile listener)
        with get_recorder().span("compile:lower_step", loop=True):
            if self._jitted is None:
                self._jitted = self._build(state)
            return self._jitted.lower(state, images, labels)
