"""Per-stage MPMD programs — separately compiled, matching the SPMD
pipeline to rounding.

Each stage compiles ONLY its own program (stage 0's executable carries
the embedding table and no head, the last stage's the reverse): forward
for its layer slice,
a vjp-based backward fed by the downstream stage's shipped cotangent, and
a stage-local optimizer apply. The math is lifted from
``parallel/pipeline.py`` (same ``Block.apply`` scan, same fp32 layernorm,
same ``head_loss/M``), so the only parity question is accumulation order.

Summation discipline (tests/test_mpmd.py holds the result to the real SPMD
engine on a ``{'data': 1, 'pipe': S}`` mesh, where psum/pmean are
identities — to a stated number of ulps: the order of the adds below is
the SPMD program's, but XLA:CPU orders the sums *inside* each compiled
program as it likes, so two compilations agree to rounding, not to the
bit; two runs of these same programs, as after a stage's recovery, do):

- The SPMD pipeline differentiates one ``lax.scan`` over ticks; scan's
  transpose accumulates each stage's parameter cotangent in REVERSE tick
  order, i.e. descending microbatch. So per-microbatch stage grads here
  are summed with a left fold in **descending** microbatch order —
  ``((0 + g[M-1]) + g[M-2]) + ... + g[0]`` — which reproduces the scan
  transpose add-for-add (``0 + g`` is bitwise ``g``).
- The loss scalar is accumulated ascending (forward tick order), like
  the scan carry.
- optax's sgd/adam update leaf-wise, so the stage-local apply over a
  stage's param slice matches the SPMD whole-tree update exactly.
  (Global-norm-clipped transforms would couple stages and break this —
  callers wanting clipping must apply it per stage on both sides.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from tpu_sandbox.models.transformer import Block, TransformerConfig
from tpu_sandbox.ops.losses import cross_entropy_loss
from tpu_sandbox.parallel.pipeline import (
    _layernorm,
    merge_transformer_params,
    split_transformer_params,
)


def check_layer_split(n_layers: int, n_stages: int,
                      layer_split) -> list[int]:
    """Validate (or derive) the per-stage layer counts. ``None`` keeps
    the original contract: layers must divide evenly."""
    if layer_split is None:
        if n_layers % n_stages:
            raise ValueError(
                f"{n_layers} layers not divisible into {n_stages} stages "
                "(pass layer_split for an uneven pipeline)")
        return [n_layers // n_stages] * n_stages
    split = [int(x) for x in layer_split]
    if len(split) != n_stages:
        raise ValueError(
            f"layer_split {split} has {len(split)} entries for "
            f"{n_stages} stages")
    if any(x < 1 for x in split) or sum(split) != n_layers:
        raise ValueError(
            f"layer_split {split} must be positive and sum to {n_layers}")
    return split


def stage_params(flat_params: dict, stage: int, n_stages: int, *,
                 layer_split=None) -> dict:
    """Slice a full TransformerLM param tree to one stage's subtree:
    ``{"stages": [layers_of_stage, ...]}`` plus ``"pre"`` on stage 0 and
    ``"post"`` on the last stage — the same leaves the SPMD engine
    shards to that pipe rank, so checkpoints interchange leaf-for-leaf.
    ``layer_split`` gives each stage's layer count for uneven
    pipelines."""
    # n_stages=1 skips the splitter's own divisibility check — uneven
    # pipelines validate through check_layer_split instead
    pre, stacked, post = split_transformer_params(flat_params, 1)
    n_layers = jax.tree.leaves(stacked)[0].shape[0]
    split = check_layer_split(n_layers, n_stages, layer_split)
    lo = sum(split[:stage])
    hi = lo + split[stage]
    sliced = jax.tree.map(lambda x: np.asarray(x)[lo:hi], stacked)
    out = {"stages": sliced}
    if stage == 0:
        out["pre"] = jax.tree.map(np.asarray, pre)
    if stage == n_stages - 1:
        out["post"] = jax.tree.map(np.asarray, post)
    return out


def merge_stage_params(parts: list[dict]) -> dict:
    """Per-stage param subtrees (stage order) -> flat TransformerLM tree."""
    stacked = jax.tree.map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs]),
        *[p["stages"] for p in parts])
    return merge_transformer_params(
        jax.tree.map(np.asarray, parts[0]["pre"]), stacked,
        jax.tree.map(np.asarray, parts[-1]["post"]))


def tree_add(a, b):
    """Elementwise host add — the accumulation op of the scan transpose
    (IEEE fp32 add is the same bit pattern on host numpy and XLA:CPU)."""
    return jax.tree.map(lambda x, y: np.asarray(x) + np.asarray(y), a, b)


def tree_zeros_like(t):
    return jax.tree.map(lambda x: np.zeros_like(np.asarray(x)), t)


def accumulate_descending(grads_by_mb: dict):
    """Left-fold per-microbatch grads in descending microbatch order —
    the scan-transpose order (module docstring). ``grads_by_mb`` maps
    microbatch index -> grad tree and must be dense over [0, M)."""
    order = sorted(grads_by_mb, reverse=True)
    acc = tree_zeros_like(grads_by_mb[order[0]])
    for m in order:
        acc = tree_add(acc, grads_by_mb[m])
    return acc


class StageProgram:
    """Compiled step functions for one pipeline stage.

    ``device`` pins the stage to its own mesh: every jitted call runs
    where its (committed) params live, so N stages on one process give
    N separate single-device meshes each executing only its own
    executable — the CPU twin of one mesh per stage-gang.
    """

    def __init__(self, config: TransformerConfig,
                 tx: optax.GradientTransformation, stage: int,
                 n_stages: int, microbatches: int, *, device=None,
                 layer_split=None):
        self.layer_split = check_layer_split(config.n_layers, n_stages,
                                             layer_split)
        self.config = config
        self.tx = tx
        self.stage = stage
        self.n_stages = n_stages
        self.microbatches = microbatches
        self.device = device
        self.is_first = stage == 0
        self.is_last = stage == n_stages - 1
        self._block = Block(config, None)
        self._build()

    # -- the per-stage math (identical to parallel/pipeline.py) -------------

    def _stage_apply(self, sp, h):
        def one(hh, layer_params):
            return self._block.apply({"params": layer_params}, hh), None

        out, _ = lax.scan(one, h, sp)
        return out

    def _embed(self, pre, tokens):
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1]), tokens.shape)
        tok = pre["tok_emb"]["embedding"][tokens]
        pos = pre["pos_emb"]["embedding"][positions]
        return (tok + pos).astype(self.config.dtype)

    def _head_loss(self, post, h, targets):
        dt = self.config.dtype
        hn = _layernorm(h, post["ln_f"]).astype(dt)
        logits = (hn @ post["lm_head"]["kernel"].astype(dt)
                  + post["lm_head"]["bias"].astype(dt))
        return cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), targets.reshape(-1))

    def _forward(self, params, x):
        h = self._embed(params["pre"], x) if self.is_first else x
        return self._stage_apply(params["stages"], h)

    # -- compiled entry points ----------------------------------------------

    def _build(self) -> None:
        M = self.microbatches

        def fwd(params, x):
            return self._forward(params, x)

        def bwd(params, x, g_out):
            # recompute-forward + transpose, exactly what the SPMD scan's
            # remat backward does for this tick
            if self.is_first:
                _, vjp = jax.vjp(lambda p: self._forward(p, x), params)
                return vjp(g_out)[0], None
            _, vjp = jax.vjp(self._forward, params, x)
            return vjp(g_out)

        def loss_grad(params, x, targets):
            def f(p, xx):
                out = self._forward(p, xx)  # reads pre/stages only
                return self._head_loss(p["post"], out, targets) / M

            lv, grads = jax.value_and_grad(f, argnums=(0, 1))(params, x)
            return lv, grads[0], grads[1]

        def apply_grads(params, opt_state, grads):
            updates, new_opt = self.tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_opt

        # -- ZB-H1 backward split: grad-input (B) vs grad-weight (W) as
        # separate programs. B runs the cotangent chain layer by layer
        # and stashes each layer's (input, output-cotangent) pair; W is
        # then PURE weight-grad work from the stash — it never re-walks
        # the chain, which is what makes deferring it into the drain
        # bubble a win instead of a 2x backward. The split is exact
        # math against the fused backward but NOT bitwise: each
        # per-layer vjp compiles as its own XLA unit, whose reduction
        # grouping differs from the fused scan transpose by a few ulps
        # (parity held at 1e-6 loss / per-leaf allclose by
        # tests/test_mpmd_fastfabric.py). The bitwise contracts are
        # untouched where they bind: fused 1F1B vs SPMD, and ZB vs ZB —
        # replay after a fault re-runs the SAME split programs, so the
        # fault matrix still lands bitwise. Stage 0 is the exception to
        # the split: its weight grads need the internal chain anyway
        # (nothing upstream wants its g_in), so it keeps the
        # chain-walking W (``bwd_weight_chain``) and skips B entirely.

        def _fwd_collect(params, h0):
            # forward over the layer slice, stacking each layer's INPUT
            def one(h, lp):
                return self._block.apply({"params": lp}, h), h

            return lax.scan(one, h0, params["stages"])

        def _chain(params, hs, g_top):
            # reverse sweep: per-layer grad-input vjp, stacking each
            # layer's OUTPUT cotangent alongside its stashed input
            def one(g, xs):
                lp, h_in = xs
                _, vjp = jax.vjp(
                    lambda hh: self._block.apply({"params": lp}, hh), h_in)
                return vjp(g)[0], g

            return lax.scan(one, g_top, (params["stages"], hs),
                            reverse=True)

        def _weight_grads(params, stash):
            hs, gs = stash

            def one(c, xs):
                lp, h_in, g = xs
                _, vjp = jax.vjp(
                    lambda p: self._block.apply({"params": p}, h_in), lp)
                return c, vjp(g)[0]

            _, g_stages = lax.scan(one, 0, (params["stages"], hs, gs))
            return g_stages

        def bwd_input(params, x, g_out):
            _, hs = _fwd_collect(params, x)
            gx, gs = _chain(params, hs, g_out)
            return gx, (hs, gs)

        def bwd_weight(params, stash):
            return {"stages": _weight_grads(params, stash)}

        def bwd_weight_chain(params, x, g_out):
            # stage 0's W: the full vjp w.r.t. params (embed included) —
            # its chain feeds nothing upstream, so it rides inside W
            _, vjp = jax.vjp(lambda p: self._forward(p, x), params)
            return vjp(g_out)[0]

        def loss_bwd_input(params, x, targets):
            h_out, hs = _fwd_collect(params, x)
            lv, head_vjp = jax.vjp(
                lambda hh: self._head_loss(params["post"], hh, targets) / M,
                h_out)
            (g_top,) = head_vjp(jnp.ones_like(lv))
            gx, gs = _chain(params, hs, g_top)
            return lv, gx, (hs, gs, h_out)

        def loss_bwd_weight(params, targets, stash):
            hs, gs, h_out = stash
            g_post = jax.grad(
                lambda post: self._head_loss(post, h_out, targets) / M)(
                params["post"])
            return {"stages": _weight_grads(params, (hs, gs)),
                    "post": g_post}

        self.fwd = jax.jit(fwd)
        self.bwd = jax.jit(bwd)
        self.loss_grad = jax.jit(loss_grad)
        self.apply_grads = jax.jit(apply_grads)
        self.bwd_input = jax.jit(bwd_input)
        self.bwd_weight = jax.jit(bwd_weight)
        self.bwd_weight_chain = jax.jit(bwd_weight_chain)
        self.loss_bwd_input = jax.jit(loss_bwd_input)
        self.loss_bwd_weight = jax.jit(loss_bwd_weight)

    # -- placement ----------------------------------------------------------

    def place(self, tree):
        """Commit a pytree to this stage's device (jit dispatch follows
        committed operands, so the stage's programs execute on its mesh)."""
        if self.device is None:
            return tree
        return jax.device_put(tree, self.device)

    def init_opt_state(self, params):
        return self.place(self.tx.init(params))
