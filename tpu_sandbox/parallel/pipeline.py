"""Pipeline parallelism: microbatched stages over a 'pipe' axis, SPMD-style.

The reference has no pipeline dimension (SURVEY §2.2 "PP: ABSENT — no stage
split, no send/recv"); this adds it TPU-style. There are no point-to-point
sends on a TPU mesh — the pipeline is an SPMD program under ``shard_map``
where every stage runs the same code each tick and activations move to the
next stage with ``lax.ppermute`` over neighbor ICI links:

- the transformer's homogeneous blocks are STACKED: their params carry a
  leading [n_layers] dim, reshaped to [n_stages, layers_per_stage, ...] and
  sharded on 'pipe' — each device materializes only its own stage's layers
  (the model-memory win pipeline parallelism exists for);
- a batch is split into M microbatches; the tick loop is a ``lax.scan``,
  and the whole pipeline is one differentiable compiled program — backward
  runs the reverse pipeline automatically.

Schedules — GPipe and circular (interleaved) are ONE implementation,
parameterized by ``circular_chunks`` (v):

- v=1 is GPipe: each device holds n_layers/S consecutive blocks; M + S - 1
  ticks, bubble (S-1)/(M+S-1).
- v>1 is the circular schedule (Megatron's interleaved stages, praxis's
  circular pipeline): each device holds v NON-consecutive layer chunks
  (global layer order = chunk-major round-robin: chunk c of device i holds
  layers [c·S·L + i·L .. +L)), and a microbatch rings around the devices v
  times. Unit u = t - idx at tick t decodes to (chunk c, microbatch m);
  the ring automatically delivers chunk c+1 of a microbatch to device 0
  exactly when its schedule slot arrives. M·v + S - 1 ticks for M·v units
  of work per device: the bubble shrinks to (S-1)/(M·v + S - 1) — ~v×
  smaller at equal M. Cost: v× as many (smaller) ppermute hops; needs
  M % S == 0.

  Bubble fraction at S=4 stages (``bubble_fraction()``):

      M      4      8      16
      v=1  0.429  0.273  0.158
      v=2  0.273  0.158  0.086
      v=4  0.158  0.086  0.045

Work is gated to the stage that owns it (VERDICT r01 weak #3 fixed — the
first version embedded/headed the full batch on EVERY stage and carried a
[M, mb, S, D] outputs buffer):

- the embedding runs per tick on one microbatch, under ``lax.cond(idx==0)``;
- the head + loss run per tick on the microbatch EXITING the last stage,
  under ``lax.cond(idx==n_stages-1)`` — logits for the full batch are never
  materialized; the scan carries only (loss_sum, ring buffer);
- per-stage FLOPs therefore no longer scale with n_stages, and the loss
  mask keeps exactly one backprop path alive (broadcasting the outputs
  with a psum before the loss would make every stage backprop a full copy,
  inflating grads by n_stages through psum's summing transpose).

Memory schedule: ``remat=True`` (default) wraps each tick in
``jax.checkpoint``, so backward saves only the scan carry per tick —
(M+S-1) x [mb, seq, d_model] — and recomputes block internals, the same
activation-memory class as a 1F1B schedule (which bounds in-flight
microbatches to S) and far below naive GPipe autodiff (every block's
internals for all M microbatches). Bubble fraction is (S-1)/(M+S-1) either
way; 1F1B's advantage over GPipe is memory, not bubble, and remat delivers
that here without a hand-scheduled backward.

Composes with data parallelism over a ('data', 'pipe') mesh (batch sharded
on 'data', grads pmean'd on 'data'), and with tensor parallelism over a
('data', 'model', 'pipe') mesh: pass ``model_axis='model'`` and the stage
blocks run Megatron-style — qkv/up kernels column-sharded (heads / d_ff),
out/down kernels row-sharded, ONE psum per residual branch, bias added
after the psum.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_sandbox.models.transformer import Block, TransformerConfig, TransformerLM
from tpu_sandbox.ops.attention import causal_attention
from tpu_sandbox.ops.losses import cross_entropy_loss
from tpu_sandbox.parallel.pjit_engine import _path_str
from tpu_sandbox.train.state import TrainState


def split_transformer_params(params: dict, n_stages: int):
    """TransformerLM params -> (pre, stacked blocks [L,...], post).

    Blocks are stacked leaf-wise into a leading layer dim; the engine
    reshapes that to [n_stages, layers_per_stage, ...] and shards it.
    """
    block_keys = sorted(
        (k for k in params if k.startswith("block")), key=lambda k: int(k[5:])
    )
    if len(block_keys) % n_stages:
        raise ValueError(
            f"{len(block_keys)} layers not divisible into {n_stages} stages"
        )
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *[params[k] for k in block_keys])
    pre = {k: params[k] for k in ("tok_emb", "pos_emb")}
    post = {k: params[k] for k in ("ln_f", "lm_head")}
    return pre, stacked, post


def merge_transformer_params(pre: dict, stacked, post: dict) -> dict:
    """Inverse of split_transformer_params (for checkpoints/eval parity)."""
    n_layers = jax.tree.leaves(stacked)[0].shape[0]
    out = dict(pre)
    for i in range(n_layers):
        out[f"block{i}"] = jax.tree.map(lambda x: x[i], stacked)
    out.update(post)
    return out


def _layernorm(x, p):
    """flax.linen.LayerNorm(dtype=fp32) semantics (eps 1e-6)."""
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    return (xf - mean) / jnp.sqrt(var + 1e-6) * p["scale"] + p["bias"]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tp_region_input(x, axis_name):
    """Megatron's 'f' operator: identity forward, psum backward.

    The input to a column-parallel matmul is consumed by every model rank's
    weight shard; each rank's backward produces only its shard's partial
    cotangent, so the cotangent must be all-reduced over the model axis
    here (the conjugate of the explicit psum after the row-parallel matmul,
    whose transpose is the identity). Without it, everything upstream —
    layernorms, earlier blocks, embeddings — trains on 1/m of its gradient.
    """
    return x


def _tp_region_input_fwd(x, axis_name):
    return x, None


def _tp_region_input_bwd(axis_name, _, g):
    return (lax.psum(g, axis_name),)


_tp_region_input.defvjp(_tp_region_input_fwd, _tp_region_input_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tp_region_output(x, axis_name):
    """Megatron's 'g' operator: psum forward, identity backward.

    The conjugate of ``_tp_region_input``. Spelled as a custom_vjp (not a
    bare ``lax.psum``) so the backward is the identity BY CONSTRUCTION:
    shard_map's own transpose of psum is another psum (each rank's output
    is consumed by every rank's downstream replica), which here would
    multiply the row-parallel kernel gradients by the model-axis size."""
    return lax.psum(x, axis_name)


def _tp_region_output_fwd(x, axis_name):
    return lax.psum(x, axis_name), None


def _tp_region_output_bwd(axis_name, _, g):
    return (g,)


_tp_region_output.defvjp(_tp_region_output_fwd, _tp_region_output_bwd)


class PipelineParallel:
    """Pipelined TransformerLM training over a ('data', 'pipe') mesh —
    optionally ('data', 'model', 'pipe') with tensor-parallel stages."""

    def __init__(
        self,
        config: TransformerConfig,
        tx: optax.GradientTransformation,
        mesh: Mesh,
        *,
        microbatches: int,
        data_axis: str = "data",
        pipe_axis: str = "pipe",
        model_axis: str | None = None,
        seq_axis: str | None = None,
        seq_attn: str = "ring",
        circular_chunks: int = 1,
        remat: bool = True,
        donate: bool = True,
        attention_fn: Callable | None = None,
    ):
        axes = (data_axis, pipe_axis) + ((model_axis,) if model_axis else ()) \
            + ((seq_axis,) if seq_axis else ())
        for ax in axes:
            if ax not in mesh.axis_names:
                raise ValueError(f"axis {ax!r} not in mesh axes {mesh.axis_names}")
        self.config = config
        self.tx = tx
        self.mesh = mesh
        self.microbatches = microbatches
        self.data_axis, self.pipe_axis = data_axis, pipe_axis
        self.model_axis = model_axis
        self.remat = remat
        self.n_stages = mesh.shape[pipe_axis]
        self.circular_chunks = v = circular_chunks
        if v < 1:
            raise ValueError(f"circular_chunks must be >= 1, got {v}")
        if config.n_layers % (self.n_stages * v):
            raise ValueError(
                f"{config.n_layers} layers not divisible into "
                f"{self.n_stages} stages x {v} chunks"
            )
        if v > 1 and microbatches % self.n_stages:
            raise ValueError(
                f"the circular schedule needs microbatches ({microbatches}) "
                f"divisible by n_stages ({self.n_stages})"
            )
        if model_axis:
            m = mesh.shape[model_axis]
            if config.n_heads % m or config.d_ff % m:
                raise ValueError(
                    f"tensor-parallel stages shard heads and d_ff: n_heads="
                    f"{config.n_heads} and d_ff={config.d_ff} must divide by "
                    f"{model_axis}={m}"
                )
        # sequence parallelism INSIDE the pipeline stages: activations ride
        # the pipe as [mb, S/sp, D] slices and attention mixes positions
        # across the 'sp' ring (ring_attention locates its shard itself via
        # lax.axis_index, so it drops in as the per-block attention_fn;
        # causality uses global positions). Embedding offsets positions per
        # shard; the loss/grads add a pmean over 'sp' (equal shards ⇒ mean
        # of local means is the global mean). Composes with model_axis:
        # dp x tp x pp x sp on one mesh.
        self.seq_axis = seq_axis
        if seq_axis:
            if attention_fn is not None:
                raise ValueError(
                    "seq_axis owns attention: pass seq_attn='ring'|"
                    "'flash_ring' instead of attention_fn"
                )
            if seq_attn == "ring":
                from tpu_sandbox.parallel.ring_attention import ring_attention

                attention_fn = functools.partial(
                    ring_attention, axis_name=seq_axis
                )
            elif seq_attn == "flash_ring":
                from tpu_sandbox.parallel.flash_ring import (
                    flash_ring_attention,
                )

                def attention_fn(q, k, v):
                    return flash_ring_attention(q, k, v, seq_axis)
            else:
                raise ValueError(
                    f"seq_attn must be 'ring' or 'flash_ring', got {seq_attn!r}"
                )
        # attention_fn is injected through to every stage block (and the
        # init/parity twin) exactly as models.transformer.TransformerLM:89
        # accepts it — flash (O(S) memory) instead of the dense [S,S]
        # causal_attention at the sequence lengths the SP schemes target.
        # Params are attention_fn-independent, so checkpoints interchange.
        self.attention_fn = attention_fn
        self.block = Block(config, attention_fn)
        # init / parity twin: ring attention only exists inside the
        # shard_map (axis must be bound), so the twin stays dense there —
        # params are attention_fn-independent either way
        self.model = TransformerLM(config, None if seq_axis else attention_fn)
        self._build(donate)

    def bubble_fraction(self) -> float:
        """Idle fraction of the pipeline schedule:
        (S-1) / (M·v + S - 1)."""
        ticks = self.microbatches * self.circular_chunks + self.n_stages - 1
        return (self.n_stages - 1) / ticks

    # -- state --------------------------------------------------------------

    def init_state(self, rng, sample_tokens) -> TrainState:
        state = TrainState.create(self.model, rng, sample_tokens, self.tx)
        pre, stacked, post = split_transformer_params(state.params, self.n_stages)
        v, n = self.circular_chunks, self.n_stages
        lps = self.config.n_layers // (n * v)
        # global layer order is chunk-major round-robin ([v, n, lps]);
        # swap to [n, v, lps] so the sharded 'pipe' dim leads
        stacked = jax.tree.map(
            lambda x: x.reshape(v, n, lps, *x.shape[1:]).swapaxes(0, 1),
            stacked,
        )
        params = {"pre": pre, "stages": stacked, "post": post}
        return state.replace(params=params, opt_state=self.tx.init(params))

    def _stage_leaf_spec(self, path: str, ndim: int) -> P:
        """'pipe' on the stacked leading dim; with tensor-parallel stages,
        'model' on the Megatron dim of each kernel/bias. Kernel dims are
        indexed from the END — the leading [stage, chunk, layer] stack is
        layout-dependent (chunk dim only exists conceptually; the leaves
        are [S, v, L, ...])."""
        spec = [self.pipe_axis] + [None] * (ndim - 1)
        m = self.model_axis
        if m:
            if "qkv/kernel" in path:
                spec[ndim - 2] = m  # [..., d_model, 3, H, hd] -> heads
            elif "qkv/bias" in path:
                spec[ndim - 2] = m  # [..., 3, H, hd]
            elif "out/kernel" in path:
                spec[ndim - 3] = m  # [..., H, hd, d_model] -> row-parallel
            elif "up/kernel" in path:
                spec[ndim - 1] = m  # [..., d_model, d_ff] -> columns
            elif "up/bias" in path:
                spec[ndim - 1] = m  # [..., d_ff]
            elif "down/kernel" in path:
                spec[ndim - 2] = m  # [..., d_ff, d_model] -> row-parallel
            # out/bias, down/bias, layernorms: replicated over 'model'
        while spec and spec[-1] is None:
            spec.pop()
        return P(*spec)

    def _param_specs(self, params):
        def stage_spec(path, leaf):
            return self._stage_leaf_spec(_path_str(path), jnp.ndim(leaf))

        return {
            "pre": jax.tree.map(lambda _: P(), params["pre"]),
            "stages": jax.tree_util.tree_map_with_path(
                stage_spec, params["stages"]
            ),
            "post": jax.tree.map(lambda _: P(), params["post"]),
        }

    def _state_specs(self, state: TrainState) -> TrainState:
        # optimizer states (sgd/adam moments) embed param-shaped leaves whose
        # paths contain the params subtree names: 'stages' leaves shard like
        # their params, everything else replicates
        def opt_leaf_spec(path, leaf):
            path_s = _path_str(path)
            if "stages" in path_s.split("/"):
                return self._stage_leaf_spec(path_s, jnp.ndim(leaf))
            return P()

        return TrainState(
            step=P(),
            params=self._param_specs(state.params),
            batch_stats=jax.tree.map(lambda _: P(), state.batch_stats),
            opt_state=jax.tree_util.tree_map_with_path(opt_leaf_spec, state.opt_state),
        )

    def shard_state(self, state: TrainState) -> TrainState:
        specs = self._state_specs(state)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)), state, specs
        )

    def shard_batch(self, tokens, targets):
        b, s = jnp.shape(tokens)
        if self.seq_axis:
            n_sp = self.mesh.shape[self.seq_axis]
            if s % n_sp:
                raise ValueError(
                    f"sequence length {s} not divisible by the "
                    f"{self.seq_axis}={n_sp} shards"
                )
        sh = NamedSharding(
            self.mesh, P(self.data_axis, self.seq_axis)
            if self.seq_axis else P(self.data_axis)
        )
        return (
            jax.device_put(jnp.asarray(tokens), sh),
            jax.device_put(jnp.asarray(targets), sh),
        )

    # -- stage compute ------------------------------------------------------

    def _stage_apply(self, stage_params, h):
        """Apply this stage's layers_per_stage blocks sequentially."""
        if self.model_axis is None:

            def one(hh, layer_params):
                return self.block.apply({"params": layer_params}, hh), None

        else:
            one = self._tp_block_step

        out, _ = lax.scan(one, h, stage_params)
        return out

    def _tp_block_step(self, h, p):
        """One transformer block with Megatron tensor parallelism over
        ``model_axis`` — manual math (flax modules can't psum between the
        row-parallel matmul and its bias), numerically matching Block.apply:
        LayerNorm fp32/eps 1e-6, gelu, residuals, cfg.dtype matmuls.

        Local shards: qkv kernel holds H/m heads, up kernel d_ff/m columns
        (biases likewise local); out/down kernels hold the matching rows and
        their partial products psum once per residual branch, bias (full,
        replicated) added after the psum so it is counted exactly once.
        """
        cfg, m_ax = self.config, self.model_axis
        dt = cfg.dtype

        a = p["attn"]
        hn = _tp_region_input(_layernorm(h, p["ln1"]).astype(dt), m_ax)
        qkv = (
            jnp.einsum("bsd,dthk->bsthk", hn, a["qkv"]["kernel"].astype(dt))
            + a["qkv"]["bias"].astype(dt)
        )
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        # heads are already local shards (H/m); any [B,S,H,D] attention_fn
        # works per-head unchanged — flash here keeps TP stages O(S) memory
        # instead of causal_attention's dense [S,S] score materialization
        attn_fn = self.attention_fn or causal_attention
        attn = attn_fn(q, k, v)  # local heads only
        partial = jnp.einsum(
            "bshk,hkd->bsd", attn, a["out"]["kernel"].astype(dt)
        )
        attn_out = _tp_region_output(partial, m_ax) + a["out"]["bias"].astype(dt)
        h = h + attn_out

        mlp = p["mlp"]
        hn = _tp_region_input(_layernorm(h, p["ln2"]).astype(dt), m_ax)
        up = hn @ mlp["up"]["kernel"].astype(dt) + mlp["up"]["bias"].astype(dt)
        partial = jax.nn.gelu(up) @ mlp["down"]["kernel"].astype(dt)
        h = h + _tp_region_output(partial, m_ax) + mlp["down"]["bias"].astype(dt)
        return h, None

    # -- the pipeline -------------------------------------------------------

    def _build(self, donate: bool) -> None:
        cfg, n_stages, M = self.config, self.n_stages, self.microbatches
        daxis, paxis = self.data_axis, self.pipe_axis
        saxis = self.seq_axis
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def embed(pre, tokens):
            # sequence-sharded: local slice covers global positions
            # [sp_idx*s_local, ...) — pos_emb must see the global index
            base = lax.axis_index(saxis) * tokens.shape[1] if saxis else 0
            positions = jnp.broadcast_to(
                base + jnp.arange(tokens.shape[1]), tokens.shape
            )
            tok = pre["tok_emb"]["embedding"][tokens]
            pos = pre["pos_emb"]["embedding"][positions]
            return (tok + pos).astype(cfg.dtype)

        def head_loss(post, h, targets):
            """ln_f + lm_head + CE for ONE microbatch -> mean loss.

            Logits stay in compute dtype: cross_entropy_loss upcasts on
            its plain path (bit-identical) and the fused Pallas CE
            upcasts per row-block in VMEM — no [tokens, vocab] fp32
            materialization per microbatch (cf. TransformerConfig
            .fp32_logits)."""
            hn = _layernorm(h, post["ln_f"]).astype(cfg.dtype)
            logits = (
                hn @ post["lm_head"]["kernel"].astype(cfg.dtype)
                + post["lm_head"]["bias"].astype(cfg.dtype)
            )
            return cross_entropy_loss(
                logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
            )

        v = self.circular_chunks

        def body(state: TrainState, tokens, targets):
            idx = lax.axis_index(paxis)
            b, s = tokens.shape
            if b % M:
                raise ValueError(f"local batch {b} not divisible by {M} microbatches")
            mb = b // M
            tokens_mb = tokens.reshape(M, mb, s)
            targets_mb = targets.reshape(M, mb, s)

            def loss_fn(params):
                # local shard: [1, v, lps, ...] -> chunk stack [v, lps, ...]
                my_chunks = jax.tree.map(lambda x: x[0], params["stages"])

                def tick(carry, t):
                    loss_sum, buf = carry
                    # schedule decode: unit u = t - idx; groups of n_stages
                    # microbatches run chunk c before the next group enters
                    # (v=1 degenerates to GPipe: c == 0, m == u)
                    u = t - idx
                    active = jnp.logical_and(u >= 0, u < M * v)
                    uc = jnp.clip(u, 0, M * v - 1)
                    r = uc % (n_stages * v)
                    c = r // n_stages
                    m = (uc // (n_stages * v)) * n_stages + r % n_stages
                    toks = lax.dynamic_index_in_dim(
                        tokens_mb, m, 0, keepdims=False
                    )
                    # embed is (stage 0, chunk 0)'s job; elsewhere the ring
                    # buffer feeds
                    h_in = lax.cond(
                        jnp.logical_and(idx == 0, c == 0),
                        lambda: embed(params["pre"], toks),
                        lambda: buf,
                    )
                    stage = jax.tree.map(
                        lambda x: lax.dynamic_index_in_dim(
                            x, c, 0, keepdims=False
                        ),
                        my_chunks,
                    )
                    out = self._stage_apply(stage, h_in)
                    tgt = lax.dynamic_index_in_dim(
                        targets_mb, m, 0, keepdims=False
                    )
                    # head + loss are (last stage, last chunk)'s job, on
                    # active units only; the cond mask keeps exactly one
                    # backprop path alive (a psum broadcast here would
                    # inflate grads by n_stages via its summing transpose)
                    mb_loss = lax.cond(
                        jnp.logical_and(
                            jnp.logical_and(idx == n_stages - 1, c == v - 1),
                            active,
                        ),
                        lambda: head_loss(params["post"], out, tgt) / M,
                        lambda: jnp.float32(0.0),
                    )
                    buf = lax.ppermute(out, paxis, perm)
                    return (loss_sum + mb_loss, buf), None

                if self.remat:
                    tick = jax.checkpoint(tick)
                zero = jnp.zeros((mb, s, cfg.d_model), cfg.dtype)
                (loss_sum, _), _ = lax.scan(
                    tick, (jnp.float32(0.0), zero),
                    jnp.arange(M * v + n_stages - 1),
                )
                return loss_sum

            loss, grads = jax.value_and_grad(loss_fn)(state.params)
            # pre grads are nonzero only on stage 0 (the embed cond), post
            # grads only on the last stage (the loss cond); psum makes both
            # global+replicated. stage grads stay local: no 'pipe' comm.
            grads = {
                "pre": lax.psum(grads["pre"], paxis),
                "stages": grads["stages"],
                "post": lax.psum(grads["post"], paxis),
            }
            grads = lax.pmean(grads, daxis)
            loss = lax.pmean(lax.psum(loss, paxis), daxis)
            if saxis:
                # each sp shard's CE is the mean over ITS positions and its
                # param grads are the partials of that local mean (attention
                # cross-terms already routed by the ring's VJP): with equal
                # shards, the global mean is the mean of local means
                grads = lax.pmean(grads, saxis)
                loss = lax.pmean(loss, saxis)
            updates, new_opt = self.tx.update(grads, state.opt_state, state.params)
            return (
                state.replace(
                    step=state.step + 1,
                    params=optax.apply_updates(state.params, updates),
                    opt_state=new_opt,
                ),
                loss,
            )

        self._body = body
        self._jitted = None
        self._donate = donate

    def _compile_for(self, state: TrainState) -> Callable:
        specs = self._state_specs(state)
        bspec = (P(self.data_axis, self.seq_axis) if self.seq_axis
                 else P(self.data_axis))
        smapped = jax.shard_map(
            self._body,
            mesh=self.mesh,
            in_specs=(specs, bspec, bspec),
            out_specs=(specs, P()),
            check_vma=False,
        )
        return jax.jit(smapped, donate_argnums=(0,) if self._donate else ())

    def train_step(self, state: TrainState, tokens, targets):
        if self._jitted is None:
            self._jitted = self._compile_for(state)
        return self._jitted(state, tokens, targets)

    def lower_step(self, state: TrainState, tokens, targets):
        """AOT-lower the pipelined step without executing it — same hook
        as ``DataParallel.lower_step`` so the HLO analysis tools (traffic,
        schedule, graftlint pass 2) can treat every engine uniformly."""
        if self._jitted is None:
            self._jitted = self._compile_for(state)
        return self._jitted.lower(state, tokens, targets)

    # -- parity helpers ------------------------------------------------------

    def merged_params(self, state: TrainState) -> dict:
        # [n, v, lps, ...] -> chunk-major [v, n, lps, ...] -> flat [L, ...]
        stacked = jax.tree.map(
            lambda x: np.asarray(x).swapaxes(0, 1).reshape(-1, *x.shape[3:]),
            state.params["stages"],
        )
        return merge_transformer_params(
            jax.tree.map(np.asarray, state.params["pre"]),
            stacked,
            jax.tree.map(np.asarray, state.params["post"]),
        )
