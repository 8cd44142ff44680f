"""Jamba-style hybrid decoder (``model_type`` ``jamba``): Mamba-1 layers with
an attention layer every ``attn_layer_period`` layers, a gated MLP after
every mixer, the head tied to the embedding. Built for **serving**: the same
module runs a whole sequence from an empty state (the full forward, and
prefill, which hands its last state over) and one token on a state it is
given (decode).

Built from the published ``config.json`` keys under their published names
(``JambaConfig.from_dict``). bfloat16 matrices and activations; the vectors
(norm scales, ``A_log``, ``D``, the convolution's taps, biases) float32. The
equations (``benchmark/configs/ai21-jamba2-3b.json`` lists what the
published config does not settle, under ``assumed``):

*Layer* ``i`` is attention where ``i % attn_layer_period ==
attn_layer_offset`` and Mamba elsewhere (``transformers``'
``JambaConfig.layers_block_type``). ``h = x + Mixer(RMSNorm(x))``, then
``h + MLP(RMSNorm(h))`` with ``MLP(u) = W_down(silu(W_gate u) o W_up u)``
(``num_experts`` 1: every feed-forward is this MLP). After the last layer
``RMSNorm``, logits by the embedding's transpose. No bias but the
convolution's and ``dt``'s.

*Attention*: ``num_attention_heads`` query heads on
``num_key_value_heads`` key/value heads of ``hidden_size /
num_attention_heads``, causal, scale ``head_dim ** -0.5``, **no positional
encoding**.

*Mamba-1* (``D = mamba_expand x hidden_size`` channels, ``N =
mamba_d_state``, kernel ``K = mamba_d_conv``, ``R = mamba_dt_rank``):
``[x | z] = u W_in``; ``x <- silu(conv_K(x) + b)``, causal and depthwise;
``[dt | B | C] = x W_x`` (widths ``R | N | N``), **each through an RMSNorm
of its own** (Jamba's addition to Mamba-1); ``dt <- softplus(dt W_dt +
b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(dt_t (x) A) o h_{t-1} + (dt_t o
x_t) (x) B_t``, ``y_t = h_t C_t + D o x_t`` (``ops/selective_scan.py``);
``out = (y o silu(z)) W_out``. ``dt``, the exponent, the state and its
update in float32.

*State.* A Mamba layer keeps, a sequence, the scan's ``h [N, D]``
(``state_dtype``, float32) and the convolution's last ``K - 1`` inputs
(``[K - 1, D]``, the compute dtype). Runs of Mamba layers between the
attention layers are alike, so each run's weights are stacked and one
``lax.scan`` walks them (one traced body a run); the model's state is, a
run, ``conv [layers, K - 1, B, D]`` and ``ssm [layers, B, N, D]`` --
channels minor, so that a TPU pads nothing. Scopes in a device trace:
``mamba`` around each mixer and inside it ``conv``, ``ssm_scan`` (a
sequence) or ``ssm_step`` (a token).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_sandbox.models.xing4 import RMSNorm
from tpu_sandbox.ops.attention import causal_attention
from tpu_sandbox.ops.pallas_short_conv import short_conv
from tpu_sandbox.ops.selective_scan import selective_scan, selective_step

_F32 = jnp.float32
# Mamba's usual start for ``b_dt``: softplus^-1 of a step drawn
# log-uniformly in [1e-3, 1e-1], floored at 1e-4
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


@dataclass(frozen=True)
class JambaConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    attn_layer_offset: int
    attn_layer_period: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    mamba_d_conv: int
    mamba_d_state: int
    mamba_dt_rank: int
    mamba_expand: int
    rms_norm_eps: float
    # the deployment: how the program computes and what it holds
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    scan_chunk: int = 64
    flash: bool = False

    @classmethod
    def from_dict(cls, config: dict, **deployment) -> "JambaConfig":
        """From the published keys; ``deployment`` sets the fields below
        them (``dtype``, ``param_dtype``, ``state_dtype``, ``flash``)."""
        wrong = [f"{key}={config.get(key)!r}" for key, want in (
            ("hidden_act", "silu"), ("num_experts", 1),
            ("mamba_conv_bias", True), ("mamba_proj_bias", False),
            ("tie_word_embeddings", True), ("sliding_window", None))
            if config.get(key, want) != want]
        if wrong:
            raise ValueError(f"jamba: only silu, dense MLPs, a biased "
                             f"convolution, a tied head: {wrong}")
        if config["num_attention_heads"] % config["num_key_value_heads"] or (
                config["hidden_size"] % config["num_attention_heads"]):
            raise ValueError("heads do not divide into their groups")
        return cls(**{key: config[key] for key in cls.__dataclass_fields__
                      if key in config}, **deployment)

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        return tuple(
            "attn" if i % self.attn_layer_period == self.attn_layer_offset
            else "mamba" for i in range(self.num_hidden_layers))

    @property
    def runs(self) -> tuple[tuple[str, int, int], ...]:
        """``(kind, first layer, layers)``: each attention layer alone, the
        Mamba layers between them as one run."""
        out: list[tuple[str, int, int]] = []
        for i, kind in enumerate(self.layer_kinds):
            if kind == "mamba" and out and out[-1][0] == "mamba" \
                    and out[-1][1] + out[-1][2] == i:
                out[-1] = ("mamba", out[-1][1], out[-1][2] + 1)
            else:
                out.append((kind, i, 1))
        return tuple(out)

    @property
    def mamba_runs(self) -> tuple[int, ...]:
        return tuple(n for kind, _, n in self.runs if kind == "mamba")


def state_shapes(cfg: JambaConfig, slots: int) -> dict:
    """The recurrent state of ``slots`` sequences, a run of Mamba layers:
    ``conv`` the convolution's last inputs, ``ssm`` the scan's state."""
    k, n, d = cfg.mamba_d_conv, cfg.mamba_d_state, cfg.d_inner
    return {
        "conv": tuple(jax.ShapeDtypeStruct((r, k - 1, slots, d), cfg.dtype)
                      for r in cfg.mamba_runs),
        "ssm": tuple(jax.ShapeDtypeStruct((r, slots, n, d), cfg.state_dtype)
                     for r in cfg.mamba_runs)}


def _dt_bias_init(key, shape, dtype=_F32):
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key, shape, dtype=_F32):
    n, d = shape
    return jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=dtype))[:, None],
                            (n, d))


def _dense(cfg: JambaConfig, features: int, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name=name)


class MambaMixer(nn.Module):
    """``u [B, S, C]`` -> ``(out [B, S, C], state)``. ``state`` None: a
    sequence from an empty state, and the layer's state behind ``last_pos
    []`` (the sequence's last real position: what lies behind it does not
    move the state) comes back as ``(conv [K - 1, B, D], ssm [B, N, D])``.
    ``state`` given as ``(conv [L, K - 1, B, D], ssm [L, B, N, D], layer
    [])``, a run's stacked state and this layer's place in it: one token on
    it, the layer's entry **updated in place** under the scopes that name
    the work (``conv``, ``ssm_step``: a device trace reads the whole update
    there), the rows not ``live [B]`` kept bit for bit; ``(conv, ssm)``
    comes back."""

    config: JambaConfig

    @nn.compact
    def __call__(self, u, state, last_pos, live):
        cfg = self.config
        b, s, _ = u.shape
        k, n, r, d = (cfg.mamba_d_conv, cfg.mamba_d_state, cfg.mamba_dt_rank,
                      cfg.d_inner)
        xz = _dense(cfg, 2 * d, "in_proj")(u)
        z = xz[..., d:]
        taps = self.param("conv_kernel", nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=0, out_axis=1), (k, d), _F32)
        bias = self.param("conv_bias", nn.initializers.zeros, (d,), _F32)
        with jax.named_scope("conv"):
            if state is None:
                # x where it lies in ``in_proj``'s result: no slice is made
                x = short_conv(xz, taps, bias, start=0)
                last = s - 1 if last_pos is None else last_pos
                at = last - (k - 2) + jnp.arange(k - 1)
                tail = jnp.where((at >= 0)[:, None, None], jnp.moveaxis(
                    jnp.take(xz[..., :d], jnp.maximum(at, 0), axis=1), 1, 0), 0)
            else:
                conv, ssm, layer = state
                held = jax.lax.dynamic_index_in_dim(conv, layer, 0, False)
                window = jnp.concatenate(
                    [held, jnp.moveaxis(xz[..., :d], 1, 0)], 0)     # [K,B,D]
                pre = bias + jnp.sum(window.astype(_F32) * taps[:, None, :], 0)
                x = jax.nn.silu(pre).astype(cfg.dtype)[:, None]
                conv = jax.lax.dynamic_update_index_in_dim(
                    conv, jnp.where(live[None, :, None], window[1:], held),
                    layer, 0)
        dbc = _dense(cfg, r + 2 * n, "x_proj")(x)
        norm = functools.partial(RMSNorm, cfg.rms_norm_eps, cfg.dtype)
        dt = norm(name="dt_norm")(dbc[..., :r])
        b_in = norm(name="b_norm")(dbc[..., r:r + n])
        c_in = norm(name="c_norm")(dbc[..., r + n:])
        w_dt = self.param("dt_proj", nn.initializers.lecun_normal(), (r, d),
                          cfg.param_dtype)
        dt = jax.nn.softplus(
            jnp.einsum("bsr,rd->bsd", dt, w_dt.astype(cfg.dtype),
                       preferred_element_type=_F32)
            + self.param("dt_bias", _dt_bias_init, (d,), _F32))
        a = -jnp.exp(self.param("A_log", _a_log_init, (n, d), _F32))
        skip = self.param("D", nn.initializers.ones, (d,), _F32)
        if state is None:
            if last_pos is not None:
                dt = jnp.where((jnp.arange(s) <= last_pos)[None, :, None],
                               dt, 0.0)
            with jax.named_scope("ssm_scan"):
                y, h = selective_scan(x, dt, a, b_in, c_in,
                                      chunk=cfg.scan_chunk,
                                      state_dtype=cfg.state_dtype)
            state = (tail.astype(cfg.dtype), h)
        else:
            with jax.named_scope("ssm_step"):
                held = jax.lax.dynamic_index_in_dim(ssm, layer, 0, False)
                y, h = selective_step(held, x[:, 0], dt[:, 0], a,
                                      b_in[:, 0], c_in[:, 0],
                                      state_dtype=cfg.state_dtype)
                ssm = jax.lax.dynamic_update_index_in_dim(
                    ssm, jnp.where(live[:, None, None], h, held), layer, 0)
                y = y[:, None]
            state = (conv, ssm)
        y = (y + skip * x.astype(_F32)) * jax.nn.silu(z.astype(_F32))
        return _dense(cfg, cfg.hidden_size, "out_proj")(
            y.astype(cfg.dtype)), state


class Attention(nn.Module):
    """Multi-query attention. ``attention_fn(q [B, S, Hq, D], k, v [B, S,
    Hkv, D]) -> [B, S, Hq, D]`` stands in for the causal attention over the
    sequence itself (serving's decode reads a paged cache there)."""

    config: JambaConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        b, s, _ = u.shape
        hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        q = _dense(cfg, hq * d, "q")(u).reshape(b, s, hq, d)
        k = _dense(cfg, hkv * d, "k")(u).reshape(b, s, hkv, d)
        v = _dense(cfg, hkv * d, "v")(u).reshape(b, s, hkv, d)
        # serving's prefill taps the keys and values here, as
        # ``models/transformer.py``'s: a no-op unless ``kv_cache`` is mutable
        self.sow("kv_cache", "kv", (k, v), reduce_fn=lambda _, x: x)
        if self.attention_fn is not None:
            out = self.attention_fn(q, k, v)
        else:
            def per_query_head(a):  # query head i reads head i Hkv // Hq
                return jnp.broadcast_to(a[:, :, :, None], (
                    b, s, hkv, hq // hkv, d)).reshape(b, s, hq, d)

            k, v = per_query_head(k), per_query_head(v)
            if cfg.flash:
                from tpu_sandbox.ops.pallas_attention import flash_attention

                out = flash_attention(q, k, v)
            else:
                out = causal_attention(q, k, v)
        return _dense(cfg, cfg.hidden_size, "o")(out.reshape(b, s, hq * d))


class Mlp(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        gate = _dense(cfg, cfg.intermediate_size, "gate")(u)
        up = _dense(cfg, cfg.intermediate_size, "up")(u)
        return _dense(cfg, cfg.hidden_size, "down")(jax.nn.silu(gate) * up)


class Block(nn.Module):
    """One layer, in the form ``nn.scan`` walks: ``((x, held), layer) ->
    ((x, held), left)``. ``held`` is the run's stacked state ``(conv,
    ssm)``, carried and updated in place at ``layer`` by a token step; a
    sequence carries None and leaves the layer's last state as ``left``.
    An attention layer has no state."""

    config: JambaConfig
    kind: str
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, carry, layer=None, last_pos=None, live=None):
        cfg = self.config
        x, held = carry
        left = None
        norm = functools.partial(RMSNorm, cfg.rms_norm_eps, cfg.dtype)
        u = norm(name="norm1")(x)
        if self.kind == "attn":
            y = Attention(cfg, self.attention_fn, name="attn")(u)
        elif held is None:
            y, left = MambaMixer(cfg, name="mamba")(u, None, last_pos, live)
        else:
            y, held = MambaMixer(cfg, name="mamba")(
                u, (*held, layer), last_pos, live)
        x = x + y
        return (x + Mlp(cfg, name="mlp")(norm(name="norm2")(x)), held), left


class JambaLM(nn.Module):
    """``tokens [B, S]`` -> ``(logits float32, state)``.

    ``state`` None: the sequences run from an empty state and the state
    behind position ``last_pos`` (the sequence's end where None) comes
    back, with logits ``[B, S, vocab]``, or ``[B, 1, vocab]`` at
    ``last_pos`` where it is given. ``state`` given (``state_shapes``): S
    is 1, the token runs on it, and the rows not ``live`` keep theirs bit
    for bit."""

    config: JambaConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, tokens, state=None, *, last_pos=None, live=None):
        cfg = self.config
        if state is not None and live is None:
            live = jnp.ones(tokens.shape[:1], bool)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="tok_emb")
        x = embed(tokens)
        conv, ssm = [], []
        for kind, first, count in cfg.runs:
            if kind == "attn":
                (x, _), _ = Block(cfg, "attn", self.attention_fn,
                                  name=f"block{first}")((x, None))
                continue
            run = nn.scan(
                Block, variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(0, nn.broadcast, nn.broadcast), out_axes=0,
                length=count)(cfg, "mamba",
                              name=f"blocks{first}_{first + count - 1}")
            if state is None:
                (x, _), left = run((x, None), None, last_pos, live)
            else:
                held = (state["conv"][len(conv)], state["ssm"][len(ssm)])
                (x, left), _ = run((x, held), jnp.arange(count), last_pos,
                                   live)
            conv.append(left[0])
            ssm.append(left[1])
        if state is None and last_pos is not None:
            x = jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(x)
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("bsc,vc->bsv", x,
                                embed.embedding.astype(cfg.dtype),
                                preferred_element_type=_F32)
        return logits, {"conv": tuple(conv), "ssm": tuple(ssm)}
