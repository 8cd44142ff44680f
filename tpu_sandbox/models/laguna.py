"""Laguna-style decoder (``Laguna-XS.2``): a block list read from the
published per-layer lists -- ``layer_types`` (``full_attention`` |
``sliding_attention``), ``mlp_layer_types`` (``dense`` | ``sparse``) and
``num_attention_heads_per_layer`` -- of grouped-query attention whose
**query-head count, rotary rule and reach differ by layer type**, a
**per-head output gate**, and a routed-expert MLP with one shared expert.
Built for **serving**, like ``models/longcat_flash.py``: the same module runs
a whole sequence (the full forward, and prefill, which is handed each
layer's keys and values for the cache as the layer makes them, ``kv_fn``)
and one token a row against a cache it does not own (decode, through
``attention_fn``).

Built from the published ``config.json`` keys under their published names
(``LagunaConfig.from_dict``). Matrices and activations in ``dtype`` /
``param_dtype`` (bfloat16 as served), norms, softmax and the router in
float32. The equations (``benchmark/configs/laguna-xs.2.json`` lists what the
published config does not settle, under ``assumed``)::

    a  = h + Attn_l(RMSNorm(h))
    h' = a + MLP_l(RMSNorm(a))

    Attn_l(x): H_l query heads (48 full, 64 window) on 8 key/value heads of
      128; q, k rotated by the layer type's rule (``RopeRule``: full --
      YaRN frequencies on the first half of a head's dimensions, cosines and
      sines times ``attention_factor``, the second half unrotated; window --
      plain RoPE on all of them; rotate-half pairing); scores q k / sqrt(128),
      query head i on key/value head i // (H_l / 8); causal, and on window
      layers key j is seen by query i iff i - window < j <= i; softmax;
      o_h = sigmoid(x w_g)_h * (P_h v); output concat(o) W_o.
    MLP_l: dense -- W_down(silu(W_gate x) * W_up x); sparse --
      ``parallel.expert.ExpertShare`` with the ``sigmoid_norm`` score rule
      (top-k of sigmoid + bias, the chosen normalised to sum
      ``moe_routed_scaling_factor``), gated-silu experts, one shared expert,
      and this chip's ``held`` experts.

**What a position leaves behind** is its rotated key and its value a layer,
``[8, 128]`` each (what ``kv_fn`` is handed): all of a sequence's
on a full layer, its last ``sliding_window`` on a window layer
(``serve/cache.py`` holds those in a ring).

A whole sequence attends a key/value head at a time (``jax.lax.scan`` over
the 8 groups, the group's queries, gate and share of the output projection
computed inside): a 32768-token prompt's 64 query heads are 0.5 GB an array,
a group's 67 MB, and the flash kernel wants q, k and v of one shape, which a
group's ``[S, G, 128]`` with its one key/value head broadcast is. The share
of a decode call works over the static row buffer with **a row tile an
expert** (two rows an expert a step at 64 sessions: the tile is the smallest,
16); a whole sequence's is a ``PromptShare``.

Scopes in a device trace: ``block{i}/attn/{qkv, rope, attn_gate, o}``,
``block0/mlp0/{gate, up, down}``, ``block{i}/moe/{router, dispatch, experts,
combine, shared}``, ``lm_head``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_sandbox.models.latent import (GatedMlp, RMSNorm, apply_rope,
                                       yarn_inv_freq)
from tpu_sandbox.models.longcat_flash import Head
from tpu_sandbox.parallel.expert import (ExpertShare, PromptShare,
                                         share_row_tile, share_rows)

_F32 = jnp.float32

FULL, WINDOW = "full", "window"
_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}


@dataclass(frozen=True)
class RopeRule:
    """A layer type's rotary rule: ``rotary_dim`` leading dimensions of a
    head rotated (rotate-half pairing among them) at ``theta``, YaRN's
    blend where ``yarn`` (``factor, beta_fast, beta_slow,
    original_max_position_embeddings``), cosines and sines times
    ``attention_factor``."""

    theta: float
    rotary_dim: int
    attention_factor: float = 1.0
    yarn: tuple[float, float, float, int] | None = None

    @classmethod
    def from_dict(cls, rule: dict, head_dim: int) -> "RopeRule":
        rotary = int(head_dim * rule.get("partial_rotary_factor", 1))
        if rule["rope_type"] == "default":
            return cls(float(rule["rope_theta"]), rotary)
        if rule["rope_type"] != "yarn":
            raise ValueError(f"rope_type {rule['rope_type']!r}: default | yarn")
        return cls(float(rule["rope_theta"]), rotary,
                   float(rule["attention_factor"]),
                   (float(rule["factor"]), float(rule["beta_fast"]),
                    float(rule["beta_slow"]),
                    int(rule["original_max_position_embeddings"])))

    def inv_freq(self):
        if self.yarn is None:
            d = self.rotary_dim
            return 1.0 / self.theta ** (jnp.arange(0, d, 2, dtype=_F32) / d)
        return yarn_inv_freq(self.rotary_dim, self.theta, *self.yarn)[0]


@dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_key_value_heads: int
    head_dim: int
    rms_norm_eps: float
    num_experts: int              # the router's outputs (published)
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    sliding_window: int
    moe_routed_scaling_factor: float
    # a layer: full | window, its query heads, dense | sparse
    layer_kinds: tuple[str, ...]
    heads: tuple[int, ...]
    mlp_kinds: tuple[str, ...]
    rope_full: RopeRule
    rope_window: RopeRule
    # the deployment: which experts live here, how much room their rows get
    # over the mean, and how the program computes
    held: tuple[int, ...] = ()
    local_rows_factor: float = 4.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    flash: bool = False

    @classmethod
    def from_dict(cls, config: dict, **deployment) -> "LagunaConfig":
        """From the published keys plus the file's ``deployment``: ``held``,
        ``local_rows_factor`` and, where the file's ``num_experts`` counts
        the experts held here (a chip's share), the router's published
        width ``routed_experts_total``. ``deployment`` keyword arguments
        set ``dtype``, ``param_dtype`` and ``flash``."""
        n = config["num_hidden_layers"]
        wrong = [f"{key}={config.get(key)!r}" for key, want in (
            ("attention_bias", False), ("tie_word_embeddings", False),
            ("moe_apply_router_weight_on_input", False))
            if config.get(key, want) != want]
        if config.get("gating") not in (True, "per-head"):
            wrong.append(f"gating={config.get('gating')!r}")
        lists = [config[key] for key in (
            "layer_types", "mlp_layer_types", "num_attention_heads_per_layer")]
        if wrong or any(len(x) != n for x in lists):
            raise ValueError(f"laguna: a per-head gate, no bias, an untied "
                             f"head, {n} entries a per-layer list: {wrong}")
        dep = config.get("deployment", {})
        e = dep.get("routed_experts_total", config["num_experts"])
        rules = config["rope_parameters"]
        if config["shared_expert_intermediate_size"] \
                % config["moe_intermediate_size"]:
            raise ValueError("laguna: the shared expert is a whole number "
                             "of routed experts wide")
        return cls(
            **{key: config[key] for key in cls.__dataclass_fields__
               if key in config} | {"num_experts": e},
            layer_kinds=tuple(_KINDS[t] for t in lists[0]),
            mlp_kinds=tuple(lists[1]), heads=tuple(lists[2]),
            rope_full=RopeRule.from_dict(rules["full_attention"],
                                         config["head_dim"]),
            rope_window=RopeRule.from_dict(rules["sliding_attention"],
                                           config["head_dim"]),
            held=tuple(dep.get("held", range(e))),
            local_rows_factor=dep.get("local_rows_factor", 4.0), **deployment)

    def rope(self, kind: str) -> RopeRule:
        return self.rope_full if kind == FULL else self.rope_window

    def window(self, kind: str) -> int | None:
        """The keys a layer of ``kind`` reaches back over, itself counted."""
        return self.sliding_window if kind == WINDOW else None


def rotate(x, rule: RopeRule, positions=None):
    """``x [B, S, H, D]`` under ``rule`` at ``positions [B, S]`` (0..S-1
    where None), in ``x``'s type."""
    d = rule.rotary_dim
    turned = apply_rope(x[..., :d], rule.inv_freq(), rule.attention_factor,
                        positions).astype(x.dtype)
    return turned if d == x.shape[-1] else jnp.concatenate(
        [turned, x[..., d:]], -1)


def banded_attention(q, k, v, *, window: int | None):
    """Plain ``jnp`` attention of ``q, k, v [B, S, H, D]`` under an explicit
    mask: causal, and (``window``) key j seen by query i iff ``i - window <
    j``. What the flash kernel is held to, and the form of the CPU tests."""
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=_F32) * q.shape[-1] ** -0.5
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i if window is None else (j <= i) & (j > i - window)
    w = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)


class GatedAttention(nn.Module):
    """``x [B, S, C]`` at ``positions [B, S]`` (0..S-1 where None) -> ``[B,
    S, C]``: layer ``kind``'s attention with ``heads`` query heads.
    ``attention_fn(q [B, H, D], k [B, Hkv, D], v [B, Hkv, D]) -> [B, H, D]``
    stands for one token's attention over what its sequence has cached, its
    own key and value among it (S is 1). Without it the sequence attends to
    itself, a key/value head at a time. ``kv_fn(k, v, out) -> out`` is handed
    every position's rotated key and its value ``[B, S, Hkv, D]`` beside the
    layer's output, which it gives back (serving's prefill stores them and
    ties the store to the stream there: tapped and stored at the program's
    end -- where the compiler also puts a store nothing waits for -- twelve
    layers' keys and values of a 32768-token prompt are 1.6 GB that live
    through the whole forward)."""

    config: LagunaConfig
    kind: str
    heads: int
    attention_fn: Callable | None = None
    kv_fn: Callable | None = None

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.config
        b, s, c = x.shape
        h, hkv, d = self.heads, cfg.num_key_value_heads, cfg.head_dim
        g = h // hkv
        rule = cfg.rope(self.kind)

        def weight(name, shape, fan_in_axes):
            out_axes = tuple(i for i in range(len(shape))
                             if i not in fan_in_axes)
            return self.param(
                name, nn.initializers.variance_scaling(
                    1.0, "fan_in", "truncated_normal", in_axis=fan_in_axes,
                    out_axis=out_axes), shape, cfg.param_dtype
            ).astype(cfg.dtype)

        # a key/value head's group of query heads lies side by side
        w_q = weight("q", (c, hkv, g, d), (0,))
        w_k = weight("k", (c, hkv, d), (0,))
        w_v = weight("v", (c, hkv, d), (0,))
        w_g = weight("gate", (c, hkv, g), (0,))
        w_o = weight("o", (hkv, g, d, c), (0, 1, 2))
        with jax.named_scope("qkv"):
            k = jnp.einsum("bsc,chd->bshd", x, w_k)
            v = jnp.einsum("bsc,chd->bshd", x, w_v)
        with jax.named_scope("rope"):
            k = rotate(k, rule, positions)

        def gate(x, w):
            """The per-head gate of the layer's normed input."""
            with jax.named_scope("attn_gate"):
                return jax.nn.sigmoid(jnp.einsum(
                    "bsc,c...->bs...", x, w,
                    preferred_element_type=_F32))[..., None]

        if self.attention_fn is not None:
            with jax.named_scope("qkv"):
                q = jnp.einsum("bsc,chgd->bshgd", x, w_q).reshape(b, s, h, d)
            with jax.named_scope("rope"):
                q = rotate(q, rule, positions)
            ctx = self.attention_fn(q[:, 0], k[:, 0], v[:, 0])[:, None]
            ctx = (ctx.reshape(b, s, hkv, g, d) * gate(x, w_g)
                   ).astype(cfg.dtype)
            with jax.named_scope("o"):
                return jnp.einsum("bshgd,hgdc->bsc", ctx, w_o)

        window = cfg.window(self.kind)
        if cfg.flash:
            from tpu_sandbox.ops.pallas_attention import flash_attention

            def attend(q, k, v):
                return flash_attention(q, k, v, window=window)
        else:
            def attend(q, k, v):
                return banded_attention(q, k, v, window=window)

        def group(acc, args):
            """One key/value head: its ``g`` query heads, their gate and
            their rows of the output projection."""
            w_q, w_g, w_o, k, v = args           # k, v [B, S, D]
            with jax.named_scope("qkv"):
                q = jnp.einsum("bsc,cgd->bsgd", x, w_q)
            with jax.named_scope("rope"):
                q = rotate(q, rule, positions)
            ctx = attend(q, *(jnp.broadcast_to(t[:, :, None], q.shape)
                              for t in (k, v)))
            ctx = (ctx * gate(x, w_g)).astype(cfg.dtype)
            with jax.named_scope("o"):
                return acc + jnp.einsum("bsgd,gdc->bsc", ctx, w_o,
                                        preferred_element_type=_F32), None

        out, _ = jax.lax.scan(group, jnp.zeros((b, s, c), _F32), (
            jnp.moveaxis(w_q, 1, 0), jnp.moveaxis(w_g, 1, 0), w_o,
            jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
        out = out.astype(cfg.dtype)
        # what the positions leave behind: serving's prefill takes it here
        return out if self.kv_fn is None else self.kv_fn(k, v, out)


def expert_share(cfg: LagunaConfig, tokens: int, name: str | None,
                 whole_sequence: bool = False) -> ExpertShare:
    """This chip's share of a layer's routed experts for a call of
    ``tokens`` tokens, with the shared expert. A decode step's share works
    over the static row buffer (``share_rows``), **its row tile the rows of
    one held expert** (``share_row_tile`` of a share of one): 64 held
    experts see two rows each a step, and a tile as large as the whole
    buffer (what a share of 16 takes) would multiply 64 such tiles of
    zeros. A whole sequence's is a ``PromptShare``."""
    sizes = (tokens, cfg.num_experts_per_tok, len(cfg.held), cfg.num_experts,
             cfg.local_rows_factor)
    tile = share_row_tile(tokens, cfg.num_experts_per_tok, 1, cfg.num_experts,
                          cfg.local_rows_factor)
    return (PromptShare if whole_sequence else ExpertShare)(
        d_model=cfg.hidden_size, d_ff=cfg.moe_intermediate_size,
        n_routed_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
        held=cfg.held, local_rows=share_rows(*sizes, tile), row_tile=tile,
        n_shared_experts=cfg.shared_expert_intermediate_size
        // cfg.moe_intermediate_size,
        routed_scaling_factor=cfg.moe_routed_scaling_factor,
        bias_update_rate=0.0, dtype=cfg.dtype, score_rule="sigmoid_norm",
        param_dtype=cfg.param_dtype, name=name)


class Layer(nn.Module):
    config: LagunaConfig
    index: int
    attention_fn: Callable | None = None
    kv_fn: Callable | None = None

    @nn.compact
    def __call__(self, x, positions=None):
        cfg, i = self.config, self.index
        # a whole sequence's stream is settled behind every residual add
        # (``models/longcat_flash.py::DoubleLayer``)
        settle = (jax.lax.optimization_barrier if self.attention_fn is None
                  else lambda h: h)
        h = settle(x + GatedAttention(
            cfg, cfg.layer_kinds[i], cfg.heads[i], self.attention_fn,
            self.kv_fn, name="attn")(RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                                 name="attn_norm")(x), positions))
        u = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="mlp_norm")(h)
        if cfg.mlp_kinds[i] == "dense":
            y = GatedMlp(cfg.intermediate_size, cfg.hidden_size, cfg.dtype,
                         cfg.param_dtype, name="mlp0")(u)
        else:
            y = expert_share(cfg, u.shape[0] * u.shape[1], "moe",
                             self.attention_fn is None)(u)
        return settle(h + y)


class LagunaLM(nn.Module):
    """``tokens [B, S]`` at ``positions [B, S]`` (0..S-1 where None) ->
    float32 logits ``[B, S, vocab]``, or ``[B, 1, vocab]`` at ``last_pos``
    where it is given (prefill keeps the last real position's). With an
    ``attention_fn`` S is 1 and every layer attends through it, in layer
    order (``GatedAttention``), as a whole sequence's layers hand their keys
    and values to ``kv_fn``. The router's bias and the share's counters live in
    ``batch_stats`` (``models/longcat_flash.py::split_stats``)."""

    config: LagunaConfig
    attention_fn: Callable | None = None
    kv_fn: Callable | None = None

    @nn.compact
    def __call__(self, tokens, positions=None, *, last_pos=None):
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="tok_emb")(tokens)
        for i in range(cfg.num_hidden_layers):
            x = Layer(cfg, i, self.attention_fn, self.kv_fn,
                      name=f"block{i}")(x, positions)
        if last_pos is not None:
            x = jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(x)
        return Head(cfg.vocab_size, cfg.dtype, cfg.param_dtype,
                    name="lm_head")(x)


def counter_shapes(cfg: LagunaConfig) -> dict:
    """The share's counters a sparse layer, as the programs carry them."""
    names = ("rows_held", "rows_dropped", "expert_rows_max", "steps")
    return {f"block{i}": {n: jax.ShapeDtypeStruct((), jnp.int32)
                          for n in names}
            for i, kind in enumerate(cfg.mlp_kinds) if kind != "dense"}
