"""AOT-compiled static-shape prefill and single-token decode steps.

Both steps run the *unmodified* ``TransformerLM`` — prefill taps per-layer
K/V through the model's ``kv_cache`` sow collection, decode injects an
``attention_fn`` that reads/writes the paged cache — so serving exercises
exactly the weights and math the training stack produced.

Bitwise discipline (the parity tests in tests/test_serve.py hold these):

- Decode computes single-query attention with the query axis padded to 2:
  at q=1 XLA:CPU switches to a matvec kernel whose output-contraction
  accumulation order differs from the full forward's gemm by ~1 ulp; at
  q>=2 the gemm kernel is used and row outputs are bitwise identical
  regardless of row count.
- The softmax *sum* reduce is grouping-stable only between equal (or
  vector-aligned) k-axis lengths: reducing 17 real weights over a k=17
  axis and over a zero-tailed k=32 axis rounds differently (~1 ulp) once
  the length exceeds the unrolled-reduce threshold (16 on XLA:CPU). So
  the bitwise reference for a decode step at context length n is the
  one-shot forward evaluated at the cache's ``max_context`` padding —
  the same k-axis length decode reduces over. While n <= 16 the
  exact-length one-shot matches too, and power-of-two bucket lengths are
  mutually bitwise (prefill at bucket 8 == forward at 32, etc.).
- All other per-position ops (Dense, LayerNorm, embeds, the score
  einsum's d-contraction, the length-masked max) are row-independent or
  exactly associative and bitwise at any slice.

Static shapes everywhere: prefill is compiled once per bucket length,
decode once per (max_batch, page geometry). The page buffers are donated
through both steps.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
from tpu_sandbox.serve.cache import CacheConfig


def sample_token(logits_row: np.ndarray, *, seed: int, step_index: int,
                 temperature: float, top_k: int = 0) -> int:
    """Replay-exact temperature/top-k sampling over one row of fp32 logits.

    The draw is keyed by ``fold_in(key(seed), step_index)``, where
    ``step_index`` is the request's decode-step index (number of tokens
    generated so far). A request that is preempt-requeued or replayed after
    replica death re-runs from its original prompt, recomputes bitwise
    identical logits (see module docstring), folds the same indices into
    the same key, and therefore re-draws the same tokens — sampling keeps
    the same zero-loss guarantee as greedy decode.

    Gumbel-max over host fp32: ``argmax(logits/T + g)`` with Gumbel noise
    from ``jax.random`` — deterministic given the key, no CDF rounding.
    """
    logits = np.asarray(logits_row, np.float32) / np.float32(temperature)
    if top_k > 0 and top_k < logits.shape[-1]:
        kth = np.sort(logits)[-top_k]
        logits = np.where(logits >= kth, logits, -np.inf)
    key = jax.random.fold_in(jax.random.key(seed), step_index)
    g = np.asarray(jax.random.gumbel(key, logits.shape, jnp.float32))
    return int((logits + g).argmax())


@dataclass(frozen=True)
class DecodeStep:
    """Compiled step functions plus the geometry they were built for."""

    model_cfg: TransformerConfig
    cache_cfg: CacheConfig
    max_batch: int
    buckets: tuple[int, ...]
    cache_dtype: Any
    # bucket length -> compiled prefill(params, k, v, tokens, dest, last)
    prefill: dict[int, Callable]
    # compiled decode(params, k, v, tokens, lengths, block_tables)
    decode: Callable

    def pick_bucket(self, prompt_len: int) -> int:
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt of {prompt_len} tokens exceeds largest prefill bucket "
            f"{self.buckets[-1]}")


def page_shapes(model_cfg: TransformerConfig, cache_cfg: CacheConfig,
                cache_dtype: Any) -> tuple[jax.ShapeDtypeStruct, ...]:
    head_dim = model_cfg.d_model // model_cfg.n_heads
    shape = (model_cfg.n_layers, cache_cfg.num_blocks, cache_cfg.block_size,
             model_cfg.n_heads, head_dim)
    s = jax.ShapeDtypeStruct(shape, cache_dtype)
    return s, s


def init_pages(model_cfg: TransformerConfig, cache_cfg: CacheConfig,
               cache_dtype: Any = jnp.float32):
    """Zeroed K and V page buffers (finite everywhere: padding scatters may
    multiply stale page content by zero weights, which must stay exact)."""
    ks, vs = page_shapes(model_cfg, cache_cfg, cache_dtype)
    return jnp.zeros(ks.shape, ks.dtype), jnp.zeros(vs.shape, vs.dtype)


def _flat(pages: jnp.ndarray) -> jnp.ndarray:
    L, nb, bs, H, D = pages.shape
    return pages.reshape(L, nb * bs, H, D)


def make_prefill_fn(model_cfg: TransformerConfig, cache_cfg: CacheConfig,
                    cache_dtype: Any = jnp.float32):
    """prefill(params, k_pages, v_pages, tokens[1, Lb], dest_idx[Lb],
    last_pos[]) -> (next_logits[vocab], k_pages, v_pages).

    ``dest_idx`` maps each bucket position to its flat page slot — null
    block (slot 0) for bucket padding and shared-prefix positions, so the
    scatter never rewrites shared content. Page buffers are donated.
    """
    model = TransformerLM(model_cfg)

    # the function's name is the program's name in a device trace
    # (``jit_serve_prefill``); flax scopes the model's own ops
    # (``block7/attn``, ``block7/mlp``, ``lm_head``), ``write_kv`` the
    # scatter into the pages
    def serve_prefill(params, k_pages, v_pages, tokens, dest_idx, last_pos):
        logits, taps = model.apply(
            {"params": params}, tokens, mutable=["kv_cache"])
        fk, fv = _flat(k_pages), _flat(v_pages)
        with jax.named_scope("write_kv"):
            for i in range(model_cfg.n_layers):
                k, v = taps["kv_cache"][f"block{i}"]["attn"]["kv"]
                fk = fk.at[i, dest_idx].set(k[0].astype(cache_dtype))
                fv = fv.at[i, dest_idx].set(v[0].astype(cache_dtype))
        next_logits = jax.lax.dynamic_index_in_dim(
            logits[0], last_pos, axis=0, keepdims=False)
        return (next_logits,
                fk.reshape(k_pages.shape), fv.reshape(v_pages.shape))

    return jax.jit(serve_prefill, donate_argnums=(1, 2))


def make_decode_fn(model_cfg: TransformerConfig, cache_cfg: CacheConfig,
                   max_batch: int, cache_dtype: Any = jnp.float32):
    """decode(params, k_pages, v_pages, tokens[B, 1], lengths[B],
    block_tables[B, max_blocks]) -> (logits[B, vocab], k_pages, v_pages).

    ``lengths[b]`` counts tokens *including* the one being fed, so its
    position is ``lengths[b] - 1`` and attention covers kv positions
    ``< lengths[b]`` (the causal row for that query). Empty slots use
    ``lengths == 0``: their writes land in the null block and their
    attention weights collapse to zeros.
    """
    bs = cache_cfg.block_size
    head_dim = model_cfg.d_model // model_cfg.n_heads
    max_ctx = cache_cfg.max_context
    scale = jnp.sqrt(jnp.asarray(head_dim, jnp.float32))

    # ``jit_serve_decode`` in a device trace; inside each ``block{i}/attn``
    # the page write and the context gather carry scopes of their own
    # (``write_kv``, ``gather_ctx``), the rest is the attention math
    def serve_decode(params, k_pages, v_pages, tokens, lengths, block_tables):
        fk, fv = _flat(k_pages), _flat(v_pages)
        pos = jnp.maximum(lengths - 1, 0)                      # [B]
        dest = (jnp.take_along_axis(
            block_tables, (pos // bs)[:, None], axis=1)[:, 0] * bs
            + pos % bs)                                        # [B]
        # flat slot of every block-table position, in sequence order
        ctx_idx = (block_tables[:, :, None] * bs
                   + jnp.arange(bs)[None, None, :]).reshape(
                       tokens.shape[0], max_ctx)               # [B, max_ctx]
        kv_mask = (jnp.arange(max_ctx)[None, :] < lengths[:, None])

        layer = itertools.count()

        def attention_fn(q, k, v):
            # q/k/v: [B, 1, H, D] — the new token at position lengths-1
            nonlocal fk, fv
            i = next(layer)
            with jax.named_scope("write_kv"):
                fk = fk.at[i, dest].set(k[:, 0].astype(cache_dtype))
                fv = fv.at[i, dest].set(v[:, 0].astype(cache_dtype))
            with jax.named_scope("gather_ctx"):
                kc = fk[i][ctx_idx].astype(q.dtype)            # [B, ctx, H, D]
                vc = fv[i][ctx_idx].astype(v.dtype)
            # query padded to q=2: XLA's q=1 matvec kernel accumulates the
            # output contraction in a different order than the full
            # forward's gemm (~1 ulp); at q>=2 the gemm kernel matches
            # bitwise (see module docstring / tests/test_serve.py)
            q2 = jnp.concatenate([q, q], axis=1)               # [B, 2, H, D]
            scores = jnp.einsum("bqhd,bkhd->bhqk", q2, kc).astype(jnp.float32)
            scores = scores / scale
            scores = jnp.where(kv_mask[:, None, None, :], scores, -jnp.inf)
            w = jnp.nan_to_num(jnp.exp(scores - scores.max(-1, keepdims=True)))
            w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-30)
            out = jnp.einsum("bhqk,bkhd->bqhd", w.astype(vc.dtype), vc)
            return out[:, :1]

        model = TransformerLM(model_cfg, attention_fn=attention_fn)
        logits = model.apply({"params": params}, tokens, pos[:, None])
        return (logits[:, 0],
                fk.reshape(k_pages.shape), fv.reshape(v_pages.shape))

    return jax.jit(serve_decode, donate_argnums=(1, 2))


def build_decode_step(model_cfg: TransformerConfig, cache_cfg: CacheConfig,
                      *, max_batch: int = 4,
                      buckets: tuple[int, ...] = (16, 32, 64),
                      cache_dtype: Any = jnp.float32) -> DecodeStep:
    """AOT-compile every step function for the given static geometry."""
    buckets = tuple(sorted(b for b in buckets if b <= cache_cfg.max_context))
    if not buckets:
        raise ValueError("no prefill bucket fits max_context")
    params_shape = jax.eval_shape(
        lambda: TransformerLM(model_cfg).init(
            jax.random.key(0),
            jnp.zeros((1, buckets[0]), jnp.int32))["params"])
    kd, vd = page_shapes(model_cfg, cache_cfg, cache_dtype)

    prefill = {}
    for b in buckets:
        fn = make_prefill_fn(model_cfg, cache_cfg, cache_dtype)
        prefill[b] = fn.lower(
            params_shape, kd, vd,
            jax.ShapeDtypeStruct((1, b), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
        ).compile()

    decode = make_decode_fn(
        model_cfg, cache_cfg, max_batch, cache_dtype).lower(
        params_shape, kd, vd,
        jax.ShapeDtypeStruct((max_batch, 1), jnp.int32),
        jax.ShapeDtypeStruct((max_batch,), jnp.int32),
        jax.ShapeDtypeStruct(
            (max_batch, cache_cfg.max_blocks_per_seq), jnp.int32),
    ).compile()

    return DecodeStep(
        model_cfg=model_cfg, cache_cfg=cache_cfg, max_batch=max_batch,
        buckets=buckets, cache_dtype=cache_dtype,
        prefill=prefill, decode=decode,
    )
