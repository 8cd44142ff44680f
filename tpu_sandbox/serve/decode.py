"""AOT-compiled static-shape prefill and single-token decode steps, a
model family at a time (``build_decode_step`` picks by the configuration's
type; four families: ``TransformerLM``, ``JambaLM``, ``LongcatFlashLM``,
``LagunaLM``).

Both steps run the *unmodified* model — prefill taps per-layer K/V through
the model's ``kv_cache`` sow collection, decode injects an ``attention_fn``
that reads/writes the paged cache — so serving exercises exactly the
weights and math the training stack produced.

One page store for every family (``DecodeStep.buffers``, donated through both
programs leaf by leaf): ``k_pages`` and ``v_pages`` are each a ``Pages`` of
**one buffer an attention layer**, ``[num_blocks, block_size, n_kv_heads *
head_dim]`` at the key/value heads (``TransformerLM``: every layer, every
query head a key/value head of its own; ``JambaLM``: the two attention
layers, one key/value head for twenty query heads). A layer's new rows go
in through ``_store`` (a scatter into that layer's donated leaf, in place),
so no value of the whole pool's size exists inside a program and one
layer's write cannot copy another layer's pages. (Until PR 42
``TransformerLM`` held one stacked ``[n_layers, ...]`` buffer; its 24
functional updates a step had the compiler re-lay 3.2 GB out and back 96
times, 1.57 of the step's 1.70 s on the chip.)

A decode step's attention reads the context where it lies (``_attend``,
PR 43): behind ``_store``, so that the new token's own key and value are
read from the pages like every other, one Pallas kernel a layer
(``ops/pallas_paged_attention.py``) takes the layer's K and V pages as they
are, copies each row's live blocks through the block table into VMEM,
several pages a compute step, and returns the new token's output: bytes
follow the live tokens, a row with ``lengths == 0`` reads nothing and gives
zeros, and what lies behind a length never reaches an output. **Which form
a program takes follows from the pages' shape and type alone**
(``pages_per_step``): pages the kernel cannot tile, or too small for a
kernel of page copies to beat one XLA gather, take the ``jnp`` form
(``_attend_jnp``: whole blocks by the table at ``max_context`` through
``_gather``, two products, a masked softmax). Either way the read of the
context stands under a ``gather_ctx`` scope inside ``block{i}/attn``, the
write under ``write_kv``, and every site counts its form
(``paged_attn.kernel_choice``).

The third family (``LongcatFlashLM``: latent attention, rotary positions,
routed experts) holds **a latent page buffer an attention sub-layer**
(``page_shapes(..., latent=True)``: one row ``[c | k_pe]`` of
``kv_lora_rank + qk_rope_head_dim`` = 576 values a position in 640 lanes, no
heads, no separate V: ``v_pages`` is empty; two leaves a double layer).
Prefill runs the prompt from position 0, attends in the *expanded* form and
stores each sub-layer's rows (behind norm, scale and rotation); decode
rotates the new token at ``lengths - 1``, writes its row and attends in the
*absorbed* form over the pages (``_attend_latent``: the same shape rule,
the same kernel with the values read out of the key row's first 512 lanes,
the same scopes and counter, and ``mla.cache_layout``). The third donated
buffer is the expert shares' device counters (``rows_held``,
``rows_dropped``, ``real_choices``, ``zero_choices``, ``steps`` a layer: no
transfer a step; whoever wants them reads them once), the router's bias
rides with the weights (``params = {"params", "router_bias"}``).

The fourth family (``LagunaLM``: grouped-query attention whose layers are
*full* or *window*, a per-head gate, routed experts) is the first written
against **layer kinds**: ``page_shapes(..., kinds=...)`` gives a layer the
pages of its kind -- K/V rows of one width, a full layer's from the cache's
``num_blocks``, a window layer's from its ``window_blocks`` -- and both
programs take **two tables**: prefill a second ``dest_idx`` (a prompt's last
window through the ring, ``cache.window_dest_indices``; a window layer
stores the bucket's last ``ring_blocks`` blocks' rows alone), decode the
rings ``[B, ring_blocks]`` beside the block tables. A window layer's read
(``_attend(..., window=, kind=)``) starts at the block that holds the
first position of the window; the two reads stand under
``gather_ctx/full`` and ``gather_ctx/window``. The third donated buffer is
the shares' counters, as LongCat's.

A model with recurrent layers (``JambaLM``, ``recurrent``) holds beside the
pages the Mamba layers' **slot state** (``models/jamba.py::state_shapes``:
the scan's state and the convolution's last inputs of every decode slot, a
run of layers stacked as the weights are). Prefill runs a prompt from an
empty state and stores the state behind its **last real token** into the
slot it is given — bucket padding has ``dt = 0`` and does not move it, the
convolution's tail is read at ``last_pos`` — which is also the slot's
reset at admission. Decode moves the state of the rows with ``lengths >
0`` and leaves the others' bit for bit (empty slots and the rows of
another weight version ride every call).

**Every family's programs pick on the device.** Behind the logits each of
the six gives every row's greedy pick (``_greedy_pick``;
``DecodeStep.picks``): the engine takes it for a greedy request and brings
the logits to the host only for one that samples; and it can dispatch the
next decode call on those picks before it has read them
(``DecodeStep.next_tokens``).

What the steps hold to (tests/test_serve.py, tests/test_serve_jamba.py,
tests/test_serve_longcat.py, tests/test_paged_attention.py):

- **Replay: same program, same bits.** A row's logits are a function of
  its own tokens, block table and length: not of its slot, of the rows
  beside it, or of which physical blocks the allocator gave. On the
  ``jnp`` form every row reduces over the same ``max_context`` positions;
  in the kernel the chunking follows the length alone. So a request
  preempted and requeued, or replayed on a peer after a replica's death,
  recomputes the logits it had and draws the same tokens, greedy or sampled
  (``sample_token``).
- **Against the one-shot forward: to rounding.** Decode and the full
  forward are different compiled programs (another reduction order, an
  online softmax in the kernel, scores accumulated in float32 where the
  forward rounds them to the compute type), so their logits agree to a few
  float32 ulps with a float32 cache
  (``test_decode_matches_padded_forward_to_rounding_fp32``) and to the
  cache's rounding with a bfloat16 one; nothing here is bitwise against
  another program, on XLA:CPU or anywhere else.
- **On the chip the contract is the benchmark's**:
  ``benchmark/reference/gpt2.py::compare_served`` (``jamba.py``'s,
  ``longcat_flash.py``'s) hold
  the served logits and tokens to limits of their own against a float32
  reference, in every run.

Static shapes everywhere: prefill is compiled once per bucket length,
decode once per (max_batch, page geometry). The page buffers are donated
through both steps.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import TYPE_CHECKING, Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
from tpu_sandbox.serve.cache import CacheConfig

if TYPE_CHECKING:  # imported where it is built: a second of imports (the
    # Pallas kernels' modules) that a TransformerLM replica never needs
    from tpu_sandbox.models.jamba import JambaConfig
    from tpu_sandbox.models.laguna import LagunaConfig
    from tpu_sandbox.models.longcat_flash import LongcatFlashConfig


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

    model_cfg: TransformerConfig | JambaConfig
    cache_cfg: CacheConfig
    max_batch: int
    buckets: tuple[int, ...]
    cache_dtype: Any
    # bucket length -> compiled prefill(params, *buffers, tokens, dest,
    # last[, slot | window_dest]): the slot only where ``recurrent``, the
    # second destinations only beside window layers (``cache_cfg.window``)
    prefill: dict[int, Callable]
    # compiled decode(params, *buffers, tokens, lengths, block_tables[,
    # window_tables])
    decode: Callable
    # shapes of the device state both programs take and give back, donated
    # (``buffer_shapes``): ``(k_pages, v_pages)``, each a ``Pages`` of one
    # buffer an attention layer, and, where ``recurrent``, the slot state
    buffers: tuple = ()
    recurrent: bool = False
    # both programs give, right behind the logits, every row's greedy pick
    # as one float32 ``[..., 3]``: the token, its log-probability, the
    # row's log-sum-exp. A greedy request then needs neither the logits on
    # the host nor a pass over the vocabulary there; a sampled one still
    # takes the logits. True of every family ``build_decode_step`` builds:
    # a field only because the tests' program-less stub steps have none
    # (``getattr(step, "picks", False)``) and give logits alone
    picks: bool = False
    # compiled next_tokens(picks[B, 3]) -> tokens[B, 1] int32: a decode
    # call's input tokens from the picks of the call before it, without a
    # visit to the host (``engine._decode_ahead``); None only in a stub
    next_tokens: Callable | None = None

    def pick_bucket(self, prompt_len: int) -> int:
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt of {prompt_len} tokens exceeds largest prefill bucket "
            f"{self.buckets[-1]}")


@jax.tree_util.register_pytree_node_class
class Pages(tuple):
    """The K or the V pages of a model: one buffer an attention layer
    (``page_shapes``). A tuple of them that is a pytree of its own, so that
    a program takes and gives it back whole and donates it leaf by leaf,
    and that answers ``delete()`` for whoever holds the pool."""

    def tree_flatten(self):
        return tuple(self), None

    @classmethod
    def tree_unflatten(cls, _, layers):
        return cls(layers)

    def delete(self) -> None:
        for layer in self:
            layer.delete()

    def blocks(self, ids: jnp.ndarray, n_kv_heads: int) -> jnp.ndarray:
        """The blocks ``ids [n]`` of every layer as one array ``[L, n,
        block, H, D]``: the form pages leave a replica in
        (``serve/disagg.py``)."""
        rows = jnp.stack([layer[ids] for layer in self])
        return rows.reshape(*rows.shape[:3], n_kv_heads, -1)

    def with_blocks(self, ids: jnp.ndarray, rows) -> Pages:
        """These pages with ``rows [L, n, block, H, D]`` (``blocks``' form)
        at the blocks ``ids [n]`` of every layer."""
        return Pages(
            layer.at[ids].set(jnp.asarray(r, layer.dtype).reshape(
                len(ids), *layer.shape[1:]))
            for layer, r in zip(self, rows, strict=True))


def page_shapes(cache_cfg: CacheConfig, n_layers: int, n_kv_heads: int,
                head_dim: int, cache_dtype: Any, *,
                latent: bool = False,
                kinds: tuple[str, ...] | None = None) -> tuple[Pages, Pages]:
    """``(k_pages, v_pages)``: a buffer ``[num_blocks, block_size,
    n_kv_heads * head_dim]`` each of ``n_layers`` attention layers. A
    token's heads lie side by side in the minor dimension: with 64 there
    (``[..., 16, 64]``) the chip's compiler keeps a second layout of every
    buffer, blocks minor, and copies each out and back every step (96
    copies of 134 MB at gpt2-medium, ``tools/aot_serve_step.py``).

    ``latent``: the second geometry. A position leaves **one row** of
    ``head_dim`` values (MLA's ``[c | k_pe]``: 576), no heads and no
    separate V -- ``v_pages`` is empty, the values are lanes of the same
    row -- padded to whole 128-lane tiles (640): the chip lays a minor
    dimension out in whole tiles anyway, and a row that says so is one the
    paged kernel can copy (``mla.cache_layout`` counts what that wastes).

    ``kinds`` (a layer: ``full`` | ``window``): a window layer's buffer has
    the blocks of the cache's window pool (``window_blocks``), rows of the
    same width."""
    if latent:
        head_dim += -head_dim % 128
    pages = Pages(jax.ShapeDtypeStruct(
        (cache_cfg.window_blocks if kind == "window" else cache_cfg.num_blocks,
         cache_cfg.block_size, n_kv_heads * head_dim), cache_dtype)
        for kind in (kinds or ("full",) * n_layers))
    return pages, (Pages() if latent else pages)


def buffer_shapes(model_cfg: TransformerConfig | JambaConfig,
                  cache_cfg: CacheConfig, max_batch: int,
                  cache_dtype: Any) -> tuple:
    """The device state a family's programs hold (``DecodeStep.buffers``):
    ``(k_pages, v_pages)`` and, for a model with recurrent layers, the
    Mamba layers' state a decode slot."""
    if isinstance(model_cfg, TransformerConfig):
        return page_shapes(
            cache_cfg, model_cfg.n_layers, model_cfg.n_heads,
            model_cfg.d_model // model_cfg.n_heads, cache_dtype)
    if _family(model_cfg) == "longcat":
        from tpu_sandbox.models.longcat_flash import counter_shapes

        # a latent page buffer an attention sub-layer, two a double layer;
        # beside them the expert shares' counters
        return (*page_shapes(cache_cfg, 2 * model_cfg.num_layers, 1,
                             model_cfg.latent_dim, cache_dtype, latent=True),
                counter_shapes(model_cfg))
    if _family(model_cfg) == "laguna":
        from tpu_sandbox.models.laguna import counter_shapes

        # pages by the layer's kind; beside them the shares' counters
        return (*page_shapes(
            cache_cfg, model_cfg.num_hidden_layers,
            model_cfg.num_key_value_heads, model_cfg.head_dim, cache_dtype,
            kinds=model_cfg.layer_kinds), counter_shapes(model_cfg))
    from tpu_sandbox.models.jamba import state_shapes

    return (*page_shapes(
        cache_cfg, model_cfg.layer_kinds.count("attn"),
        model_cfg.num_key_value_heads, model_cfg.head_dim, cache_dtype),
        state_shapes(model_cfg, max_batch))


def init_buffers(step: DecodeStep) -> tuple:
    """Zeroed device state of ``step``'s shapes (finite everywhere: padding
    scatters may multiply stale page content by zero weights, which must
    stay exact)."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), step.buffers)


def _store(pages: jnp.ndarray, dest: jnp.ndarray, rows: jnp.ndarray):
    """One layer's pages ``[blocks, block, H * D]`` with ``rows [n, H, D]``
    written at the flat slots ``dest [n]``."""
    nb, bs, width = pages.shape
    return pages.reshape(nb * bs, width).at[dest].set(
        rows.reshape(-1, width).astype(pages.dtype)).reshape(pages.shape)


def _gather(pages: jnp.ndarray, block_tables: jnp.ndarray, n_kv_heads: int):
    """One layer's pages ``[blocks, block, H * D]`` -> every row's context
    ``[B, max_blocks * block, H, D]`` in sequence order: whole blocks by
    ``block_tables [B, max_blocks]``."""
    return pages[block_tables].reshape(
        block_tables.shape[0], -1, n_kv_heads, pages.shape[-1] // n_kv_heads)


def _decode_slots(cache_cfg: CacheConfig, lengths: jnp.ndarray,
                  block_tables: jnp.ndarray):
    """Of a decode call's rows: the position of the token being fed
    (``lengths`` counts it) and the flat page slot its key and value go to
    (the null block's for ``lengths == 0``)."""
    bs = cache_cfg.block_size
    pos = jnp.maximum(lengths - 1, 0)                          # [B]
    dest = (jnp.take_along_axis(
        block_tables, (pos // bs)[:, None], axis=1)[:, 0] * bs
        + pos % bs)                                            # [B]
    return pos, dest


# what one compute step of the kernel copies of K (and as much of V), and
# the positions it may hold: sixteen 32 KB pages at gpt2-medium and Laguna,
# sixty-four 4 KB pages at Jamba, twenty-four 20 KB latent pages at LongCat
# (PERF.md section 6. PR 43: the kernel alone on the chip read 390 / 518 /
# 589 / 611 GB/s of live keys and values at 4 / 8 / 16 / 32 pages a step of
# GPT-2's, and 113 / 132 / 142 at 16 / 32 / 64 of Jamba's. PR 51's race
# split a call three ways: at 32 KB pages the step is its transfer and the
# products hide under it; at 4 and 20 KB it was the core's issue and wait of
# each copy, 22-33 ns, *plus* the products, and a longer step would not have
# bought those back -- the kernel's bookkeeping did, and the rule stayed)
_STEP_BYTES = 512 * 1024
_STEP_TOKENS = 1024


def pages_per_step(width: int, block_size: int, cache_dtype: Any,
                   max_blocks: int) -> int | None:
    """The shape rule of the decode programs' attention: how many pages a
    compute step of ``ops/pallas_paged_attention.py`` copies at this page
    geometry, or None where the ``jnp`` form (``_attend_jnp``) reads the
    context. From the pages alone -- a row's width ``Hkv * D``, the block's
    positions, the cache's type -- never from the model's name or a flag.
    The kernel copies whole pages into VMEM tiles, so it takes a row that
    is a multiple of the 128 lanes and a block that is a multiple of the
    type's sublane tile (8 rows of 32 bits, 16 of bfloat16); the tiny
    models of the tests are neither, and the CPU suite does not run them
    through the interpreter. The smallest page that fits is 4 KB, Jamba's
    (one head of 128, 16 positions, bfloat16), and there the kernel of
    small copies still beat XLA's one gather a layer by 22 % of the whole
    step (``jamba2_serve_decode_replay``, PERF.md section 6, PR 43), so the
    rule has no floor on a page's bytes."""
    itemsize = jnp.dtype(cache_dtype).itemsize
    if width % 128 or block_size % (32 // itemsize):
        return None
    pages = max(1, min(_STEP_BYTES // (block_size * width * itemsize),
                       _STEP_TOKENS // block_size, max_blocks))
    # the scores of a step are [heads, positions]: whole 128-lane tiles of
    # positions where a step holds more than one (25 pages of a latent
    # row's 640 lanes become 24)
    if pages * block_size > 128 and block_size <= 128:
        pages -= pages % (128 // block_size)
    return pages


def _step_labels(pages: int | None, buffers: int) -> dict:
    """What a compute step whose pages are all live costs the kernel's
    scalar side, for ``paged_attn.kernel_choice``: the copies it issues (a
    page of each of ``buffers``: K and V, or the one latent buffer) and the
    waits it makes (one a buffer: a DMA semaphore counts bytes). A row's
    last, partial step issues and waits page by page. 0 and 0 for the
    ``jnp`` form."""
    return {"copies_per_step": (pages or 0) * buffers,
            "waits_per_step": buffers if pages else 0}


def _ring_seen(block_tables: jnp.ndarray, lengths: jnp.ndarray,
               block_size: int, window: int) -> jnp.ndarray:
    """``[B, ring * block]`` bool: which rows of a gathered ring (entry r
    holds the newest block b of the sequence with ``b % ring == r``) lie in
    the row's window ``[lengths - window, lengths)``."""
    ring = block_tables.shape[1]
    last = (lengths[:, None] - 1) // block_size                  # [B, 1]
    block = last - (last - jnp.arange(ring)[None, :]) % ring     # [B, ring]
    pos = (block[:, :, None] * block_size
           + jnp.arange(block_size)).reshape(lengths.shape[0], -1)
    return ((pos >= jnp.maximum(lengths[:, None] - window, 0))
            & (pos < lengths[:, None]))


def _attend_jnp(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                block_tables: jnp.ndarray, lengths: jnp.ndarray,
                n_kv_heads: int, window: int | None = None,
                scope: str = "gather_ctx") -> jnp.ndarray:
    """``_attend`` in plain ``jnp``: every row's context gathered whole at
    ``max_context`` (``gather_ctx``), the products and the softmax over it,
    the positions behind ``lengths`` masked (``window``: the table a ring,
    the positions outside the window masked, ``_ring_seen``)."""
    bsz, hq, hd = q.shape
    with jax.named_scope(scope):
        kc = _gather(k_pages, block_tables, n_kv_heads).astype(q.dtype)
        vc = _gather(v_pages, block_tables, n_kv_heads).astype(q.dtype)
    # a key/value head's group of query heads side by side
    qg = q.reshape(bsz, n_kv_heads, hq // n_kv_heads, hd)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg, kc,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    if window is None:
        kv_mask = jnp.arange(kc.shape[1])[None, :] < lengths[:, None]
    else:
        kv_mask = _ring_seen(block_tables, lengths, k_pages.shape[1], window)
    scores = jnp.where(kv_mask[:, None, None, :], scores, -jnp.inf)
    w = jnp.nan_to_num(jnp.exp(scores - scores.max(-1, keepdims=True)))
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhgk,bkhd->bhgd", w.astype(vc.dtype), vc)
    return out.reshape(bsz, hq, hd)


def _attend(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
            block_tables: jnp.ndarray, lengths: jnp.ndarray,
            n_kv_heads: int, *, window: int | None = None,
            kind: str | None = None) -> jnp.ndarray:
    """The new token's attention output ``[B, Hq, D]`` of ``q [B, Hq, D]``
    over the first ``lengths[b]`` positions of one layer's pages, its own
    among them (``_store`` ran): the paged-attention kernel where
    ``pages_per_step`` takes the pages' shape, which reads them where they
    lie, else ``_attend_jnp``. ``lengths == 0`` gives zeros. Either way the
    read of the context through the table stands under ``gather_ctx`` -- the
    kernel whole, the ``jnp`` form's gathers -- and the site counts which
    it took (``paged_attn.kernel_choice``).

    A model written against layer kinds names the layer's ``kind`` (the
    scope is then ``gather_ctx/<kind>``) and, for a window layer, its
    ``window``: ``block_tables`` is the rows' rings, and the read covers
    the last ``min(lengths, window)`` positions alone."""
    from tpu_sandbox.obs import get_registry
    from tpu_sandbox.ops.pallas_common import kernel_site

    _, block_size, width = k_pages.shape
    pages = pages_per_step(width, block_size, k_pages.dtype,
                           block_tables.shape[1])
    kernel_site("paged_attn", get_registry().counter(
        "paged_attn.kernel_choice", labels={
            "impl": "jnp" if pages is None else "pallas",
            "kv_heads": n_kv_heads, "group": q.shape[1] // n_kv_heads,
            "head_dim": q.shape[2], "block_size": block_size,
            "pages_per_step": pages or 0,
            "max_blocks": block_tables.shape[1], "batch": q.shape[0],
            "qk_dim": q.shape[2], "v_dim": q.shape[2]}
        | _step_labels(pages, buffers=2)
        | ({} if kind is None else {"kind": kind, "window": window or 0})))
    scope = "gather_ctx" if kind is None else f"gather_ctx/{kind}"
    if pages is None:
        return _attend_jnp(q, k_pages, v_pages, block_tables, lengths,
                           n_kv_heads, window, scope)
    from tpu_sandbox.ops.pallas_paged_attention import paged_attention

    with jax.named_scope(scope):
        return paged_attention(q, k_pages, v_pages, block_tables, lengths,
                               pages_per_step=pages, window=window)


def _attend_latent(q: jnp.ndarray, pages: jnp.ndarray,
                   block_tables: jnp.ndarray, lengths: jnp.ndarray, *,
                   qk_dim: int, v_dim: int, scale: float) -> jnp.ndarray:
    """``_attend`` over a latent page buffer ``[blocks, block, lanes]``:
    the absorbed queries ``q [B, Hq, qk_dim]`` (``[q~ | q_pe]``) against
    each row's first ``lengths[b]`` cached rows ``[c | k_pe | padding]``,
    the values their first ``v_dim`` lanes: ``[B, Hq, v_dim]``. The same
    rule on the pages' shape and type picks the form (the kernel with
    ``v_pages`` None, or the gathered rows through
    ``models/longcat_flash.py::absorbed_attention``), under the same
    ``gather_ctx`` scope, counted by the same counter (``kv_heads`` 1,
    ``group`` the query heads) and by ``mla.cache_layout``."""
    from tpu_sandbox.obs import get_registry
    from tpu_sandbox.ops.pallas_common import kernel_site

    _, block_size, lanes = pages.shape
    step = pages_per_step(lanes, block_size, pages.dtype,
                          block_tables.shape[1])
    kernel_site("paged_attn", get_registry().counter(
        "paged_attn.kernel_choice", labels={
            "impl": "jnp" if step is None else "pallas",
            "kv_heads": 1, "group": q.shape[1], "head_dim": lanes,
            "block_size": block_size, "pages_per_step": step or 0,
            "max_blocks": block_tables.shape[1], "batch": q.shape[0],
            "qk_dim": qk_dim, "v_dim": v_dim}
        | _step_labels(step, buffers=1)))
    kernel_site("latent_cache", get_registry().counter(
        "mla.cache_layout", labels={
            "latent": v_dim, "rope": qk_dim - v_dim, "lanes": lanes,
            "pad_lanes": lanes - qk_dim, "block_size": block_size}))
    if step is None:
        from tpu_sandbox.models.longcat_flash import absorbed_attention

        with jax.named_scope("gather_ctx"):
            rows = pages[block_tables].reshape(
                block_tables.shape[0], -1, lanes)[..., :qk_dim]
        return absorbed_attention(q, rows, lengths, v_dim=v_dim, scale=scale)
    from tpu_sandbox.ops.pallas_paged_attention import paged_attention

    with jax.named_scope("gather_ctx"):
        return paged_attention(q, pages, None, block_tables, lengths,
                               pages_per_step=step, scale=scale, v_dim=v_dim)


def _store_latent(pages: jnp.ndarray, dest: jnp.ndarray, rows: jnp.ndarray):
    """``_store`` of latent rows ``[n, qk_dim]``, zeros over the pages'
    padding lanes."""
    return _store(pages, dest, jnp.pad(
        rows, ((0, 0), (0, pages.shape[-1] - rows.shape[-1]))))


def make_prefill_fn(model_cfg: TransformerConfig):
    """prefill(params, k_pages, v_pages, tokens[1, Lb], dest_idx[Lb],
    last_pos[]) -> (next_logits[vocab], their greedy pick
    (``_greedy_pick``), k_pages, v_pages).

    ``dest_idx`` maps each bucket position to its flat page slot — null
    block (slot 0) for bucket padding and shared-prefix positions, so the
    scatter never rewrites shared content. The pages are donated.
    """
    model = TransformerLM(model_cfg)

    # the function's name is the program's name in a device trace
    # (``jit_serve_prefill``); flax scopes the model's own ops
    # (``block7/attn``, ``block7/mlp``, ``lm_head``), ``write_kv`` the
    # scatter into the pages
    def serve_prefill(params, k_pages, v_pages, tokens, dest_idx, last_pos):
        logits, taps = model.apply(
            {"params": params}, tokens, mutable=["kv_cache"])
        k_pages, v_pages = list(k_pages), list(v_pages)
        with jax.named_scope("write_kv"):
            for i in range(model_cfg.n_layers):
                k, v = taps["kv_cache"][f"block{i}"]["attn"]["kv"]
                k_pages[i] = _store(k_pages[i], dest_idx, k[0])
                v_pages[i] = _store(v_pages[i], dest_idx, v[0])
        next_logits = jax.lax.dynamic_index_in_dim(
            logits[0], last_pos, axis=0, keepdims=False)
        return (next_logits, _greedy_pick(next_logits, "TransformerLM"),
                Pages(k_pages), Pages(v_pages))

    return jax.jit(serve_prefill, donate_argnums=(1, 2))


def make_decode_fn(model_cfg: TransformerConfig, cache_cfg: CacheConfig):
    """decode(params, k_pages, v_pages, tokens[B, 1], lengths[B],
    block_tables[B, max_blocks]) -> (logits[B, vocab], every row's greedy
    pick (``_greedy_pick``), k_pages, v_pages).

    ``lengths[b]`` counts tokens *including* the one being fed, so its
    position is ``lengths[b] - 1`` and attention covers kv positions
    ``< lengths[b]`` (the causal row for that query). Empty slots use
    ``lengths == 0``: their writes land in the null block and their
    attention weights collapse to zeros.
    """
    # ``jit_serve_decode`` in a device trace; inside each ``block{i}/attn``
    # the page write and the read of the context carry scopes of their own
    # (``write_kv``, ``gather_ctx``: ``_attend``)
    def serve_decode(params, k_pages, v_pages, tokens, lengths, block_tables):
        k_pages, v_pages = list(k_pages), list(v_pages)
        pos, dest = _decode_slots(cache_cfg, lengths, block_tables)
        layer = itertools.count()

        def attention_fn(q, k, v):
            # q/k/v: [B, 1, H, D] — the new token at position lengths-1
            i = next(layer)
            with jax.named_scope("write_kv"):
                k_pages[i] = _store(k_pages[i], dest, k[:, 0])
                v_pages[i] = _store(v_pages[i], dest, v[:, 0])
            return _attend(q[:, 0], k_pages[i], v_pages[i], block_tables,
                           lengths, model_cfg.n_heads)[:, None]

        model = TransformerLM(model_cfg, attention_fn=attention_fn)
        logits = model.apply({"params": params}, tokens, pos[:, None])[:, 0]
        return (logits, _greedy_pick(logits, "TransformerLM"),
                Pages(k_pages), Pages(v_pages))

    return jax.jit(serve_decode, donate_argnums=(1, 2))


def _greedy_pick(logits: jnp.ndarray, model: str):
    """``logits [..., vocab]`` float32 -> float32 ``[..., 3]``: the token
    (the first largest logit, as ``numpy.argmax`` on the host picks it, so
    a replay draws the same token; an id under 2^24 is exact in float32),
    its log-probability, the row's log-sum-exp. **One** small array: the
    host waits for the step once, and three waits a step wander more by
    process than 33.5 MB of logits do. As part of the model's head in a
    device trace. On the host the same is those logits brought over, an
    argmax over them and a float64 ``exp`` of every one (62 of 83 ms a step
    at 128 x 65,536). ``model``: the module the family's scopes start
    with."""
    with jax.named_scope(f"{model}/lm_head"):
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.stack([jnp.argmax(logits, axis=-1).astype(jnp.float32),
                          logits.max(axis=-1) - lse, lse], axis=-1)


def _next_tokens(picks: jnp.ndarray) -> jnp.ndarray:
    """``_greedy_pick``'s ``[B, 3]`` -> the tokens ``[B, 1]`` int32 a decode
    call takes."""
    return picks[:, :1].astype(jnp.int32)


def make_jamba_prefill_fn(model_cfg: JambaConfig):
    """prefill(params, k_pages, v_pages, state, tokens[1, Lb], dest_idx[Lb],
    last_pos[], slot[]) -> (next_logits[vocab], their greedy pick
    (``_greedy_pick``), k_pages, v_pages, state).

    The prompt runs from an empty state; what the Mamba layers hold behind
    ``last_pos`` replaces slot ``slot``'s state whole. ``dest_idx`` as in
    ``make_prefill_fn``. Pages and state are donated."""
    from tpu_sandbox.models.jamba import JambaLM

    model = JambaLM(model_cfg)
    attn = [i for i, kind in enumerate(model_cfg.layer_kinds)
            if kind == "attn"]

    def serve_prefill(params, k_pages, v_pages, state, tokens, dest_idx,
                      last_pos, slot):
        (logits, last), taps = model.apply(
            {"params": params}, tokens, last_pos=last_pos,
            mutable=["kv_cache"])
        k_pages, v_pages = list(k_pages), list(v_pages)
        with jax.named_scope("write_kv"):
            for n, i in enumerate(attn):
                k, v = taps["kv_cache"][f"block{i}"]["attn"]["kv"]
                k_pages[n] = _store(k_pages[n], dest_idx, k[0])
                v_pages[n] = _store(v_pages[n], dest_idx, v[0])
        with jax.named_scope("write_state"):
            state = {
                "conv": tuple(
                    jax.lax.dynamic_update_slice_in_dim(old, new, slot, 2)
                    for old, new in zip(state["conv"], last["conv"])),
                "ssm": tuple(
                    jax.lax.dynamic_update_slice_in_dim(old, new, slot, 1)
                    for old, new in zip(state["ssm"], last["ssm"]))}
        logits = logits[0, 0]
        return (logits, _greedy_pick(logits, "JambaLM"), Pages(k_pages),
                Pages(v_pages), state)

    return jax.jit(serve_prefill, donate_argnums=(1, 2, 3))


def make_jamba_decode_fn(model_cfg: JambaConfig, cache_cfg: CacheConfig):
    """decode(params, k_pages, v_pages, state, tokens[B, 1], lengths[B],
    block_tables[B, max_blocks]) -> (logits[B, vocab], every row's greedy
    pick (``_greedy_pick``), k_pages, v_pages, state). ``lengths`` as in
    ``make_decode_fn``; a row with ``lengths == 0`` writes its key and value
    to the null block and **keeps its state** (``JambaLM``'s ``live``)."""
    from tpu_sandbox.models.jamba import JambaLM

    def serve_decode(params, k_pages, v_pages, state, tokens, lengths,
                     block_tables):
        k_pages, v_pages = list(k_pages), list(v_pages)
        _, dest = _decode_slots(cache_cfg, lengths, block_tables)
        layer = itertools.count()

        def attention_fn(q, k, v):
            # q [B, 1, Hq, D]; k, v [B, 1, Hkv, D]: the new token
            i = next(layer)
            with jax.named_scope("write_kv"):
                k_pages[i] = _store(k_pages[i], dest, k[:, 0])
                v_pages[i] = _store(v_pages[i], dest, v[:, 0])
            return _attend(q[:, 0], k_pages[i], v_pages[i], block_tables,
                           lengths, model_cfg.num_key_value_heads)[:, None]

        model = JambaLM(model_cfg, attention_fn=attention_fn)
        logits, state = model.apply({"params": params}, tokens, state,
                                    live=lengths > 0)
        logits = logits[:, 0]
        return (logits, _greedy_pick(logits, "JambaLM"), Pages(k_pages),
                Pages(v_pages), state)

    return jax.jit(serve_decode, donate_argnums=(1, 2, 3))


def _share_variables(params: dict, counters: dict) -> dict:
    """A model with expert shares (LongCat's, Laguna's): its variables from
    what the engine holds: the weights with
    the router's bias a layer (``params``) and the shares' counters, the
    third donated buffer."""
    from tpu_sandbox.models.longcat_flash import join_stats

    return {"params": params["params"],
            "batch_stats": join_stats(params["router_bias"], counters)}


def _share_counters(mutated: dict) -> dict:
    from tpu_sandbox.models.longcat_flash import split_stats

    return split_stats(mutated["batch_stats"])[1]


def make_longcat_prefill_fn(model_cfg: LongcatFlashConfig):
    """prefill(params, pages, (), counters, tokens[1, Lb], dest_idx[Lb],
    last_pos[]) -> (next_logits[vocab], their greedy pick, pages, (),
    counters). ``params`` is ``{"params", "router_bias"}``. The prompt runs
    from position 0 and attends to itself in the expanded form; every
    sub-layer's latent rows (``[c | k_pe]`` behind norm, scale and
    rotation) go to its page buffer at ``dest_idx`` (as
    ``make_prefill_fn``). Pages and counters are donated."""
    from tpu_sandbox.models.longcat_flash import LongcatFlashLM

    model = LongcatFlashLM(model_cfg)

    def serve_prefill(params, pages, no_v, counters, tokens, dest_idx,
                      last_pos):
        logits, taps = model.apply(
            _share_variables(params, counters), tokens, last_pos=last_pos,
            mutable=["kv_cache", "batch_stats"])
        pages = list(pages)
        with jax.named_scope("write_kv"):
            for i in range(model_cfg.num_layers):
                for j in (0, 1):
                    rows = taps["kv_cache"][f"block{i}"][f"mla{j}"]["latent"]
                    pages[2 * i + j] = _store_latent(
                        pages[2 * i + j], dest_idx, rows[0])
        logits = logits[0, 0]
        return (logits, _greedy_pick(logits, "LongcatFlashLM"), Pages(pages),
                no_v, _share_counters(taps))

    return jax.jit(serve_prefill, donate_argnums=(1, 2, 3))


def make_longcat_decode_fn(model_cfg: LongcatFlashConfig,
                           cache_cfg: CacheConfig):
    """decode(params, pages, (), counters, tokens[B, 1], lengths[B],
    block_tables[B, max_blocks]) -> (logits[B, vocab], every row's greedy
    pick, pages, (), counters). ``lengths`` as in ``make_decode_fn``: the
    token's **rotary position is ``lengths - 1``**, its latent row goes to
    the null block where ``lengths == 0``, and every sub-layer attends in
    the absorbed form (``_attend_latent``)."""
    from tpu_sandbox.models.longcat_flash import LongcatFlashLM

    scale = model_cfg.qk_head_dim ** -0.5

    def serve_decode(params, pages, no_v, counters, tokens, lengths,
                     block_tables):
        pages = list(pages)
        pos, dest = _decode_slots(cache_cfg, lengths, block_tables)
        layer = itertools.count()

        def attention_fn(q, row):
            # q [B, H, 576] absorbed; row [B, 576]: the new token's latent
            i = next(layer)
            with jax.named_scope("write_kv"):
                pages[i] = _store_latent(pages[i], dest, row)
            return _attend_latent(
                q, pages[i], block_tables, lengths,
                qk_dim=model_cfg.latent_dim, v_dim=model_cfg.kv_lora_rank,
                scale=scale)

        model = LongcatFlashLM(model_cfg, attention_fn=attention_fn)
        logits, mutated = model.apply(
            _share_variables(params, counters), tokens, pos[:, None],
            mutable=["batch_stats"])
        logits = logits[:, 0]
        return (logits, _greedy_pick(logits, "LongcatFlashLM"), Pages(pages),
                no_v, _share_counters(mutated))

    return jax.jit(serve_decode, donate_argnums=(1, 2, 3))


def make_laguna_prefill_fn(model_cfg: LagunaConfig, cache_cfg: CacheConfig):
    """prefill(params, k_pages, v_pages, counters, tokens[1, Lb],
    dest_idx[Lb], last_pos[], window_dest[Lb]) -> (next_logits[vocab], their
    greedy pick, k_pages, v_pages, counters). ``params`` is ``{"params",
    "router_bias"}``. The prompt runs from position 0; a full layer's keys
    and values go to its pages at ``dest_idx`` (as ``make_prefill_fn``), a
    window layer's at ``window_dest`` -- of which only the ``ring_blocks``
    blocks' worth of rows up to ``last_pos`` are stored at all: whatever
    lies before them is aimed at the null block. Pages and counters are
    donated."""
    from tpu_sandbox.models.laguna import WINDOW, LagunaLM

    tail = cache_cfg.ring_blocks * cache_cfg.block_size

    def serve_prefill(params, k_pages, v_pages, counters, tokens, dest_idx,
                      last_pos, window_dest):
        k_pages, v_pages = list(k_pages), list(v_pages)
        bucket = tokens.shape[1]
        rows = min(tail, bucket)
        start = jnp.clip(last_pos + 1 - rows, 0, bucket - rows)
        layer = itertools.count()

        def kv_fn(k, v, out):
            # k, v [1, Lb, Hkv, D]: stored as the layer gives them and tied
            # to the layer's output, or the compiler keeps every layer's to
            # the program's end and stores there
            i = next(layer)
            dest = dest_idx
            if model_cfg.layer_kinds[i] == WINDOW:
                k, v = (jax.lax.dynamic_slice_in_dim(x, start, rows, 1)
                        for x in (k, v))
                dest = jax.lax.dynamic_slice_in_dim(window_dest, start, rows)
            with jax.named_scope("write_kv"):
                k_pages[i], v_pages[i], out = jax.lax.optimization_barrier((
                    _store(k_pages[i], dest, k[0]),
                    _store(v_pages[i], dest, v[0]), out))
            return out

        logits, mutated = LagunaLM(model_cfg, kv_fn=kv_fn).apply(
            _share_variables(params, counters), tokens, last_pos=last_pos,
            mutable=["batch_stats"])
        logits = logits[0, 0]
        return (logits, _greedy_pick(logits, "LagunaLM"), Pages(k_pages),
                Pages(v_pages), _share_counters(mutated))

    return jax.jit(serve_prefill, donate_argnums=(1, 2, 3))


def make_laguna_decode_fn(model_cfg: LagunaConfig, cache_cfg: CacheConfig):
    """decode(params, k_pages, v_pages, counters, tokens[B, 1], lengths[B],
    block_tables[B, max_blocks], window_tables[B, ring_blocks]) ->
    (logits[B, vocab], every row's greedy pick, k_pages, v_pages,
    counters). ``lengths`` as in ``make_decode_fn``; the token's rotary
    position is ``lengths - 1``; a full layer writes and reads through
    ``block_tables``, a window layer through the ring (position p in entry
    ``(p // block_size) % ring_blocks``) over its window alone."""
    from tpu_sandbox.models.laguna import LagunaLM

    def serve_decode(params, k_pages, v_pages, counters, tokens, lengths,
                     block_tables, window_tables):
        k_pages, v_pages = list(k_pages), list(v_pages)
        bs, ring = cache_cfg.block_size, cache_cfg.ring_blocks
        pos, dest = _decode_slots(cache_cfg, lengths, block_tables)
        window_dest = jnp.take_along_axis(
            window_tables, (pos // bs % ring)[:, None], axis=1)[:, 0] * bs \
            + pos % bs
        layer = itertools.count()

        def attention_fn(q, k, v):
            # q [B, Hq, D]; k, v [B, Hkv, D]: the new token
            i = next(layer)
            kind = model_cfg.layer_kinds[i]
            window = model_cfg.window(kind)
            at, tables = ((dest, block_tables) if window is None
                          else (window_dest, window_tables))
            with jax.named_scope("write_kv"):
                k_pages[i] = _store(k_pages[i], at, k)
                v_pages[i] = _store(v_pages[i], at, v)
            return _attend(q, k_pages[i], v_pages[i], tables, lengths,
                           model_cfg.num_key_value_heads, window=window,
                           kind=kind)

        model = LagunaLM(model_cfg, attention_fn=attention_fn)
        logits, mutated = model.apply(
            _share_variables(params, counters), tokens, pos[:, None],
            mutable=["batch_stats"])
        logits = logits[:, 0]
        return (logits, _greedy_pick(logits, "LagunaLM"), Pages(k_pages),
                Pages(v_pages), _share_counters(mutated))

    return jax.jit(serve_decode, donate_argnums=(1, 2, 3))


def _family(model_cfg) -> str:
    """The family of programs a configuration's type names."""
    if isinstance(model_cfg, TransformerConfig):
        return "transformer"
    name = type(model_cfg).__name__
    if name == "JambaConfig":
        return "jamba"
    if name == "LongcatFlashConfig":
        return "longcat"
    if name == "LagunaConfig":
        return "laguna"
    raise TypeError(f"no serving family for {name}")


def lower_step(model_cfg: TransformerConfig | JambaConfig,
               cache_cfg: CacheConfig, *, max_batch: int, cache_dtype: Any,
               placed: Callable = lambda shapes: shapes):
    """``(params, held, lower)`` of a family's programs at one geometry: the
    shapes of the weights and of the device state (``buffer_shapes``), and
    ``lower(bucket)`` — the prefill program of that bucket length, traced
    and lowered; the decode program for ``None``. ``placed`` maps every
    tree of shapes on its way in (``tools/aot_serve_step.py`` gives them a
    described chip's sharding)."""
    family = _family(model_cfg)
    recurrent = family == "jamba"
    if family == "jamba":
        from tpu_sandbox.models.jamba import JambaLM

        model = JambaLM(model_cfg)
        prefill_fn = make_jamba_prefill_fn(model_cfg)
        decode_fn = make_jamba_decode_fn(model_cfg, cache_cfg)
    elif family == "longcat":
        from tpu_sandbox.models.longcat_flash import LongcatFlashLM

        model = LongcatFlashLM(model_cfg)
        prefill_fn = make_longcat_prefill_fn(model_cfg)
        decode_fn = make_longcat_decode_fn(model_cfg, cache_cfg)
    elif family == "laguna":
        from tpu_sandbox.models.laguna import LagunaLM

        if cache_cfg.window != model_cfg.sliding_window:
            raise ValueError(
                f"the cache's window {cache_cfg.window} is not the model's "
                f"{model_cfg.sliding_window}")
        model = LagunaLM(model_cfg)
        prefill_fn = make_laguna_prefill_fn(model_cfg, cache_cfg)
        decode_fn = make_laguna_decode_fn(model_cfg, cache_cfg)
    else:
        model = TransformerLM(model_cfg)
        prefill_fn = make_prefill_fn(model_cfg)
        decode_fn = make_decode_fn(model_cfg, cache_cfg)

    def ints(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))

    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    params = variables["params"]
    if family in ("longcat", "laguna"):
        from tpu_sandbox.models.longcat_flash import split_stats

        # what the engine holds as this family's weights: the router's
        # bias a layer rides with them (``_share_variables``)
        params = {"params": params,
                  "router_bias": split_stats(variables["batch_stats"])[0]}
    params = placed(params)
    held = placed(buffer_shapes(model_cfg, cache_cfg, max_batch, cache_dtype))

    def lower(bucket: int | None):
        if bucket is None:
            rings = (ints(max_batch, cache_cfg.ring_blocks),) \
                if cache_cfg.window else ()
            return decode_fn.lower(
                params, *held, ints(max_batch, 1), ints(max_batch),
                ints(max_batch, cache_cfg.max_blocks_per_seq), *rings)
        # a recurrent family's prefill takes the slot whose state it resets,
        # one with window layers their destinations
        more = (ints(),) if recurrent else \
            (ints(bucket),) if cache_cfg.window else ()
        return prefill_fn.lower(params, *held, ints(1, bucket), ints(bucket),
                                ints(), *more)

    return params, held, lower


def build_decode_step(model_cfg: TransformerConfig | JambaConfig,
                      cache_cfg: CacheConfig, *, max_batch: int = 4,
                      buckets: tuple[int, ...] = (16, 32, 64),
                      cache_dtype: Any = jnp.float32) -> DecodeStep:
    """AOT-compile every step function for the given static geometry."""
    buckets = tuple(sorted(b for b in buckets if b <= cache_cfg.max_context))
    if not buckets:
        raise ValueError("no prefill bucket fits max_context")
    _, held, lower = lower_step(model_cfg, cache_cfg, max_batch=max_batch,
                                cache_dtype=cache_dtype)
    # every family's programs give each row's greedy pick, and the tokens
    # of the next call from it; Jamba's holds slot state beside the pages
    # (a slot into prefill, no prefix reuse)
    if model_cfg.vocab_size >= 2 ** 24:
        raise ValueError("the greedy pick carries its token in a float32")
    next_tokens = jax.jit(_next_tokens).lower(jax.ShapeDtypeStruct(
        (max_batch, 3), jnp.float32)).compile()
    return DecodeStep(
        model_cfg=model_cfg, cache_cfg=cache_cfg, max_batch=max_batch,
        buckets=buckets, cache_dtype=cache_dtype,
        prefill={b: lower(b).compile() for b in buckets},
        decode=lower(None).compile(), buffers=held,
        recurrent=_family(model_cfg) == "jamba", picks=True,
        next_tokens=next_tokens)
