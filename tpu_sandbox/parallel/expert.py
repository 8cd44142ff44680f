"""Expert parallelism: switch-style (top-1) Mixture-of-Experts MLP.

The reference has no MoE and no all_to_all (SURVEY §2.2 "EP: ABSENT");
this module adds the capability TPU-style. The layer is written as pure
einsum dataflow — gate, capacity-bounded dispatch, per-expert FFN, combine —
with the expert dimension explicit in every tensor. Expert parallelism is
then *a sharding rule, not an engine*: shard the expert-weight leading dim
and the dispatched tensor's expert dim over an 'expert' mesh axis
(PjitEngine rule ``("w_(up|down)", P("expert", None, None))``) and XLA
inserts the all-to-alls that route tokens to their expert's device.

Routing is top-k with per-sequence capacity C = capacity_factor * S / E:
k=1 is Switch Transformer (combine weight = the router probability
itself), k>1 is GShard-style (gates = the top-k probabilities normalized
to sum to 1; capacity is granted choice-major — every token's first
choice queues before any second choice, so a 2nd choice never evicts a
1st). Overflow tokens pass through the residual (their combine weights
are zero) — the standard TPU-friendly static-shape treatment: no
data-dependent shapes, everything MXU-shaped einsums.

The router also exposes its load-balancing auxiliary loss (Switch eq. 4,
computed over first choices) via ``self.sow("aux_loss", ...)`` for
engines that want to add it; PjitEngine(task="lm") folds it into the
objective with ``aux_weight``.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_sandbox.models.transformer import TransformerConfig

_F32 = jnp.float32


class MoeMlp(nn.Module):
    """Drop-in MLP replacement for models.transformer.Block (mlp_cls)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        e = cfg.n_experts
        if e <= 0:
            raise ValueError("MoeMlp needs config.n_experts > 0")
        if not 1 <= cfg.router_top_k <= e:
            raise ValueError(
                f"router_top_k must be in [1, n_experts={e}], "
                f"got {cfg.router_top_k}"
            )
        b, s, d = x.shape
        capacity = max(1, int(cfg.capacity_factor * s / e))

        # --- router (fp32 for numerics) ---
        gate_logits = nn.Dense(e, dtype=jnp.float32, name="router")(
            x.astype(jnp.float32)
        )  # [B,S,E]
        probs = jnp.asarray(jax.nn.softmax(gate_logits, axis=-1))
        k = cfg.router_top_k
        top_vals, top_idx = jax.lax.top_k(probs, k)  # [B,S,K]
        # Switch (k=1): gate = the raw router prob; GShard (k>1): top-k
        # gates renormalized so kept tokens mix to weight ~1
        gates = top_vals if k == 1 else (
            top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)
        )

        onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)  # [B,S,K,E]
        # capacity positions, CHOICE-MAJOR: flatten [K,S] with choice as
        # the slow axis so every 1st choice queues before any 2nd choice,
        # then cumulative-count per expert (per sequence)
        oh_km = onehot.transpose(0, 2, 1, 3).reshape(b, k * s, e)
        pos_km = jnp.cumsum(oh_km, axis=1) * oh_km - 1.0  # -1 if not routed
        pos = pos_km.reshape(b, k, s, e).transpose(0, 2, 1, 3)  # [B,S,K,E]
        in_capacity = (pos >= 0) & (pos < capacity)
        pos_onehot = jax.nn.one_hot(
            jnp.where(in_capacity, pos, -1.0).astype(jnp.int32),
            capacity, dtype=jnp.float32,
        )  # [B,S,K,E,C] (all-zero row for dropped/unrouted)
        dispatch_k = onehot[..., None] * pos_onehot  # [B,S,K,E,C]
        dispatch = dispatch_k.sum(2)  # [B,S,E,C] — positions are disjoint
        combine = (dispatch_k * gates[..., None, None]).sum(2)  # [B,S,E,C]

        # load-balance aux loss (Switch eq. 4): E * sum_e f_e * P_e,
        # f_e over FIRST choices (the GShard convention for k>1)
        frac_tokens = jnp.mean(onehot[:, :, 0], axis=(0, 1))  # [E]
        frac_probs = jnp.mean(probs, axis=(0, 1))  # [E]
        self.sow("aux_loss", "load_balance", e * jnp.sum(frac_tokens * frac_probs))

        # --- dispatch -> expert FFN -> combine (dtype follows the model) ---
        xd = x.astype(cfg.dtype)
        dispatched = jnp.einsum(
            "bsec,bsd->ebcd", dispatch.astype(cfg.dtype), xd
        )  # [E,B,C,D] — expert dim leading: THE expert-parallel shard dim
        w_up = self.param(
            "w_up", nn.initializers.lecun_normal(), (e, d, cfg.d_ff)
        ).astype(cfg.dtype)
        w_down = self.param(
            "w_down", nn.initializers.lecun_normal(), (e, cfg.d_ff, d)
        ).astype(cfg.dtype)
        h = nn.gelu(jnp.einsum("ebcd,edf->ebcf", dispatched, w_up))
        out = jnp.einsum("ebcf,efd->ebcd", h, w_down)  # [E,B,C,D]
        y = jnp.einsum("bsec,ebcd->bsd", combine.astype(cfg.dtype), out)
        return y


# --- a share of the experts, with device work that follows shapes alone ---

def router_scores(x: jnp.ndarray, w: jnp.ndarray,
                  rule: str = "sigmoid") -> jnp.ndarray:
    """Router scores ``[T, E]`` in float32 at full matmul precision (on a
    TPU a float32 product is otherwise rounded to bf16 passes): the
    sigmoid of every output, or (``rule`` ``softmax``) the softmax over
    all of them."""
    logits = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if rule == "softmax":
        return jax.nn.softmax(logits, axis=-1)
    return jax.nn.sigmoid(logits)


def share_rows(tokens: int, top_k: int, n_held: int, n_experts: int,
               factor: float, row_tile: int) -> int:
    """R, the rows of a share's static buffer: ``factor`` times the mean
    number of assignments the ``n_held`` experts of ``n_experts`` see in a
    step of ``tokens`` tokens, rounded up to the row tile."""
    mean_rows = tokens * top_k * n_held / n_experts
    return int(math.ceil(factor * mean_rows / row_tile)) * row_tile


def share_row_tile(tokens: int, top_k: int, n_held: int, n_experts: int,
                   factor: float, cap: int = 256) -> int:
    """The row tile of a share whose calls differ in size (a served model:
    a prompt of thousands of tokens, then a decode step of one a session):
    the power of two that holds all of R (``share_rows``), between the
    bfloat16 sublane tile 16 and ``cap``, the tile of a training step.
    Every tile of the buffer is multiplied by an expert's whole matrices
    (``ops/pallas_grouped_matmul.py``), each held expert owns one tile at
    least, and what R adds are tiles more: where R is one tile, a step
    reads each held expert's matrices once and one expert's once more."""
    rows = max(1.0, factor * tokens * top_k * n_held / n_experts)
    return int(min(cap, max(16, 2 ** math.ceil(math.log2(rows)))))


def _members(sel: jnp.ndarray, n_experts: int, values=None) -> jnp.ndarray:
    """``[T, E]``: where expert e is among ``sel[t]`` (distinct ids), 1 or
    ``values[t, j]`` of the choice j that names it, else 0. One ``[T, E]``
    select a choice, summed: no ``[T, k, E]`` array, no gather, no scatter."""
    ids = jnp.arange(n_experts, dtype=sel.dtype)
    out = 0
    for j in range(sel.shape[1]):
        hit = sel[:, j:j + 1] == ids
        out = out + (hit.astype(jnp.float32) if values is None
                     else jnp.where(hit, values[:, j:j + 1], 0))
    return out


@jax.custom_vjp
def _chosen(scores: jnp.ndarray, sel: jnp.ndarray) -> jnp.ndarray:
    """``scores[t, sel[t, j]]`` as ``[T, k]``: one masked row sum a choice (a
    gather of single numbers costs the chip more than these passes over
    ``[T, E]``), and the transpose puts each choice's cotangent back the
    same way (``_members``)."""
    ids = jnp.arange(scores.shape[-1], dtype=sel.dtype)
    return jnp.stack([jnp.where(sel[:, j:j + 1] == ids, scores, 0).sum(-1)
                      for j in range(sel.shape[1])], -1)


def _chosen_fwd(scores, sel):
    # the scores ride along for their width alone; the sigmoid's own
    # backward pass keeps them anyway
    return _chosen(scores, sel), (sel, scores)


def _chosen_bwd(res, g):
    sel, scores = res
    return _members(sel, scores.shape[-1], g), None


_chosen.defvjp(_chosen_fwd, _chosen_bwd)


def count_share_table(tokens: int, top_k: int, held: int, width: int,
                      buffer_rows: int, c: int,
                      collect: str = "gather") -> None:
    """One count a traced ``routed`` call site, through the helper every
    kernel's site counts by. The share's own work is gathers and a sort,
    no kernel, so no ``trace:kernel`` span opens here; the grouped products
    it feeds open their own."""
    from tpu_sandbox.obs import get_registry
    from tpu_sandbox.ops.pallas_common import kernel_site

    kernel_site("expert_share", get_registry().counter(
        "moe.share_table", labels={
            "tokens": tokens, "top_k": top_k, "held": held, "width": width,
            "buffer_rows": buffer_rows, "c": c, "collect": collect}))


def _pick(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``table[idx]`` for a table of a few entries, as a one-hot sum: no
    gather, so nothing whose cost could follow the indices."""
    return (jax.nn.one_hot(idx, table.shape[0], dtype=table.dtype)
            * table).sum(-1)


@jax.custom_vjp
def _spread(src, idx, mask, back_idx, back_mask):
    """``out[r] = src[idx[r]] * mask[r]``: rows of ``src [N, C]`` into a
    buffer of P rows. Every source row lands in at most m buffer rows, the
    ones ``back_idx [N, m]`` names where ``back_mask`` is set, so the
    transpose is ``_collect`` (a gather too), not a scatter-add."""
    del back_idx, back_mask
    return jnp.where(mask[:, None], src[idx], 0).astype(src.dtype)


@jax.custom_vjp
def _collect(buf, back_idx, back_mask, idx, mask):
    """``out[n] = sum_j buf[back_idx[n, j]] * back_mask[n, j]``: the m
    buffer rows of a source row, gathered slot by slot (``[m, N, C]``: as
    ``[N, m, C]`` the rows want laying out anew before they can be summed,
    and at m 4, C 3584 that copy cost more than the gather), added in
    float32 and rounded once. Transpose: ``_spread``."""
    del idx, mask
    n, m = back_idx.shape
    rows = buf[back_idx.T.reshape(m * n)].reshape(m, n, buf.shape[-1])
    return jnp.where(back_mask.T[..., None], rows, 0).sum(0).astype(buf.dtype)


def _spread_fwd(src, idx, mask, back_idx, back_mask):
    return _spread(src, idx, mask, back_idx, back_mask), (
        idx, mask, back_idx, back_mask)


def _spread_bwd(res, g):
    idx, mask, back_idx, back_mask = res
    return _collect(g, back_idx, back_mask, idx, mask), None, None, None, None


def _collect_fwd(buf, back_idx, back_mask, idx, mask):
    return _collect(buf, back_idx, back_mask, idx, mask), (
        idx, mask, back_idx, back_mask)


def _collect_bwd(res, g):
    idx, mask, back_idx, back_mask = res
    return _spread(g, idx, mask, back_idx, back_mask), None, None, None, None


_spread.defvjp(_spread_fwd, _spread_bwd)
_collect.defvjp(_collect_fwd, _collect_bwd)


def _held_hits(sel: jnp.ndarray, held: tuple[int, ...]):
    """``[T, k, h]``: where choice j of token t names held expert i, and
    the axis a slot of the share's table sums it over: the table is as wide
    as what a token can hold here, ``min(k, h)`` — a slot a choice where a
    token chooses no more experts than are held, else a slot a held expert
    (``top_k`` gives a token distinct experts, so it names each at most
    once)."""
    hit = sel[:, :, None] == jnp.asarray(held, sel.dtype)
    return hit, (2 if sel.shape[1] <= len(held) else 1)


def _slot_values(sel: jnp.ndarray, held: tuple[int, ...], values: jnp.ndarray):
    """``values [T, k]`` of a token's choices as ``[T, min(k, h)]`` of its
    slots (0 where a slot holds nothing): a masked sum, like ``_chosen``,
    and so is its transpose."""
    hit, axis = _held_hits(sel, held)
    return jnp.where(hit, values[:, :, None], 0).sum(axis)


def share_layout(sel: jnp.ndarray, held: tuple[int, ...], local_rows: int,
                 row_tile: int) -> dict:
    """Where each held assignment of ``sel [T, k]`` (what ``top_k`` gives:
    distinct expert ids a token) sits in a buffer of ``local_rows +
    len(held) * row_tile`` rows, and back: a table ``[T, m]`` of a token's
    slots (``_held_hits``), m = min(k, len(held)).

    Held assignments are ordered by expert, then by token; the first
    ``local_rows`` of that order are kept and the tail is dropped —
    whatever the per-expert imbalance, only the share's total can drop a
    row. Each expert's kept rows start on a row tile and every expert owns
    at least one tile, so a tile belongs to one expert; the extra
    ``len(held)`` tiles are what that alignment can cost at worst. An
    assignment's rank inside its expert is the count of that expert's
    tokens before it, so nothing is as large as tokens x choices but the
    comparisons that fill the table; the way back from a buffer row to its
    slot is one sort of the table's T m keys. All of it is comparisons,
    prefix sums and that sort over static shapes.
    """
    t, h = sel.shape[0], len(held)
    hit, axis = _held_hits(sel, held)
    ids = jnp.arange(h, dtype=jnp.int32)
    loc = jnp.where(hit.any(axis), jnp.where(hit, ids, 0).sum(axis), h)  # h = absent
    m = loc.shape[1]
    if (h + 1) * t * m >= 2 ** 31:
        raise ValueError(f"{t} tokens x {m} slots x {h} experts pass int32")
    mine = loc[:, :, None] == ids                                   # [T, m, h]
    count = jnp.cumsum(mine.any(1).astype(jnp.int32), 0)            # [T, h]
    n = count[-1]
    start = jnp.cumsum(n) - n
    kept_n = jnp.clip(local_rows - start, 0, n)
    aligned = jnp.maximum(row_tile, -(-kept_n // row_tile) * row_tile)
    a_start = jnp.cumsum(aligned) - aligned
    p = local_rows + h * row_tile
    tiles = p // row_tile
    tile_start = jnp.arange(tiles, dtype=jnp.int32) * row_tile
    tile_group = jnp.minimum(
        (tile_start[:, None] >= (a_start + aligned)[None, :]).sum(1), h - 1
    ).astype(jnp.int32)
    # buffer row -> slot: the slots by (expert, token), absent ones last
    slot = jnp.arange(t * m, dtype=jnp.int32).reshape(t, m)
    order = jnp.sort((loc * (t * m) + slot).reshape(t * m)) % (t * m)
    e_r = jnp.repeat(tile_group, row_tile)
    off_r = jnp.arange(p, dtype=jnp.int32) - _pick(a_start, e_r)
    valid = off_r < _pick(kept_n, e_r)
    a_r = order[jnp.clip(_pick(start, e_r) + off_r, 0, t * m - 1)]
    # slot -> buffer row
    off_a = jnp.where(mine, count[:, None, :] - 1, 0).sum(2)        # [T, m]
    e_a = jnp.minimum(loc, h - 1)
    kept = (loc < h) & (off_a < _pick(kept_n, e_a))
    # a slot with no row points somewhere harmless, spread over the buffer
    # so that the access pattern does not depend on how many there are
    dest = jnp.where(kept, _pick(a_start, e_a) + off_a, slot % p)
    return {"tile_group": tile_group, "row_assignment": a_r, "row_valid": valid,
            "dest": dest, "kept": kept,
            "rows_held": kept_n.sum(), "rows_dropped": (n - kept_n).sum(),
            "expert_rows_max": n.max()}


class ExpertShare(nn.Module):
    """The part of a routed-expert layer that one chip of an expert-parallel
    deployment computes: it routes over all ``n_routed_experts``, holds the
    experts in ``held``, and returns what those give. Assignments to absent
    experts are left out; nothing stands in for the other chips or their
    exchange.

    Scores in float32 by one of two rules (``score_rule``), top-k of
    ``score + bias`` either way (the bias, ``e_score_correction_bias``,
    lives in ``batch_stats``: no gradient moves it; each training step
    moves it by ``bias_update_rate`` towards the experts that saw fewer
    tokens than the mean; 0 leaves it, as a served model's):

    - ``sigmoid_norm`` (Xing4, Nemotron): the sigmoid of every output, the
      weights the chosen scores normalised to sum ``routed_scaling_factor``;
    - ``softmax`` (LongCat-Flash): the softmax over all outputs, the
      weights the chosen probabilities times ``routed_scaling_factor``,
      **not** normalised over the choices.

    **Zero-compute experts** (``n_zero_experts``): the router scores
    ``n_routed_experts + n_zero_experts`` outputs, and a choice that falls
    on one of the last ``n_zero_experts`` adds ``weight * x`` (the identity
    expert) and takes no row of the buffer. Every chip computes that term
    alike for its own tokens, like a shared expert: it is whole here, and
    counted once where shares are summed. ``batch_stats`` then also counts
    ``real_choices`` and ``zero_choices``.

    The matrices (experts, shared expert) are held in ``param_dtype``
    (float32 where a step trains them; bfloat16 where a served model holds
    nothing else); the router's stays float32 for its float32 product.

    What a model may choose: the experts' ``kind`` (``gated_silu``: three
    products, ``down(silu(gate x) * up x)``; ``relu2``: two,
    ``down(relu(up x) ** 2)``); a shared expert of the same kind and of
    width ``d_ff * n_shared_experts`` that every chip computes alike
    (``n_shared_experts`` 0: none here; a model whose shared expert has
    another width or input owns it); and, through ``routed``, an input for
    the router other than the experts' own (``route_on``: experts in a
    latent, scores from the full width). A model that wraps the routed part
    in layers of its own subclasses this module and calls ``routed`` from
    its ``__call__``, so that they share one scope.

    The device work is a function of the shapes: held assignments are laid
    out by expert, then token, in one buffer (``share_layout``), all of
    whose row tiles are multiplied by ``ops.pallas_grouped_matmul`` whether
    they hold rows or zeros. The share's own bookkeeping is a table of
    m = min(top_k, held) slots a token, what a token can hold here, not
    its top_k choices: dispatch gathers a row a buffer row, combine m rows
    a token, each the other's transpose, and a row's weight travels as a
    row of its token's m slot weights through the same pair (the registry
    counts the table a traced call site, ``moe.share_table``). The router's
    chosen scores and counts are masked sums over ``[T, E]``, so nothing of
    tokens x choices x experts elements is built. ``batch_stats`` also
    accumulates the counters ``rows_held``, ``rows_dropped``,
    ``expert_rows_max`` and ``steps`` (int32: exact for 2**31 rows, which
    float32 is not past 2**24).
    """

    d_model: int
    d_ff: int
    n_routed_experts: int
    top_k: int
    held: tuple[int, ...]
    local_rows: int
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    bias_update_rate: float = 1e-3
    dtype: Any = jnp.bfloat16
    row_tile: int = 256
    kind: str = "gated_silu"
    score_rule: str = "sigmoid_norm"
    n_zero_experts: int = 0
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        lead, c = x.shape[:-1], x.shape[-1]
        x = x.reshape(-1, c).astype(self.dtype)
        y = self.routed(x)
        if self.n_shared_experts:
            with jax.named_scope("shared"):
                y = y + self.dense_expert(
                    x, self.d_ff * self.n_shared_experts, c, "shared")
        return y.reshape(*lead, c)

    @nn.nowrap
    def dense_expert(self, x: jnp.ndarray, width: int, out: int, prefix: str):
        """One expert of this layer's ``kind`` as plain dense layers
        ``{prefix}_gate`` (gated only), ``{prefix}_up``, ``{prefix}_down``:
        ``x [T, C_in]`` -> ``[T, out]``."""
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype,
                                  param_dtype=self.param_dtype)
        u = dense(width, name=f"{prefix}_up")(x)
        if self.kind == "gated_silu":
            hidden = nn.silu(dense(width, name=f"{prefix}_gate")(x)) * u
        else:
            hidden = jnp.square(nn.relu(u))
        return dense(out, name=f"{prefix}_down")(hidden)

    @nn.nowrap
    def routed(self, x: jnp.ndarray, route_on: jnp.ndarray | None = None):
        """What the held experts give for ``x [T, d_model]``, the router
        scoring ``route_on [T, C_r]`` (``x`` if None). Moves the bias and
        the counters where ``batch_stats`` is mutable; sows ``sel``."""
        if self.kind not in ("gated_silu", "relu2"):
            raise ValueError(f"unknown expert kind {self.kind!r}")
        if self.score_rule not in ("sigmoid_norm", "softmax"):
            raise ValueError(f"unknown score rule {self.score_rule!r}")
        t, k = x.shape[0], self.top_k
        # the router's outputs: the routed experts, then the zero experts
        n_real, zeros = self.n_routed_experts, self.n_zero_experts
        e = n_real + zeros
        if self.local_rows % self.row_tile:
            raise ValueError(f"local_rows {self.local_rows} is not a multiple "
                             f"of the row tile {self.row_tile}")
        init = nn.initializers.lecun_normal()
        bias = self.variable("batch_stats", "e_score_correction_bias",
                             jnp.zeros, (e,), jnp.float32)
        counters = {name: self.variable("batch_stats", name, jnp.zeros, (),
                                        jnp.int32)
                    for name in ("rows_held", "rows_dropped",
                                 "expert_rows_max", "steps")
                    + (("real_choices", "zero_choices") if zeros else ())}

        with jax.named_scope("router"):
            scored = x if route_on is None else route_on
            w_r = self.param("router", init, (scored.shape[-1], e), jnp.float32)
            softmax = self.score_rule == "softmax"
            scores = router_scores(scored, w_r,
                                   "softmax" if softmax else "sigmoid")
            _, sel = jax.lax.top_k(
                scores + jax.lax.stop_gradient(bias.value), k)      # [T, k]
            s_sel = _chosen(scores, sel)
            if softmax:
                weights = s_sel * self.routed_scaling_factor
            else:
                weights = (s_sel / (s_sel.sum(-1, keepdims=True) + 1e-20)
                           * self.routed_scaling_factor)
            counts = _members(sel, e).sum(0)                        # [E]

        y, lay = self._held(x, sel, weights, init)

        on_zero = sel >= n_real                                     # [T, k]
        if zeros:
            with jax.named_scope("zero"):
                w_zero = jnp.where(on_zero, weights, 0).sum(-1, keepdims=True)
                y = (y.astype(jnp.float32)
                     + w_zero * x.astype(jnp.float32)).astype(self.dtype)

        if not self.is_initializing() and self.is_mutable_collection(
                "batch_stats"):
            bias.value = bias.value + self.bias_update_rate * jnp.sign(
                counts.mean() - counts)
            counters["rows_held"].value += lay["rows_held"]
            counters["rows_dropped"].value += lay["rows_dropped"]
            counters["expert_rows_max"].value = jnp.maximum(
                counters["expert_rows_max"].value, lay["expert_rows_max"])
            counters["steps"].value += 1
            if zeros:
                n_zero = on_zero.sum().astype(jnp.int32)
                counters["zero_choices"].value += n_zero
                counters["real_choices"].value += t * k - n_zero
        self.sow("intermediates", "sel", sel)
        return y

    @nn.nowrap
    def _held(self, x, sel, weights, init):
        """What the held experts give the tokens that chose them, over the
        share's static row buffer: ``(y, counts)``, the counts ``rows_held``,
        ``rows_dropped`` and ``expert_rows_max`` of ``share_layout``. (A
        model whose call cannot live with a static buffer overrides this:
        ``PromptShare``, below.)"""
        from tpu_sandbox.ops.pallas_grouped_matmul import grouped_matmul

        c = x.shape[-1]
        t, k, h = x.shape[0], self.top_k, len(self.held)
        with jax.named_scope("dispatch"):
            held = tuple(self.held)
            lay = share_layout(sel, held, self.local_rows, self.row_tile)
            m = lay["dest"].shape[1]
            count_share_table(t, k, h, m, lay["row_valid"].shape[0], c)
            tok_r = lay["row_assignment"] // m
            back = (lay["dest"], lay["kept"])
            rows = _spread(x, tok_r, lay["row_valid"], *back)
            # a row's weight: its token's m slot weights come as a row (a
            # gather of single numbers, and its transpose of T m of them,
            # cost the chip more), and the row's own slot is picked
            w_slots = _slot_values(sel, held, weights)              # [T, m]
            w_rows = (_spread(w_slots, tok_r, lay["row_valid"], *back)
                      * jax.nn.one_hot(lay["row_assignment"] % m, m)
                      ).sum(-1, keepdims=True)                      # [P, 1]

        with jax.named_scope("experts"):
            group = lay["tile_group"]

            def product(name, rows, shape):
                w = self.param(name, init, (h, *shape), self.param_dtype)
                return grouped_matmul(rows, w.astype(self.dtype), group,
                                      self.row_tile)

            if self.kind == "gated_silu":
                gate = product("w_gate", rows, (c, self.d_ff))
                up = product("w_up", rows, (c, self.d_ff))
                hidden = nn.silu(gate) * up
            else:
                hidden = jnp.square(nn.relu(
                    product("w_up", rows, (c, self.d_ff))))
            out = product("w_down", hidden.astype(self.dtype), (self.d_ff, c))

        with jax.named_scope("combine"):
            out = (out.astype(jnp.float32) * w_rows).astype(self.dtype)
            y = _collect(out, *back, tok_r, lay["row_valid"])
        return y, lay


class PromptShare(ExpertShare):
    """The share of a whole sequence (the full forward, a prompt): no
    buffer. Every held expert's products run over all T tokens, one expert
    after another, and a token that did not choose an expert gets its
    output at weight 0: T x held rows of work where the buffer does R, and
    **no row can drop**. Under random weights the positions of a long
    prompt come to share most of their hidden state and choose the same
    experts (one held expert was given 3443 of a prompt's 6144 tokens, a
    prompt 1.3 held rows a token where an even router gives 0.25; my chip
    runs, PR 45); a buffer that shapes alone keep from dropping holds
    ``min(top_k, held)`` rows a token, 12 T here, which is this form's 16 T
    with a dispatch in front. Same parameters, scopes and counters as the
    buffered share (``rows_dropped`` 0); ``moe.share_table`` counts it with
    ``collect=dense``."""

    @nn.nowrap
    def _held(self, x, sel, weights, init):
        c, h = x.shape[-1], len(self.held)
        count_share_table(x.shape[0], self.top_k, h, 0, 0, c, "dense")
        with jax.named_scope("dispatch"):
            held = jnp.asarray(self.held, sel.dtype)
            hit = sel[:, :, None] == held                           # [T, k, h]
            w_held = jnp.where(hit, weights[:, :, None], 0).sum(1)  # [T, h]
            n = hit.any(1).sum(0)                                   # [h]
        with jax.named_scope("experts"):
            stacks = [self.param(name, init, shape, self.param_dtype)
                      for name, shape in (("w_gate", (h, c, self.d_ff)),
                                          ("w_up", (h, c, self.d_ff)),
                                          ("w_down", (h, self.d_ff, c)))]

            def one(acc, expert):
                gate, up, down, w_e = expert
                hidden = (nn.silu(jnp.dot(x, gate.astype(self.dtype)))
                          * jnp.dot(x, up.astype(self.dtype)))
                out = jnp.dot(hidden.astype(self.dtype),
                              down.astype(self.dtype))
                return acc + w_e[:, None] * out.astype(_F32), None

            y, _ = jax.lax.scan(one, jnp.zeros(x.shape, _F32),
                                (*stacks, w_held.T))
        return y.astype(self.dtype), {
            "rows_held": n.sum(), "rows_dropped": jnp.zeros((), jnp.int32),
            "expert_rows_max": n.max()}
