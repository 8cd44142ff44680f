"""Plain float32 reference of a Jamba decoder (``model_type`` ``jamba``;
Lieber et al. 2024, "Jamba: A Hybrid Transformer-Mamba Language Model", and
the published ``modeling_jamba`` equations): token embedding; per layer
pre-RMSNorm, a mixer -- Mamba-1 (Gu & Dao 2023) with Jamba's RMSNorms on
``dt``, ``B`` and ``C``, or causal multi-query attention without positional
encoding where ``i % attn_layer_period == attn_layer_offset`` -- residual,
pre-RMSNorm, gated ``silu`` MLP, residual; final RMSNorm; logits by the
embedding's transpose. ``jax.numpy`` only: no flax, no kernels, no cache, no
chunks, no state handed over.

**The Mamba layer is the recurrence itself**, a token after another
(``lax.scan`` over the positions, the state ``[D, N]`` as published): no
chunk, no cumulative decay. The convolution is ``K`` shifted sums. Attention
runs one query head at a time against the one key/value head.

It takes the program's weights **as they are stored** (bfloat16 matrices)
and upcasts one layer at a time inside that layer's program, so that the
3 B parameters never stand in float32 at once; a sequence at a time
(``served_rows``), after the window, when the engine's pages and state are
freed.

Departures from the published block: none in the equations. What the
published config does not settle is listed under ``assumed`` in
``benchmark/configs/ai21-jamba2-3b.json`` (the initial ``A_log``, ``D`` and
``b_dt``, random weights). Layout only: the program keeps ``A_log`` and the
state as ``[N, D]`` (channels minor, for the TPU's tiles);
``from_program_tree`` notes where each layer lies in the program's stacked
runs and ``_mamba`` transposes ``A_log`` back.

Two controls put a lower precision in the reference's place
(``sweeps/jamba_serve_precision.py``): ``matmul_dtype`` rounds both operands
of every matrix product (float8), ``state_dtype`` rounds the scan's state
after every token (bfloat16 where the configuration states float32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32

#: What decides ``correct`` in ``jamba2_serve_decode_replay``: the served
#: tokens, the engine's own mean log-probability of them and its slot state
#: after the window against one full forward of this reference over prompt
#: and served tokens (``compare_served``), 16 sessions a run. The system
#: computes its products in bfloat16 (2^-9 relative rounding, float32
#: accumulation) through 28 layers whose residual stream is rounded to
#: bfloat16 twice a layer, prefills through a padded bucket and then decodes
#: every served token through the recurrent state and the paged cache. Read
#: on the chip in PR 41 (``PERF.md`` section 4): the program on 68 seeds,
#: and two controls one precision below the configuration's
#: (``sweeps/jamba_serve_precision.py``) -- the reference with every
#: product's operands rounded to float8 on four seeds, and the scan's state
#: rounded to bfloat16 after every token (the reference so on two seeds, the
#: program itself on three). Each limit stands between the largest sound
#: reading and the smallest of a control that has to break it.
TOLERANCE = {
    # widest gap of a served token's logit under the reference's largest,
    # in standard deviations of the row. Sound 0-0.035; float8 0.0, 0.041,
    # 0.151, 0.451: with the head tied to the embedding a random-weight
    # model's first choice is nearly always the token it was fed, by many
    # standard deviations, so roundings seldom make a near tie and NO
    # control breaks this one reliably. Kept at the accepted serving cell's
    # 0.2 as the guard against a wrong token; not the precision's judge.
    "chosen_gap_rel": 0.2,
    # widest distance, over the sessions, between the engine's mean
    # log-probability of a session's 125-400 tokens and the reference's.
    # Sound 0.0008-0.0103 on 67 seeds and 0.0198 on one (a tail: the worst
    # of 16 sessions); float8 0.047, 0.054, 0.085, 0.107; bfloat16 state
    # 0.043, 0.044, 0.069. 1.5 x over the largest sound reading, 1.4 x
    # under the smallest of the controls: what room the two leave.
    "chosen_logprob_abs": 3e-2,
    # widest distance, over the sessions, between the slot's slow states
    # after the window and the reference's recurrence (``slow_states``),
    # RMS over RMS. Sound 0.0029-0.0152; bfloat16 state 0.063, 0.130, 0.199,
    # 0.211; float8 0.082-0.206. 2.0 x over, 2.1 x under. The limit
    # that tells a narrow state at every size: at short contexts (the CPU
    # tests) a bfloat16 state is one more bfloat16 rounding a layer and the
    # logits cannot tell it from the program's own.
    "slow_state_rel": 3e-2,
}


def from_program_tree(params, config: dict) -> dict:
    """The program's flax tree -> ``{"params", "layers"}``: ``layers[i]`` is
    ``(kind, module, index)`` -- an attention layer a module of its own
    (``block7``), a Mamba layer the ``index``-th of its run's stacked
    weights (``blocks0_6``). Nothing is copied or upcast here."""
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    layers, run = [], None
    for i in range(config["num_hidden_layers"]):
        if i % period == offset:
            layers.append(("attn", f"block{i}", 0))
            run = None
            continue
        if run is None:
            last = i
            while last + 1 < config["num_hidden_layers"] \
                    and (last + 1) % period != offset:
                last += 1
            run = (f"blocks{i}_{last}", i)
        layers.append(("mamba", run[0], i - run[1]))
    return {"params": params, "layers": layers}


def _layer(tree: dict, i: int):
    kind, module, index = tree["layers"][i]
    p = tree["params"][module]
    return kind, (p if kind == "attn"
                  else jax.tree.map(lambda a: a[index], p))


def _rounder(matmul_dtype):
    if matmul_dtype is None:
        return lambda a: a
    return lambda a: a.astype(matmul_dtype).astype(_F32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _mlp(p, x, r):
    gate = r(x) @ r(p["gate"]["kernel"])
    up = r(x) @ r(p["up"]["kernel"])
    return r(jax.nn.silu(gate) * up) @ r(p["down"]["kernel"])


def _mamba(p, u, last, *, eps, r, state_dtype):
    """``u [B, S, C]`` -> the mixer's output, the recurrence token by
    token, and the state ``[B, D, N]`` as it stood behind position
    ``last [B]``."""
    d, n = p["A_log"].shape[1], p["A_log"].shape[0]
    k = p["conv_kernel"].shape[0]
    s = u.shape[1]
    xz = r(u) @ r(p["in_proj"]["kernel"])
    x, z = xz[..., :d], xz[..., d:]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    x = jax.nn.silu(p["conv_bias"] + sum(
        padded[:, i:i + s] * p["conv_kernel"][i] for i in range(k)))
    dbc = r(x) @ r(p["x_proj"]["kernel"])
    rank = dbc.shape[-1] - 2 * n
    dt = _rms_norm(dbc[..., :rank], p["dt_norm"]["scale"], eps)
    b_in = _rms_norm(dbc[..., rank:rank + n], p["b_norm"]["scale"], eps)
    c_in = _rms_norm(dbc[..., rank + n:], p["c_norm"]["scale"], eps)
    dt = jax.nn.softplus(r(dt) @ r(p["dt_proj"]) + p["dt_bias"])
    a = -jnp.exp(p["A_log"].T)                                   # [D, N]

    def token(carry, inputs):
        h, kept = carry
        t, x_t, dt_t, b_t, c_t = inputs           # [], [B, D] x 2, [B, N] x 2
        h = (jnp.exp(dt_t[..., None] * a) * h
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        if state_dtype is not None:
            # not a cast there and back, which XLA may drop as excess
            # precision (on the chip it does)
            kind = jnp.finfo(state_dtype)
            h = jax.lax.reduce_precision(h, kind.nexp, kind.nmant)
        kept = jnp.where((t == last)[:, None, None], h, kept)
        return (h, kept), jnp.sum(h * c_t[:, None, :], -1)

    empty = jnp.zeros((u.shape[0], d, n), _F32)
    (_, kept), y = jax.lax.scan(
        token, (empty, empty),
        (jnp.arange(s), *(jnp.moveaxis(t, 1, 0)
                          for t in (x, dt, b_in, c_in))))
    y = (jnp.moveaxis(y, 0, 1) + p["D"] * x) * jax.nn.silu(z)
    return r(y) @ r(p["out_proj"]["kernel"]), kept


def _attention(p, u, *, heads, kv_heads, r):
    """One query head at a time against its key/value head."""
    b, s, _ = u.shape
    q = r(u) @ r(p["q"]["kernel"])
    k = r(u) @ r(p["k"]["kernel"])
    v = r(u) @ r(p["v"]["kernel"])
    hd = q.shape[-1] // heads
    causal = jnp.tril(jnp.ones((s, s), bool))
    out = []
    for h in range(heads):
        g = h * kv_heads // heads
        q_h = q[..., h * hd:(h + 1) * hd]
        k_h, v_h = (t[..., g * hd:(g + 1) * hd] for t in (k, v))
        scores = jnp.einsum("bqd,bkd->bqk", r(q_h), r(k_h)) / np.sqrt(hd)
        w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bqk,bkd->bqd", r(w), r(v_h)))
    return r(jnp.concatenate(out, -1)) @ r(p["o"]["kernel"])


@functools.cache
def _layer_program(kind: str, eps: float, heads: int, kv_heads: int,
                   matmul_dtype, state_dtype):
    """One jitted program a kind of layer: the layer's weights come as they
    are stored and are upcast inside."""
    r = _rounder(matmul_dtype)

    def run(p, x, last):
        p = jax.tree.map(lambda a: a.astype(_F32), p)
        u = _rms_norm(x, p["norm1"]["scale"], eps)
        kept = None
        if kind == "mamba":
            y, kept = _mamba(p["mamba"], u, last, eps=eps, r=r,
                             state_dtype=state_dtype)
        else:
            y = _attention(p["attn"], u, heads=heads, kv_heads=kv_heads, r=r)
        x = x + y
        return (x + _mlp(p["mlp"], _rms_norm(x, p["norm2"]["scale"], eps), r),
                kept)

    return jax.jit(run)


def hidden(tree: dict, tokens, config: dict, *, last=None, matmul_dtype=None,
           state_dtype=None):
    """``tokens [B, S]`` -> the last layer's output ``[B, S, C]`` float32,
    a layer's program after another, and the **first** Mamba layer's state
    ``[B, D, N]`` behind position ``last [B]`` (the sequence's end where
    None)."""
    params = tree["params"]
    tokens = jnp.asarray(tokens)
    last = jnp.full(tokens.shape[:1], tokens.shape[1] - 1, jnp.int32) \
        if last is None else jnp.asarray(last, jnp.int32)
    x = jnp.asarray(params["tok_emb"]["embedding"])[tokens].astype(_F32)
    first_state = None
    for i in range(len(tree["layers"])):
        kind, p = _layer(tree, i)
        x, kept = _layer_program(
            kind, config["rms_norm_eps"], config["num_attention_heads"],
            config["num_key_value_heads"], matmul_dtype, state_dtype)(
                p, x, last)
        if first_state is None:
            first_state = kept
    return x, first_state


@functools.cache
def _head_program(eps: float, matmul_dtype):
    r = _rounder(matmul_dtype)

    def run(scale, embedding, x):
        x = _rms_norm(x, scale.astype(_F32), eps)
        return r(x) @ r(embedding.astype(_F32)).T

    return jax.jit(run)


def forward(tree: dict, tokens, config: dict, *, matmul_dtype=None,
            state_dtype=None):
    """``tokens [B, S]`` -> logits ``[B, S, vocab]`` float32 at ``highest``
    matmul precision (on a TPU a float32 matmul is otherwise bfloat16)."""
    params = tree["params"]
    with jax.default_matmul_precision("highest"):
        x, _ = hidden(tree, tokens, config, matmul_dtype=matmul_dtype,
                      state_dtype=state_dtype)
        return _head_program(config["rms_norm_eps"], matmul_dtype)(
            params["norm_f"]["scale"], params["tok_emb"]["embedding"], x)


@jax.jit
def _reduce_rows(logits, chosen):
    peak = logits.max(axis=-1)
    lse = peak + jnp.log(jnp.exp(logits - peak[..., None]).sum(axis=-1))
    at = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
    return {"gap_rel": (peak - at) / logits.std(axis=-1),
            "logprob": at - lse, "argmax": logits.argmax(axis=-1),
            "argmax_logprob": peak - lse}


def served_rows(tree: dict, tokens, rows, chosen, config: dict, *,
                last=None, matmul_dtype=None, state_dtype=None) -> dict:
    """For served sequences: ``tokens [B, S]`` (prompt and served tokens,
    zero padded: causal, so the padding reaches no row that counts),
    ``rows [B, R]`` the positions whose logits chose a served token and
    ``chosen [B, R]`` those tokens. One full forward, logits at ``rows``
    only, reduced on the device to what ``compare_served`` needs, each
    ``[B, R]``: ``gap_rel`` (how far the chosen token's logit lies under
    the row's largest, in standard deviations of the row), ``logprob``
    (log-softmax at the chosen token), the row's own first choice
    ``argmax`` and its ``argmax_logprob``; and ``slow_state [B, D / 4]``,
    the slow states (``slow_states``) of the first Mamba layer behind
    position ``last [B]``."""
    params = tree["params"]
    rows, chosen = jnp.asarray(rows), jnp.asarray(chosen)
    with jax.default_matmul_precision("highest"):
        x, state = hidden(tree, tokens, config, last=last,
                          matmul_dtype=matmul_dtype, state_dtype=state_dtype)
        x = jnp.take_along_axis(x, rows[..., None], axis=1)
        logits = _head_program(config["rms_norm_eps"], matmul_dtype)(
            params["norm_f"]["scale"], params["tok_emb"]["embedding"], x)
        out = _reduce_rows(logits, chosen)
    out = {k: np.asarray(v) for k, v in out.items()}
    out["slow_state"] = slow_states(tree, np.moveaxis(np.asarray(state), 1, 2))
    return out


def first_mamba(tree: dict) -> tuple[str, int]:
    """``(module, index)`` of the first Mamba layer in the program's tree."""
    return next((module, index) for kind, module, index in tree["layers"]
                if kind == "mamba")


def slow_states(tree: dict, state) -> np.ndarray:
    """Of the first Mamba layer's scan state ``[..., N, D]`` (the program's
    layout), the entries where a rounding of the state shows first: state
    index 0 (``A = -1``, the slowest decay) of the quarter of the channels
    with the smallest ``b_dt`` (the smallest steps). There a state lives for
    hundreds of tokens, so that what is lost by rounding it after every
    token adds up, and in the first layer the rest of the model's rounding
    has not arrived yet. ``[..., D / 4]``."""
    module, index = first_mamba(tree)
    bias = np.asarray(tree["params"][module]["mamba"]["dt_bias"][index],
                      np.float32)
    slow = np.sort(np.argsort(bias, kind="stable")[:len(bias) // 4])
    return np.asarray(state, np.float32)[..., 0, slow]


def compare_served(gap_rel, ref_logprob, counts, system_mean_logprob,
                   ref_slow_state, system_slow_state
                   ) -> tuple[dict, list[str]]:
    """From ``served_rows``' ``[N, R]`` arrays (as
    ``reference/gpt2.py::compare_served``): sequence ``i`` counts in its
    first ``counts[i]`` rows. ``chosen_gap_rel`` is the widest gap of any
    served token; ``chosen_logprob_abs`` the widest distance, over the
    sequences, between the system's mean log-probability of a sequence's
    tokens and the reference's; ``slow_state_rel`` the widest distance, over
    the sequences, between the system's slow states (``slow_states`` of the
    slot's state after its last served token) and the reference's, RMS over
    RMS of the reference's."""
    counts = np.asarray(counts)
    valid = np.arange(np.shape(gap_rel)[1])[None, :] < counts[:, None]
    ref_mean = np.where(valid, ref_logprob, 0.0).sum(axis=1) / counts
    want = np.asarray(ref_slow_state, np.float64)
    apart = np.asarray(system_slow_state, np.float64) - want
    dev = {"chosen_gap_rel": float(np.max(np.where(valid, gap_rel, 0.0))),
           "chosen_logprob_abs": float(np.max(np.abs(
               np.asarray(system_mean_logprob, np.float64) - ref_mean))),
           "slow_state_rel": float(np.max(
               np.sqrt(np.mean(np.square(apart), -1))
               / np.sqrt(np.mean(np.square(want), -1))))}
    bad = [f"jamba vs float32 reference: {k} {v:.3g} > {TOLERANCE[k]}"
           for k, v in dev.items() if not v <= TOLERANCE[k]]
    return dev, bad
