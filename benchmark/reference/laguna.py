"""Plain float32 reference of the Laguna language model (the row
``Laguna-XS.2`` of the catalog; poolside, ``model_type`` ``laguna``): token
embedding; ``num_hidden_layers`` layers of gated grouped-query attention --
*full* or *window* by ``layer_types``, with the layer's own count of query
heads and its type's rotary rule -- and a dense or a routed-expert MLP;
RMSNorm; an untied head. ``jax.numpy`` only: no flax, no kernels, no cache,
no ring, no row buffer, no batching -- attention under an explicit mask built
from positions, the routed layer a loop over the held experts. Nothing of the
program is imported.

The equations (``x [S, C]``, one sequence; ``RMSNorm`` with ``rms_norm_eps``
and a learned scale; no bias anywhere)::

    a  = h + Attn_l(RMSNorm(h))
    h' = a + MLP_l(RMSNorm(a))
    logits = RMSNorm(h_L) W_head

    Attn_l(x):  H_l = num_attention_heads_per_layer[l]; 8 key/value heads of 128
      q = x W_q  [S, H_l, 128];  k, v = x W_k, x W_v  [S, 8, 128]
      q, k = RoPE_type(q), RoPE_type(k)        # rotate-half pairing
        full_attention:    YaRN frequencies (theta 5e5, factor 64 over 4096,
                           beta_fast 64, beta_slow 1) on dimensions 0..63,
                           cos and sin times attention_factor; 64..127 pass
        sliding_attention: plain RoPE, theta 1e4, on all 128
      score = q k^T / sqrt(128), query head i on key/value head i // (H_l / 8)
      seen(i, j) = j <= i, and on sliding layers also i - sliding_window < j
      o_h = sigmoid(x w_g)_h * (softmax(score_h) v)      # the per-head gate
      Attn_l(x) = concat_h(o_h) W_o

    MLP_l, dense:   W_down(silu(W_gate x) * W_up x)
    MLP_l, sparse:  s = sigmoid(x W_r) over num_experts outputs (float32)
      sel = the num_experts_per_tok largest of s + e_score_correction_bias
      w_e = moe_routed_scaling_factor * s_e / sum_{sel} s
      sum_{e in sel, held} w_e Expert_e(x) + Expert_shared(x)
      Expert(x) = W_down(silu(W_gate x) * W_up x)

Departures from the row: none in the equations. What the row does not say is
listed under ``assumed`` in ``benchmark/configs/laguna-xs.2.json`` (the gate
per head and a sigmoid of the layer's normed input; sigmoid scores
normalised over the chosen; the window counts the token itself; no norm on
queries or keys; ``silu``; rotate-half with the rotated dimensions first; the
drawn ``e_score_correction_bias``). The chip's share: the experts not in
``held`` are left out, as in the program; the shared expert is whole
(``moe`` takes ``held`` and ``shared``, so that a test can add the shares up
to the whole layer).

What is done for room and time, and changes no number: queries go a block of
``QUERY_BLOCK`` at a time and a key/value head at a time, and a block is
given only the keys some query of it can see (a sliding layer: the block's
own and the ``sliding_window`` before it) -- the mask is explicit all the
same, from the absolute positions of what was given. Weights are taken **as
they are stored** (bfloat16) and widened one sub-layer at a time inside that
sub-layer's program, a routed layer one expert at a time.

Four controls put a fault in the reference's place
(``sweeps/laguna_serve_precision.py``): ``window`` False lets the sliding
layers see every key; ``gate`` False leaves the gate out; ``window_rope``
``"full"`` gives the sliding layers the full layers' rotary rule;
``cache_dtype`` rounds every key (behind its rotation) and value to float8
where the configuration states bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32

#: queries a block of the attention (module docstring)
QUERY_BLOCK = 1024

#: What decides ``correct`` in ``laguna_serve_decode_replay``: the served
#: tokens and the engine's own mean log-probability of them against one full
#: forward of this reference over prompt and served tokens
#: (``compare_served``), 8 sessions a run (the shortest, the longest, six
#: dealt by the seed; about 490 served tokens each). The system computes its
#: products in bfloat16 with float32 accumulation through 12 layers whose
#: stream is rounded to bfloat16 twice a layer, prefills through a padded
#: bucket under the flash kernel's band and then decodes every served token
#: through two bfloat16 paged caches.
#:
#: Both readings of each limit are of one call on the committed program (my
#: chip run, PR 48: the cell's nine runs on nine seeds, and
#: ``sweeps/laguna_serve_precision.py --control 1 --sessions 3`` on seed
#: 4800000483; ``sweeps/NOTES_pr48_laguna_serve.md`` has every number).
TOLERANCE = {
    # widest gap of a served token's logit under the reference's largest,
    # in standard deviations of the row: the guard against a wrong token.
    # The program reads 0.031-0.065 on nine seeds. The controls: the window
    # left out 0.875, the full layers' rotary rule on the window layers
    # 1.02, the gate left out 4.17 (and the float8 cache 0.32). The limit
    # stands 4.6 x over the program's largest and 2.9 x under the
    # structural controls' smallest.
    "chosen_gap_rel": 0.3,
    # the mean, over the sessions, of the distance between the engine's mean
    # log-probability of a session's served tokens and the reference's. The
    # program reads 0.00032-0.00088 on nine seeds; keys and values cached
    # in float8 (e4m3), one precision below the configuration's bfloat16,
    # 0.0064; the window left out 0.055, the wrong rotary rule 0.074, the
    # gate left out 0.75. The limit is the geometric mean of the program's
    # largest and the float8 control's reading: 2.7 x from either.
    "chosen_logprob_mean_abs": 2.4e-3,
}


def from_program_tree(params, config: dict) -> dict:
    """The program's weights as the engine holds them (``{"params",
    "router_bias"}``), as they are stored: nothing is copied or widened
    here."""
    del config
    return {"params": params["params"], "bias": params["router_bias"]}


def _rms_norm(x, scale, eps):
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale.astype(_F32)


def inv_freq(rule: dict, head_dim: int) -> np.ndarray:
    """A layer type's inverse frequencies ``[rotary / 2]`` (float64): plain
    RoPE's, or YaRN's blend of them with the same divided by ``factor``."""
    dim = int(head_dim * rule.get("partial_rotary_factor", 1))
    theta = float(rule["rope_theta"])
    plain = theta ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rule["rope_type"] == "default":
        return plain

    def correction_dim(rotations):
        return dim * math.log(rule["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rule["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rule["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return plain / rule["factor"] * ramp + plain * (1 - ramp)


def rope(x, freq, factor: float):
    """``x [S, H, D]`` at positions 0..S-1: the first ``2 len(freq)``
    dimensions rotated (dimension i with i + len(freq)), cosines and sines
    times ``factor``; the rest pass."""
    s, half = x.shape[0], len(freq)
    ang = jnp.arange(s, dtype=_F32)[:, None] * jnp.asarray(freq, _F32)
    cos, sin = jnp.cos(ang)[:, None] * factor, jnp.sin(ang)[:, None] * factor
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def attention(p, u, *, rule: tuple, window: int | None, gate: bool = True,
              cache_dtype=None):
    """``u [S, C]`` -> ``[S, C]``. ``rule``: ``(inv_freq tuple,
    attention_factor)``; ``window`` None on a full layer."""
    s, c = u.shape
    freq, factor = rule
    w_q, w_k, w_v = (p[n].astype(_F32) for n in ("q", "k", "v"))
    hkv, d = w_k.shape[1:]
    g = w_q.shape[2]
    q = rope(jnp.einsum("sc,chd->shd", u, w_q.reshape(c, hkv * g, d)),
             freq, factor)                                     # [S, H, D]
    k = rope(jnp.einsum("sc,chd->shd", u, w_k), freq, factor)  # [S, 8, D]
    v = jnp.einsum("sc,chd->shd", u, w_v)
    if cache_dtype is not None:   # the control: a narrower cache
        k, v = (x.astype(cache_dtype).astype(_F32) for x in (k, v))
    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} positions are no whole blocks of {block}")
    # the keys a block of queries is given: all of them, or its own and the
    # ``window`` before it (zeros in front of position 0, masked below)
    behind = 0 if window is None else min(window, s - block)
    if window is not None:
        k, v = (jnp.pad(x, ((behind, 0), (0, 0), (0, 0))) for x in (k, v))

    def queries(start):
        """The block of queries at ``start``: ``[block, H, D]``."""
        q_pos = start + jnp.arange(block)
        q_b = jax.lax.dynamic_slice_in_dim(q, start, block)
        if window is None:
            k_b, v_b, k_pos = k, v, jnp.arange(s)
            seen = k_pos[None, :] <= q_pos[:, None]
        else:
            k_b, v_b = (jax.lax.dynamic_slice_in_dim(x, start, block + behind)
                        for x in (k, v))
            k_pos = start - behind + jnp.arange(block + behind)
            seen = ((k_pos[None, :] >= 0) & (k_pos[None, :] <= q_pos[:, None])
                    & (k_pos[None, :] > q_pos[:, None] - window))

        def head(args):
            q_h, k_h, v_h = args                    # [block, G, D], [K, D] x 2
            score = jnp.einsum("qgd,kd->gqk", q_h, k_h) / math.sqrt(d)
            prob = jax.nn.softmax(jnp.where(seen, score, -jnp.inf), -1)
            return jnp.einsum("gqk,kd->qgd", prob, v_h)

        out = jax.lax.map(head, (
            jnp.moveaxis(q_b.reshape(block, hkv, g, d), 1, 0),
            jnp.moveaxis(k_b, 1, 0), jnp.moveaxis(v_b, 1, 0)))  # [8,block,G,D]
        return jnp.moveaxis(out, 0, 1)

    out = jax.lax.map(queries, jnp.arange(0, s, block)).reshape(s, hkv, g, d)
    if gate:
        out = out * jax.nn.sigmoid(jnp.einsum(
            "sc,chg->shg", u, p["gate"].astype(_F32)))[..., None]
    return jnp.einsum("shgd,hgdc->sc", out, p["o"].astype(_F32))


def _w(p):
    return p["kernel"].astype(_F32)


def mlp(p, u, names=("gate", "up", "down")):
    gate, up, down = (_w(p[n]) for n in names)
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def moe(p, bias, u, *, top_k: int, factor: float, held: tuple[int, ...],
        stored: tuple[int, ...] | None = None, shared: bool = True):
    """``u [S, C]`` -> what the experts ``held`` give, and (``shared``) the
    shared expert's term. ``stored``: the experts whose weights ``p``
    stacks, in order (``held`` where None)."""
    stored = held if stored is None else stored
    scores = jax.nn.sigmoid(u @ p["router"].astype(_F32))
    _, sel = jax.lax.top_k(scores + bias.astype(_F32), top_k)   # [S, k]
    chosen = jnp.take_along_axis(scores, sel, axis=-1)
    w = factor * chosen / chosen.sum(-1, keepdims=True)
    out = jnp.zeros_like(u)

    def one(carry, args):
        e, gate, up, down = args
        w_e = jnp.where(sel == e, w, 0.0).sum(-1, keepdims=True)
        y = (jax.nn.silu(u @ gate.astype(_F32)) * (u @ up.astype(_F32))
             ) @ down.astype(_F32)
        return carry + w_e * y, None

    if held:
        stacks = [p[name] for name in ("w_gate", "w_up", "w_down")]
        if tuple(held) != tuple(stored):    # a test's share of what is stored
            at = np.asarray([stored.index(e) for e in held], np.int32)
            stacks = [w[at] for w in stacks]
        out, _ = jax.lax.scan(one, out,
                              (jnp.asarray(held, jnp.int32), *stacks))
    if shared:
        out = out + mlp(p, u, ("shared_gate", "shared_up", "shared_down"))
    return out


def _sizes(config: dict, *, window: bool = True, gate: bool = True,
           window_rope: str = "sliding", cache_dtype=None) -> tuple:
    """What the jitted sub-layers are built from, hashable; the controls
    change it here."""
    dep = config.get("deployment", {})
    rules = {}
    for kind, name in (("full_attention", "full_attention"),
                       ("sliding_attention", "full_attention"
                        if window_rope == "full" else "sliding_attention")):
        rule = config["rope_parameters"][name]
        rules[kind] = (tuple(inv_freq(rule, config["head_dim"]).tolist()),
                       float(rule.get("attention_factor", 1.0)))
    return (("eps", config["rms_norm_eps"]),
            ("window", config["sliding_window"] if window else None),
            ("gate", gate), ("cache_dtype", cache_dtype),
            ("rules", tuple(sorted(rules.items()))),
            ("top_k", config["num_experts_per_tok"]),
            ("factor", float(config["moe_routed_scaling_factor"])),
            ("held", tuple(dep.get("held", range(dep.get(
                "routed_experts_total", config["num_experts"]))))))


@functools.cache
def _programs(sizes: tuple):
    """The jitted sub-layers of one set of sizes: each widens its own
    weights, so one sub-layer's stand in float32 at a time."""
    z = dict(sizes)
    eps, rules = z["eps"], dict(z["rules"])

    def attend(kind):
        window = z["window"] if kind == "sliding_attention" else None

        def f(norm, p, x):
            return x + attention(p, _rms_norm(x, norm["scale"], eps),
                                 rule=rules[kind], window=window,
                                 gate=z["gate"], cache_dtype=z["cache_dtype"])
        return f

    def dense(norm, p, h):
        return h + mlp(p, _rms_norm(h, norm["scale"], eps))

    def sparse(norm, p, bias, h):
        return h + moe(p, bias, _rms_norm(h, norm["scale"], eps),
                       top_k=z["top_k"], factor=z["factor"], held=z["held"])

    def head(norm, kernel, x):
        return _rms_norm(x, norm["scale"], eps) @ kernel.astype(_F32)

    return {name: jax.jit(f) for name, f in (
        ("full_attention", attend("full_attention")),
        ("sliding_attention", attend("sliding_attention")),
        ("dense", dense), ("sparse", sparse), ("head", head))}


def hidden(tree: dict, tokens, config: dict, **controls):
    """``tokens [S]`` -> the stream behind the last layer ``[S, C]`` (call
    under ``jax.default_matmul_precision("highest")``)."""
    run = _programs(_sizes(config, **controls))
    params = tree["params"]
    x = params["tok_emb"]["embedding"][jnp.asarray(tokens)].astype(_F32)
    for i in range(config["num_hidden_layers"]):
        p = params[f"block{i}"]
        x = run[config["layer_types"][i]](p["attn_norm"], p["attn"], x)
        if config["mlp_layer_types"][i] == "dense":
            x = run["dense"](p["mlp_norm"], p["mlp0"], x)
        else:
            x = run["sparse"](p["mlp_norm"], p["moe"],
                              tree["bias"][f"block{i}"], x)
    return x


def forward(tree: dict, tokens, config: dict, **controls):
    """``tokens [B, S]`` -> logits ``[B, S, vocab]`` float32 at ``highest``
    matmul precision (on a TPU a float32 matmul is otherwise bfloat16), a
    sequence at a time."""
    params = tree["params"]
    with jax.default_matmul_precision("highest"):
        head = _programs(_sizes(config))["head"]
        return jnp.stack([
            head(params["norm_f"], params["lm_head"]["kernel"],
                 hidden(tree, row, config, **controls))
            for row in np.asarray(tokens)])


@jax.jit
def _reduce_rows(logits, chosen):
    peak = logits.max(axis=-1)
    lse = peak + jnp.log(jnp.exp(logits - peak[..., None]).sum(axis=-1))
    at = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
    return {"gap_rel": (peak - at) / logits.std(axis=-1),
            "logprob": at - lse, "argmax": logits.argmax(axis=-1),
            "argmax_logprob": peak - lse}


def served_rows(tree: dict, tokens, rows, chosen, config: dict,
                **controls) -> dict:
    """For one served sequence: ``tokens [1, S]`` (prompt and served tokens,
    zero padded: causal, so the padding reaches no row that counts),
    ``rows [1, R]`` the positions whose logits chose a served token and
    ``chosen [1, R]`` those tokens. One full forward, logits at ``rows``
    only, reduced on the device to what ``compare_served`` needs, each
    ``[1, R]``: ``gap_rel`` (how far the chosen token's logit lies under
    the row's largest, in standard deviations of the row), ``logprob``
    (log-softmax at the chosen token), the row's own first choice
    ``argmax`` and its ``argmax_logprob``."""
    params = tree["params"]
    rows, chosen = jnp.asarray(rows), jnp.asarray(chosen)
    with jax.default_matmul_precision("highest"):
        x = hidden(tree, np.asarray(tokens)[0], config, **controls)
        head = _programs(_sizes(config))["head"]
        logits = head(params["norm_f"], params["lm_head"]["kernel"],
                      x[rows[0]])
        out = _reduce_rows(logits[None], chosen)
    return {k: np.asarray(v) for k, v in out.items()}


def compare_served(gap_rel, ref_logprob, counts, system_mean_logprob
                   ) -> tuple[dict, list[str]]:
    """From ``served_rows``' ``[N, R]`` arrays (as
    ``reference/gpt2.py::compare_served``): sequence ``i`` counts in its
    first ``counts[i]`` rows. ``chosen_gap_rel`` is the widest gap of any
    served token; ``chosen_logprob_mean_abs`` the mean, over the sequences,
    of the distance between the system's mean log-probability of a
    sequence's tokens and the reference's."""
    counts = np.asarray(counts)
    valid = np.arange(np.shape(gap_rel)[1])[None, :] < counts[:, None]
    ref_mean = np.where(valid, ref_logprob, 0.0).sum(axis=1) / counts
    dev = {"chosen_gap_rel": float(np.max(np.where(valid, gap_rel, 0.0))),
           "chosen_logprob_mean_abs": float(np.mean(np.abs(
               np.asarray(system_mean_logprob, np.float64) - ref_mean)))}
    bad = [f"laguna vs float32 reference: {k} {v:.3g} > {TOLERANCE[k]}"
           for k, v in dev.items() if not v <= TOLERANCE[k]]
    return dev, bad
