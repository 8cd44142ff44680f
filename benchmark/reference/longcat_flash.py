"""Plain float32 reference of the LongCat-Flash language model (the row
``LongCat-Flash-Omni`` of the catalog; Meituan LongCat team 2025, "LongCat-
Flash Technical Report", and the published ``LongcatFlashDecoderLayer``):
token embedding; ``num_layers`` shortcut-connected double layers; RMSNorm;
an untied head. ``jax.numpy`` only: no flax, no kernels, no cache, no
absorbed form, no row buffer -- attention in the expanded form a head at a
time, the routed layer a loop over the held experts. Nothing of the program
is imported.

The equations (``x [S, C]``, one sequence; ``RMSNorm`` with ``rms_norm_eps``
and a learned scale)::

    h  = x + MLA_0(RMSNorm_a0(x))
    u0 = RMSNorm_f0(h)
    s  = MoE(u0)                      # the shortcut: from the first sub-layer
    h  = h + MLP_0(u0)
    h  = h + MLA_1(RMSNorm_a1(h))
    h  = h + MLP_1(RMSNorm_f1(h))
    x' = h + s                        # ... joined after the second

    MLP(u)  = W_down(silu(W_gate u) * W_up u)

    q        = W_qb(RMSNorm(W_qa u) * sqrt(C / q_lora_rank))
    q_nope, q_pe = split(q) a head;  q_pe = RoPE(q_pe, pos)
    c, k_pe  = split(W_kva u);  k_pe = RoPE(k_pe, pos)      # one for all heads
    c        = RMSNorm(c) * sqrt(C / kv_lora_rank)
    k_nope, v = split(W_kvb c) a head
    score    = (q_nope k_nope + q_pe k_pe) / sqrt(nope + rope), causal softmax
    MLA(u)   = W_o concat_heads(softmax(score) v)

    p      = softmax(W_r u0) over n_routed_experts + zero_expert_num outputs
    sel    = top-k of (p + e_score_correction_bias)
    w_j    = routed_scaling_factor * p[sel_j]             # not normalised
    MoE(u0) = sum_{sel_j real, held} w_j Expert_{sel_j}(u0)
              + (sum_{sel_j zero} w_j) u0
    Expert_e(u) = W_down,e(silu(W_gate,e u) * W_up,e u)

Departures from the published layer: none in the equations. What the row
does not settle is listed under ``assumed`` in
``benchmark/configs/longcat-flash-omni.json`` (no ``norm_topk_prob``, no
router bias term, ``silu``, an untied head, rotate-half RoPE pairing without
scaling, the drawn ``e_score_correction_bias``). The chip's share: the real
experts not in ``held`` are left out, as in the program; the zero experts'
term is whole (``moe`` takes ``held`` and ``zero``, so that a test can add
the shares up to the whole layer).

It takes the program's weights **as they are stored** (bfloat16) and widens
one sub-layer at a time inside that sub-layer's program -- a routed layer
one expert at a time -- so that the 5.2 B parameters never stand in float32
at once (20.7 GB); a sequence at a time (``served_rows``), after the
window, when the engine's pages are freed.

Three controls put a fault in the reference's place
(``sweeps/longcat_serve_precision.py``): ``latent_dtype`` rounds the row a
position leaves in the cache, ``[c | k_pe]`` behind norm, scale and
rotation, to float8 where the configuration states bfloat16; ``scale_q`` /
``scale_kv`` False leave ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` out.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32

#: What decides ``correct`` in ``longcat_serve_decode_replay``: the served
#: tokens and the engine's own mean log-probability of them against one full
#: forward of this reference over prompt and served tokens
#: (``compare_served``), 16 sessions a run. The system computes its products
#: in bfloat16 (2^-9 relative rounding, float32 accumulation) through 4
#: double layers whose stream is rounded to bfloat16 five times a layer,
#: prefills through a padded bucket in the expanded form and then decodes
#: every served token in the absorbed form over the bfloat16 latent cache.
#: The head is untied, so a random-weight model's first choice is a near tie
#: far more often than where the head is the embedding's transpose.
#:
#: Both readings of each limit are of one call on the committed program
#: (``sweeps/longcat_serve_precision.py --control 2 --f8-control 6``, seeds
#: 4500000451-456; my chip run, PR 45, second session; PERF.md section 6),
#: the program's also of the eight runs of the cell from ``git archive`` of
#: the final tree (seeds 4500000501-508).
TOLERANCE = {
    # widest gap of a served token's logit under the reference's largest,
    # in standard deviations of the row: the guard against a wrong token.
    # With an untied head the first two choices of a random-weight model
    # lie nearer than where the head is the embedding's transpose, and the
    # rounding of a bfloat16 step picks the second now and then: the
    # program reads 0.09-0.34 on fourteen seeds (0.07-0.22 over the 35 runs
    # of the first session). The controls that leave a ``mla_scale_*``
    # factor out read 3.27-3.35 and 6.99-7.07: the limit stands 2.4 x over
    # the program's largest and 4 x under the controls' smallest. The float8 latent reads
    # 0.23-0.57, among the program's: it is judged by the next one.
    "chosen_gap_rel": 0.8,
    # the mean, over the 16 sessions, of the distance between the engine's
    # mean log-probability of a session's ~385 served tokens and the
    # reference's. The program reads 0.00070-0.00170 on fourteen seeds; the
    # latent cached in float8, one precision below the configuration's
    # bfloat16, 0.0060, 0.0061, 0.0082, 0.0084, 0.0092, 0.0106 on six of
    # them; a missing ``mla_scale_*`` 0.79 and 3.0. The limit is the two
    # readings' geometric mean: 1.9 x over the program's largest, 1.9 x
    # under the float8 control's smallest, which reads 3.5 x the program's
    # largest. (The *widest* session of the 16, which the other serving
    # cells limit, tells the two apart by 2.1 x only -- the program
    # 0.0021-0.0067, the float8 latent 0.0143-0.0647: an extreme value
    # wanders 3 x over the program's own seeds -- and is not limited here.
    # A fault in one session of 16 shows at a sixteenth in the mean; what
    # breaks one session's tokens shows in ``chosen_gap_rel``, which is over
    # every token.)
    "chosen_logprob_mean_abs": 3.2e-3,
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


def _rope(x, theta: float):
    """``x [S, ..., d]`` at positions 0..S-1, rotate-half pairing."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=_F32) / d)
    ang = jnp.arange(s, dtype=_F32)[:, None] * inv
    ang = ang.reshape(s, *([1] * (x.ndim - 2)), d // 2)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _w(p):
    return p["kernel"].astype(_F32)


def mlp(p, u):
    return (jax.nn.silu(u @ _w(p["gate"])) * (u @ _w(p["up"]))) @ _w(p["down"])


def mla(p, u, *, nope: int, rope: int, eps: float, theta: float,
        scale_q: float, scale_kv: float, latent_dtype=None):
    """``u [S, C]`` -> ``[S, C]``, expanded form, a head at a time."""
    s, c = u.shape
    rank = p["kv_a_norm"]["scale"].shape[0]
    q = (_rms_norm(u @ _w(p["q_a"]), p["q_a_norm"]["scale"], eps) * scale_q)
    q = jnp.einsum("sr,rhd->shd", q, _w(p["q_b"]))            # [S, H, 192]
    kva = u @ _w(p["kv_a"])
    lat = _rms_norm(kva[:, :rank], p["kv_a_norm"]["scale"], eps) * scale_kv
    k_pe = _rope(kva[:, rank:], theta)                        # [S, rope]
    if latent_dtype is not None:   # the control: a narrower cache
        lat = lat.astype(latent_dtype).astype(_F32)
        k_pe = k_pe.astype(latent_dtype).astype(_F32)
    kv = jnp.einsum("sc,chd->shd", lat, p["kv_b"].astype(_F32))  # [S, H, 256]
    q_pe = _rope(q[..., nope:], theta)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(args):
        q_n, q_r, k_n, v = args                               # [S, d] each
        score = (q_n @ k_n.T + q_r @ k_pe.T) / math.sqrt(nope + rope)
        return jax.nn.softmax(jnp.where(causal, score, -jnp.inf), -1) @ v

    out = jax.lax.map(head, (
        jnp.moveaxis(q[..., :nope], 1, 0), jnp.moveaxis(q_pe, 1, 0),
        jnp.moveaxis(kv[..., :nope], 1, 0), jnp.moveaxis(kv[..., nope:], 1, 0)))
    return jnp.einsum("hsd,hdc->sc", out, _w(p["o"]))


def moe(p, bias, u0, *, n_real: int, top_k: int, factor: float,
        held: tuple[int, ...], stored: tuple[int, ...] | None = None,
        zero: bool = True):
    """``u0 [S, C]`` -> what the experts ``held`` give, and (``zero``) the
    zero experts' term. ``stored``: the real experts whose weights ``p``
    stacks, in order (``held`` where None)."""
    stored = held if stored is None else stored
    probs = jax.nn.softmax(u0 @ p["router"].astype(_F32), axis=-1)
    _, sel = jax.lax.top_k(probs + bias.astype(_F32), top_k)   # [S, k]
    w = factor * jnp.take_along_axis(probs, sel, axis=-1)
    out = jnp.zeros_like(u0)

    def one(carry, args):
        e, gate, up, down = args
        w_e = jnp.where(sel == e, w, 0.0).sum(-1, keepdims=True)
        y = (jax.nn.silu(u0 @ gate.astype(_F32)) * (u0 @ up.astype(_F32))
             ) @ down.astype(_F32)
        return carry + w_e * y, None

    if held:
        stacks = [p[name] for name in ("w_gate", "w_up", "w_down")]
        if tuple(held) != tuple(stored):    # a test's share of what is stored
            at = np.asarray([stored.index(e) for e in held], np.int32)
            stacks = [w[at] for w in stacks]
        out, _ = jax.lax.scan(one, out,
                              (jnp.asarray(held, jnp.int32), *stacks))
    if zero:
        out = out + jnp.where(sel >= n_real, w, 0.0).sum(-1, keepdims=True) * u0
    return out


def _sizes(config: dict) -> dict:
    dep = config.get("deployment", {})
    n_real = dep.get("routed_experts_total", config["n_routed_experts"])
    c = config["hidden_size"]
    return {
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "eps": config["rms_norm_eps"], "theta": float(config["rope_theta"]),
        "scale_q": math.sqrt(c / config["q_lora_rank"])
        if config["mla_scale_q_lora"] else 1.0,
        "scale_kv": math.sqrt(c / config["kv_lora_rank"])
        if config["mla_scale_kv_lora"] else 1.0,
        "n_real": n_real, "top_k": config["moe_topk"],
        "factor": float(config["routed_scaling_factor"]),
        "held": tuple(dep.get("held", range(n_real)))}


@functools.cache
def _programs(sizes: tuple, latent_dtype):
    """The jitted sub-layers of one set of sizes: each widens its own
    weights, so one sub-layer's stand in float32 at a time."""
    z = dict(sizes)
    attn = {k: z[k] for k in ("nope", "rope", "eps", "theta", "scale_q",
                              "scale_kv")}
    routed = {k: z[k] for k in ("n_real", "top_k", "factor", "held")}
    eps = z["eps"]

    def attention(norm, p, x):
        return x + mla(p, _rms_norm(x, norm["scale"], eps), **attn,
                       latent_dtype=latent_dtype)

    def first_ffn(norm, dense, experts, bias, h):
        u0 = _rms_norm(h, norm["scale"], eps)
        return h + mlp(dense, u0), moe(experts, bias, u0, **routed)

    def second_ffn(norm, dense, h, shortcut):
        return h + mlp(dense, _rms_norm(h, norm["scale"], eps)) + shortcut

    def head(norm, kernel, x):
        return _rms_norm(x, norm["scale"], eps) @ kernel.astype(_F32)

    return {name: jax.jit(f) for name, f in (
        ("attention", attention), ("first_ffn", first_ffn),
        ("second_ffn", second_ffn), ("head", head))}


def _controlled(config: dict, scale_q: bool, scale_kv: bool) -> tuple:
    sizes = _sizes(config)
    if not scale_q:
        sizes["scale_q"] = 1.0
    if not scale_kv:
        sizes["scale_kv"] = 1.0
    return tuple(sorted(sizes.items()))


def hidden(tree: dict, tokens, config: dict, *, latent_dtype=None,
           scale_q: bool = True, scale_kv: bool = True):
    """``tokens [S]`` -> the stream behind the last double layer ``[S, C]``
    (call under ``jax.default_matmul_precision("highest")``)."""
    run = _programs(_controlled(config, scale_q, scale_kv), latent_dtype)
    params = tree["params"]
    x = params["tok_emb"]["embedding"][jnp.asarray(tokens)].astype(_F32)
    for i in range(config["num_layers"]):
        p = params[f"block{i}"]
        h = run["attention"](p["attn_norm0"], p["mla0"], x)
        h, shortcut = run["first_ffn"](p["ffn_norm0"], p["mlp0"], p["moe"],
                                       tree["bias"][f"block{i}"], h)
        h = run["attention"](p["attn_norm1"], p["mla1"], h)
        x = run["second_ffn"](p["ffn_norm1"], p["mlp1"], h, shortcut)
    return x


def forward(tree: dict, tokens, config: dict, **controls):
    """``tokens [B, S]`` -> logits ``[B, S, vocab]`` float32 at ``highest``
    matmul precision (on a TPU a float32 matmul is otherwise bfloat16), a
    sequence at a time."""
    params = tree["params"]
    with jax.default_matmul_precision("highest"):
        head = _programs(_controlled(config, True, True), None)["head"]
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
        head = _programs(_controlled(config, True, True), None)["head"]
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
    bad = [f"longcat vs float32 reference: {k} {v:.3g} > {TOLERANCE[k]}"
           for k, v in dev.items() if not v <= TOLERANCE[k]]
    return dev, bad
