"""Plain float32 reference of the Xing4.0 decoder as this repository runs
it: hyper-connected residual streams (mHC), RoPE latent attention (MLA),
gated MLPs, a sigmoid router over all experts of which a share is held,
one multi-token-prediction module. ``jax.numpy`` only: no flax, no kernel,
no remat, no sort and no row buffer, nothing imported from the program.
Two loops are ``lax.scan`` s rather than Python's, for the sake of the time
the chip's compiler takes and of nothing else: Sinkhorn's iterations and the
held experts one after another.

It reads the program's parameter tree by its names (``from_program_tree``
only casts), and the published ``config.json`` keys as a dict ``cfg``, plus
``held`` (the experts this share holds), ``local_rows`` (R) and the rule by
which a share's total above R drops rows: held assignments in the order
(expert as listed in ``held``, then token, then choice), the first R kept.

What the published config does not settle is listed, word for word, under
``assumed`` in ``benchmark/configs/xing4.0-29b-a4b.json``; the equations
are in ISSUE 27 and in ``tpu_sandbox/models/xing4.py``'s docstring. Streams
are held as ``[n, B, S, C]`` (a layout, not a change of the mathematics:
``[B, S, n, C]`` would pad the 4 to a tile of 8 on the chip).

Two hooks let the on-chip check fit a 16 GB chip without changing a number:
``wrap`` is applied to every block, to each of its two sub-layers, to each
held expert's turn and to every group of ``head_block`` heads of the attention,
which are computed one after another (the runner passes ``jax.checkpoint``: the reference is then
differentiated block by block); ``matmul_dtype`` rounds the operands
of every matrix product on the way forward (float32 products of rounded
operands, gradients as if unrounded), which is how
the tolerances' second reading -- the reference one precision below the
program's bf16 -- is made.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: The program computes in bf16 (2^-9 relative rounding) with float32
#: accumulation, float32 norms, router, Sinkhorn and mixing coefficients,
#: and a bf16 residual stream rounded twice a block; the reference is
#: float32 throughout. Each entry: what it bounds, and why that value; the
#: two readings each limit lies between are in PERF.md section 4.
TOLERANCE = {
    # RMS of the logit difference over the RMS of the reference's logits,
    # over the tokens whose top-k choice agrees in every expert layer
    "logit_rms_rel": 8e-2,
    # the same over the tokens where a choice flipped: another expert's
    # output is another function, so they are reported apart
    "logit_rms_rel_flipped": 0.1,
    # |loss - reference loss|, nats (a mean over 8192 tokens: the one
    # limit the float8 reference nearly meets)
    "loss_abs": 2.5e-3,
    # RMS-relative deviation of a parameter's gradient; flipped choices move
    # whole rows between experts, so the routed parts are wider; and behind
    # the mixed input stands an RMSNorm that removes its scale, so of
    # ``phi_pre``'s gradient only what tells the streams apart is left, a
    # smaller signal under the same rounding (0.085 to 0.18 over nine seeds
    # where the other matrices read 0.06 to 0.09)
    "grad_rel": 0.2,
    "grad_rel_routed": 0.5,
    "grad_rel_input_mix": 0.35,
    # share of (token, expert layer) pairs whose top-k set differs: the 4th
    # and 5th of 64 sigmoid scores lie ~1e-2 apart and the program's inputs
    # to the router carry bf16's ~1e-2 relative noise
    "route_flips": 0.25,
    # the float32 parts, fed the same input as the reference's: these agree
    # to rounding; a bf16 router, norm or Sinkhorn reads 1e-3 to 1e-2
    "fp32_rel": 1e-5,
}


def from_program_tree(params, batch_stats=None) -> dict:
    """The program's flax trees as one float32 tree under the same names;
    ``batch_stats`` (the routers' ``e_score_correction_bias``) merged in."""
    tree = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    for block, stats in (batch_stats or {}).items():
        tree[block] = dict(tree[block])
        tree[block]["moe"] = dict(tree[block]["moe"],
                                  bias=jnp.asarray(
                                      stats["moe"]["e_score_correction_bias"],
                                      jnp.float32))
    return tree


def _rounder(cfg):
    """Operands rounded to ``matmul_dtype`` on the way forward; the gradient
    passes as if they were not (a float8 cotangent, unscaled, underflows to
    zero and would say nothing about precision)."""
    dtype = cfg.get("matmul_dtype")
    if dtype is None:
        return lambda a: a
    return lambda a: a + jax.lax.stop_gradient(
        a.astype(dtype).astype(jnp.float32) - a)


def _mm(cfg, subscripts, a, b):
    r = _rounder(cfg)
    return jnp.einsum(subscripts, r(a), r(b))


def rms_norm(x, eps, scale=None):
    y = x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y if scale is None else y * scale


def sinkhorn(logits, iters, eps):
    """``logits [n, n, ...]``: exp, then ``iters`` times rows over their
    sums, columns over theirs."""
    def once(m, _):
        m = m / (m.sum(1, keepdims=True) + eps)
        return m / (m.sum(0, keepdims=True) + eps), None

    return jax.lax.scan(once, jnp.exp(logits), None, length=iters)[0]


def yarn(cfg):
    """Blended inverse frequencies ``[rope / 2]``, the cos/sin scale and
    the softmax scale of the published YaRN settings."""
    rs, dim, theta = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    extra = theta ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq = extra / rs["factor"] * ramp + extra * (1 - ramp)

    def mscale(m):
        return 1.0 if rs["factor"] <= 1 else 0.1 * m * math.log(rs["factor"]) + 1.0

    m_all = mscale(rs["mscale_all_dim"])
    qk = cfg["qk_nope_head_dim"] + dim
    return (jnp.asarray(inv_freq, jnp.float32), mscale(rs["mscale"]) / m_all,
            qk ** -0.5 * m_all * m_all)


def rope(x, inv_freq, cs):
    """``x [B, S, H, d]``, dimension i paired with i + d/2."""
    s, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None] * cs, jnp.sin(ang)[None, :, None] * cs
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def mhc_coefficients(p, streams, cfg):
    """H_pre [n,B,S], H_post [n,B,S], H_res [n,n,B,S] of one sub-layer."""
    n = cfg["hc_mult"]
    inv = 1.0 / jnp.sqrt(jnp.mean(jnp.square(streams), (0, 3)) + cfg["hc_eps"])

    def proj(phi):  # x~ Phi, the coefficient index leading
        return jnp.einsum("nbsc,nck->kbs", streams, phi) * inv

    h_pre = jax.nn.sigmoid(p["alpha_pre"] * proj(p["phi_pre"])
                           + p["b_pre"][:, None, None])
    h_post = 2 * jax.nn.sigmoid(p["alpha_post"] * proj(p["phi_post"])
                                + p["b_post"][:, None, None])
    raw = (p["alpha_res"] * proj(p["phi_res"]).reshape(n, n, *inv.shape)
           + p["b_res"][:, :, None, None])
    h_res = sinkhorn(jnp.clip(raw, cfg["mhc_h_res_clamp_min"],
                              cfg["mhc_h_res_clamp_max"]),
                     cfg["hc_sinkhorn_iters"], cfg["hc_eps"])
    return h_pre, h_post, h_res


def hyper_connected(p_mhc, norm_scale, streams, cfg, fn):
    """``X' = H_res X + H_post fn(RMSNorm(H_pre X))``; ``fn`` may return
    ``(y, extra)``."""
    h_pre, h_post, h_res = mhc_coefficients(p_mhc, streams, cfg)
    u = jnp.einsum("nbs,nbsc->bsc", h_pre, streams)
    y = fn(rms_norm(u, cfg["rms_norm_eps"], norm_scale))
    y, extra = y if isinstance(y, tuple) else (y, None)
    new = (jnp.einsum("ijbs,jbsc->ibsc", h_res, streams)
           + h_post[..., None] * y[None])
    return new, extra


def latent_attention(p, x, cfg, wrap, head_block):
    eps, nope, rank = cfg["rms_norm_eps"], cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    h = cfg["num_attention_heads"]
    inv_freq, cs, scale = yarn(cfg)
    c_q = rms_norm(_mm(cfg, "bsc,cr->bsr", x, p["q_a"]["kernel"]), eps,
                   p["q_a_norm"]["scale"])
    q = _mm(cfg, "bsr,rhd->bshd", c_q, p["q_b"]["kernel"])
    kva = _mm(cfg, "bsc,cr->bsr", x, p["kv_a"]["kernel"])
    c_kv = rms_norm(kva[..., :rank], eps, p["kv_a_norm"]["scale"])
    kv = _mm(cfg, "bsr,rhd->bshd", c_kv, p["kv_b"]["kernel"])
    q_pe = rope(q[..., nope:], inv_freq, cs)
    k_pe = rope(kva[:, :, None, rank:], inv_freq, cs)
    s = x.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def heads(q_nope, q_pe, k_nope, k_pe, v):
        scores = (_mm(cfg, "bqhd,bkhd->bhqk", q_nope, k_nope)
                  + _mm(cfg, "bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0])) * scale
        w = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), -1)
        return _mm(cfg, "bhqk,bkhd->bqhd", w, v)

    block = head_block or h
    groups = h // block

    def grouped(a):  # [B,S,H,d] -> [groups, B, S, block, d]
        return jnp.moveaxis(a.reshape(*a.shape[:2], groups, block, -1), 2, 0)

    # one group of heads after another (``lax.map``), so that with ``wrap``
    # only one group's S x S scores are alive at a time
    out = jax.lax.map(
        lambda g: wrap(heads)(g[0], g[1], g[2], k_pe, g[3]),
        (grouped(q[..., :nope]), grouped(q_pe), grouped(kv[..., :nope]),
         grouped(kv[..., nope:])))
    out = jnp.moveaxis(out, 0, 2).reshape(*x.shape[:2], h, -1)
    return _mm(cfg, "bshd,hdc->bsc", out, p["o"]["kernel"])


def gated_mlp(cfg, x, gate, up, down):
    return _mm(cfg, "...f,fc->...c", jax.nn.silu(_mm(cfg, "...c,cf->...f", x, gate))
               * _mm(cfg, "...c,cf->...f", x, up), down)


def route(p, x, cfg):
    """scores [T,E] (float32, never rounded), chosen experts [T,k], weights
    [T,k]."""
    scores = jax.nn.sigmoid(x @ p["router"])
    bias = p.get("bias", jnp.zeros(scores.shape[-1]))
    _, sel = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    s_sel = jnp.take_along_axis(scores, sel, -1)
    w = s_sel / (s_sel.sum(-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    return scores, sel, w


def kept_assignments(sel, held, local_rows):
    """[T,k] bool: assignments to a held expert that the share keeps. Held
    assignments are ranked by (expert as listed, token, choice); ranks below
    ``local_rows`` are kept, the tail is dropped. No sort: a rank is the
    count of held assignments before it."""
    flat = sel.reshape(-1)
    kept = jnp.zeros(flat.shape, bool)
    before = 0
    for e in held:
        mine = flat == e
        rank = before + jnp.cumsum(mine) - 1
        kept = kept | (mine & (rank < local_rows))
        before = before + mine.sum()
    return kept.reshape(sel.shape)


def expert_share(p, x, cfg, with_shared=True, wrap=lambda f: f):
    """What the experts in ``cfg['held']`` give for ``x [B,S,C]``, plus the
    shared expert: a loop over the held experts with a mask each."""
    lead, c = x.shape[:-1], x.shape[-1]
    x = x.reshape(-1, c)
    held = list(cfg["held"])
    _, sel, w = route(p, x, cfg)
    kept = kept_assignments(sel, held, cfg["local_rows"])

    def add_expert(y, held_expert):  # one held expert after another
        e, gate, up, down = held_expert
        weight = jnp.where((sel == e) & kept, w, 0.0).sum(-1)       # [T]
        return y + weight[:, None] * gated_mlp(cfg, x, gate, up, down), None

    y, _ = jax.lax.scan(wrap(add_expert), jnp.zeros_like(x), (
        jnp.asarray(held), p["w_gate"], p["w_up"], p["w_down"]))
    if with_shared and cfg.get("n_shared_experts"):
        y = y + gated_mlp(cfg, x, p["shared_gate"]["kernel"],
                          p["shared_up"]["kernel"], p["shared_down"]["kernel"])
    return y.reshape(*lead, c), sel.reshape(*lead, -1)


def block(p, streams, cfg, dense, wrap, head_block):
    """One decoder block -> (streams, chosen experts or None)."""
    def attn_sublayer(p, streams):
        return hyper_connected(
            p["mhc_attn"], p["attn_norm"]["scale"], streams, cfg,
            lambda x: latent_attention(p["mla"], x, cfg, wrap, head_block))[0]

    def ffn_sublayer(p, streams):
        if dense:
            fn = lambda x: gated_mlp(  # noqa: E731
                cfg, x, p["mlp"]["gate"]["kernel"], p["mlp"]["up"]["kernel"],
                p["mlp"]["down"]["kernel"])
        else:
            fn = lambda x: expert_share(p["moe"], x, cfg, wrap=wrap)  # noqa: E731
        return hyper_connected(p["mhc_ffn"], p["ffn_norm"]["scale"], streams,
                               cfg, fn)

    def both(p, streams):
        return wrap(ffn_sublayer)(p, wrap(attn_sublayer)(p, streams))

    return wrap(both)(p, streams)


def forward(p, tokens, cfg, *, wrap=lambda f: f, head_block=None):
    """tokens [B,S] -> (logits [B,S,V] float32, MTP logits or None, the
    chosen experts of every expert layer, main layers first)."""
    n, eps = cfg["hc_mult"], cfg["rms_norm_eps"]

    def enter(x):
        return jnp.broadcast_to(x[None], (n, *x.shape))

    def head(h):
        return _mm(cfg, "bsc,cv->bsv", rms_norm(h, eps, p["norm_f"]["scale"]),
                   p["lm_head"]["kernel"])

    emb = p["tok_emb"]["embedding"][tokens]
    streams, chosen = enter(emb), []
    for i in range(cfg["num_hidden_layers"]):
        dense = i < cfg["first_k_dense_replace"]
        streams, sel = block(p[f"block{i}"], streams, cfg, dense, wrap, head_block)
        if not dense:
            chosen.append(sel)
    h = streams.sum(0)
    logits, mtp = wrap(head)(h), None
    if cfg.get("num_nextn_predict_layers"):
        merged = _mm(cfg, "bsc,cd->bsd", jnp.concatenate([
            rms_norm(jnp.roll(emb, -1, 1), eps, p["mtp_norm_emb"]["scale"]),
            rms_norm(h, eps, p["mtp_norm_h"]["scale"])], -1),
            p["mtp_proj"]["kernel"])
        out, sel = block(p["mtp_block"], enter(merged), cfg, False, wrap,
                         head_block)
        chosen.append(sel)
        mtp = wrap(head)(out.sum(0))
    return logits, mtp, chosen


def cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, targets[..., None], -1).mean()


def loss_fn(p, tokens, targets, cfg, *, mtp_loss_weight=0.3, **hooks):
    """(loss, (logits, chosen)): next-token cross entropy, plus
    ``mtp_loss_weight`` times the MTP module's, whose logits at position i
    are held to the target of position i + 1."""
    logits, mtp, chosen = forward(p, tokens, cfg, **hooks)
    loss = cross_entropy(logits, targets)
    if mtp is not None:
        loss = loss + mtp_loss_weight * cross_entropy(mtp[:, :-1], targets[:, 1:])
    return loss, (logits, chosen)


def grad_program(cfg, **hooks):
    """The jitted ``(picked, rest, tokens, targets) -> (loss, logits, chosen,
    grads of picked)``: ``picked`` and ``rest`` are the parameter tree's
    leaves by '/'-joined path, in two dicts."""
    def run(picked, rest, tokens, targets):
        def objective(picked):
            return loss_fn(unflatten({**rest, **picked}), tokens, targets,
                           cfg, **hooks)

        (loss, (logits, chosen)), grads = jax.value_and_grad(
            objective, has_aux=True)(picked)
        return loss, logits, chosen, grads

    return jax.jit(run)


def loss_and_grads(p, tokens, targets, cfg, wanted=None, **hooks):
    """Loss, logits, chosen experts and the gradients of the leaves whose
    '/'-joined path is in ``wanted`` (all if None), at ``highest`` matmul
    precision (on a TPU a float32 product is otherwise bf16)."""
    flat = flat_paths(p)
    wanted = list(flat) if wanted is None else list(wanted)
    with jax.default_matmul_precision("highest"):
        return grad_program(cfg, **hooks)(
            {k: flat[k] for k in wanted},
            {k: v for k, v in flat.items() if k not in wanted},
            jnp.asarray(tokens), jnp.asarray(targets))


def flat_paths(tree) -> dict:
    """A tree of dicts (and tuples) as one dict, keys joined by '/'."""
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path):
            leaf for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def unflatten(flat: dict) -> dict:
    """The inverse of ``flat_paths`` for a tree of dicts."""
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for key in parents:
            node = node.setdefault(key, {})
        node[last] = leaf
    return tree


def rms_rel(system, reference) -> float:
    system = np.asarray(system, np.float64)
    reference = np.asarray(reference, np.float64)
    return float(np.sqrt(np.mean(np.square(system - reference)))
                 / (np.sqrt(np.mean(np.square(reference))) or 1.0))


def route_flips(system_chosen, ref_chosen):
    """(share of (token, layer) pairs whose top-k sets differ, [B,S] bool of
    the tokens with a flip in any layer)."""
    flipped = [np.sort(np.asarray(a), -1) != np.sort(np.asarray(b), -1)
               for a, b in zip(system_chosen, ref_chosen, strict=True)]
    per_layer = np.stack([f.any(-1) for f in flipped])
    return float(per_layer.mean()), per_layer.any(0)


#: gradients whose rows move between experts when a choice flips
ROUTED = ("/moe/router", "/moe/w_gate", "/moe/w_up", "/moe/w_down")
def off_start(tree: dict, seed: int) -> dict:
    """``{path: array}`` for the small mHC parameters of every sub-layer,
    moved off the values a run starts from by seeded draws: the point at
    which the on-chip check compares gradients. At the start itself every
    stream is the embedding and ``H_res`` is the identity to e^-8, so the
    gradients of ``Phi_pre``, ``Phi_res`` and ``alpha_res`` are differences
    of nearly equal sums that rounding decides (bf16 against float32 read
    0.2 to 5.9 there), and no limit on them could tell a wrong Sinkhorn
    backward from a right one. Here ``H_post`` differs by stream (so the
    streams do from the first sub-layer on), ``H_res`` is a generic doubly
    stochastic mix with the diagonal ahead, and the alphas are 0.1, so that
    the coefficients follow the token. The program and the reference are
    both given this point; the timed run is not."""
    rng = np.random.default_rng(seed)
    out = {}
    for path, leaf in flat_paths(tree).items():
        *_, module, name = ("", "") + tuple(path.split("/"))
        if not module.startswith("mhc_"):
            continue
        shape = np.shape(leaf)
        if name in ("b_pre", "b_post"):
            out[path] = np.asarray(leaf, np.float32) + 0.5 * rng.standard_normal(
                shape).astype(np.float32)
        elif name == "b_res":
            out[path] = (2.0 * np.eye(shape[0]) + rng.standard_normal(shape)
                         ).astype(np.float32)
        elif name.startswith("alpha_"):
            out[path] = np.full(shape, 0.1, np.float32)
    return out


def compare(system: dict, ref: dict) -> tuple[dict, list[str]]:
    """``system`` / ``ref``: ``logits``, ``loss``, ``chosen`` (a list), ``grads``
    (path -> array), and optionally ``fp32`` (name -> array: a float32 part
    fed the same input). Returns the deviations and the limits broken."""
    share, flipped = route_flips(system["chosen"], ref["chosen"])
    sys_logits = np.asarray(system["logits"], np.float32)
    ref_logits = np.asarray(ref["logits"], np.float32)
    dev = {"route_flips": share,
           "loss_abs": abs(float(system["loss"]) - float(ref["loss"]))}
    if not flipped.all():
        dev["logit_rms_rel"] = rms_rel(sys_logits[~flipped], ref_logits[~flipped])
    if flipped.any():
        dev["logit_rms_rel_flipped"] = rms_rel(sys_logits[flipped],
                                               ref_logits[flipped])
    limit = dict(TOLERANCE)
    for path, grad in ref.get("grads", {}).items():
        key = f"grad_rel:{path}"
        dev[key] = rms_rel(system["grads"][path], grad)
        limit[key] = TOLERANCE[
            "grad_rel_routed" if any(r in path for r in ROUTED)
            else "grad_rel_input_mix" if path.endswith("/phi_pre")
            else "grad_rel"]
    for name, value in ref.get("fp32", {}).items():
        dev[f"fp32_rel:{name}"] = rms_rel(system["fp32"][name], value)
        limit[f"fp32_rel:{name}"] = TOLERANCE["fp32_rel"]
    bad = [f"xing4 vs float32 reference: {k} {v:.3g} > {limit[k]}"
           for k, v in dev.items() if not v <= limit[k]]
    return dev, bad
