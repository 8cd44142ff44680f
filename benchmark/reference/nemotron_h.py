"""Plain float32 reference of the Nemotron-H hybrid decoder as this
repository runs it: blocks of one mixer each (Mamba-2 state-space, grouped-
query attention, routed experts in a latent of which a share is held), one
multi-token-prediction module. ``jax.numpy`` only: no flax, no kernel, no
remat, no sort, no row buffer and **no chunked scan** -- the state-space
layer is the recurrence itself, token by token (``lax.scan`` over the
sequence). Nothing is imported from the program; the helpers that know no
model (reading the program's tree, rounded products, the sigmoid router,
the share's drop rule, the loss, paths, RMS deviation, flipped choices) are
``reference/xing4.py``'s.

It reads the program's parameter tree by its names (``from_program_tree``
only casts), and the published ``config.json`` keys as a dict ``cfg``, plus
``held`` (the experts this share holds), ``local_rows`` (R) and the rule by
which a share's total above R drops rows (``kept_assignments``). It is given
the same share as the program: the heads, groups, experts and vocabulary
rows the configuration file counts.

What the published config does not settle is listed, word for word, under
``assumed`` in ``benchmark/configs/nemotron-3-super-120b-a12b.json``; the
equations are in ISSUE 31 and in ``tpu_sandbox/models/nemotron_h.py``'s
docstring.

Hooks that let the on-chip check fit a 16 GB chip without changing a
number: ``wrap`` is applied to every block, to each held expert's turn, to
every group of ``head_block`` query heads of the attention (computed one
after another) and to every ``scan_segment`` tokens of the recurrence (the
runner passes ``jax.checkpoint``: the reference is then differentiated
block by block, and the recurrence keeps its state once a segment, not once
a token); ``matmul_dtype`` rounds the operands of every product on the way
forward (float32 products of rounded operands, gradients as if unrounded),
which is how the tolerances' second reading -- the reference one precision
below the program's bf16 -- is made. In the recurrence the operands are
what the program's chunked form multiplies: ``dt x``, ``B``, ``C`` and the
state where ``C`` reads it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.xing4 import (  # noqa: F401  (the runner's API)
    _mm, _rounder, cross_entropy, flat_paths, from_program_tree,
    kept_assignments, rms_norm, rms_rel, route, route_flips, unflatten)

#: The program computes in bf16 (2^-9 relative rounding) with float32
#: accumulation, float32 norms, router, time steps and decays, and a bf16
#: residual rounded once a block; the reference is float32 throughout. Each
#: entry: what it bounds, and why that value; the two readings each limit
#: lies between are in PERF.md section 4.
TOLERANCE = {
    # RMS of the logit difference over the RMS of the reference's logits,
    # over the tokens whose top-k choice agrees in every expert layer
    "logit_rms_rel": 4e-2,
    # the same over the tokens where a choice flipped (with 22 choices of
    # 512 in five layers that is most tokens): other experts' output is
    # another function, so they are reported apart; only 8 of 512 experts
    # are here, so a flip seldom moves a logit and the two read alike
    "logit_rms_rel_flipped": 4e-2,
    # |loss - reference loss|, nats: a mean over 8192 tokens that precision
    # hardly moves (float8 reads 1.2e-4, bf16 up to 1.9e-4): the limit of
    # the other LM cells, not one between the two readings
    "loss_abs": 2.5e-3,
    # RMS-relative deviation of a parameter's gradient: the matrices
    "grad_rel": 0.05,
    # the Mamba-2 scalars a head (A_log, dt_bias, D: 64 numbers each, so
    # their deviation follows the seed more than a matrix's does)
    "grad_rel_heads": 0.06,
    # what only the routed experts feed (the latent's projections, the held
    # experts' products): a flipped choice moves whole rows between experts
    "grad_rel_routed": 0.2,
    # the router itself: every flipped choice is a changed gradient row
    "grad_rel_router": 0.27,
    # share of (token, expert layer) pairs whose top-k set differs: the 22nd
    # and 23rd of 512 sigmoid scores lie ~0.02 apart in the logit and the
    # program's inputs to the router carry bf16's rounding
    "route_flips": 0.33,
    # the float32 parts, fed the same input as the reference's: these agree
    # to rounding (the decays, a sum of 128 terms in an exponent, to 2e-6);
    # computed in bf16 they read 1e-3 to 1e-2
    "fp32_rel": 1e-5,
}
#: which limit holds a gradient, by the end of its path; the rest: grad_rel
GRADIENT_LIMITS = (
    ("grad_rel_router", ("/moe/router",)),
    ("grad_rel_routed", ("/moe/w_up", "/moe/w_down", "/moe/latent_down/kernel",
                         "/moe/latent_up/kernel")),
    ("grad_rel_heads", ("/mamba/A_log", "/mamba/dt_bias", "/mamba/D")))


def relu2(x):
    return jnp.square(jax.nn.relu(x))


# --- Mamba-2 ---

def time_step(dt, dt_bias):
    return jax.nn.softplus(dt + dt_bias)


def causal_conv(x, kernel, bias):
    """``y_t = bias + sum_i kernel[i] x_{t - (K - 1) + i}`` per channel,
    ``x [B, S, C]``, zeros before the sequence."""
    k = kernel.shape[0]
    y = jnp.zeros_like(x) + bias
    for i in range(k):
        shift = k - 1 - i
        y = y + kernel[i] * jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[
            :, :x.shape[1]]
    return y


def ssm_recurrence(x, dt, a, b, c, cfg=None, *, wrap=lambda f: f,
                   segment=None):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t``,
    ``h_0 = 0``, one token after another: ``x [B, S, H, P]``, ``dt
    [B, S, H]``, ``a [H]``, ``b``, ``c`` ``[B, S, G, N]`` -> ``y
    [B, S, H, P]``. Head h reads group ``h G // H``."""
    r = _rounder(cfg or {})
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    rep = h // g
    segment = segment or s

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs        # [B,H,P] [B,H] [B,G,N] [B,G,N]
        b_t, c_t = jnp.repeat(b_t, rep, 1), jnp.repeat(c_t, rep, 1)
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + r(dt_t[..., None] * x_t)[..., None] * r(b_t)[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", r(state), r(c_t))

    def run_segment(state, inputs):
        return jax.lax.scan(step, state, inputs)

    def by_time(v):  # [B, S, ...] -> [segments, tokens a segment, B, ...]
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(s // segment, segment, *v.shape[1:])

    _, y = jax.lax.scan(wrap(run_segment), jnp.zeros((bsz, h, p, n), x.dtype),
                        tuple(by_time(v) for v in (x, dt, b, c)))
    return jnp.moveaxis(y.reshape(s, bsz, h, p), 0, 1)


def mamba_mixer(p, u, cfg, wrap=lambda f: f, segment=None):
    h, hp, g, n = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                   cfg["n_groups"], cfg["ssm_state_size"])
    bsz, s, _ = u.shape
    d_in = h * hp
    zxbcdt = _mm(cfg, "bsc,cf->bsf", u, p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * g * n], -1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_kernel"], p["conv_bias"]))
    x, b, c = jnp.split(xbc, [d_in, d_in + g * n], -1)
    x = x.reshape(bsz, s, h, hp)
    y = ssm_recurrence(
        x, time_step(dt, p["dt_bias"]), -jnp.exp(p["A_log"]),
        b.reshape(bsz, s, g, n), c.reshape(bsz, s, g, n), cfg, wrap=wrap,
        segment=segment)
    y = (y + p["D"][:, None] * x).reshape(bsz, s, d_in)
    gated = (y * jax.nn.silu(z)).reshape(bsz, s, g, d_in // g)
    y = rms_norm(gated, cfg["layer_norm_epsilon"]).reshape(bsz, s, d_in)
    return _mm(cfg, "bsf,fc->bsc", y * p["norm_scale"], p["out_proj"]["kernel"])


# --- attention ---

def attention(p, u, cfg, wrap=lambda f: f, head_block=None):
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    s = u.shape[1]
    q = _mm(cfg, "bsc,chd->bshd", u, p["q"]["kernel"])
    kv = _mm(cfg, "bsc,cjhd->bsjhd", u, p["kv"]["kernel"])
    k, v = kv[:, :, 0], kv[:, :, 1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    block = head_block or hq // hkv
    if (hq // hkv) % block:
        raise ValueError(f"head_block {block} does not divide the "
                         f"{hq // hkv} query heads of a key/value head")

    def heads(q, k, v):     # q [B,S,block,D] on one key/value head [B,S,D]
        scores = _mm(cfg, "bqhd,bkd->bhqk", q, k) * d ** -0.5
        w = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), -1)
        return _mm(cfg, "bhqk,bkd->bqhd", w, v)

    # one group of query heads after another (``lax.map``), so that with
    # ``wrap`` only one group's S x S scores are alive at a time; query head
    # i reads key/value head i Hkv // Hq
    groups = hq // block
    kv_of = jnp.arange(groups) * block // (hq // hkv)
    qg = jnp.moveaxis(q.reshape(*q.shape[:2], groups, block, d), 2, 0)
    out = jax.lax.map(
        lambda a: wrap(heads)(a[0], k[:, :, a[1]], v[:, :, a[1]]), (qg, kv_of))
    out = jnp.moveaxis(out, 0, 2).reshape(*u.shape[:2], hq, d)
    return _mm(cfg, "bshd,hdc->bsc", out, p["o"]["kernel"])


# --- experts in a latent ---

def latent_moe(p, u, cfg, wrap=lambda f: f, with_shared=True,
               with_latent=True):
    """What the experts in ``cfg['held']`` give for ``u [B,S,C]`` through
    the latent, plus the shared expert: a loop over the held experts with a
    mask each. ``with_latent`` False returns the held experts' sum in the
    latent, before ``latent_up`` (the share test adds shares there)."""
    lead, c = u.shape[:-1], u.shape[-1]
    u = u.reshape(-1, c)
    held = list(cfg["held"])
    _, sel, w = route(p, u, cfg)
    kept = kept_assignments(sel, held, cfg["local_rows"])
    latent = _mm(cfg, "tc,cl->tl", u, p["latent_down"]["kernel"])

    def add_expert(y, held_expert):  # one held expert after another
        e, up, down = held_expert
        weight = jnp.where((sel == e) & kept, w, 0.0).sum(-1)       # [T]
        out = _mm(cfg, "tf,fl->tl", relu2(_mm(cfg, "tl,lf->tf", latent, up)),
                  down)
        return y + weight[:, None] * out, None

    y, _ = jax.lax.scan(wrap(add_expert), jnp.zeros_like(latent), (
        jnp.asarray(held), p["w_up"], p["w_down"]))
    if not with_latent:
        return y, sel.reshape(*lead, -1)
    y = _mm(cfg, "tl,lc->tc", y, p["latent_up"]["kernel"])
    if with_shared:
        y = y + _mm(cfg, "tf,fc->tc", relu2(
            _mm(cfg, "tc,cf->tf", u, p["shared_up"]["kernel"])),
            p["shared_down"]["kernel"])
    return y.reshape(*lead, c), sel.reshape(*lead, -1)


# --- the model ---

def block(p, x, kind, cfg, wrap, head_block, segment):
    """``x + Mixer(RMSNorm(x))`` -> (x, chosen experts or None)."""
    def run(p, x):
        u = rms_norm(x, cfg["layer_norm_epsilon"], p["norm"]["scale"])
        if kind == "M":
            return x + mamba_mixer(p["mamba"], u, cfg, wrap, segment), None
        if kind == "*":
            return x + attention(p["attn"], u, cfg, wrap, head_block), None
        y, sel = latent_moe(p["moe"], u, cfg, wrap)
        return x + y, sel

    return wrap(run)(p, x)


def forward(p, tokens, cfg, *, wrap=lambda f: f, head_block=None,
            scan_segment=None):
    """tokens [B,S] -> (logits [B,S,V] float32, MTP logits or None, the
    chosen experts of every expert layer, main layers first)."""
    eps = cfg["layer_norm_epsilon"]

    def head(h):
        return _mm(cfg, "bsc,cv->bsv", rms_norm(h, eps, p["norm_f"]["scale"]),
                   p["lm_head"]["kernel"])

    emb = p["tok_emb"]["embedding"][tokens]
    h, chosen = emb, []
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        h, sel = block(p[f"block{i}"], h, kind, cfg, wrap, head_block,
                       scan_segment)
        if sel is not None:
            chosen.append(sel)
    logits, mtp = wrap(head)(h), None
    if cfg.get("num_nextn_predict_layers"):
        out = _mm(cfg, "bsc,cd->bsd", jnp.concatenate([
            rms_norm(jnp.roll(emb, -1, 1), eps, p["mtp_norm_emb"]["scale"]),
            rms_norm(h, eps, p["mtp_norm_h"]["scale"])], -1),
            p["mtp_proj"]["kernel"])
        for j, kind in enumerate(cfg["mtp_hybrid_override_pattern"]):
            out, sel = block(p[f"mtp_block{j}"], out, kind, cfg, wrap,
                             head_block, scan_segment)
            if sel is not None:
                chosen.append(sel)
        mtp = wrap(head)(out)
    return logits, mtp, chosen


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
        limit[key] = TOLERANCE[next(
            (name for name, ends in GRADIENT_LIMITS if path.endswith(ends)),
            "grad_rel")]
    for name, value in ref.get("fp32", {}).items():
        dev[f"fp32_rel:{name}"] = rms_rel(system["fp32"][name], value)
        limit[f"fp32_rel:{name}"] = TOLERANCE["fp32_rel"]
    bad = [f"nemotron_h vs float32 reference: {k} {v:.3g} > {limit[k]}"
           for k, v in dev.items() if not v <= limit[k]]
    return dev, bad
