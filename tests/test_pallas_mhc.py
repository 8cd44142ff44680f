"""ops/pallas_mhc.py on the CPU (interpret mode): each kernel against the
``jnp`` formulation it replaces, forward and through ``jax.grad``; the
three-piece split against ``HIGHEST``; the tile rule and its fallback; and
the counter that says which of them a traced call site was built with."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sandbox.obs import get_registry
from tpu_sandbox.ops import pallas_mhc as mhc

N = 4
K = N * N + 2 * N
EPS = 1e-6


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2))
                 / (np.sqrt(np.mean(b ** 2)) + 1e-30))


def choices():
    """kernel -> {labels: count} of ``mhc.kernel_choice`` so far."""
    out = {}
    for key, count in get_registry().snapshot()["counters"].items():
        if key.startswith("mhc.kernel_choice"):
            labels = dict(kv.split("=") for kv in key[key.index("{") + 1:-1]
                          .split(","))
            out.setdefault(labels.pop("kernel"), {})[
                tuple(sorted(labels.items()))] = count
    return out


def new_choices(before):
    """What was counted since ``before``: kernel -> [(labels, count)]."""
    new = {}
    for kernel, series in choices().items():
        for labels, count in series.items():
            delta = count - before.get(kernel, {}).get(labels, 0)
            if delta:
                new.setdefault(kernel, []).append((dict(labels), delta))
    return new


def operands(c, tokens, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 8)
    lead = (2, tokens // 2)
    # streams of unequal size, so that the norm and the mixes tell them apart
    x = (jax.random.normal(ks[0], (N, *lead, c))
         * (1.0 + jnp.arange(N))[:, None, None, None]).astype(dtype)
    phi = jax.random.normal(ks[1], (N, c, K)) / np.sqrt(N * c)
    alpha = jnp.float32(0.3)
    b = jax.random.normal(ks[2], (N,))
    y = jax.random.normal(ks[3], (*lead, c)).astype(dtype)
    h_res = jax.random.uniform(ks[4], (N, N, *lead))
    h_post = jax.random.uniform(ks[5], (N, *lead))
    weights = {"u": jax.random.normal(ks[6], (*lead, c)),
               "proj": jax.random.normal(ks[7], (K, *lead)),
               "x": jax.random.normal(ks[6], x.shape)}
    return x, phi, alpha, b, y, h_res, h_post, weights


@pytest.fixture
def small_tiles(monkeypatch):
    """Caps under which 384 tokens take a tile of 192 (256 does not divide
    them) and a width of 256 two tiles of 128: the reductions then run
    across grid steps on both axes."""
    monkeypatch.setattr(mhc, "_TOKEN_CAP", 256)
    monkeypatch.setattr(mhc, "_WIDTH_CAP", 128)


CASES = [pytest.param(c, t, d, id=f"c{c}-t{t}-{jnp.dtype(d).name}")
         for c in (128, 256) for t in (256, 384)
         for d in (jnp.bfloat16, jnp.float32)]


def limits(dtype):
    """(a result stored in the streams' dtype, a float32 result)."""
    return (5e-3, 1e-4) if dtype == jnp.bfloat16 else (1e-5, 1e-4)


@pytest.mark.parametrize("c,tokens,dtype", CASES)
def test_pre_kernels_match_the_jnp_formulation(c, tokens, dtype, small_tiles):
    x, phi, alpha, b, _, _, _, w = operands(c, tokens, dtype)

    def loss(fn, x, phi, alpha, b):
        u, proj, thru = fn(x, phi, alpha, b, eps=EPS, dtype=dtype)
        total = ((u.astype(jnp.float32) * w["u"]).sum()
                 + (proj * w["proj"]).sum()
                 + (thru.astype(jnp.float32) * w["x"]).sum())
        return total, (u, proj)

    def run(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: loss(fn, *a), argnums=(0, 1, 2, 3), has_aux=True))(
                x, phi, alpha, b)

    before = choices()
    (_, (u, proj)), grads = run(mhc.pre)
    new = new_choices(before)
    assert set(new) == {"pre_fwd", "pre_bwd"}, new
    tile = 192 if tokens == 384 else 256
    assert all(labels["tile_tokens"] == str(tile)
               for series in new.values() for labels, _ in series), new
    (_, (ref_u, ref_proj)), ref_grads = run(mhc.pre_jnp)
    stored, f32 = limits(dtype)
    assert rel(u, ref_u) < stored and rel(proj, ref_proj) < f32
    for name, got, want, limit in zip(
            ("dx", "dphi", "dalpha_pre", "db_pre"), grads, ref_grads,
            (stored, f32, f32, f32)):
        assert rel(got, want) < limit, name


@pytest.mark.parametrize("c,tokens,dtype", CASES)
def test_post_kernels_match_the_jnp_formulation(c, tokens, dtype, small_tiles):
    x, _, _, _, y, h_res, h_post, w = operands(c, tokens, dtype, seed=1)

    def run(fn):
        def loss(x, y, h_res, h_post):
            out = fn(x, y, h_res, h_post)
            return (out.astype(jnp.float32) * w["x"]).sum(), out

        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True))(x, y, h_res, h_post)

    before = choices()
    (_, out), grads = run(mhc.post)
    new = new_choices(before)
    assert set(new) == {"post_fwd", "post_bwd"}, new
    assert all(labels["tile_c"] == "128"
               for series in new.values() for labels, _ in series)
    (_, ref_out), ref_grads = run(mhc.post_jnp)
    stored, f32 = limits(dtype)
    # the same products in the same order: the new streams agree to the bit
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref_out, np.float32))
    for name, got, want, limit in zip(
            ("dx", "dy", "dh_res", "dh_post"), grads, ref_grads,
            (stored, stored, f32, f32)):
        assert rel(got, want) < limit, name


def test_three_bf16_pieces_are_the_float32_they_came_from():
    a = jax.random.normal(jax.random.key(3), (257, 24)) * jnp.exp(
        4 * jax.random.normal(jax.random.key(4), (257, 24)))
    pieces = mhc._split3(a)
    assert all(p.dtype == jnp.bfloat16 for p in pieces)
    total = sum(np.asarray(p, np.float64) for p in pieces)
    np.testing.assert_array_equal(total.astype(np.float32), np.asarray(a))


@pytest.mark.parametrize("c", [128, 256])
def test_split_projection_reproduces_highest_on_bf16_streams(c):
    """The raw projection of bf16 streams through three bf16 pieces of Phi
    is the float32 product at ``HIGHEST`` to float32 rounding (a plain bf16
    product of the same operands is 1e-3 away)."""
    x, phi, alpha, b, *_ = operands(c, 256, jnp.bfloat16, seed=2)
    x = x.reshape(N, -1, c)
    _, stats = mhc._pre_fwd(x, phi, alpha, b, eps=EPS, dtype=jnp.bfloat16,
                            interpret=True)
    exact = np.einsum("ntc,nck->tk", np.asarray(x, np.float64),
                      np.asarray(phi, np.float64))
    highest = jnp.einsum("ntc,nck->tk", x.astype(jnp.float32), phi,
                         precision=jax.lax.Precision.HIGHEST)
    plain = jnp.einsum("ntc,nck->tk", x, phi.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    assert rel(stats[:, :K], exact) < 3e-7
    assert rel(stats[:, :K], exact) < 2 * rel(highest, exact) + 1e-7
    assert rel(plain, exact) > 3e-4
    # the rest of the side array: zero but for the sum of squares
    assert float(jnp.abs(stats[:, K:mhc._SS_LANE]).max()) == 0.0
    np.testing.assert_allclose(
        stats[:, mhc._SS_LANE],
        np.square(np.asarray(x, np.float64)).sum((0, 2)), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_fallback_engages_where_the_width_is_no_lane_multiple(dtype):
    x, phi, alpha, b, y, h_res, h_post, _ = operands(64, 64, dtype)
    before = choices()
    u, proj, thru = mhc.pre(x, phi, alpha, b, eps=EPS, dtype=dtype)
    out = mhc.post(x, y, h_res, h_post)
    new = new_choices(before)
    assert set(new) == {"fallback"}, new
    (labels, count), = new["fallback"]
    assert count == 2 and labels == {
        "n": "4", "c": "64", "tokens": "64", "tile_tokens": "0",
        "tile_c": "0", "passes": "0"}
    ref_u, ref_proj, _ = mhc.pre_jnp(x, phi, alpha, b, eps=EPS, dtype=dtype)
    np.testing.assert_array_equal(np.asarray(u, np.float32),
                                  np.asarray(ref_u, np.float32))
    np.testing.assert_array_equal(proj, ref_proj)
    assert thru is x
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(mhc.post_jnp(x, y, h_res, h_post), np.float32))


@pytest.mark.parametrize("kernel,want", [
    ("pre_fwd", (128, 3584)), ("post_fwd", (128, 896)),
    ("post_bwd", (128, 896)), ("pre_bwd", (128, 3584))])
def test_tile_rule_at_the_cells_shape(kernel, want):
    """4 streams of 8192 tokens x 3584 in bf16: 128 tokens a tile; the
    ``pre`` kernels take the whole width (backward, beside Phi's six slots
    and its float32 gradient, that fills the VMEM budget), the others the
    largest lane-multiple divisor of it under the cap."""
    tiles = mhc.choose_tiles(kernel, 4, 3584, 8192, 2)
    assert tiles == want
    assert mhc._vmem_bytes(kernel, 4, 3584, *tiles, 2) <= mhc._VMEM_BUDGET


@pytest.mark.parametrize("n,c,tokens,itemsize,why", [
    (4, 64, 256, 2, "width under a lane tile"),
    (4, 192, 256, 2, "width no lane multiple"),
    (4, 128, 40, 2, "tokens no multiple of bf16's 16 rows"),
    (4, 128, 36, 4, "tokens no multiple of float32's 8 rows"),
    (5, 128, 256, 2, "35 coefficients, a slot holds 32"),
    (4, 128 * 1024, 256, 4, "a row group of the whole width over the budget"),
])
def test_tile_rule_falls_back(n, c, tokens, itemsize, why):
    assert mhc.choose_tiles("pre_bwd", n, c, tokens, itemsize) is None, why


def test_tile_rule_takes_divisors_only():
    assert mhc.choose_tiles("post_fwd", 4, 384, 48, 2) == (48, 384)
    assert mhc.choose_tiles("post_fwd", 4, 640, 1040, 2) == (80, 640)
    assert mhc.choose_tiles("post_fwd", 4, 1280, 1040, 2) == (80, 640)
    assert mhc.choose_tiles("pre_fwd", 4, 128, 32, 4) == (32, 128)


# --- in the model ---

def tiny_model(hidden, dtype=jnp.float32, remat=True):
    from tests.test_xing4_model import B, S, tiny
    from tpu_sandbox.models import xing4

    cfg = xing4.Xing4Config.from_dict(
        tiny(hidden_size=hidden, num_nextn_predict_layers=0,
             hc_sinkhorn_iters=3),
        tokens_per_step=B * S, dtype=dtype, remat=remat, flash=False)
    return xing4.Xing4LM(cfg), jnp.zeros((B, S), jnp.int32)


def test_tracing_a_tiny_step_counts_each_call_site_once():
    """Two blocks of two sub-layers at width 128, every block under
    ``remat``: tracing the gradient counts each sub-layer's forward kernels
    twice (the primal function, then ``custom_vjp``'s forward rule: what the
    step runs as forward and recomputation) and its backward kernels once,
    with the shape and the tiles in the labels; nothing falls back."""
    model, tokens = tiny_model(128)
    variables = jax.eval_shape(model.init, jax.random.key(0), tokens)

    def loss(params):
        return model.apply({**variables, "params": params}, tokens).sum()

    before = choices()
    jax.eval_shape(jax.grad(loss), variables["params"])
    new = new_choices(before)
    want = {"pre_fwd": (8, "6", "128"), "post_fwd": (8, "0", "128"),
            "post_bwd": (4, "0", "128"), "pre_bwd": (4, "12", "128")}
    assert set(new) == set(want), new
    for kernel, (count, passes, tile_c) in want.items():
        (labels, got), = new[kernel]
        assert got == count, (kernel, got)
        assert labels == {"n": "4", "c": "128", "tokens": "32",
                          "tile_tokens": "32", "tile_c": tile_c,
                          "passes": passes}, (kernel, labels)


def test_hyper_connection_agrees_with_its_fallback_on_every_parameter(
        monkeypatch):
    """One sub-layer's mHC around a fixed ``y``: through the kernels and
    through ``jnp`` (the tile rule made to find nothing) the new streams and
    the gradients of the streams, ``y``, the three Phi, the three alphas and
    the three biases agree."""
    import flax.linen as nn

    from tests.test_xing4_model import B, S
    from tpu_sandbox.models import xing4

    model, _ = tiny_model(128)
    mix = xing4.HyperConnection(model.config)
    streams = (jax.random.normal(jax.random.key(1), (4, B, S, 128))
               * (1.0 + jnp.arange(4.0))[:, None, None, None])
    y = jax.random.normal(jax.random.key(2), (B, S, 128))
    weight = jax.random.normal(jax.random.key(3), streams.shape)
    params = jax.jit(lambda key: jax.tree.map(
        lambda a: a * 5 if a.ndim == 0 else a + 0.3 * jax.random.normal(
            jax.random.key(a.size), a.shape),
        mix.init(key, streams, method="pre")["params"]))(jax.random.key(0))

    def both(m, streams, y):
        u, kept, coefficients = m.pre(streams)
        return m.post(kept, y + u, coefficients)

    def loss(params, streams, y):
        out = nn.apply(both, mix)({"params": params}, streams, y)
        return (out * weight).sum(), out

    run = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))
    before = choices()
    (_, out), grads = run(params, streams, y)
    assert "fallback" not in new_choices(before)
    monkeypatch.setattr(mhc, "choose_tiles", lambda *a, **k: None)
    before = choices()
    (_, ref_out), ref_grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(params, streams, y)
    assert set(new_choices(before)) == {"fallback"}
    assert rel(out, ref_out) < 1e-6
    got, want = (jax.tree_util.tree_flatten_with_path(g)[0]
                 for g in (grads, ref_grads))
    assert len(got) == 9 + 2
    for (path, a), (_, b) in zip(got, want):
        assert rel(a, b) < 2e-4, jax.tree_util.keystr(path)
