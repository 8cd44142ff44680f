"""ops/pallas_short_conv.py on the CPU (interpret mode): the kernel pair
against ``silu(causal_conv(...))`` differentiated by JAX, over channel
widths, bias or none, batches, dtypes and a tile smaller than the sequence
(the halo across a tile's edge, the first token included); the tile rule and
the fallback by shape; the counter a call site adds to; the call traced once
for two sites of one shape."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sandbox.obs import get_registry
from tpu_sandbox.ops import pallas_short_conv as sc
from tpu_sandbox.ops.pallas_short_conv import causal_conv

K = 4


def plain(x, taps, bias, start=0, dtype=None):
    c = taps.shape[1]
    y = jax.nn.silu(causal_conv(x[..., start:start + c], taps,
                                0.0 if bias is None else bias))
    return y.astype(dtype or x.dtype)


def kernel(x, taps, bias, **kw):
    return sc.short_conv(x, taps, bias, interpret=True, **kw)


def operands(b, s, c, dtype, bias, *, width=None, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (b, s, width or c)).astype(dtype)
    taps = 0.5 * jax.random.normal(ks[1], (K, c))
    return (x, taps, jax.random.normal(ks[2], (c,)) if bias else None,
            jax.random.normal(ks[3], (b, s, c)))


def choices():
    """labels -> count of ``conv.kernel_choice`` so far."""
    return {key[key.index("{") + 1:-1]: count
            for key, count in get_registry().snapshot()["counters"].items()
            if key.startswith("conv.kernel_choice")}


def new_choices(before):
    return {labels: count - before.get(labels, 0)
            for labels, count in choices().items()
            if count != before.get(labels, 0)}


@pytest.fixture
def small_tiles(monkeypatch):
    """Caps under which 96 tokens take three tiles of 32 and 256 channels
    two of 128: halos, the rows ``dx`` takes from the following tile and
    the taps' sums all cross grid steps."""
    monkeypatch.setattr(sc, "_TOKEN_CAP", 32)
    monkeypatch.setattr(sc, "_WIDTH_CAP", 128)
    monkeypatch.setattr(sc, "_ROW_GROUP", 16)
    # and, with the tokens on the lanes, 384 tokens three tiles of 128 in
    # chunks of 128 and 2 x 64, 320 channels five tiles of 64
    monkeypatch.setattr(sc, "_LANE_TOKEN_CAP", 128)
    monkeypatch.setattr(sc, "_CHANNEL_CAP", 64)
    jax.clear_caches()
    yield
    jax.clear_caches()


def on_lanes(width):
    """The pair an array of this width takes: no lane multiple, so XLA lays
    it tokens-minor and the pair that reads ``[B, C, S]`` runs."""
    return width % 128 != 0


# a lane multiple (the row-major pair); 22.5 lane tiles' worth scaled down
# to 2.5 and one packed tile under a lane tile (the tokens on the lanes)
CASES = [pytest.param(c, b, bias, d,
                      id=f"c{c}-b{b}-bias{int(bias)}-{jnp.dtype(d).name}")
         for c in (256, 320, 112) for b, bias in ((1, True), (2, False))
         for d in (jnp.float32, jnp.bfloat16)]


def compare(x, taps, bvec, w, fn, tol):
    """``fn`` against the plain form: result, gradients, the first token;
    returns what its call sites counted."""
    args = (0, 1, 2) if bvec is not None else (0, 1)

    def run(fn):
        def loss(x, taps, bvec):
            y = fn(x, taps, bvec)
            return (y.astype(jnp.float32) * w).sum(), y
        return jax.jit(jax.value_and_grad(loss, args, has_aux=True))(
            x, taps, bvec)

    before = choices()
    (_, y), grads = run(fn)
    (_, ref_y), ref_grads = run(plain)
    assert y.dtype == x.dtype and grads[0].dtype == x.dtype
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref_y, np.float32), atol=tol)
    for got, want in zip(grads, ref_grads):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=tol,
                                   atol=tol * np.abs(want).max())
    # the first token reads the last tap alone
    first = jax.nn.silu((0.0 if bvec is None else bvec)
                        + taps[K - 1] * x[:, 0].astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(y[:, 0], np.float32), first,
                               atol=tol)
    return new_choices(before)


def tolerance(dtype):
    """The same float32 arithmetic in the same order: a rounding of the
    result's dtype at most (XLA:CPU may contract a multiply-add)."""
    return 1e-2 if dtype == jnp.bfloat16 else 1e-5


@pytest.mark.parametrize("c,b,bias,dtype", CASES)
def test_kernels_match_the_jnp_formulation(c, b, bias, dtype, small_tiles):
    """Three token tiles either way: 96 tokens in tiles of 32 rows, or,
    ``[B, C, S]`` in and out, 384 in tiles of 128 lanes (the halo a lane
    tile, ``dx``'s tokens from the following tile along the lanes)."""
    s, tile = (384, 128) if on_lanes(c) else (96, 32)
    x, taps, bvec, w = operands(b, s, c, dtype, bias)
    rule = sc.choose_tiles_cf if on_lanes(c) else sc.choose_tiles
    assert rule(c, 0, s, K, jnp.dtype(dtype).itemsize)[0] == tile
    new = compare(x, taps, bvec, w, kernel, tolerance(dtype))
    # one forward and one backward site, and none that fell back
    assert new == {f"bias={int(bias)},channels={c},impl=pallas,taps={K},"
                   f"tile_tokens={tile},tokens={b * s}": 2}


@pytest.mark.parametrize("width", [512, 520], ids=["rows", "lanes"])
@pytest.mark.parametrize("dtype,result", [
    (jnp.bfloat16, jnp.bfloat16), (jnp.bfloat16, jnp.float32)],
    ids=["in_place", "float32_result"])
def test_a_slice_of_a_wider_array_is_read_in_place(dtype, result, width,
                                                   small_tiles):
    """Nemotron's call: 256 channels from channel 128 of 512, or of 520 (no
    lane multiple: the other pair); the input's cotangent is zero beside
    them. With a float32 result (Olmo's keys and queries) the cotangent
    comes in float32 and ``dx`` leaves in bf16."""
    x, taps, bvec, w = operands(2, 128 if on_lanes(width) else 64, 256, dtype,
                                True, width=width)

    def grads(fn):
        return jax.jit(jax.grad(lambda x, t, b: (
            fn(x, t, b).astype(jnp.float32) * w).sum(), (0, 1, 2)))(
                x, taps, bvec)

    before = choices()
    got = grads(lambda *a: kernel(*a, start=128, dtype=result))
    assert all("impl=pallas" in labels for labels in new_choices(before))
    want = grads(lambda *a: plain(*a, start=128, dtype=result))
    y = kernel(x, taps, bvec, start=128, dtype=result)
    assert y.dtype == result and y.shape == (*x.shape[:2], 256)
    assert got[0].shape == x.shape and got[0].dtype == dtype
    assert not np.asarray(got[0][..., :128], np.float32).any()
    assert not np.asarray(got[0][..., 384:], np.float32).any()
    for g, r in zip(got, want):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        np.testing.assert_allclose(g, r, rtol=1e-2,
                                   atol=1e-2 * np.abs(r).max())


@pytest.mark.parametrize("c,start,tokens,itemsize,want", [
    (5120, 4096, 8192, 2, (512, 1024)),     # Nemotron's xBC inside in_proj
    (5760, 0, 8192, 2, (512, 640)),         # Olmo's values
    (5120, 0, 4096, 4, (512, 1024)),        # float32
    (256, 0, 48, 2, (48, 256)), (256, 0, 48, 4, (48, 256)),
    (256, 128, 64, 2, (64, 128)),           # the width divides the offset
])
def test_tile_rule(c, start, tokens, itemsize, want):
    tiles = sc.choose_tiles(c, start, tokens, K, itemsize)
    assert tiles == want
    assert sc._vmem_bytes(*tiles, itemsize) <= sc._VMEM_BUDGET


@pytest.mark.parametrize("c,start,tokens,taps,itemsize,why", [
    (256, 0, 40, 4, 2, "tokens no multiple of bf16's 16 rows"),
    (256, 0, 36, 4, 4, "tokens no multiple of float32's 8 rows"),
    (256, 0, 64, 8, 2, "eight taps and a bias, a sublane tile holds eight"),
    (256, 64, 64, 4, 2, "an offset no lane multiple divides"),
    (2880, 0, 8192, 4, 2, "a width no lane multiple divides: Olmo's keys, "
                          "which take the other pair"),
    (127, 0, 64, 4, 4, "nor one under a lane tile"),
])
def test_tile_rule_falls_back(c, start, tokens, taps, itemsize, why):
    assert sc.choose_tiles(c, start, tokens, taps, itemsize) is None, why


@pytest.mark.parametrize("c,start,tokens,itemsize,want", [
    (5120, 4096, 8192, 2, (2048, 256)),     # Nemotron's xBC inside in_proj
    (2880, 0, 8192, 2, (2048, 240)),        # Olmo's keys: 12 channel tiles
    (48, 16, 384, 4, (384, 16)),            # the tile divides the offset
    (5120, 0, 8192, 4, (2048, 256)),        # float32
    (256, 0, 96, 2, None),                  # tokens no lane multiple
    (120, 0, 128, 2, None),                 # channels no packed tile's rows
    (127, 0, 128, 4, None),                 # divide: one under a lane tile
    (128, 8, 128, 2, None),                 # nor their offset
])
def test_tile_rule_with_the_tokens_on_the_lanes(c, start, tokens, itemsize,
                                                want):
    assert sc.choose_tiles_cf(c, start, tokens, K, itemsize) == want


@pytest.mark.parametrize("width,want", [
    (9280, "2048"), (2880, "2048"), (5760, "512"), (5120, "512")])
def test_the_pair_follows_the_arrays_width(width, want):
    """No lane multiple: XLA lays the array tokens-minor, the pair that
    reads ``[B, C, S]`` takes it (token tiles of 2048); a lane multiple:
    the row-major pair (512)."""
    x = jax.ShapeDtypeStruct((1, 8192, width), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((K, 2560 if width > 6000 else width),
                                jnp.float32)
    before = choices()
    jax.eval_shape(lambda x, t: sc.short_conv(
        x, t, start=x.shape[-1] - t.shape[1] - 64 * (width > 6000),
        interpret=True), x, taps)
    (labels,) = new_choices(before)
    assert f"impl=pallas,taps={K},tile_tokens={want}," in labels


@pytest.mark.parametrize("s,c,dtype", [
    (20, 128, jnp.float32), (24, 128, jnp.bfloat16), (128, 127, jnp.float32)],
    ids=["tokens20", "tokens24_bf16", "channels127"])
def test_a_shape_no_tile_divides_falls_back(s, c, dtype):
    """``model.init``'s short sample, or channels one under a lane tile: the
    ``jnp`` form, counted as such, and differentiable as before."""
    x, taps, bvec, w = operands(1, s, c, dtype, True)
    before = choices()
    y = sc.short_conv(x, taps, bvec, interpret=True)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(plain(x, taps, bvec), np.float32))
    jax.grad(lambda t: sc.short_conv(x, t, bvec).astype(jnp.float32).sum())(
        taps)
    assert new_choices(before) == {
        f"bias=1,channels={c},impl=jnp,taps={K},tile_tokens=0,tokens={s}": 2}


def test_an_offset_no_lane_multiple_is_sliced_first():
    x, taps, bvec, _ = operands(1, 32, 128, jnp.float32, False, width=256)
    y = sc.short_conv(x, taps, start=64, interpret=True)
    np.testing.assert_allclose(y, plain(x, taps, None, start=64), atol=1e-6)


def test_two_sites_of_one_shape_trace_the_call_once():
    """Counted at both sites, traced at the first: the ``trace:kernel`` span
    opens inside the jitted call (``pallas_common.traced_once``)."""
    # a shape no other test of this process gives the jitted calls
    x, taps, _, _ = operands(1, 48, 384, jnp.bfloat16, False)
    registry = get_registry()

    def sites(kernel):
        return sum(h["count"] for key, h in
                   registry.snapshot()["histograms"].items()
                   if key.startswith(f"trace.kernel_s{{kernel={kernel},"))

    def twice(x, taps):
        return sc.short_conv(sc.short_conv(x, taps, interpret=True), taps,
                             interpret=True).astype(jnp.float32).sum()

    before, fwd, bwd = choices(), sites("short_conv_fwd"), sites(
        "short_conv_bwd")
    jax.eval_shape(jax.grad(twice, (0, 1)), x, taps)
    assert sites("short_conv_fwd") - fwd == 1
    assert sites("short_conv_bwd") - bwd == 1
    # two forward sites and two backward ones
    assert new_choices(before) == {
        f"bias=0,channels=384,impl=pallas,taps={K},tile_tokens=48,"
        "tokens=48": 4}


def test_the_mixers_call_the_kernel_where_the_shape_allows():
    """A Mamba-2 mixer and a Gated DeltaNet at tiny widths (no lane
    multiples: the tokens go on the lanes, 128 of them): every site of the
    gradient's trace is the kernel's, forward and backward."""
    from tests.test_nemotron_h_model import TINY as NEMOTRON
    from tests.test_olmo_hybrid_model import TINY as OLMO
    from tpu_sandbox.models import nemotron_h, olmo_hybrid

    cfg = nemotron_h.NemotronHConfig.from_dict(
        NEMOTRON, tokens_per_step=64, dtype=jnp.float32, remat=False)
    mamba = nemotron_h.Mamba2Mixer(cfg)
    cfg = olmo_hybrid.OlmoHybridConfig.from_dict(
        OLMO, dtype=jnp.float32, remat=False)
    gdn = olmo_hybrid.GatedDeltaNet(cfg)
    for mixer, width, calls in ((mamba, NEMOTRON["hidden_size"], 1),
                                (gdn, OLMO["hidden_size"], 3)):
        u = jnp.zeros((2, 128, width), jnp.float32)
        variables = jax.eval_shape(mixer.init, jax.random.key(0), u)
        before = choices()
        jax.eval_shape(jax.grad(lambda p: mixer.apply(
            {"params": p}, u).sum()), variables["params"])
        new = new_choices(before)
        assert all("impl=pallas" in k for k in new), new
        assert sum(new.values()) == 2 * calls, new
