"""Flash-attention kernel vs the reference math (interpret mode on CPU).

Mirrors the test strategy used for the other Pallas kernel (test_aux's CE
checks): same call path as TPU, interpret=True, numerical parity against
ops.attention.causal_attention which is itself torch-verified via the
transformer tests.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sandbox.ops import pallas_attention as pa
from tpu_sandbox.ops.attention import causal_attention
from tpu_sandbox.ops.pallas_attention import flash_attention, flash_attention_fn


def _rand_qkv(b=2, s=256, h=2, d=32, seed=0):
    rng = np.random.default_rng(seed)
    shape = (b, s, h, d)
    return tuple(
        jnp.asarray(rng.standard_normal(shape, dtype=np.float32))
        for _ in range(3)
    )


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = _rand_qkv()
    ref = causal_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.usefixtures("light_compile")
def test_forward_unaligned_seq_and_headdim():
    # S=200 pads to 256, D=24 pads to the 128 lane tile
    q, k, v = _rand_qkv(s=200, d=24, seed=1)
    ref = causal_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    q, k, v = _rand_qkv(s=128, d=16, seed=2)
    w = jnp.asarray(
        np.random.default_rng(3).standard_normal(q.shape, dtype=np.float32)
    )

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True) * w)

    def loss_ref(q, k, v):
        return jnp.sum(causal_attention(q, k, v, causal=causal) * w)

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-5, atol=5e-5,
            err_msg=f"grad d{name} mismatch",
        )


@pytest.mark.usefixtures("light_compile")
def test_gradients_unaligned_seq_and_headdim():
    """Backward through the padding path: S=200 pads to 256 (zero-cotangent
    padded rows), D=24 pads to the 128-lane tile."""
    q, k, v = _rand_qkv(s=200, d=24, seed=5)
    w = jnp.asarray(
        np.random.default_rng(6).standard_normal(q.shape, dtype=np.float32)
    )

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=True) * w)

    def loss_ref(q, k, v):
        return jnp.sum(causal_attention(q, k, v) * w)

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-5, atol=5e-5,
            err_msg=f"grad d{name} mismatch",
        )


@pytest.mark.usefixtures("light_compile")
def test_pallas_bwd_matches_jnp_blockwise_bwd():
    """The Pallas backward kernels against the jnp scan backward they
    replaced (kept as the O(S·block) reference implementation)."""
    from tpu_sandbox.ops.pallas_attention import (
        _blockwise_bwd,
        _flash_bwd,
        _flash_fwd,
    )

    rng = np.random.default_rng(7)
    b, h, s, d = 2, 2, 256, 128
    q, k, v, g = (
        jnp.asarray(rng.standard_normal((b, h, s, d), dtype=np.float32))
        for _ in range(4)
    )
    scale = 1.0 / d**0.5
    out, lse = _flash_fwd(q, k, v, scale, True, 128, 128, True, s)
    ref = _blockwise_bwd(q, k, v, out, lse, g, scale, True, 128, s)
    delta = jnp.sum(g * out, axis=-1)
    got = _flash_bwd(q, k, v, delta, lse, g, scale, True, 128, 128, True, s)
    for gf, gr, name in zip(got, ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=2e-5, atol=2e-5,
            err_msg=f"{name} mismatch",
        )


@pytest.mark.usefixtures("light_compile")
def test_transformer_with_flash_attention():
    from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=32, d_model=32, n_heads=2, n_layers=2,
                            d_ff=64, max_len=128)
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(0, 32, size=(2, 128)), jnp.int32)

    ref_model = TransformerLM(cfg)
    variables = jax.jit(ref_model.init)(jax.random.key(0), tokens)
    ref_logits = jax.jit(ref_model.apply)(variables, tokens)

    flash_model = TransformerLM(cfg, attention_fn=flash_attention_fn(
        interpret=True))
    logits = jax.jit(flash_model.apply)(variables, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)


# -- the tile rule (PR 28) ---------------------------------------------------

MIB = 2**20
#       name                 true S  D    Dv   itemsize  budget
RULE_CASES = [
    ("gpt2_cell_bf16",        1024, 128, 128, 2, pa._VMEM_BUDGET),
    ("xing4_cell_bf16",       4096, 256, 128, 2, pa._VMEM_BUDGET),
    ("xing4_cell_fp32",       4096, 256, 128, 4, pa._VMEM_BUDGET),
    ("below_one_tile",         100, 128, 128, 2, pa._VMEM_BUDGET),
    ("no_tile_multiple",      1000, 128, 128, 4, pa._VMEM_BUDGET),
    ("odd_count_of_lanes",    1100, 128, 128, 2, pa._VMEM_BUDGET),   # 9 x 128
    ("prime_count_of_lanes",  1400, 256, 128, 2, pa._VMEM_BUDGET),   # 11 x 128
    ("tight_budget",          4096, 256, 128, 2, 3 * MIB),
    ("budget_under_any_tile", 4096, 256, 128, 4, MIB // 4),
]


@pytest.mark.parametrize("kernel", pa._KERNELS)
@pytest.mark.parametrize("name,s,d,dv,itemsize,budget", RULE_CASES,
                         ids=[c[0] for c in RULE_CASES])
def test_tile_rule(kernel, name, s, d, dv, itemsize, budget):
    sp = pa._pad_len(s)
    assert sp % 128 == 0 and 0 <= sp - s < 128
    bq, bk = pa.choose_tiles(kernel, sp, sp, d, dv, itemsize, budget=budget)
    for blk in (bq, bk):
        assert blk % 128 == 0 and sp % blk == 0, (bq, bk, sp)
    fits = pa._vmem_bytes(kernel, bq, bk, d, dv, itemsize) <= budget
    # the smallest tile is the floor: a budget under it is not the rule's
    # to meet, every other one is
    assert fits or (bq, bk) == (128, 128)
    if fits and bk < sp:     # and no larger key tile would have fitted
        nxt = min(t for t in range(bk + 128, sp + 1, 128) if sp % t == 0)
        assert nxt > pa._TILE_CAP or pa._vmem_bytes(
            kernel, bq, nxt, d, dv, itemsize) > budget


def test_tile_rule_reads_shapes_and_keeps_explicit_blocks():
    # more tile for a longer sequence, never more than the sequence
    assert pa.choose_tiles("fwd", 128, 128, 128, 128, 2) == (128, 128)
    small = pa.choose_tiles("fwd", 512, 512, 128, 128, 2)
    large = pa.choose_tiles("fwd", 4096, 4096, 128, 128, 2)
    assert small <= (512, 512) and large >= small
    # a wider operand never earns a larger tile under one budget
    for kernel in pa._KERNELS:
        a = pa.choose_tiles(kernel, 4096, 4096, 128, 128, 2, budget=6 * MIB)
        b = pa.choose_tiles(kernel, 4096, 4096, 256, 128, 4, budget=6 * MIB)
        assert b[0] * b[1] <= a[0] * a[1]
    # an explicit side is kept, the other one chosen
    assert pa.choose_tiles("dq", 1024, 1024, 128, 128, 2,
                           block_q=128)[0] == 128
    assert pa.choose_tiles("dkv", 1024, 1024, 128, 128, 2, block_q=128,
                           block_k=256) == (128, 256)
    with pytest.raises(ValueError):
        pa.choose_tiles("bwd", 1024, 1024, 128, 128, 2)
    # explicit blocks size the padding; none pads to the lane tile
    assert pa._pad_len(600, 128, 256) == 768 and pa._pad_len(600) == 640
    assert pa._pad_len(5, None, 256) == 256


@pytest.mark.parametrize("nq,nk,bq,bk,causal,kv_len,want", [
    (8, 8, 512, 512, True, 4096, (28, 8)),     # Xing4 at 512 x 512: 36 of 64
    (4, 4, 1024, 1024, True, 4096, (6, 4)),    # and at the rule's: 10 of 16
    (1, 1, 1024, 1024, True, 1024, (0, 1)),    # GPT-2: the one tile a head
    (8, 8, 128, 128, False, 1024, (64, 0)),
    (8, 8, 128, 128, False, 1000, (56, 8)),    # the last column is padded
    (4, 2, 256, 512, True, 1024, (2, 4)),
    (2, 4, 512, 256, True, 1000, (2, 4)),
])
def test_tile_census_counts_interior_and_masked_tiles(nq, nk, bq, bk, causal,
                                                      kv_len, want):
    tile = dict(causal=causal, block_q=bq, block_k=bk, kv_len=kv_len)
    assert pa._tile_census(nq, nk, 0, 0, **tile) == want
    # a traced offset: only the run knows
    assert jax.jit(lambda o: pa._tile_census(nq, nk, o, 0, **tile) is None)(
        jnp.int32(0))


@pytest.mark.parametrize("census,want", [
    ((0, 1), [True]),          # every tile masked: one body
    ((6, 4), [False, True]),   # interior and masked tiles
    ((64, 0), [False]),        # no mask is ever built
    (None, [False, True]),     # traced offsets: both
])
def test_only_the_variants_the_grid_needs_are_built(census, want):
    built = []
    jax.make_jaxpr(lambda i: pa._on_tile(
        i, i, 0, 0, lambda valid: built.append(valid is not None),
        census=census, sk=4096, causal=True, block_q=128, block_k=128,
        kv_len=4096))(jnp.int32(0))
    assert built == want


def _tile_choices():
    from tpu_sandbox.obs import get_registry

    return {k: v for k, v in get_registry().snapshot()["counters"].items()
            if k.startswith("attn.tile_choice")}


def _new_tile_choices(before):
    return {k: v - before.get(k, 0) for k, v in _tile_choices().items()
            if v != before.get(k, 0)}


def test_tile_choice_is_counted_at_trace_time():
    before = _tile_choices()
    q, k, v = _rand_qkv(b=1, s=384, h=3, d=32, seed=11)
    jax.grad(lambda q: flash_attention(q, k, v, interpret=True).sum())(q)
    new = _new_tile_choices(before)
    assert len(new) == 3 and set(new.values()) == {1}, new
    for kernel in pa._KERNELS:
        (key,) = [k for k in new if f"kernel={kernel}" in k]
        assert "s=384" in key and "d=128" in key and "dv=128" in key
        assert "block_q=384" in key and "block_k=384" in key
        assert "steps=3" in key and "steps_with_work=3" in key


# -- the kernels at unequal tiles: interior, diagonal and padded-key tiles ----

def _qkvg(s, d_qk, d_v, dtype=jnp.float32, b=1, h=2, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k = (jax.random.normal(x, (b, s, h, d_qk), dtype) for x in keys[:2])
    v, g = (jax.random.normal(x, (b, s, h, d_v), dtype) for x in keys[2:])
    return q, k, v, g


def _grads(attn, q, k, v, g, **kw):
    def out_and_grads(q, k, v, g):  # one compiled program, not one a primitive
        out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, **kw), q, k, v)
        return (out, *vjp(g.astype(out.dtype)))
    return jax.jit(out_and_grads)(q, k, v, g)


@functools.cache
def _plain_grads(d_qk, d_v, causal):
    """Plain attention's (out, dq, dk, dv) at ``_qkvg(600, d_qk, d_v)``: one
    a head size and mask, for every tiling held to it."""
    return _grads(causal_attention, *_qkvg(600, d_qk, d_v), causal=causal)


# S 600 pads to 768 under blocks (128, 256) / (256, 128): six (three) query
# tiles against three (six) key tiles; the first column is interior from the
# third row on, the diagonal crosses two tiles a row, the last key tile holds
# 168 padded keys; at (256, 256) the diagonal tiles are square
TILINGS = [(128, 256), (256, 128), (256, 256)]
HEADS = [(64, 64), (192, 128)]


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("block_q,block_k", TILINGS)
@pytest.mark.parametrize("d_qk,d_v", HEADS)
def test_unequal_tiles_match_plain_attention_fp32(d_qk, d_v, block_q, block_k,
                                                   causal):
    q, k, v, g = _qkvg(600, d_qk, d_v)
    got = _grads(flash_attention, q, k, v, g, causal=causal, block_q=block_q,
                 block_k=block_k, interpret=True)
    want = _plain_grads(d_qk, d_v, causal)
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("block_q,block_k", TILINGS)
@pytest.mark.parametrize("d,dv", [(128, 128), (256, 128)])
def test_unequal_tiles_match_blockwise_bwd(d, dv, block_q, block_k, causal):
    """The two backward kernels, each at its own grid, against the jnp scan
    backward, on lane-aligned [B, H, S, D] with 168 padded keys."""
    b, h, s, kv_len = 1, 2, 768, 600
    keys = jax.random.split(jax.random.key(3), 4)
    q, k = (jax.random.normal(x, (b, h, s, d), jnp.float32) for x in keys[:2])
    v, g = (jax.random.normal(x, (b, h, s, dv), jnp.float32) for x in keys[2:])
    scale = d ** -0.5
    out, lse = pa._flash_fwd(q, k, v, scale, causal, block_q, block_k, True,
                             kv_len)
    # rows past kv_len are padding too: their cotangent is zero, as the
    # wrapper's zero padding makes it
    g = g.at[:, :, kv_len:].set(0.0)
    want = pa._blockwise_bwd(q, k, v, out, lse, g, scale, causal, 128, kv_len)
    got = pa._flash_bwd(q, k, v, jnp.sum(g * out, -1), lse, g, scale, causal,
                        block_q, block_k, True, kv_len)
    for a, w, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(a[:, :, :kv_len], w[:, :, :kv_len],
                                   rtol=2e-5, atol=2e-5, err_msg=name)


def test_rule_tiles_match_explicit_small_tiles_bitwise_in_shape_and_close():
    """The default (rule-chosen, one 640 tile) against explicit 128 x 128:
    the same function, to float32 rounding."""
    q, k, v, g = _qkvg(600, 64, 64, seed=4)
    a = _grads(flash_attention, q, k, v, g, interpret=True)
    b = _grads(flash_attention, q, k, v, g, block_q=128, block_k=128,
               interpret=True)
    for x, y in zip(a, b):
        assert x.shape == y.shape
        np.testing.assert_allclose(x, y, rtol=2e-5, atol=2e-5)


# -- bf16: the backward feeds the MXU bf16, and a planted fault shows --------

BF16_REL_RMS = 1e-2   # bf16 keeps 8 bits: p and ds rounded before a product


def _rel_rms(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def _bf16_case(d_qk, d_v, causal):
    q, k, v, g = _qkvg(600, d_qk, d_v, jnp.bfloat16, seed=5)
    want = _grads(causal_attention,
                  *(x.astype(jnp.float32) for x in (q, k, v)), g,
                  causal=causal)
    return (q, k, v, g), want


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("d_qk,d_v", HEADS)
def test_bf16_gradients_against_the_float32_reference(d_qk, d_v, causal):
    args, want = _bf16_case(d_qk, d_v, causal)
    got = _grads(flash_attention, *args, causal=causal, block_q=128,
                 block_k=256, interpret=True)
    for a, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert a.dtype == jnp.bfloat16
        assert _rel_rms(a, w) < BF16_REL_RMS, (name, _rel_rms(a, w))


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("fault", ["delta_left_out", "ds_not_scaled"])
def test_a_planted_fault_breaks_the_bf16_tolerance_tenfold(fault, monkeypatch):
    args, want = _bf16_case(64, 64, True)
    if fault == "delta_left_out":
        real = pa._flash_bwd
        monkeypatch.setattr(pa, "_flash_bwd", lambda q, k, v, delta, *a, **kw:
                            real(q, k, v, jnp.zeros_like(delta), *a, **kw))
    else:
        real = pa._bwd_tile

        def unscaled(*a):
            q, k, do, p, ds = real(*a)
            return q, k, do, p, ds / a[-1]
        monkeypatch.setattr(pa, "_bwd_tile", unscaled)
    got = _grads(flash_attention, *args, block_q=128, block_k=256,
                 interpret=True)
    worst = max(_rel_rms(a, w) for a, w in zip(got[1:3], want[1:3]))
    assert worst > 10 * BF16_REL_RMS, worst


def _dot_operand_dtypes(jaxpr):
    """(lhs dtype, rhs dtype) of every dot_general, kernels' bodies included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(tuple(str(x.aval.dtype) for x in eqn.invars))
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _dot_operand_dtypes(sub)
    return found


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_every_product_takes_its_operands_in_the_input_dtype(dtype):
    """The dtype is observed, not configured: float32 inputs are multiplied
    in float32 throughout (no rounding of p or ds on the way), bfloat16
    inputs reach all nine products (2 + 4 + 3) as bfloat16 (the grid here
    is one masked tile a head, so each kernel's body stands once)."""
    q, k, v, g = _qkvg(256, 64, 64, jnp.dtype(dtype), seed=6)
    jaxpr = jax.make_jaxpr(lambda q, k, v: _grads(
        flash_attention, q, k, v, g, interpret=True))(q, k, v)
    dots = _dot_operand_dtypes(jaxpr.jaxpr)
    assert len(dots) == 9, dots
    assert set(dots) == {(dtype, dtype)}, dots


@pytest.mark.parametrize("blocks", [{}, {"block_q": 128, "block_k": 128}],
                         ids=["rule_tiles", "the_rings_128"])
def test_traced_offsets_equal_static_ones(blocks):
    """The ring's call: offsets as traced scalars reach the index maps as
    scalar prefetch and give what the same python ints give, which is what
    plain attention gives on the keys at or before each query."""
    from tpu_sandbox.ops.pallas_attention import flash_attention_lse

    q, k, v, _ = _qkvg(256, 64, 64, seed=7)
    for q_off, kv_off in [(0, 0), (256, 0), (0, 256), (256, 128)]:
        want = flash_attention_lse(q, k, v, q_offset=q_off, kv_offset=kv_off,
                                   interpret=True, **blocks)
        got = jax.jit(lambda a, b: flash_attention_lse(
            q, k, v, q_offset=a, kv_offset=b, interpret=True, **blocks))(
                jnp.int32(q_off), jnp.int32(kv_off))
        for a, w in zip(got, want):
            np.testing.assert_array_equal(a, w)
        if q_off >= kv_off:      # every query sees a key: compare the values
            qp, kp = q_off + jnp.arange(256), kv_off + jnp.arange(256)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 64 ** -0.5
            s = jnp.where(qp[:, None] >= kp[None, :], s, -jnp.inf)
            ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            np.testing.assert_allclose(got[0], ref, rtol=2e-5, atol=2e-5)


def test_traced_offsets_without_causality_build_no_mask():
    """Traced offsets leave the census unknown; where nothing can mask (no
    causality, no padded key) the masked variant is still not built."""
    from tpu_sandbox.ops.pallas_attention import flash_attention_lse

    q, k, v, _ = _qkvg(256, 64, 64, seed=9)
    want = flash_attention_lse(q, k, v, causal=False, interpret=True)
    got = jax.jit(lambda a: flash_attention_lse(
        q, k, v, causal=False, kv_offset=a, interpret=True))(jnp.int32(0))
    for a, w in zip(got, want):
        np.testing.assert_array_equal(a, w)
    np.testing.assert_allclose(got[0], causal_attention(q, k, v, causal=False),
                               rtol=2e-5, atol=2e-5)


# -- the packed form: the kernels on [B, S, H·D], whole heads to a block -----

# (H, D): two heads, four heads and one head to a 128-lane block
PACKED_HEADS = [(4, 64), (8, 32), (2, 128)]
# S and explicit tiles: the rule's one tile; unequal tiles that divide S
# (packed); tiles whose lcm pads S 384 to 512 (padded, by the same rule)
PACKED_TILINGS = [(128, {}, True),
                  (384, {"block_q": 128, "block_k": 384}, True),
                  (384, {"block_q": 256, "block_k": 128}, False)]


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("s,blocks,packed", PACKED_TILINGS,
                         ids=["s128_rule", "s384_128x384", "s384_256x128"])
@pytest.mark.parametrize("h,d", PACKED_HEADS)
def test_packed_form_matches_padded_form_and_plain_attention(
        h, d, s, blocks, packed, dtype, monkeypatch):
    """out, dq, dk, dv of the packed form against the padded form on the
    same call and against plain attention in float32."""
    args = _qkvg(s, d, d, jnp.dtype(dtype), b=2, h=h, seed=8)
    assert bool(pa._heads_per_block(*args[:3], blocks.get("block_q"),
                                    blocks.get("block_k"))) == packed
    want = _grads(causal_attention,
                  *(x.astype(jnp.float32) for x in args[:3]), args[3])
    got = _grads(flash_attention, *args, interpret=True, **blocks)
    monkeypatch.setattr(pa, "_heads_per_block", lambda *a: None)
    padded = _grads(flash_attention, *args, interpret=True, **blocks)
    for a, p, w, name in zip(got, padded, want, ("out", "dq", "dk", "dv")):
        assert a.dtype == p.dtype == jnp.dtype(dtype)
        if dtype == "float32":
            np.testing.assert_allclose(a, w, rtol=2e-5, atol=2e-5,
                                       err_msg=name)
            np.testing.assert_allclose(a, p, rtol=2e-5, atol=2e-5,
                                       err_msg=name)
        else:
            assert _rel_rms(a, w) < BF16_REL_RMS, (name, _rel_rms(a, w))
            assert _rel_rms(a, p) < BF16_REL_RMS, (name, _rel_rms(a, p))


@pytest.mark.usefixtures("light_compile")
def test_a_head_reading_its_neighbours_lanes_breaks_the_bf16_tolerance_tenfold(
        monkeypatch):
    """The planted fault of the packed form: of the two heads of a block,
    each takes the other's lanes of its operands into the products (and
    writes its own lanes of the results, as before)."""
    args = _qkvg(256, 64, 64, jnp.bfloat16, h=4, seed=5)
    want = _grads(causal_attention,
                  *(x.astype(jnp.float32) for x in args[:3]), args[3])
    real = pa._own
    monkeypatch.setattr(pa, "_own", lambda x, lanes: real(x, ~lanes))
    got = _grads(flash_attention, *args, interpret=True)
    worst = max(_rel_rms(a, w) for a, w in zip(got, want))
    assert worst > 10 * BF16_REL_RMS, worst


FALLBACKS = [
    # name, S, H, D (q.k), Dv: shapes the rule keeps on the padded form
    ("odd_heads_at_64", 256, 3, 64, 64),
    ("latent_192_128", 256, 2, 192, 128),
    ("s_200", 200, 4, 64, 64),
]


@pytest.mark.usefixtures("light_compile")
@pytest.mark.parametrize("name,s,h,d,dv", FALLBACKS,
                         ids=[c[0] for c in FALLBACKS])
def test_shapes_outside_the_rule_take_the_padded_form_and_agree(name, s, h, d,
                                                                dv):
    args = _qkvg(s, d, dv, h=h, seed=12)
    assert pa._heads_per_block(*args[:3], None, None) is None
    before = _tile_choices()
    got = _grads(flash_attention, *args, interpret=True)
    new = _new_tile_choices(before)
    assert len(new) == 3 and all(
        "layout=padded" in key and "heads_per_block=1" in key
        and f"d={-(-d // 128) * 128}," in key for key in new), new
    want = _grads(causal_attention, *args)
    for a, w, part in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(a, w, rtol=2e-5, atol=2e-5, err_msg=part)


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations carry
    (custom_vjp, pjit, cond), the kernels' bodies left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def test_no_layout_glue_round_the_calls_at_the_gpt2_cells_shape():
    """At (8, 1024, 16, 64) bf16, forward and gradient: no pad, transpose
    or slice equation outside the three kernels, one body a kernel (its
    products: 2, 4, 3), and the counter's labels say what was built."""
    q = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16)
    before = _tile_choices()

    def out_and_grads(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, interpret=True), q, k, v)
        return (out, *vjp(g))

    jaxpr = jax.make_jaxpr(out_and_grads)(q, q, q, q).jaxpr
    eqns = list(_equations(jaxpr))
    names = [e.primitive.name for e in eqns]
    assert names.count("pallas_call") == 3, names
    glue = {"pad", "transpose", "slice", "dynamic_slice", "gather",
            "concatenate", "broadcast_in_dim"} & set(names)
    assert not glue, names
    products = sorted(len(_dot_operand_dtypes(e.params["jaxpr"]))
                      for e in eqns if e.primitive.name == "pallas_call")
    assert products == [2, 3, 4], products
    new = _new_tile_choices(before)
    assert len(new) == 3 and set(new.values()) == {1}, new
    for kernel in pa._KERNELS:
        (key,) = [k for k in new if f"kernel={kernel}" in k]
        for label in ("layout=packed", "heads_per_block=2", "d=64,", "dv=64",
                      "block_q=1024", "block_k=1024", "s=1024", "steps=128",
                      "steps_with_work=128"):
            assert label in key, (label, key)
