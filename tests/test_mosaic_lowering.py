"""Mosaic (TPU) lowering checks for every Pallas kernel — WITHOUT a TPU.

VERDICT r01 weak #7: interpret-mode tests can't see Mosaic lowering
failures (r01's kernels indeed failed on the real chip with a block-shape
constraint: the last two block dims must be (8k, 128m)-aligned or equal
the array dims — caught only by the on-chip bench). Mosaic lowering runs
at MLIR-lowering time, not execution time, so ``lower(lowering_platforms=
("tpu",))`` on the CPU backend exercises the exact check that failed,
machine-independent. These tests pin it for the fwd kernel, both backward
kernels, the lse/partial variants the ring engines use, and the CE kernel,
across the shape classes the bench exercises (block-aligned, non-multiple
sequence lengths, bf16, head_dim below the lane width).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sandbox.ops.losses import cross_entropy_loss  # noqa: F401 (parity)
from tpu_sandbox.ops.pallas_attention import (
    flash_attention,
    flash_attention_lse,
    make_flash_bwd_lse,
)
from tpu_sandbox.ops.pallas_ce import pallas_cross_entropy


def _lower_tpu(fn, *args):
    jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize(
    "b,s,h,d,dt",
    [
        (2, 512, 4, 64, jnp.float32),
        (2, 384, 4, 64, jnp.bfloat16),   # non-multiple-of-block S
        (1, 1024, 8, 128, jnp.bfloat16),
    ],
)
def test_flash_attention_fwd_bwd_lowers_for_tpu(b, s, h, d, dt):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)), dt)
               for _ in range(3))

    def loss(q, k, v):
        out = flash_attention(q, k, v, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


def test_flash_lse_and_partial_bwd_lower_for_tpu():
    """The ring engines' building blocks: forward-with-lse at unequal
    q/kv lengths + the per-hop partial backward factory."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 384, 2, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 384, 2, 64)), jnp.bfloat16)

    def fwd(q, k, v):
        out, lse = flash_attention_lse(q, k, v, interpret=False,
                                       kv_offset=128)
        return out.astype(jnp.float32).sum() + lse.sum()

    _lower_tpu(fwd, q, k, v)

    def partial_bwd(q, k, v):
        out, lse = flash_attention_lse(q, k, v, interpret=False)
        g = jnp.ones_like(out)
        fn = make_flash_bwd_lse(q, out.astype(q.dtype), g.astype(q.dtype),
                                lse, interpret=False)
        dq, dk, dv = fn(k, v, 0)
        return dq.sum() + dk.sum() + dv.sum()

    _lower_tpu(partial_bwd, q, k, v)


CELL_SHAPES = [
    # name, B, S, H, D (q.k), Dv, scale, heads a block (None: the padded
    # form): the LM cells' attention in bf16, two heads of each, and the
    # packed form's two cells whole
    ("gpt2m_s1024_64_64", 1, 1024, 2, 64, 64, None, 2),
    ("xing4_s4096_192_128", 1, 4096, 2, 192, 128, 192 ** -0.5 * 2.00474,
     None),
    ("gpt2m_cell_packed", 8, 1024, 16, 64, 64, None, 2),
    ("nemotron3s_cell_packed", 1, 8192, 16, 128, 128, None, 1),
]


@pytest.mark.parametrize("name,b,s,h,d,dv,scale,group", CELL_SHAPES,
                         ids=[c[0] for c in CELL_SHAPES])
def test_flash_attention_at_the_cells_shapes_lowers_for_tpu(
        name, b, s, h, d, dv, scale, group):
    """Forward and backward at the tiles the rule picks for the cells'
    own shapes (PR 28): far larger blocks than the 128 x 128 the cases
    above were written for, index maps that read scalar prefetch, and a
    ``vmem_limit_bytes``; in the form the shape rule gives each (packed at
    64 / 64 and 128 / 128: column blocks of [B, S, H·D], the lane
    selection of a head in its group; padded at 192 / 128)."""
    from tpu_sandbox.ops.pallas_attention import (_heads_per_block, _pad_len,
                                                  choose_tiles)

    sp = _pad_len(s)
    for kernel in ("fwd", "dkv", "dq"):
        bq, bk = choose_tiles(kernel, sp, sp, -(-d // 128) * 128,
                              -(-dv // 128) * 128, 2)
        assert bq >= 512 and bk >= 512, (kernel, bq, bk)
    q, k = (jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16),) * 2
    v = jax.ShapeDtypeStruct((b, s, h, dv), jnp.bfloat16)
    assert _heads_per_block(q, k, v, None, None) == group

    def loss(q, k, v):
        out = flash_attention(q, k, v, scale=scale, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


MHC_SHAPES = [
    # name, n, C, B, S, dtype: the Xing4 cell's streams, and a float32 shape
    # (the products then stay at HIGHEST, the row groups are 8 rows)
    ("xing4_cell_bf16", 4, 3584, 2, 4096, jnp.bfloat16),
    ("float32", 4, 512, 2, 384, jnp.float32),
]


@pytest.mark.parametrize("name,n,c,b,s,dtype", MHC_SHAPES,
                         ids=[m[0] for m in MHC_SHAPES])
def test_mhc_kernels_lower_for_tpu(name, n, c, b, s, dtype):
    """``ops/pallas_mhc.py``: ``pre`` and ``post`` forward and backward (four
    kernels) at the tiles the rule picks, with the lane rolls that add and
    fill the slots, products contracted over the tokens, a block held in
    one buffer and an aliased cotangent."""
    from tpu_sandbox.ops import pallas_mhc

    k = n * n + 2 * n
    x = jax.ShapeDtypeStruct((n, b, s, c), dtype)
    phi = jax.ShapeDtypeStruct((n, c, k), jnp.float32)
    alpha = jax.ShapeDtypeStruct((), jnp.float32)
    bias = jax.ShapeDtypeStruct((n,), jnp.float32)

    def loss(x, phi, alpha, bias):
        u, proj, kept = pallas_mhc.pre(x, phi, alpha, bias, eps=1e-6,
                                       dtype=dtype, interpret=False)
        h_res = jax.nn.sigmoid(proj[2 * n:]).reshape(n, n, b, s)
        out = pallas_mhc.post(kept, u, h_res, proj[n:2 * n], interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).trace(
        x, phi, alpha, bias).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 4


SHORT_CONV_SHAPES = [
    # name, B, S, the array's width, C, start, bias, operand, result: the
    # two hybrid cells' calls (a width that is no lane multiple goes through
    # the pair that reads the tokens on the lanes), a slice read in place
    # by the row-major pair, and a float32 operand in two sequences
    ("nemotron3s_xbc_in_in_proj", 1, 8192, 9280, 5120, 4096, True,
     jnp.bfloat16, jnp.bfloat16),
    ("olmoh_keys_2880", 1, 8192, 2880, 2880, 0, False, jnp.bfloat16,
     jnp.float32),
    ("olmoh_values_5760", 1, 8192, 5760, 5760, 0, False, jnp.bfloat16,
     jnp.bfloat16),
    ("a_slice_of_rows", 1, 8192, 10240, 5120, 4096, True,
     jnp.bfloat16, jnp.bfloat16),
    ("float32_b2", 2, 384, 512, 512, 0, True, jnp.float32, jnp.float32),
    ("float32_b2_lanes", 2, 384, 520, 512, 8, True, jnp.float32, jnp.float32),
]


@pytest.mark.parametrize("name,b,s,width,c,start,bias,dtype,result",
                         SHORT_CONV_SHAPES,
                         ids=[m[0] for m in SHORT_CONV_SHAPES])
def test_short_conv_kernels_lower_for_tpu(name, b, s, width, c, start, bias,
                                          dtype, result):
    """``ops/pallas_short_conv.py``: forward and backward at the tiles the
    rule picks: a channel offset in the index map, sublane-shifted
    (lane-shifted where the tokens fill the lanes) slices of a row group, a
    halo block whose index is clamped, a result block that stays over the
    sequence's steps."""
    from tpu_sandbox.ops import pallas_short_conv

    x = jax.ShapeDtypeStruct((b, s, width), dtype)
    taps = jax.ShapeDtypeStruct((4, c), jnp.float32)
    bvec = jax.ShapeDtypeStruct((c,), jnp.float32) if bias else None

    def loss(x, taps, bvec):
        y = pallas_short_conv.short_conv(
            x, taps, bvec, start=start, dtype=result, interpret=False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(
        x, taps, bvec).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    # the pair the cell runs, by the array's width
    pair = "_kernel_cf" if width % 128 else "_kernel"
    assert re.findall(r'kernel_name = "_(?:fwd|bwd)(\w+)"', text) == [pair] * 2


@pytest.mark.parametrize("shape", [(1, 30, 128, 64, 64), (1, 30, 4, 64, 64)],
                         ids=["olmoh_step", "olmoh_float32_check"])
def test_tri_inverse_kernels_lower_for_tpu(shape):
    """``ops/pallas_tri_inverse.py``: the delta rule's triangular inverse
    and its cotangent at the tile the rule picks, the leading axes as the
    rule hands them over (squeezed in the ``BlockSpec``): a lane gather in
    two dimensions on a 64-lane value, sublane-tile row slices, batched bf16
    products of float32's pieces, and two float32 products at ``HIGHEST``,
    one of them contracting the rows of both operands."""
    from tpu_sandbox.ops import pallas_tri_inverse as ti

    tile = ti.choose_tile(shape)
    a = jax.ShapeDtypeStruct(shape, jnp.float32)

    def both(a, dt):
        t = ti.tri_inverse_fwd(a, tile=tile, interpret=False)
        return t, ti.tri_inverse_bwd(t, dt, tile=tile, interpret=False)

    text = jax.jit(both).trace(a, a).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    assert re.findall(r'kernel_name = "(\w+)"', text) == [
        "tri_inverse_fwd", "tri_inverse_bwd"]


@pytest.mark.parametrize("blocks", [{}, {"block_q": 128, "block_k": 128}],
                         ids=["rule_tiles", "the_rings_128"])
def test_flash_lse_and_partial_bwd_with_traced_offsets_lower_for_tpu(blocks):
    """The ring's calls as the ring makes them: the offsets are traced
    scalars (``idx * s_loc``), which reach the index maps' clamp as scalar
    prefetch; forward-with-lse and the per-hop backward, at the ring's own
    explicit blocks and at the rule's."""
    q, k, v = (jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.bfloat16),) * 3

    def hop(q, k, v, q_off, kv_off):
        out, lse = flash_attention_lse(q, k, v, interpret=False,
                                       q_offset=q_off, kv_offset=kv_off,
                                       **blocks)
        fn = make_flash_bwd_lse(q, out.astype(q.dtype), q, lse,
                                q_offset=q_off, interpret=False, **blocks)
        dq, dk, dv = fn(k, v, kv_off)
        return out.sum() + lse.sum() + dq.sum() + dk.sum() + dv.sum()

    off = jax.ShapeDtypeStruct((), jnp.int32)
    _lower_tpu(hop, q, k, v, off, off)


def test_pallas_ce_lowers_for_tpu():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(64, 32000)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 32000, size=(64,)), jnp.int32)
    _lower_tpu(
        lambda lg, lb: pallas_cross_entropy(lg, lb, interpret=False),
        logits, labels,
    )


@pytest.mark.parametrize("n,c", [(256, 32768), (64, 128 * 1024)])
def test_pallas_ce_reduced_blocks_lower_for_tpu(n, c):
    """The VMEM-budgeted row blocks (32 rows at 32k vocab, the 8-row floor
    at 128k) must still lower under Mosaic — the fixed 128-row block OOMed
    scoped VMEM at LM scale (found by a chipless v5e AOT compile)."""
    from tpu_sandbox.ops.pallas_ce import _block_rows
    from tpu_sandbox.ops.pallas_common import round_up

    assert _block_rows(round_up(c, 128)) is not None
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(n, c)), jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, c, size=(n,)), jnp.int32)
    _lower_tpu(
        lambda lg, lb: pallas_cross_entropy(lg, lb, interpret=False),
        logits, labels,
    )


def test_pipeline_flash_stage_lowers_for_tpu():
    """The flash kernel reached through PipelineParallel's stage compute —
    jax.checkpoint(lax.scan over stacked per-layer params) around the
    Pallas call, fwd AND bwd (VERDICT r02 weak #4 done-criterion). Scoped
    to the stage computation: under shard_map JAX dispatches pallas_call
    lowering on the ACTUAL backend, so the full shard_map'd step cannot be
    cross-lowered for TPU from CPU ("Only interpret mode is supported on
    CPU backend"); the collectives around the stage are kernel-free and
    covered by the interpret-mode execution tests above this one."""
    import optax

    from tpu_sandbox.models.transformer import TransformerConfig
    from tpu_sandbox.ops.pallas_attention import flash_attention_fn
    from tpu_sandbox.parallel.pipeline import PipelineParallel
    from tpu_sandbox.runtime.mesh import make_mesh

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=4,
                            d_ff=64, max_len=256, dtype=jnp.bfloat16)
    mesh = make_mesh({"data": 2, "pipe": 4})
    pp = PipelineParallel(cfg, optax.sgd(0.1), mesh, microbatches=2,
                          donate=False,
                          attention_fn=flash_attention_fn(interpret=False))
    # init eagerly EXECUTES the model on CPU, where interpret=False would
    # fail — init through the dense twin instead (params are
    # attention_fn-independent, same tree either way)
    pp_dense = PipelineParallel(cfg, optax.sgd(0.1), mesh, microbatches=2,
                                donate=False)
    tokens = np.zeros((4, 256), np.int32)
    state = pp_dense.init_state(jax.random.key(0), jnp.asarray(tokens))
    # one stage's layer stack, as the tick loop slices it: [v, lps, ...] -> c=0
    stage = jax.tree.map(lambda x: x[0, 0], state.params["stages"])
    h = jnp.zeros((2, 256, cfg.d_model), cfg.dtype)

    def stage_loss(stage, h):
        out = jax.checkpoint(pp._stage_apply)(stage, h)
        return jnp.sum(out.astype(jnp.float32))

    _lower_tpu(jax.grad(stage_loss, argnums=(0, 1)), stage, h)


@pytest.mark.parametrize("c,co", [(16, 256), (64, 128)])
def test_pallas_conv_lowers_for_tpu(c, co):
    """The 3x3 s2d conv kernels (ops/pallas_conv.py) at the ConvNet's real
    per-layer widths (conv1: 16->256, conv2: 64->128, W=750), fwd + the
    full VJP (flipped-weight dgrad + fused wgrad/dbias) — manual-DMA halo
    strips and scratch accumulators must pass real Mosaic checks."""
    from tpu_sandbox.ops.pallas_conv import conv3x3

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((1, 20, 750, c)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((3, 3, c, co)), jnp.bfloat16)
    b = jnp.zeros((co,), jnp.bfloat16)

    def loss(x, k, b):
        return jnp.sum(conv3x3(x, k, b, False).astype(jnp.float32))

    _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), x, k, b)

    # the TPU-default train path runs the STATS variant (scratch
    # accumulators, pl.when init/emit, [1,co] stats outputs) — lower it too
    from tpu_sandbox.ops.pallas_conv import conv3x3_stats

    def loss_stats(x, k, b):
        y, s, ss = conv3x3_stats(x, k, b, False)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(s) + jnp.sum(ss)

    _lower_tpu(jax.grad(loss_stats, argnums=(0, 1, 2)), x, k, b)


@pytest.mark.parametrize("blk,co,w", [(4, 16, 752), (2, 32, 752)])
def test_fused_bn_tail_lowers_for_tpu(blk, co, w):
    """The fused BN-apply+relu+pool kernels (ops/pallas_bn_tail.py) at the
    s2d ConvNet's real lane widths (C=256 and C=128) — forward and both
    backward kernels."""
    from tpu_sandbox.ops.pallas_bn_tail import fused_bn_relu_pool

    rng = np.random.default_rng(4)
    c = blk * blk * co
    y = jnp.asarray(rng.standard_normal((2, 10, w, c)), jnp.bfloat16)
    gamma = jnp.ones(co, jnp.float32)
    beta = jnp.zeros(co, jnp.float32)

    def loss(y, gamma, beta):
        out, _, _ = fused_bn_relu_pool(y, gamma, beta, co, blk, 1e-5, False)
        return jnp.sum(out.astype(jnp.float32))

    _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), y, gamma, beta)


@pytest.mark.parametrize("restage", ["gt", "auto"])
@pytest.mark.parametrize("c,co", [(16, 256), (64, 128)])
def test_pallas_conv_t_lowers_for_tpu(c, co, restage, monkeypatch):
    """VERDICT r03 next-6: the TRANSPOSED conv kernels
    (ops/pallas_conv_t.py) — the plan `auto` resolves to on TPU — at the
    production widths (conv1: 16->256, conv2: 64->128, W=750), fwd + the
    full VJP (flipped-weight dgrad + fused wgrad/dbias) and the stats
    variant, under real Mosaic lowering. Both wgrad restage variants
    (r05: explicit-gT native dot vs Mosaic's own lane-lane handling)."""
    from tpu_sandbox.ops.pallas_conv_t import conv3x3_t, conv3x3_t_stats

    monkeypatch.setenv("TPU_SANDBOX_WGRAD_RESTAGE", restage)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((1, 20, c, 750)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((3, 3, c, co)), jnp.bfloat16)
    b = jnp.zeros((co,), jnp.bfloat16)

    def loss(x, k, b):
        return jnp.sum(conv3x3_t(x, k, b, False).astype(jnp.float32))

    _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), x, k, b)

    def loss_stats(x, k, b):
        y, s, ss = conv3x3_t_stats(x, k, b, False)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(s) + jnp.sum(ss)

    _lower_tpu(jax.grad(loss_stats, argnums=(0, 1, 2)), x, k, b)


@pytest.mark.parametrize("blk,co", [(4, 16), (2, 32)])
def test_fused_bn_tail_t_lowers_for_tpu(blk, co):
    """The transposed fused BN/ReLU/pool pair (ops/pallas_bn_tail_t.py)
    at production channel heights (C=256, C=128) and W=750 — forward and
    both backward kernels."""
    from tpu_sandbox.ops.pallas_bn_tail_t import fused_bn_relu_pool_t

    rng = np.random.default_rng(10)
    c = blk * blk * co
    y = jnp.asarray(rng.standard_normal((2, 10, c, 750)), jnp.bfloat16)
    gamma = jnp.ones(co, jnp.float32)
    beta = jnp.zeros(co, jnp.float32)

    def loss(y, gamma, beta):
        out, _, _ = fused_bn_relu_pool_t(y, gamma, beta, co, blk, 1e-5,
                                         False)
        return jnp.sum(out.astype(jnp.float32))

    _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), y, gamma, beta)


def test_s2dt_train_step_lowers_for_tpu(monkeypatch):
    """The INTEGRATED default-TPU-plan train step — ConvNetS2DT with
    fused tails + conv-fused stats, the fused input stage, the in-layout
    fc, SGD — lowered for TPU at the real 3000x3000 geometry (bs=1).
    A lowering regression in the production plan fails HERE, not on the
    chip (VERDICT r03 next-6 done-criterion)."""
    import optax

    from tpu_sandbox.models.convnet_s2d_t import ConvNetS2DT
    from tpu_sandbox.train import TrainState, make_train_step

    monkeypatch.setenv("TPU_SANDBOX_FORCE_COMPILED_KERNELS", "1")
    model = ConvNetS2DT(dtype=jnp.bfloat16, fused_tail=True)
    tx = optax.sgd(1e-4)
    state = jax.eval_shape(
        lambda: TrainState.create(
            model, jax.random.key(0),
            jnp.zeros((1, 3000, 3000, 1), jnp.bfloat16), tx))
    step = make_train_step(model, tx, image_size=(3000, 3000),
                           donate=False)
    imgs = jax.ShapeDtypeStruct((1, 28, 28, 1), jnp.float32)
    labs = jax.ShapeDtypeStruct((1,), jnp.int32)
    jax.jit(step).trace(state, imgs, labs).lower(
        lowering_platforms=("tpu",))


@pytest.mark.parametrize("restage", ["gt", "auto"])
def test_sparse_tap_conv1_lowers_for_tpu(restage, monkeypatch):
    """The r04 sparse-tap conv1 (ops/pallas_conv5_t.py) at the
    production geometry (16 -> 256, W=750): fwd, stats, and the fused
    wgrad/dbias under real Mosaic — both wgrad restage variants."""
    from tpu_sandbox.ops.pallas_conv5_t import conv1_s2d_t, conv1_s2d_t_stats

    monkeypatch.setenv("TPU_SANDBOX_WGRAD_RESTAGE", restage)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((1, 20, 16, 750)), jnp.bfloat16)
    k5 = jnp.asarray(rng.standard_normal((5, 5, 1, 16)), jnp.bfloat16)
    b = jnp.zeros((16,), jnp.bfloat16)

    def loss(x, k, b):
        return jnp.sum(conv1_s2d_t(x, k, b, False).astype(jnp.float32))

    _lower_tpu(jax.grad(loss, argnums=(1, 2)), x, k5, b)

    def loss_stats(x, k, b):
        y, s, ss = conv1_s2d_t_stats(x, k, b, False)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(s) + jnp.sum(ss)

    _lower_tpu(jax.grad(loss_stats, argnums=(1, 2)), x, k5, b)


def test_pallas_fc_dgrad_lowers_for_tpu():
    """The fc head's two kernels (ops/pallas_fc_t.py: the flatten and the
    input-grad with its in-VMEM un-flatten) at production geometry: K=10
    classes, C=32, W=750 (rows stored at lane offsets that are no
    multiple of 128), bs=16, under real Mosaic."""
    from tpu_sandbox.ops.pallas_fc_t import fc_t

    rng = np.random.default_rng(12)
    y = jnp.asarray(rng.standard_normal((16, 30, 32, 750)), jnp.bfloat16)
    kernel = jnp.asarray(
        rng.standard_normal((30 * 32 * 750, 10)) * 1e-4, jnp.float32)
    bias = jnp.zeros((10,), jnp.float32)

    def loss(y, kernel, bias):
        return jnp.sum(fc_t(y, kernel, bias, jnp.bfloat16, False)
                       .astype(jnp.float32))

    _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), y, kernel, bias)


def test_conv1_tail_fused_bwd_lowers_for_tpu():
    """The r05 fused conv1+tail backward (ops/pallas_conv1_tail_t.py) at
    production geometry (16 -> 256, pool to 64, W=750): the combined
    tail-dy-recompute + sparse wgrad kernel, plus the unchanged reduce
    pass, under real Mosaic."""
    from tpu_sandbox.ops.pallas_conv1_tail_t import conv1_tail_t

    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.standard_normal((1, 20, 16, 750)), jnp.bfloat16)
    k5 = jnp.asarray(rng.standard_normal((5, 5, 1, 16)), jnp.bfloat16)
    cb = jnp.zeros((16,), jnp.bfloat16)
    gamma = jnp.ones((16,), jnp.float32)
    beta = jnp.zeros((16,), jnp.float32)

    def loss(k5, cb, gamma, beta):
        out, _, _ = conv1_tail_t(x, k5, cb, gamma, beta, 16, 4, 1e-5,
                                 False)
        return jnp.sum(out.astype(jnp.float32))

    _lower_tpu(jax.grad(loss, argnums=(0, 1, 2, 3)), k5, cb, gamma, beta)
