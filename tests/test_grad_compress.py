"""Compressed gradient synchronization (parallel/collectives.py
CompressedAllReduce + the engine wiring in parallel/data_parallel.py).

The correctness bar, per mode:
  - 'none' must be BYTE-IDENTICAL to the pre-compression path — the
    policy is pure dispatch, the original lax.pmean/psum_scatter lines
    are untouched, and TrainState gains only an empty pytree slot;
  - 'bf16' tracks fp32 to cast precision;
  - 'int8' + error feedback must CONVERGE like fp32 (the acceptance
    criterion: final loss within 5e-2 relative over >= 50 steps, and a
    strictly better trajectory than int8 without feedback) — per-step
    closeness is NOT the claim, telescoped-error closeness is;
  - the traffic accounting (analytic + HLO-derived) must show the 2x /
    ~4x payload reductions the modes exist for.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from tpu_sandbox.data import synthetic_mnist
from tpu_sandbox.data.mnist import normalize
from tpu_sandbox.models import ConvNet
from tpu_sandbox.parallel import CompressedAllReduce, DataParallel, PjitEngine
from tpu_sandbox.parallel.collectives import as_compress_policy, world_group
from tpu_sandbox.train import TrainState
from tpu_sandbox.train.checkpoint import ShardedCheckpoint

WORLD = 8


#: for the tests whose claim is a tolerance or a convergence bound
light = pytest.mark.usefixtures("light_compile")


@functools.cache  # nothing donates or mutates what it returns
def setup(lr=0.05, momentum=0.0, use_bn=False):
    model = ConvNet(use_bn=use_bn)
    tx = optax.sgd(lr, momentum=momentum) if momentum else optax.sgd(lr)
    state = TrainState.create(
        model, jax.random.key(0), jnp.zeros((1, 28, 28, 1)), tx)
    images, labels = synthetic_mnist(n=16, seed=0)
    return model, tx, state, normalize(images), labels.astype("int32")


# -- policy object ----------------------------------------------------------


def test_policy_validation():
    with pytest.raises(ValueError, match="not in"):
        CompressedAllReduce(mode="fp4")
    with pytest.raises(ValueError, match="block"):
        CompressedAllReduce(mode="int8", block=0)
    assert as_compress_policy(None).mode == "none"
    assert as_compress_policy("bf16").mode == "bf16"
    p = CompressedAllReduce(mode="int8")
    assert as_compress_policy(p) is p
    assert p.needs_residual
    assert not CompressedAllReduce(
        mode="int8", error_feedback=False).needs_residual
    assert not CompressedAllReduce(mode="bf16").needs_residual


def test_wire_bytes_accounting():
    """Analytic wire accounting: exact values for an evenly-divisible
    leaf, and the headline ratios at a production-sized leaf where block
    padding is negligible."""
    n = 2048  # divides WORLD * block exactly: no padding term
    none = CompressedAllReduce().wire_bytes([n], WORLD)
    bf16 = CompressedAllReduce(mode="bf16").wire_bytes([n], WORLD)
    int8 = CompressedAllReduce(mode="int8").wire_bytes([n], WORLD)
    assert none == {"total": 4 * n, "payload": 4 * n, "overhead": 0}
    assert bf16 == {"total": 2 * n, "payload": 2 * n, "overhead": 0}
    # chunk = 256, nb = 1: shot1 = 8*256 q + 8*4 scales, shot2 = 256 + 4
    assert int8["total"] == 8 * 256 + 8 * 4 + 256 + 4
    assert int8["payload"] == n + n // WORLD
    assert int8["overhead"] == int8["total"] - int8["payload"]

    big = 1 << 20
    est = CompressedAllReduce(mode="int8").wire_bytes([big], WORLD)
    # all-in wire ratio approaches 4x as padding/scales amortize; the
    # payload ratio is exactly 4 / (1 + 1/WORLD) = 3.56x at WORLD=8
    assert 4 * big / est["total"] > 3.4
    assert 4 * big / est["payload"] == pytest.approx(
        4 / (1 + 1 / WORLD), rel=1e-3)
    # bf16 is exactly half of fp32 whatever the leaf set
    sizes = [400, 16, 12800, 32, 15680, 10]
    assert (CompressedAllReduce(mode="bf16").wire_bytes(sizes, WORLD)["total"]
            * 2 == CompressedAllReduce().wire_bytes(sizes, WORLD)["total"])


# -- the quantized collective itself ----------------------------------------


def test_int8_block_pmean_error_bound(mesh8):
    """The compressed mean tracks the exact mean within the quantizer's
    per-block bound: |err| <= mean of block absmax / 127 per shot."""
    group = world_group(mesh8)
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.standard_normal((WORLD, 33, 77)), jnp.float32)
    exact = np.asarray(jnp.mean(vals, axis=0))
    policy = CompressedAllReduce(mode="int8", block=256,
                                 error_feedback=False)
    out = np.asarray(group.compressed_all_reduce(vals, policy))
    assert out.shape == vals.shape
    for r in range(1, WORLD):  # every rank computes the SAME mean
        np.testing.assert_array_equal(out[0], out[r])
    # two quantizations of ~N(0,1) data: a couple absmax/127 steps
    bound = 2.5 * float(np.abs(vals).max()) / 127.0
    assert float(np.abs(out[0] - exact).max()) < bound


@light
def test_int8_error_feedback_telescopes(mesh8):
    """Sum over steps of (compressed mean) + final residual/WORLD ==
    sum of exact means, to fp32 roundoff: the residual carries exactly
    what the quantizer dropped, so the error telescopes instead of
    accumulating — the whole reason error feedback exists."""

    policy = CompressedAllReduce(mode="int8", block=128)
    rng = np.random.default_rng(1)
    steps = [jnp.asarray(rng.standard_normal((WORLD, 19, 53)), jnp.float32)
             for _ in range(5)]

    def body(v, res):
        return policy.pmean(v[0], "data", WORLD, res[0])

    run = jax.jit(jax.shard_map(  # one program, not one a primitive a rank
        lambda v, r: tuple(x[None] for x in body(v, r)),
        mesh=mesh8, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data")), check_vma=False))

    res = jnp.zeros((WORLD, 19, 53), jnp.float32)
    got = np.zeros((19, 53), np.float64)
    want = np.zeros((19, 53), np.float64)
    for v in steps:
        mean, res = run(v, res)
        got += np.asarray(mean[0], np.float64)
        want += np.asarray(jnp.mean(v, axis=0), np.float64)
    # the residual's cross-rank sum is what is still owed to the mean
    got += np.asarray(jnp.sum(res, axis=0), np.float64) / WORLD
    np.testing.assert_allclose(got, want, atol=1e-5)


# -- DataParallel wiring ----------------------------------------------------


def _run_steps(dp, state, images, labels, n_steps):
    dstate = dp.shard_state(state)
    di, dl = dp.shard_batch(images, labels)
    losses = []
    for _ in range(n_steps):
        dstate, loss = dp.train_step(dstate, di, dl)
        losses.append(float(jnp.mean(loss)))
    return dstate, losses


def test_none_mode_bitwise_identical(mesh8):
    """grad_compress='none' (and the default ctor) is byte-for-byte the
    pre-compression engine: same params after 3 steps, and no residual
    state is materialized."""
    model, tx, state, images, labels = setup(momentum=0.9)
    base = DataParallel(model, tx, mesh8, donate=False)
    comp = DataParallel(model, tx, mesh8, donate=False, grad_compress="none")
    assert base.compress == comp.compress == CompressedAllReduce()
    s_base, l_base = _run_steps(base, state, images, labels, 3)
    s_comp, l_comp = _run_steps(comp, state, images, labels, 3)
    assert l_base == l_comp
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        s_base.params, s_comp.params)
    assert s_comp.grad_residual is None
    assert jax.tree.leaves(s_comp.grad_residual) == []


@light
def test_bf16_mode_tracks_fp32(mesh8):
    model, tx, state, images, labels = setup()
    ref = DataParallel(model, tx, mesh8, donate=False)
    bf = DataParallel(model, tx, mesh8, donate=False, grad_compress="bf16")
    s_ref, l_ref = _run_steps(ref, state, images, labels, 3)
    s_bf, l_bf = _run_steps(bf, state, images, labels, 3)
    assert s_bf.grad_residual is None  # bf16 is stateless
    np.testing.assert_allclose(l_bf, l_ref, rtol=2e-2)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-3), s_bf.params,
        s_ref.params)


@light
@pytest.mark.parametrize(
    "block",
    [256,
     # the large-block twin re-proves the margin-grows-with-block-size
     # corollary; one full 3x55-step convergence run is enough for tier-1
     pytest.param(4096, marks=pytest.mark.slow)])
def test_int8_ef_convergence_tracks_fp32(mesh8, block):
    """THE acceptance criterion: over >= 50 steps (momentum SGD, the
    reference's training config), int8 + error feedback lands on the
    fp32 final loss (5e-2 relative, abs floor 1e-3 since all runs
    converge to ~1e-7 from an initial ~2.3) AND tracks the fp32 loss
    trajectory strictly better than int8 without feedback — 2.3x /
    3.1x mean-deviation margins at these seeds, growing with block
    size exactly as the error-feedback theory predicts. (In plateau
    regimes where quantization error is below trajectory noise the
    ordering is a coin flip — the claim is about the converging
    regime, which is what this pins.)"""
    model, tx, state, images, labels = setup(momentum=0.9)
    n_steps = 55
    _, l_fp32 = _run_steps(
        DataParallel(model, tx, mesh8, donate=False),
        state, images, labels, n_steps)
    s_ef, l_ef = _run_steps(
        DataParallel(model, tx, mesh8, donate=False,
                     grad_compress=CompressedAllReduce(
                         mode="int8", block=block)),
        state, images, labels, n_steps)
    _, l_raw = _run_steps(
        DataParallel(model, tx, mesh8, donate=False,
                     grad_compress=CompressedAllReduce(
                         mode="int8", block=block, error_feedback=False)),
        state, images, labels, n_steps)

    assert abs(l_ef[-1] - l_fp32[-1]) <= max(5e-2 * l_fp32[-1], 1e-3)
    dev_ef = float(np.mean(np.abs(np.array(l_ef) - np.array(l_fp32))))
    dev_raw = float(np.mean(np.abs(np.array(l_raw) - np.array(l_fp32))))
    assert dev_ef < dev_raw, (dev_ef, dev_raw)
    # the residual exists, is per-rank, and is doing real work
    res_leaves = jax.tree.leaves(s_ef.grad_residual)
    assert res_leaves and all(r.shape[0] == WORLD for r in res_leaves)
    assert any(float(jnp.abs(r).max()) > 0 for r in res_leaves)


def test_zero_composes_with_int8(mesh8):
    """ZeRO-1 + int8 takes the full compressed mean then slices each
    rank's block — elementwise update math, so it must match plain DP
    with the same compression to fp reassociation."""
    model, tx, state, images, labels = setup(momentum=0.9)
    s_plain, l_plain = _run_steps(
        DataParallel(model, tx, mesh8, donate=False, grad_compress="int8"),
        state, images, labels, 4)
    s_zero, l_zero = _run_steps(
        DataParallel(model, tx, mesh8, donate=False, grad_compress="int8",
                     zero=True),
        state, images, labels, 4)
    np.testing.assert_allclose(l_zero, l_plain, rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7),
        s_zero.params, s_plain.params)


def test_residual_checkpoint_round_trip(mesh8, tmp_path):
    """Crash-resume equivalence in-process: 2 steps -> sharded save
    (residual rides as a 'shard0' leaf) -> restore through the
    checkpoint_template slot -> 2 more steps == 4 uninterrupted steps,
    bitwise, residual included."""
    model, tx, state, images, labels = setup(momentum=0.9)
    dp = DataParallel(model, tx, mesh8, donate=False, grad_compress="int8")
    di, dl = dp.shard_batch(images, labels)

    dstate = dp.shard_state(state)
    for _ in range(4):
        dstate, _ = dp.train_step(dstate, di, dl)
    ref = dstate  # uninterrupted 4 steps

    dstate = dp.shard_state(state)
    for _ in range(2):
        dstate, _ = dp.train_step(dstate, di, dl)
    spec = dp.checkpoint_spec(dstate)
    assert all(
        s == "shard0"
        for s in jax.tree.leaves(spec.grad_residual))
    ck = ShardedCheckpoint(tmp_path / "ck", rank=0, world_size=1,
                           verbose=False, commit_timeout=5.0)
    assert ck.save(dstate.host_view(), spec, 2, epoch=0, offset=0)

    template = dp.checkpoint_template(
        TrainState.create(model, jax.random.key(0),
                          jnp.zeros((1, 28, 28, 1)), tx))
    restored, meta = ck.restore(template)
    assert meta["step"] == 2
    resumed = dp.shard_state(restored, stats_expanded=True)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        resumed.grad_residual, dstate.grad_residual)
    for _ in range(2):
        resumed, _ = dp.train_step(resumed, di, dl)
    for name in ("params", "opt_state", "grad_residual"):
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)),
            getattr(resumed, name), getattr(ref, name))


def test_template_without_residual_slot_would_drop_it(mesh8):
    """checkpoint_template is what guards against the silent-drop
    failure mode: it attaches the residual slot iff the policy needs
    one, and is a no-op otherwise."""
    model, tx, state, _, _ = setup()
    dp_none = DataParallel(model, tx, mesh8, donate=False)
    assert dp_none.checkpoint_template(state).grad_residual is None
    dp = DataParallel(model, tx, mesh8, donate=False, grad_compress="int8")
    t = dp.checkpoint_template(state)
    jax.tree.map(
        lambda r, p: (r.shape == np.shape(p)
                      and float(np.abs(r).max()) == 0.0),
        t.grad_residual, t.params)
    # idempotent: a template that already has the slot is left alone
    assert dp.checkpoint_template(t) is t


# -- traffic accounting against the compiled artifact -----------------------


def test_hlo_collective_bytes_drop_under_int8(mesh8):
    """The compiled SPMD step's cross-replica collective operand bytes:
    int8 swaps the fp32 all-reduce for an int8 all_to_all + all_gather
    and must land well under the fp32 bytes. (bf16 is asserted on the
    analytic path only — XLA:CPU upcasts the bf16 all-reduce operand to
    f32, so its HLO bytes are a CPU artifact.)"""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    from hlo_traffic import collective_bytes

    model, tx, state, images, labels = setup(momentum=0.9)
    got = {}
    for mode in ("none", "int8"):
        dp = DataParallel(model, tx, mesh8, donate=False,
                          grad_compress=mode)
        dstate = dp.shard_state(state)
        text = dp.lower_step(
            dstate, *dp.shard_batch(images, labels)).compile().as_text()
        got[mode] = collective_bytes(text)
    assert got["none"]["by_opcode"].keys() == {"all-reduce"}
    assert {"all-to-all", "all-gather"} <= got["int8"]["by_opcode"].keys()
    assert "all-reduce" not in got["int8"]["by_opcode"]
    # ~2.6x on this deliberately tiny model (block padding dominates its
    # small leaves); the analytic path in test_wire_bytes_accounting
    # pins the asymptotic ~4x
    assert got["int8"]["total"] < 0.45 * got["none"]["total"]


# -- PjitEngine wiring ------------------------------------------------------


def test_pjit_engine_carries_a_residual_it_is_handed(mesh8):
    """``PjitEngine`` compresses nothing and feeds nothing back, but a state
    that an int8 ``DataParallel`` run left (one residual a rank) goes
    through its step with the residual as it came, and the step is the
    one a state without residual takes."""
    model, tx, state, images, labels = setup()
    dp = DataParallel(model, tx, mesh8, donate=False, grad_compress="int8")
    left, _ = _run_steps(dp, state, images, labels, 1)
    assert any(float(jnp.abs(r).max()) > 0
               for r in jax.tree.leaves(left.grad_residual))
    eng = PjitEngine(model, tx, mesh8, donate=False)
    handed = jax.tree.map(np.asarray, left)
    new, loss = eng.train_step(
        eng.shard_state(handed), *eng.shard_batch(images, labels))
    jax.tree.map(np.testing.assert_array_equal,
                 new.grad_residual, handed.grad_residual)
    bare = handed.replace(grad_residual=None)
    eng = PjitEngine(model, tx, mesh8, donate=False)  # a step a structure
    _, l_bare = eng.train_step(
        eng.shard_state(bare), *eng.shard_batch(images, labels))
    assert float(loss) == float(l_bare)
