"""The Pallas fc head (ops/pallas_fc_t.py) == the plain einsum path it
replaces — forward (Pallas flatten + XLA dot against kernel.T),
input-grad (the Pallas kernel), weight/bias grads (XLA dots in the
parameter's layout) — in interpret mode, over the geometries of GEOMS;
Mosaic lowering at production geometry is pinned in
tests/test_mosaic_lowering.py, and the compiled head's freedom from
weight-sized loops in test_compiled_head_has_no_weight_sized_loop."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.helpers import run_child
from tpu_sandbox.ops.pallas_fc_t import (
    _pick_block_h,
    fc_dgrad_t,
    fc_flatten_t,
    fc_t,
)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (n, h, c, w): lane-aligned rows in one block per h row; the benchmark's
#: reference slab (w = 750 is no multiple of 128 and a block must hold
#: two h rows to end on a lane tile: two grid blocks); a batch that is
#: not the published 5 and more than one sublane tile deep, with
#: unaligned rows; a map no block of which is tile-aligned (one block
#: holds it whole)
GEOMS = [(3, 8, 16, 32), (2, 4, 32, 750), (9, 6, 8, 48), (5, 3, 4, 10)]
geoms = pytest.mark.parametrize("geom", GEOMS, ids=lambda g: "x".join(
    map(str, g)))


#: for the tests whose claim is a tolerance against the einsum path
light = pytest.mark.usefixtures("light_compile")


def test_block_picker():
    assert [_pick_block_h(h, c, w) for _, h, c, w in GEOMS] == [1, 2, 1, 3]
    assert _pick_block_h(750, 32, 750) == 2  # production: 375 lane tiles


def _case(n=3, h=8, c=16, w=32, k=10, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    y = jnp.asarray(rng.standard_normal((n, h, c, w)), dtype)
    kernel = jnp.asarray(
        0.01 * rng.standard_normal((h * c * w, k)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(k), jnp.float32)
    return y, kernel, bias


def _einsum_ref(y, kernel, bias, dtype):
    n, h, c, w = y.shape
    k4 = kernel.astype(dtype).reshape(h, c, w, kernel.shape[-1])
    return jnp.einsum("nhcw,hcwk->nk", y, k4) + bias.astype(dtype)


@geoms
def test_forward_matches_einsum(geom):
    n, h, c, w = geom
    y, kernel, bias = _case(n, h, c, w)
    # the flatten kernel is the reshape, bit for bit
    np.testing.assert_array_equal(np.asarray(fc_flatten_t(y)),
                                  np.asarray(y).reshape(n, -1))
    # the two paths sum the features in different orders
    np.testing.assert_allclose(
        np.asarray(fc_t(y, kernel, bias, jnp.float32)),
        np.asarray(_einsum_ref(y, kernel, bias, jnp.float32)),
        rtol=1e-5, atol=1e-5)


@light
@geoms
def test_grads_match_einsum_autodiff(geom):
    """All three cotangents (dy via the Pallas kernel, dkernel/dbias via
    XLA dots in the parameter's layout) must match the plain path."""
    y, kernel, bias = _case(*geom, seed=1)

    def loss_pallas(y, kernel, bias):
        return jnp.sum(fc_t(y, kernel, bias, jnp.float32) ** 2)

    def loss_ref(y, kernel, bias):
        return jnp.sum(_einsum_ref(y, kernel, bias, jnp.float32) ** 2)

    gp = jax.jit(jax.grad(loss_pallas, argnums=(0, 1, 2)))(y, kernel, bias)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(y, kernel, bias)
    for a, b, nm in zip(gp, gr, ("dy", "dkernel", "dbias")):
        scale = float(np.max(np.abs(np.asarray(b)))) or 1.0
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0, atol=2e-5 * scale, err_msg=nm)


@light
@geoms
def test_dgrad_kernel_alone(geom):
    """fc_dgrad_t == the broadcast-sum it replaces, incl. bf16 output
    rounding."""
    rng = np.random.default_rng(2)
    (n, h, c, w), k = geom, 10
    g = jnp.asarray(rng.standard_normal((n, k)), jnp.float32)
    wt = jnp.asarray(rng.standard_normal((k, h, c, w)), jnp.float32)
    dy = fc_dgrad_t(g, wt.reshape(k, -1), (h, c, w), jnp.bfloat16)
    ref = jnp.einsum("nk,khcw->nhcw", g, wt).astype(jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(dy, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=1e-2)


@light
@geoms
def test_bf16_compute_path(geom):
    """bf16 y (the production compute dtype): fc_t tracks the einsum
    path within bf16 rounding."""
    y, kernel, bias = _case(*geom, dtype=jnp.bfloat16, seed=3)

    def loss_pallas(kernel):
        return jnp.sum(fc_t(y, kernel, bias, jnp.bfloat16) ** 2)

    def loss_ref(kernel):
        return jnp.sum(_einsum_ref(y, kernel, bias, jnp.bfloat16) ** 2)

    gp = jax.jit(jax.grad(loss_pallas))(kernel)
    gr = jax.jit(jax.grad(loss_ref))(kernel)
    scale = float(np.max(np.abs(np.asarray(gr)))) or 1.0
    assert float(np.max(np.abs(np.asarray(gp - gr)))) / scale < 5e-3


@light
@geoms
def test_kill_switch_einsum_path(monkeypatch, geom):
    """TPU_SANDBOX_NO_PALLAS_FC=1 must keep working (the emergency
    fallback if the fc kernels fail on the runtime at hand, and these
    tests' oracle): the model's einsum branch matches the Pallas-path
    logits and grads to tolerance."""
    from tpu_sandbox.models.convnet_s2d_t import _DenseT

    rng = np.random.default_rng(4)
    y = jnp.asarray(rng.standard_normal(geom), jnp.float32)

    def run(env):
        if env:
            monkeypatch.setenv("TPU_SANDBOX_NO_PALLAS_FC", "1")
        else:
            monkeypatch.delenv("TPU_SANDBOX_NO_PALLAS_FC", raising=False)
        m = _DenseT(10, jnp.float32)
        v = jax.jit(m.init)(jax.random.key(0), y)

        def out_and_grads(p):  # one program: the switch is read as it traces
            out = m.apply({"params": p}, y)
            return jnp.sum(out ** 2), out

        (_, out), grads = jax.jit(
            jax.value_and_grad(out_and_grads, has_aux=True))(v["params"])
        return out, grads

    out_p, g_p = run(env=False)
    out_e, g_e = run(env=True)
    # the two paths sum the 4096 features in different orders
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_e),
                               rtol=1e-5, atol=1e-5)
    for key in ("kernel", "bias"):
        want = np.asarray(g_e[key], np.float32)
        np.testing.assert_allclose(
            np.asarray(g_p[key], np.float32), want, rtol=1e-5,
            atol=1e-5 * max(1.0, float(np.max(np.abs(want)))), err_msg=key)


def test_compiled_head_has_no_weight_sized_loop(tmp_path):
    """What PR 24 removed, held out: the fc head alone (fc_t forward, its
    gradients and the SGD add at [5,750,32,750] x [18000000,10]),
    compiled for a chipless v5e, moves nothing the size of the weight —
    or of the activation — through a ``while``, one slice an iteration.
    Up to PR 23 the step held two such loops over the weight (36 ms of
    89.7 on the chip) that no test and no tool saw. The compile runs in a
    child: ``make_topology`` flips its process into compiled-kernel mode
    (its docstring), which must not reach the other tests."""
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    from hlo_traffic import computations, instructions, shape_bytes, \
        while_loops

    dump = tmp_path / "head.hlo"
    run = run_child(
        [sys.executable, os.path.join(_ROOT, "tools", "hlo_traffic.py"),
         "--head", "--batch", "5", "--top", "0", "--dump-hlo", str(dump)],
        timeout=150,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "TPU_LOG_DIR": "disabled"})
    if run.returncode == 3:
        pytest.skip(run.stderr.strip().splitlines()[-1][:300])
    assert run.returncode == 0, run.stderr[-2000:]
    text = dump.read_text()

    limit = 64 << 20
    comps, _ = computations(text)
    insts = [i for body in comps.values() for i in instructions(body)]
    kernels = [line for *_, line in insts if "tpu_custom_call" in line]
    assert len(kernels) == 2, "flatten + dgrad Mosaic kernels, not interpreted"
    for lp in while_loops(text):
        assert lp["carried_max_bytes"] < limit, lp
    for name, shape_s, opcode, _, _ in insts:
        if opcode == "dynamic-update-slice":
            assert shape_bytes(shape_s) <= limit, (name, shape_s)
    # no [K, H, C, W]-shaped view of the weight anywhere
    assert "[10,750,32,750]" not in text
