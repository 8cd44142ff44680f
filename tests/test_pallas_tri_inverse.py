"""ops/pallas_tri_inverse.py on the CPU (interpret mode): the kernel pair
behind ``delta_rule.unit_lower_inverse`` against ``solve_triangular`` in
float64 where the product form loses the inverse, against ``_doubling``
entry by entry, its cotangent against ``jnp.linalg.inv``'s and against the
``jnp`` pair's, what it does not read, the tile rule and the fallback by
shape with the counter every site adds to, and the call traced once for
three sites of one shape."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid as ref
from tests.test_delta_rule import (
    inverse_choices,
    rule_inputs,
    solved,
    transitions,
)
from tpu_sandbox.obs import get_registry
from tpu_sandbox.ops import delta_rule
from tpu_sandbox.ops import pallas_tri_inverse as ti
from tpu_sandbox.ops.delta_rule import gated_delta_rule, unit_lower_inverse

pytestmark = pytest.mark.usefixtures("light_compile")


def sites(kernel):
    """``trace:kernel`` spans fired so far for ``kernel``."""
    return sum(h["count"] for key, h in
               get_registry().snapshot()["histograms"].items()
               if key.startswith(f"trace.kernel_s{{kernel={kernel},"))


@pytest.mark.parametrize("n", [64, 32, 16])
@pytest.mark.parametrize("kind", ["random", "alike"])
def test_the_kernel_against_solve_triangular_and_doubling(kind, n):
    """Float32's rounding from ``solve_triangular`` in float64 on both kinds
    of keys (``tests/test_delta_rule.py`` shows the product form lost on
    ``alike``), and ``_doubling``'s entries."""
    a = transitions(kind, n=n, batch=6, seed=n)
    before = inverse_choices()
    got = jax.jit(lambda m: unit_lower_inverse(m))(a)   # a site a case
    assert inverse_choices(since=before) == {
        f"impl=pallas,kernel=fwd,matrices=6,n={n},tile=6": 1}
    assert ref.rms_rel(got, solved(a)) < 1e-6
    doubled = jax.jit(delta_rule._doubling)(a)
    np.testing.assert_allclose(got, doubled, rtol=1e-5,
                               atol=1e-6 * float(jnp.abs(doubled).max()))


def test_the_kernel_reads_nothing_on_or_above_the_diagonal():
    a = transitions("random", n=32, batch=4)
    noisy = a + 7.0 * jnp.triu(jnp.ones((32, 32)))
    inverse = jax.jit(unit_lower_inverse)
    np.testing.assert_array_equal(inverse(noisy), inverse(a))
    got = np.asarray(inverse(noisy))
    np.testing.assert_array_equal(np.triu(got, 1), 0.0)
    np.testing.assert_array_equal(np.diagonal(got, axis1=-2, axis2=-1), 1.0)


def weighted_grad(inverse, weight):
    return jax.jit(jax.grad(lambda m: (inverse(m) * weight).sum()))


@pytest.mark.parametrize("kind,n", [("random", 16), ("alike", 64)])
def test_the_kernels_cotangent(kind, n, monkeypatch):
    """``jax.grad`` through the kernel pair against ``jnp.linalg.inv``'s
    and against the ``jnp`` pair's ``custom_vjp``; zero on and above the
    diagonal whatever ``dT`` holds there."""
    a = transitions(kind, n=n, batch=4, seed=1)
    weight = jax.random.normal(jax.random.key(1), a.shape)
    before = inverse_choices()
    got = weighted_grad(unit_lower_inverse, weight)(a)
    assert inverse_choices(since=before) == {
        f"impl=pallas,kernel={k},matrices=4,n={n},tile=4": 1
        for k in ("fwd", "bwd")}
    by_solve = weighted_grad(
        lambda m: jnp.linalg.inv(jnp.eye(n) + jnp.tril(m, -1)), weight)(a)
    scale = float(jnp.abs(by_solve).max())
    np.testing.assert_allclose(got, by_solve, rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_array_equal(np.triu(np.asarray(got)), 0.0)
    monkeypatch.setattr(ti, "choose_tile", lambda shape: None)
    by_jnp = weighted_grad(unit_lower_inverse, weight)(a)
    np.testing.assert_allclose(got, by_jnp, rtol=1e-5, atol=1e-6 * scale)


def test_the_rule_with_the_kernel_is_the_rule_without(monkeypatch):
    """``gated_delta_rule`` forward and backward at a chunk the kernel
    takes, kernel against ``jnp`` (``tests/test_delta_rule.py`` holds the
    cell's chunk to the token-by-token recurrence, kernel engaged)."""
    *args, weight = rule_inputs(128)
    rule = functools.partial(gated_delta_rule, chunk=32)

    def run(*a):
        return jax.value_and_grad(
            lambda *a: (rule(*a) * weight).sum(), range(5))(*a)

    with jax.default_matmul_precision("highest"):
        before = inverse_choices()
        got_out, got = jax.jit(run)(*args)
        assert inverse_choices(since=before) == {
            f"impl=pallas,kernel={k},matrices=24,n=32,tile=4": 1
            for k in ("fwd", "bwd")}
        monkeypatch.setattr(ti, "choose_tile", lambda shape: None)
        want_out, want = jax.jit(run)(*args)
    np.testing.assert_allclose(got_out, want_out, rtol=1e-5)
    for name, g, w in zip(("q", "k", "v", "g", "beta"), got, want):
        assert ref.rms_rel(g, w) < 1e-5, name


@pytest.mark.parametrize("shape,why", [
    ((6, 8, 8), "a chunk under the float32 tile's floor"),
    ((3, 16, 16), "no tile divides three matrices"),
    ((2, 5, 1, 32, 32), "one chunk a head: no tile of two"),
    ((32, 32), "no leading axis to tile")])
def test_a_shape_the_kernels_do_not_take_falls_back(shape, why):
    """By the shape alone, counted as ``impl=jnp`` at both sites, and the
    gradient is the ``jnp`` pair's."""
    n = shape[-1]
    assert ti.choose_tile(shape) is None, why
    a = jnp.tril(0.1 * jax.random.normal(jax.random.key(0), shape), -1)
    before = inverse_choices()
    got = jax.jit(jax.grad(lambda m: unit_lower_inverse(m).sum()))(a)
    matrices = int(np.prod(shape[:-2]))
    assert inverse_choices(since=before) == {
        f"impl=jnp,kernel={k},matrices={matrices},n={n},tile=0": 1
        for k in ("fwd", "bwd")}
    want = jax.jit(jax.grad(lambda m: jnp.linalg.inv(
        jnp.eye(n) + jnp.tril(m, -1)).sum()))(a)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_a_chunk_that_is_no_power_of_two_is_refused_and_counted():
    before = inverse_choices()
    with pytest.raises(ValueError, match="power of two"):
        unit_lower_inverse(jnp.zeros((2, 24, 24)))
    assert inverse_choices(since=before) == {
        "impl=jnp,kernel=fwd,matrices=2,n=24,tile=0": 1}


@pytest.mark.parametrize("shape,tile", [
    ((1, 30, 128, 64, 64), 64),     # the cell's step
    ((1, 30, 4, 64, 64), 4),        # its float32 check
    ((1, 30, 2, 64, 64), 2),        # model.init's sample of 128 tokens
    ((2, 3, 256, 16, 16), 256),     # small chunks: more of them a block
    ((2, 3, 30, 128, 128), 30)])
def test_the_tile_rule(shape, tile):
    assert ti.choose_tile(shape) == tile


def test_three_sites_of_one_shape_trace_the_call_once():
    """Counted at every site, traced at the first: the ``trace:kernel``
    span opens inside the jitted call (``pallas_common.traced_once``)."""
    # a shape no other test of this process gives the jitted calls
    a = transitions("random", n=16, batch=10)

    def thrice(a):
        return unit_lower_inverse(0.5 * unit_lower_inverse(
            0.5 * unit_lower_inverse(a))).sum()

    before = inverse_choices()
    fwd, bwd = sites("tri_inverse_fwd"), sites("tri_inverse_bwd")
    jax.eval_shape(jax.grad(thrice), a)
    assert sites("tri_inverse_fwd") - fwd == 1
    assert sites("tri_inverse_bwd") - bwd == 1
    assert inverse_choices(since=before) == {
        f"impl=pallas,kernel={k},matrices=10,n=16,tile=10": 3
        for k in ("fwd", "bwd")}
