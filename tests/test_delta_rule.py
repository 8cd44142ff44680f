"""ops/delta_rule.py at small sizes on the CPU: the chunked gated delta rule
against the token-by-token recurrence of the benchmark's reference (forward
and gradients, several chunk sizes, beta on both sides of 1, bf16 operands
within a band), the triangular inverse against ``solve_triangular`` where
the product form loses it, a planted fault, and the call site's count."""

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import olmo_hybrid as ref  # noqa: E402
from tpu_sandbox.ops import delta_rule  # noqa: E402
from tpu_sandbox.ops.delta_rule import (  # noqa: E402
    gated_delta_rule, unit_lower_inverse)

pytestmark = pytest.mark.usefixtures("light_compile")


def rule_inputs(s, *, h=3, dk=8, dv=16, b=2, beta_shift=0.0, seed=0):
    """Unit keys, queries of length ``dk ** -0.5``, log decays in (-0.5, 0),
    ``beta = 2 sigmoid(2 n + beta_shift)``: on both sides of 1 at shift 0,
    nearly all above 1 at + 3, below at - 3."""
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    return (unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5,
            unit(jax.random.normal(ks[1], (b, s, h, dk))),
            jax.random.normal(ks[2], (b, s, h, dv)),
            -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (b, s, h))),
            2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (b, s, h))
                                 + beta_shift),
            jax.random.normal(ks[5], (b, s, h, dv)))


def inverse_choices(since=None):
    """labels -> count of ``delta_rule.inverse_choice``, less ``since``."""
    from tpu_sandbox.obs import get_registry

    since = since or {}
    now = {key[key.index("{") + 1:-1]: count for key, count in
           get_registry().snapshot()["counters"].items()
           if key.startswith("delta_rule.inverse_choice")}
    return {labels: count - since.get(labels, 0)
            for labels, count in now.items() if count != since.get(labels, 0)}


def out_and_grads(rule):
    """One compiled program a side, not one a primitive."""
    def run(*args, weight):
        def weighted(*a):
            out = rule(*a)
            return (out.astype(jnp.float32) * weight).sum(), out
        return jax.value_and_grad(weighted, range(5), has_aux=True)(*args)
    return jax.jit(run)


@pytest.mark.parametrize("s,chunk,beta_shift", [
    (16, 16, 0.0),       # one chunk: no state crosses a boundary
    (256, 4, 0.0),       # S = 64 C: the loop over the chunks does the work
    (128, 64, 0.0),      # the cell's chunk: the inverse is the kernel's
    (256, 32, 0.0),      # eight chunks a kernel block
    (64, 8, 3.0),        # beta in (1, 2): the transition flips the key
    (64, 8, -3.0)])      # beta under 1 everywhere
def test_chunked_rule_is_the_recurrence_forward_and_backward(s, chunk, beta_shift):
    *args, weight = rule_inputs(s, beta_shift=beta_shift)
    beta = np.asarray(args[4])
    if beta_shift == 0.0:
        assert beta.min() < 0.5 and beta.max() > 1.5
    else:
        assert ((beta > 1.0).mean() > 0.9) == (beta_shift > 0)
    inverse_sites = inverse_choices()
    with jax.default_matmul_precision("highest"):
        (_, got_out), got = out_and_grads(functools.partial(
            gated_delta_rule, chunk=chunk))(*args, weight=weight)
        (_, want_out), want = out_and_grads(ref.delta_recurrence)(
            *args, weight=weight)
    # which inverse ran follows the shape: the kernel pair from a chunk of
    # 16 and two chunks a head, forward and backward one site each
    impl = "pallas" if chunk >= 16 and s // chunk >= 2 else "jnp"
    assert {",".join(labels.split(",")[:2]): count for labels, count in
            inverse_choices(since=inverse_sites).items()} == {
        f"impl={impl},kernel=fwd": 1, f"impl={impl},kernel=bwd": 1}
    np.testing.assert_allclose(got_out, want_out, rtol=2e-5, atol=2e-5)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        assert ref.rms_rel(a, b) < 2e-5, name


def test_bf16_operands_stay_within_their_band():
    """bf16 q, k, v at the cell's head sizes (float32 g, beta, decays,
    inverse and state): outputs and every gradient within 1 % of the float32
    recurrence on the same rounded inputs (read: 0.3 to 0.5 %)."""
    *args, weight = rule_inputs(256, dk=96, dv=192)
    low = [a.astype(jnp.bfloat16) for a in args[:3]] + list(args[3:])
    (_, got_out), got = out_and_grads(functools.partial(
        gated_delta_rule, chunk=64))(*low, weight=weight)
    assert got_out.dtype == jnp.bfloat16
    (_, want_out), want = out_and_grads(ref.delta_recurrence)(
        *(a.astype(jnp.float32) for a in low), weight=weight)
    assert 1e-4 < ref.rms_rel(got_out, want_out) < 1e-2
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        assert ref.rms_rel(a, b) < 1e-2, name


def test_the_recurrence_in_segments_is_the_recurrence():
    *args, _ = rule_inputs(32)
    whole = jax.jit(ref.delta_recurrence)(*args)
    in_segments = jax.jit(functools.partial(
        ref.delta_recurrence, wrap=jax.checkpoint, segment=8))(*args)
    np.testing.assert_allclose(in_segments, whole, atol=1e-6)


def test_a_dropped_state_term_is_caught(monkeypatch):
    """The planted fault: ``U = T diag(b) V`` without ``- W S_0^T``, i.e. a
    chunk that writes as if the state it starts from were empty. One chunk
    cannot see it; two must."""
    real = jnp.einsum

    def faulty(subscripts, *operands, **kw):
        out = real(subscripts, *operands, **kw)
        return jnp.zeros_like(out) if subscripts == "bhid,bhvd->bhiv" else out

    *args, _ = rule_inputs(32)
    want = jax.jit(ref.delta_recurrence)(*args)
    monkeypatch.setattr(delta_rule.jnp, "einsum", faulty)
    one_chunk = jax.jit(functools.partial(gated_delta_rule, chunk=32))(*args)
    two_chunks = jax.jit(functools.partial(gated_delta_rule, chunk=16))(*args)
    assert ref.rms_rel(one_chunk, want) < 2e-5
    assert ref.rms_rel(two_chunks, want) > 1e-2


# --- the triangular inverse ---

def product_form(a):
    """``(I - A)(I + A^2)(I + A^4)...``: exact in exact arithmetic."""
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=a.dtype)
    inv, power, size = eye - a, a, 1
    while 2 * size < n:
        power = jnp.einsum("...ij,...jk->...ik", power, power,
                           precision="highest")
        inv = jnp.einsum("...ij,...jk->...ik", inv, eye + power,
                         precision="highest")
        size *= 2
    return inv


def transitions(kind, n=64, batch=6, seed=0):
    """``A`` as the rule builds it: ``beta_i decay_ij (k_i . k_j)`` below the
    diagonal. ``random``: independent unit keys in 96 dimensions; ``alike``:
    keys that share most of their direction (as ``silu`` leaves them), a
    slow decay and beta near 1."""
    rng = np.random.default_rng(seed)
    k = rng.standard_normal((batch, n, 96))
    if kind == "alike":
        k = 0.6 * rng.standard_normal((batch, 1, 96)) + 0.6 * k
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = rng.uniform(0.9, 1.1, (batch, n)) if kind == "alike" else (
        rng.uniform(0.1, 1.9, (batch, n)))
    cum = np.cumsum(-rng.uniform(1e-3, 1e-2 if kind == "alike" else 0.5,
                                 (batch, n)), -1)
    return jnp.asarray(delta_rule.chunk_transition(
        jnp.asarray(k, jnp.float32), jnp.asarray(beta, jnp.float32),
        jnp.asarray(cum, jnp.float32)))


def solved(a):
    """``(I + A)^-1`` by ``solve_triangular`` in float64 arithmetic."""
    from scipy.linalg import solve_triangular

    n = a.shape[-1]
    a = np.tril(np.asarray(a, np.float64), -1)
    return np.stack([solve_triangular(np.eye(n) + m, np.eye(n), lower=True,
                                      unit_diagonal=True) for m in a])


@pytest.mark.parametrize("kind,product_is_lost", [("random", False),
                                                  ("alike", True)])
def test_the_inverse_against_solve_triangular(kind, product_is_lost):
    """Block doubling agrees with ``solve_triangular`` to float32's rounding
    on both; the product form does on independent keys and is lost (by more
    than a hundredfold of the doubling's error) where a chunk's keys
    resemble each other: why the rule does not use it."""
    a = transitions(kind)
    want = solved(a)
    doubling = ref.rms_rel(jax.jit(unit_lower_inverse)(a), want)
    product = ref.rms_rel(jax.jit(product_form)(a), want)
    assert doubling < 1e-6
    assert (product > 100 * doubling and product > 1e-5) == product_is_lost


def test_the_inverse_reads_nothing_on_or_above_the_diagonal_and_its_gradient():
    a = transitions("random", n=16, batch=3)
    noisy = a + jnp.triu(jnp.ones((16, 16)))
    np.testing.assert_allclose(unit_lower_inverse(noisy),
                               unit_lower_inverse(a), atol=1e-7)
    weight = jax.random.normal(jax.random.key(1), a.shape)

    def through(inverse):
        return jax.jit(jax.grad(lambda m: (inverse(m) * weight).sum()))

    by_solve = through(lambda m: jnp.linalg.inv(jnp.eye(16) + jnp.tril(m, -1)))
    np.testing.assert_allclose(through(unit_lower_inverse)(a), by_solve(a),
                               rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="power of two"):
        unit_lower_inverse(jnp.zeros((2, 24, 24)))


def test_rule_refuses_a_ragged_sequence_and_counts_its_choice():
    from tpu_sandbox.obs import get_registry

    *args, _ = rule_inputs(24)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        gated_delta_rule(*args, chunk=16)
    labels = {"impl": "jnp", "heads": 3, "key_dim": 8, "value_dim": 16,
              "chunk": 8, "tokens": 48, "inverse": "block_doubling"}
    counter = get_registry().counter("delta_rule.chunk_choice", labels=labels)
    before = counter.value
    jax.jit(functools.partial(gated_delta_rule, chunk=8))(*args)
    assert counter.value == before + 1      # one count a traced call site
