"""Flash-ring attention (Pallas per-block forward + hand-written ring
backward) vs the reference math and the jnp ring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_ring_attention import qkv, sp_mesh  # noqa: F401 (sp_mesh: fixture)
from tpu_sandbox.ops.attention import causal_attention
from tpu_sandbox.parallel.flash_ring import make_flash_ring_attention
from tpu_sandbox.parallel.ring_attention import make_ring_attention
from tpu_sandbox.runtime.mesh import make_mesh

# every claim here is a tolerance: conftest's cheaper compile
pytestmark = pytest.mark.usefixtures("light_compile")


def test_offset_lse_partials_merge_to_reference():
    """flash_attention_lse with offsets: two half-sequence partials merged
    by their logsumexps must equal full attention — the identity the ring
    forward is built on."""
    from tpu_sandbox.ops.pallas_attention import flash_attention_lse
    from tpu_sandbox.parallel.flash_ring import _merge, _NEG

    q, k, v = qkv(s=64, seed=4)
    half = 32
    ref = causal_attention(q, k, v, causal=True)

    o = jnp.zeros((*q.shape[:1], 64, *q.shape[2:]), jnp.float32)
    lse = jnp.full((q.shape[0], 64, q.shape[2]), _NEG, jnp.float32)
    for blk in range(2):
        o_b, lse_b = flash_attention_lse(
            q, k[:, blk * half:(blk + 1) * half],
            v[:, blk * half:(blk + 1) * half],
            causal=True, q_offset=0, kv_offset=blk * half, interpret=True,
        )
        o, lse = _merge(o, lse, o_b, lse_b)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_ring_matches_reference(sp_mesh, causal):
    q, k, v = qkv(seed=1)
    ref = causal_attention(q, k, v, causal=causal)
    out = make_flash_ring_attention(sp_mesh, "sp", causal=causal,
                                    interpret=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_ring_gradients_match_reference(sp_mesh):
    q, k, v = qkv(seed=2)
    w = jax.random.normal(jax.random.key(9), q.shape)

    fr = make_flash_ring_attention(sp_mesh, "sp", causal=True, interpret=True)

    def loss_fr(q, k, v):
        return jnp.sum(fr(q, k, v) * w)

    def loss_ref(q, k, v):
        return jnp.sum(causal_attention(q, k, v, causal=True) * w)

    g_fr = jax.grad(loss_fr, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fr, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
            err_msg=f"grad d{name}",
        )


def test_flash_ring_matches_jnp_ring(sp_mesh):
    q, k, v = qkv(seed=3)
    ring = make_ring_attention(sp_mesh, "sp", causal=True)(q, k, v)
    flash = make_flash_ring_attention(sp_mesh, "sp", causal=True,
                                      interpret=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(ring), atol=2e-5)


def test_seq_parallel_flash_ring_trains_like_ring():
    import optax

    from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
    from tpu_sandbox.parallel import SeqParallel

    cfg = TransformerConfig(vocab_size=16, d_model=16, n_heads=2, n_layers=2,
                            d_ff=32, max_len=32)
    mesh = make_mesh({"data": 2, "sp": 4})
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 16, size=(4, 32)).astype(np.int32)
    targets = ((tokens + 1) % 16).astype(np.int32)

    losses = {}
    for attn in ("ring", "flash_ring"):
        eng = SeqParallel(lambda a: TransformerLM(cfg, attention_fn=a),
                          optax.sgd(1e-2), mesh, attn=attn, donate=False)
        state = eng.shard_state(eng.init_state(jax.random.key(0),
                                               jnp.asarray(tokens)))
        _, loss = eng.train_step(state, *eng.shard_batch(tokens, targets))
        losses[attn] = float(np.asarray(loss))
    np.testing.assert_allclose(losses["ring"], losses["flash_ring"],
                               rtol=1e-5)
