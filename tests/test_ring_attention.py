"""Ring attention vs the single-device reference implementation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_sandbox.ops.attention import causal_attention
from tpu_sandbox.parallel.ring_attention import make_ring_attention
from tpu_sandbox.runtime.mesh import make_mesh

# every claim here is a tolerance: conftest's cheaper compile
pytestmark = pytest.mark.usefixtures("light_compile")


def qkv(b=2, s=32, h=2, d=8, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape) for k in ks)


@pytest.fixture(scope="module")
def sp_mesh():
    return make_mesh({"sp": 8})


def test_ring_matches_reference_causal(sp_mesh):
    q, k, v = qkv()
    ref = causal_attention(q, k, v, causal=True)
    ring = make_ring_attention(sp_mesh, "sp", causal=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(ref), atol=1e-5)


def test_ring_matches_reference_noncausal(sp_mesh):
    q, k, v = qkv(seed=1)
    ref = causal_attention(q, k, v, causal=False)
    ring = make_ring_attention(sp_mesh, "sp", causal=False)(q, k, v)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(ref), atol=1e-5)


def test_ring_output_stays_sharded(sp_mesh):
    q, k, v = qkv()
    out = make_ring_attention(sp_mesh, "sp")(q, k, v)
    assert len(out.addressable_shards) == 8
    assert out.addressable_shards[0].data.shape == (2, 4, 2, 8)


def test_ring_first_token_attends_only_itself(sp_mesh):
    """Causality across shard boundaries: token 0's output must equal v[0]
    regardless of later tokens."""
    q, k, v = qkv(seed=2)
    out = np.asarray(make_ring_attention(sp_mesh, "sp")(q, k, v))
    np.testing.assert_allclose(out[:, 0], np.asarray(v)[:, 0], atol=1e-5)

    # and perturbing the future must not change token 0 (nor any past token's view)
    v2 = v.at[:, 16:].set(99.0)
    out2 = np.asarray(make_ring_attention(sp_mesh, "sp")(q, k, v2))
    np.testing.assert_allclose(out2[:, :16], out[:, :16], atol=1e-5)


def test_ring_bf16_inputs(sp_mesh):
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv(seed=3))
    ref = causal_attention(q, k, v)
    ring = make_ring_attention(sp_mesh, "sp")(q, k, v)
    assert ring.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(ring, np.float32), np.asarray(ref, np.float32), atol=2e-2
    )


def test_ring_validates_axis(sp_mesh):
    with pytest.raises(ValueError, match="not in mesh"):
        make_ring_attention(sp_mesh, "nope")


# --- Ulysses (all-to-all) sequence parallelism ---------------------------

def test_ulysses_matches_reference_and_ring(sp_mesh):
    from tpu_sandbox.parallel.ulysses import make_ulysses_attention

    q, k, v = qkv(h=8, seed=2)  # H == 8 ranks -> 1 head per rank
    ref = causal_attention(q, k, v, causal=True)
    uly = make_ulysses_attention(sp_mesh, "sp", causal=True)(q, k, v)
    ring = make_ring_attention(sp_mesh, "sp", causal=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(uly), np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(uly), np.asarray(ring), atol=1e-5)


def test_ulysses_noncausal(sp_mesh):
    from tpu_sandbox.parallel.ulysses import make_ulysses_attention

    q, k, v = qkv(h=16, seed=3)  # 2 heads per rank
    ref = causal_attention(q, k, v, causal=False)
    uly = make_ulysses_attention(sp_mesh, "sp", causal=False)(q, k, v)
    np.testing.assert_allclose(np.asarray(uly), np.asarray(ref), atol=1e-5)


def test_ulysses_rejects_indivisible_heads(sp_mesh):
    from tpu_sandbox.parallel.ulysses import make_ulysses_attention

    q, k, v = qkv(h=2)  # 2 heads over 8 ranks
    with pytest.raises(ValueError, match="heads % ranks"):
        make_ulysses_attention(sp_mesh, "sp")(q, k, v)


def test_seq_parallel_ulysses_trains_like_ring():
    import optax

    from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
    from tpu_sandbox.parallel import SeqParallel

    cfg = TransformerConfig(vocab_size=16, d_model=16, n_heads=4, n_layers=2,
                            d_ff=32, max_len=32)
    mesh = make_mesh({"data": 2, "sp": 4})
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 16, size=(4, 32)).astype(np.int32)
    targets = ((tokens + 1) % 16).astype(np.int32)

    losses = {}
    for attn in ("ring", "ulysses"):
        eng = SeqParallel(lambda a: TransformerLM(cfg, attention_fn=a),
                          optax.sgd(1e-2), mesh, attn=attn, donate=False)
        state = eng.shard_state(eng.init_state(jax.random.key(0),
                                               jnp.asarray(tokens)))
        _, loss = eng.train_step(state, *eng.shard_batch(tokens, targets))
        losses[attn] = float(np.asarray(loss))
    np.testing.assert_allclose(losses["ring"], losses["ulysses"], rtol=1e-5)
