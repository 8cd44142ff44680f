"""``remat`` keeps what only the flash kernel can make: the forward kernel's
output and logsumexp carry checkpoint names (``FLASH_RESIDUALS``), every LM
model's ``remat`` saves them, and the gradient's program holds one forward
flash call a flash layer where a policy-less ``remat`` holds two. CPU,
interpret mode, tiny shapes: counts and equality, never a time."""

import collections
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_nemotron_h_model import TINY as NEMOTRON_TINY
from tests.test_olmo_hybrid_model import TINY as OLMO_TINY
from tests.test_xing4_model import TINY as XING4_TINY
from tpu_sandbox.models import nemotron_h, olmo_hybrid, xing4
from tpu_sandbox.models.transformer import TransformerConfig, TransformerLM
from tpu_sandbox.obs import get_registry
from tpu_sandbox.ops.attention import causal_attention
from tpu_sandbox.ops.losses import cross_entropy_loss
from tpu_sandbox.ops.pallas_attention import (
    FLASH_RESIDUALS, flash_attention, flash_attention_fn)

B, S, VOCAB = 2, 16, 256
NAMES = "+".join(FLASH_RESIDUALS)


def transformer(policy):
    def build(remat):
        cfg = TransformerConfig(vocab_size=VOCAB, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_len=S, remat=remat,
                                remat_policy=policy)
        return TransformerLM(cfg, attention_fn=flash_attention_fn())
    return build


def from_dict(config_cls, model_cls, config):
    def build(remat):
        return model_cls(config_cls.from_dict(
            config, tokens_per_step=B * S, dtype=jnp.float32, remat=remat,
            flash=True))
    return build


#: name -> (build(remat) -> model, flash layers, forward flash calls the
#: gradient holds a flash layer under ``remat``, blocks put under ``remat``,
#: the counter's ``model`` and ``names``)
MODELS = {
    "transformer_dots": (transformer("dots"), 2, 1, 2, "transformer", NAMES),
    "transformer_full": (transformer("full"), 2, 2, 2, "transformer", "none"),
    # a dense layer and the next-token block (its experts), latent
    # attention in each
    "xing4": (from_dict(xing4.Xing4Config, xing4.Xing4LM,
                        {**XING4_TINY, "hc_sinkhorn_iters": 6,
                         "num_hidden_layers": 1}),
              2, 1, 2, "xing4", NAMES),
    # "EM*E": one attention layer of four blocks
    "nemotron_h": (from_dict(nemotron_h.NemotronHConfig,
                             nemotron_h.NemotronHLM,
                             {**NEMOTRON_TINY, "num_nextn_predict_layers": 0}),
                   1, 1, 4, "nemotron_h", NAMES),
    # a Gated DeltaNet layer and a full-attention layer; a block is two
    # halves under remat
    "olmo_hybrid": (from_dict(olmo_hybrid.OlmoHybridConfig,
                              olmo_hybrid.OlmoHybridLM,
                              {**OLMO_TINY, "num_hidden_layers": 2,
                               "layer_types": OLMO_TINY["layer_types"][1:3]}),
                    1, 1, 4, "olmo_hybrid", NAMES),
}


def flash_calls(jaxpr) -> dict:
    """The flash ``pallas_call`` equations of a jaxpr, through every
    sub-jaxpr, by kernel: ``{"fwd": n, "dkv": n, "dq": n}`` (the models'
    other kernels -- mHC, the grouped products, the scans -- left out)."""
    kernels = {"_fwd_kernel": "fwd", "_bwd_dkv_kernel": "dkv",
               "_bwd_dq_kernel": "dq"}
    found = collections.Counter(
        kernels.get(eqn.params["jaxpr"].debug_info.func_name)
        for eqn in walk(jaxpr.jaxpr) if str(eqn.primitive) == "pallas_call")
    found.pop(None, None)
    return dict(found)


def walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from walk(sub)


@functools.cache
def point(name):
    """The model's variables and batch, shared by its ``remat`` and plain
    forms (``remat`` changes no parameter)."""
    model = MODELS[name][0](False)
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (B, S)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, VOCAB, (B, S)), jnp.int32)
    variables = jax.jit(model.init)(jax.random.key(1), tokens)
    return variables, tokens, targets


def objective(name, remat):
    model = MODELS[name][0](remat)
    variables, tokens, targets = point(name)
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss(params):
        logits, sown = model.apply({"params": params, **rest}, tokens,
                                   mutable=True)
        # the next-token blocks' logits too, so that every flash layer of
        # the model is differentiated
        heads = [logits, *jax.tree.leaves(sown.get("mtp_logits", {}))]
        return sum(cross_entropy_loss(h.reshape(-1, VOCAB),
                                      targets.reshape(-1)) for h in heads)

    return jax.value_and_grad(loss), variables["params"]


def nameless(monkeypatch, name):
    """``objective(name, True)`` under the remat of before: the same policy
    without the names (a model reads its policy when it is traced, so the
    patch holds until the test ends)."""
    module = sys.modules[type(MODELS[name][0](True)).__module__]
    monkeypatch.setattr(module, "save_flash_residuals",
                        lambda also=None: also)
    return objective(name, True)


@pytest.mark.parametrize("name", MODELS)
def test_remat_runs_the_forward_kernel_once_a_flash_layer(name, monkeypatch):
    _, layers, forwards, _, _, _ = MODELS[name]

    def calls(f, params):
        return flash_calls(jax.make_jaxpr(f)(params))

    def want(fwd):
        return {"fwd": fwd * layers, "dkv": layers, "dq": layers}

    assert calls(*objective(name, False)) == want(1)
    assert calls(*objective(name, True)) == want(forwards)
    assert calls(*nameless(monkeypatch, name)) == want(2)


@pytest.mark.parametrize("name", MODELS)
def test_remat_with_the_names_computes_what_it_did_without(name, monkeypatch):
    def values(f, params):
        return jax.jit(f)(params)

    want = values(*objective(name, False))
    got = values(*objective(name, True))
    old = values(*nameless(monkeypatch, name))  # patched from here on
    # the same kernels on the same operands: bit for bit what the nameless
    # remat computes; against no remat at all XLA:CPU fuses a block's
    # elementwise work otherwise (Nemotron-H's differs in the last bit,
    # with the names or without)
    jax.tree.map(np.testing.assert_array_equal, got, old)
    jax.tree.map(functools.partial(np.testing.assert_allclose, rtol=1e-5,
                                   atol=1e-6), got, want)


@pytest.mark.parametrize("name", MODELS)
def test_remat_saved_counts_once_a_wrapped_block(name):
    build, _, _, blocks, model, names = MODELS[name]
    _, tokens, _ = point(name)

    def counted():
        return {k: v for k, v in get_registry().snapshot()["counters"].items()
                if k.startswith("remat.saved")}

    before = counted()
    jax.eval_shape(build(False).init, jax.random.key(0), tokens)
    assert counted() == before          # no remat, nothing counted
    jax.eval_shape(build(True).init, jax.random.key(0), tokens)
    after = counted()
    series = f"remat.saved{{model={model},names={names}}}"
    assert {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)} == {series: blocks}


@pytest.mark.parametrize("shape", [(2, 128, 2, 64), (1, 24, 2, 16)],
                         ids=["packed", "padded"])
def test_outside_remat_the_names_change_nothing(shape):
    """Serving's prefill path: forward only, one ``pallas_call``, no name in
    the program; and a gradient outside ``remat`` is three calls."""
    q, k, v = (jax.random.normal(key, shape)
               for key in jax.random.split(jax.random.key(0), 3))
    jaxpr = jax.make_jaxpr(flash_attention)(q, k, v)
    assert flash_calls(jaxpr) == {"fwd": 1}
    assert "name" not in {str(e.primitive) for e in walk(jaxpr.jaxpr)}

    def total(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    def grad_calls(f):
        return flash_calls(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, k, v))

    assert grad_calls(total) == {"fwd": 1, "dkv": 1, "dq": 1}
    # under a policy-less checkpoint the kernel runs again, as before
    assert grad_calls(jax.checkpoint(total)) == {"fwd": 2, "dkv": 1, "dq": 1}
    np.testing.assert_allclose(jax.jit(flash_attention)(q, k, v),
                               causal_attention(q, k, v), atol=2e-5)
