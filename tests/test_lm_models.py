"""The two language models that hold a share of their experts
(models/xing4.py, models/nemotron_h.py) at their small sizes on the CPU,
each against the plain float32 reference of the benchmark on logits, loss
and gradients: one table of what differs between them, one test a claim.
What only one of them has is in tests/test_xing4_model.py and
tests/test_nemotron_h_model.py, where the small configurations live."""

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as nemotron_h_ref
from benchmark.reference import xing4 as xing4_ref
from tests.test_nemotron_h_model import TINY as NEMOTRON_H_TINY
from tests.test_xing4_model import TINY as XING4_TINY
from tpu_sandbox.models import nemotron_h, xing4
from tpu_sandbox.ops.losses import cross_entropy_loss

pytestmark = pytest.mark.usefixtures("light_compile")

B, S, VOCAB = 2, 16, 256


@dataclasses.dataclass(frozen=True)
class LM:
    module: types.ModuleType
    ref: types.ModuleType
    tiny: dict
    config: type
    model: type
    #: gradients that are zero by symmetry (rounding over nothing)
    symmetric: tuple = ()
    #: the wide parameters (thousands of entries, no single choice of an
    #: expert decides them) whose bf16 gradients are held to the band
    wide: tuple = ()


LMS = {
    # where every stream is the same (the first block's and the MTP block's
    # input mix) the hyper-connection's gradients are zero by symmetry
    "xing4": LM(xing4, xing4_ref, XING4_TINY, xing4.Xing4Config, xing4.Xing4LM,
                symmetric=("block0/mhc_attn/", "mtp_block/mhc_attn/"),
                wide=("tok_emb/embedding", "lm_head/kernel",
                      "block1/mla/kv_b/kernel", "block0/mlp/down/kernel")),
    "nemotron_h": LM(nemotron_h, nemotron_h_ref, NEMOTRON_H_TINY,
                     nemotron_h.NemotronHConfig, nemotron_h.NemotronHLM,
                     wide=("tok_emb/embedding", "lm_head/kernel",
                           "block1/mamba/in_proj/kernel",
                           "block1/mamba/out_proj/kernel",
                           "block2/attn/q/kernel", "block2/attn/kv/kernel")),
}


def built(name, mtp, hidden, **how):
    lm = LMS[name]
    config = {**lm.tiny, "num_nextn_predict_layers": int(mtp),
              "hidden_size": hidden}
    cfg = lm.config.from_dict(config, tokens_per_step=B * S, **how)
    return config, cfg, lm.model(cfg)


@functools.cache
def reference(name, mtp, hidden):
    """One a configuration, for every dtype and attention path of the
    system (no default arguments: ``functools.cache`` keys on them as
    given): tokens, targets, parameters off the initial point (so that every
    scale, alpha and bias matters), and what the reference computes there."""
    lm = LMS[name]
    config, cfg, model = built(name, mtp, hidden, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (B, S)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, VOCAB, (B, S)), jnp.int32)
    def off_the_start(key):
        variables = model.init(key, tokens)
        return jax.tree.map(
            lambda a: a * 5 if a.ndim == 0 else a + 0.05 * jax.random.normal(
                jax.random.key(a.size), a.shape),
            variables["params"]), variables["batch_stats"]

    params, stats = jax.jit(off_the_start)(jax.random.key(1))
    ref_cfg = {**config, "held": list(cfg.held), "local_rows": cfg.local_rows}
    ref_loss, ref_logits, _, ref_grads = lm.ref.loss_and_grads(
        lm.ref.from_program_tree(params, stats), tokens, targets, ref_cfg,
        mtp_loss_weight=lm.module.MTP_LOSS_WEIGHT)
    return (tokens, targets, params, stats), (ref_loss, ref_logits, ref_grads)


@functools.cache
def system(name, dtype, mtp, hidden):
    """(loss, logits, gradients by path) of the model at ``reference``'s
    point. Recomputation is on where it is part of the subject: the main
    float32 case at the usual width (it changes no value)."""
    lm = LMS[name]
    remat = dtype == jnp.float32 and not mtp and hidden == 64
    _, _, model = built(name, mtp, hidden, dtype=dtype, flash=True, remat=remat)
    (tokens, targets, params, stats), _ = reference(name, mtp, hidden)

    def objective(p):
        logits, sown = model.apply(
            {"params": p, "batch_stats": stats}, tokens,
            mutable=["mtp_logits", "batch_stats", "intermediates"])
        loss = cross_entropy_loss(logits.reshape(-1, VOCAB), targets.reshape(-1))
        for extra in jax.tree.leaves(sown.get("mtp_logits", {})):
            loss = loss + lm.module.MTP_LOSS_WEIGHT * cross_entropy_loss(
                extra[:, :-1].reshape(-1, VOCAB), targets[:, 1:].reshape(-1))
        return loss, logits

    (loss, logits), grads = jax.jit(
        jax.value_and_grad(objective, has_aux=True))(params)
    return loss, logits, lm.ref.flat_paths(grads)


# at width 128 Xing4's hyper-connections run ``ops/pallas_mhc.py``'s kernels
# (interpreted here); at 64, no lane multiple, their ``jnp`` fallback
@pytest.mark.parametrize("name,mtp,hidden", [
    ("xing4", False, 64), ("xing4", True, 64), ("xing4", False, 128),
    ("nemotron_h", False, 64), ("nemotron_h", True, 64)],
    ids=["xing4-main", "xing4-with_mtp", "xing4-main_kernels",
         "nemotron_h-main", "nemotron_h-with_mtp"])
def test_model_matches_the_reference_in_float32(name, mtp, hidden):
    lm = LMS[name]
    loss, logits, grads = system(name, jnp.float32, mtp, hidden)
    _, (ref_loss, ref_logits, ref_grads) = reference(name, mtp, hidden)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    assert lm.ref.rms_rel(logits, ref_logits) < 1e-5
    assert ("mtp_proj/kernel" in grads) == mtp
    # every parameter has its gradient (the reference's biases are the
    # router's, which is no parameter of the program)
    assert set(grads) == {k for k in ref_grads if not k.endswith("/bias")}
    for path, grad in grads.items():
        scale = float(np.sqrt(np.mean(np.square(ref_grads[path]))))
        if path.startswith(lm.symmetric) or scale < 1e-9:
            continue
        assert lm.ref.rms_rel(grad, ref_grads[path]) < 2e-3, path


@pytest.mark.parametrize("mtp", [False, True], ids=["main", "with_mtp"])
@pytest.mark.parametrize("name", list(LMS))
def test_model_matches_the_reference_in_bf16_within_its_band(name, mtp):
    """bf16 through three to six blocks of width 64 with flippable choices
    (two of eight, four of sixteen): logits within 6 %, the loss within
    0.03, and the gradients of the wide parameters within 25 %."""
    lm = LMS[name]
    loss, logits, grads = system(name, jnp.bfloat16, mtp, 64)
    _, (ref_loss, ref_logits, ref_grads) = reference(name, mtp, 64)
    assert abs(float(loss) - float(ref_loss)) < 3e-2
    assert lm.ref.rms_rel(logits, ref_logits) < 6e-2
    for path in lm.wide:
        assert lm.ref.rms_rel(grads[path], ref_grads[path]) < 0.25, path


@pytest.mark.parametrize("name", list(LMS))
def test_plain_attention_path_agrees_with_the_flash_path(name):
    """Off the chip's path (``flash`` off) the model computes the same
    logits through ``ops.attention`` with the same scale."""
    _, _, model = built(name, False, 64, dtype=jnp.float32, flash=False)
    (tokens, _, params, stats), _ = reference(name, False, 64)
    plain = jax.jit(model.apply)({"params": params, "batch_stats": stats},
                                 tokens)
    _, flash, _ = system(name, jnp.float32, False, 64)
    assert LMS[name].ref.rms_rel(plain, flash) < 1e-5
