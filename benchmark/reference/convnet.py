"""Plain float32 reference of the source paper's ConvNet (reference
``mnist_onegpu.py:11-31``): conv 1->16 k5 p2, BatchNorm, ReLU, maxpool 2;
conv 16->32 k5 p2, BatchNorm, ReLU, maxpool 2; dense -> 10; mean cross
entropy. ``jax.numpy`` and one XLA convolution, no flax, no Pallas, no
space-to-depth plan. BatchNorm is in training mode (batch statistics,
biased variance, eps 1e-5). The dense layer's rows are ordered (h, c, w),
the program's canonical order (``models/convnet.py``).

Departure from the paper: none in the mathematics; the parameters are
handed in as the program's tree ``{conv1, bn1, conv2, bn2, fc}``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: The plan computes in bf16 with fp32 accumulation; the reference in fp32
#: at ``highest`` precision, on a [2,16,3000] slab (the production 750-lane
#: row geometry) of random data from ``--seed``. On the chip PR 22 measured,
#: over thirteen seeds: logits 3.8e-3 to 1.2e-2 of the largest logit (twenty
#: logits and two rows: the figures move with the seed), loss 8e-5 to
#: 1.3e-2, fc gradient 6.8e-3 to 9.1e-3 of its largest entry. The limits
#: are 2.3 to 5 times the worst of those, so that no seed fails by chance,
#: and far below what a lower precision would give: fp8 (2^-4) on the conv
#: inputs moves the logits by tens of percent. (``chip_smoke.py`` holds
#: 8e-3 against the *bf16* plain net, which shares the input's rounding.)
TOLERANCE = {"logit_rel": 3e-2, "loss_abs": 3e-2, "fc_grad_rel": 5e-2}


def forward(params, x, eps: float = 1e-5):
    """x: [N, H, W, 1] float32 -> logits [N, classes] float32."""
    x = jnp.asarray(x, jnp.float32)
    for i in (1, 2):
        conv, bn = params[f"conv{i}"], params[f"bn{i}"]
        x = lax.conv_general_dilated(
            x, jnp.asarray(conv["kernel"], jnp.float32), (1, 1),
            [(2, 2), (2, 2)], dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST) + conv["bias"]
        mean = x.mean((0, 1, 2))
        var = jnp.square(x - mean).mean((0, 1, 2))
        x = (x - mean) * lax.rsqrt(var + eps) * bn["scale"] + bn["bias"]
        x = jnp.maximum(x, 0.0)
        n, h, w, c = x.shape
        x = x.reshape(n, h // 2, 2, w // 2, 2, c).max((2, 4))
    x = x.transpose(0, 1, 3, 2).reshape(x.shape[0], -1)
    return jnp.dot(x, jnp.asarray(params["fc"]["kernel"], jnp.float32),
                   precision=lax.Precision.HIGHEST) + params["fc"]["bias"]


def loss_and_logits(params, x, labels):
    logits = forward(params, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
    return loss, logits


def compare(system: dict, params, x, labels) -> tuple[dict, list[str]]:
    """Hold the system's ``{loss, logits, fc_grad}`` on ``(params, x,
    labels)`` to the reference. Returns the deviations and the failures."""
    import numpy as np

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.jit(jax.value_and_grad(
            loss_and_logits, has_aux=True))(params, x, labels)
    ref_logits = np.asarray(logits, np.float64)
    ref_grad = np.asarray(grads["fc"]["kernel"], np.float64)
    dev = {
        "logit_rel": float(np.max(np.abs(
            np.asarray(system["logits"], np.float64) - ref_logits))
            / (np.max(np.abs(ref_logits)) or 1.0)),
        "loss_abs": abs(float(system["loss"]) - float(loss)),
        "fc_grad_rel": float(np.max(np.abs(
            np.asarray(system["fc_grad"], np.float64) - ref_grad))
            / (np.max(np.abs(ref_grad)) or 1.0)),
    }
    bad = [f"convnet vs float32 reference: {k} {dev[k]:.3g} > {lim}"
           for k, lim in TOLERANCE.items() if not dev[k] <= lim]
    return dev, bad
