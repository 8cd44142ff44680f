"""Loss ops.

Parity: ``nn.CrossEntropyLoss()`` (reference mnist_onegpu.py:48,
mnist_distributed.py:64) — softmax cross-entropy with integer labels,
mean-reduced over the batch.
"""

from __future__ import annotations

import jax.numpy as jnp
import optax

#: engage the fused Pallas CE kernel only at LM-scale vocabularies: below
#: this the [N, C] materialization XLA produces is small and the kernel's
#: 128-lane padding would dominate (the ConvNet's C=10 pads 12.8x)
_FUSED_CE_MIN_CLASSES = 4096


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean softmax cross-entropy; logits [N, C] fp32, labels [N] int.

    At LM-scale class counts (C >= 4096) on a compiled-kernel backend
    this runs the fused Pallas kernel (ops/pallas_ce.py): one VMEM pass
    for max/logsumexp/label-gather, no [N, C] log-softmax
    materialization in HBM — the r04 LM-step HLO charged ~32 ms/step at
    b16/s2048/v32768 to exactly that materialization (convert + reduce
    over a 4.3 GB f32 buffer). Off-TPU (CPU tests) and at small C the
    plain optax path runs — same math, pinned against each other by
    tests/test_aux.py and tests/test_mosaic_lowering.py."""
    if logits.ndim == 2 and logits.shape[-1] >= _FUSED_CE_MIN_CLASSES:
        from tpu_sandbox.ops.pallas_common import default_interpret

        if not default_interpret(None):
            from tpu_sandbox.ops.pallas_ce import pallas_cross_entropy

            return pallas_cross_entropy(logits, labels)
    # plain path: explicit f32 (exact no-op for fp32_logits models; for
    # compute-dtype logits it restores the identical pre-r04 math)
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels).mean()
