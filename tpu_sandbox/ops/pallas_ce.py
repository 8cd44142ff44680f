"""Pallas TPU kernel: fused softmax-cross-entropy from logits.

The hot ops of the parity experiment (convs, the 18M-wide matmul) belong to
XLA — hand-scheduling them would fight the compiler (pallas_guide.md: let
XLA fuse). The loss is the one op where a fused kernel is cleanly separable:
one VMEM pass computes max, log-sum-exp, and the label logit gather per row
— no [N, C] softmax materialization in HBM.

Forward runs as a Pallas kernel (grid over row blocks, classes padded to
the 128-lane tile; padding uses a large-negative filler so exp() underflows
to 0). Backward is the closed form softmax(logits) - onehot(labels),
expressed in jnp and left to XLA (it fuses into surrounding backprop).

Falls back to interpret mode off-TPU automatically, so the same call path
is tested on CPU and compiled on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tpu_sandbox.ops.pallas_common import (
    LANE as _LANE,
    NEG as _NEG,
    default_interpret,
    kernel_site,
    round_up as _round_up,
)

_BLOCK_N = 128


def _ce_kernel(logits_ref, labels_ref, out_ref):
    logits = logits_ref[:].astype(jnp.float32)  # [BN, Cp]
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1, keepdims=True)) + m
    labels = labels_ref[:]  # [BN, 1]
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    picked = jnp.sum(
        jnp.where(cols == labels, logits, 0.0), axis=-1, keepdims=True
    )
    out_ref[:] = lse - picked


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def pallas_cross_entropy(
    logits: jnp.ndarray, labels: jnp.ndarray, interpret: bool | None = None
) -> jnp.ndarray:
    """Mean softmax cross-entropy; logits [N, C], labels [N] int. Matches
    ops.losses.cross_entropy_loss numerically (tested)."""
    return _forward(logits, labels, interpret)


def _block_rows(cp: int) -> int | None:
    """Rows per grid step, sized so the kernel's [rows, cp] f32 view stays
    within scoped VMEM (~4 MB budget of the 16 MB/core): at a 32k vocab
    that is 32 rows, small vocabs keep the full 128. Caught by a chipless
    v5e AOT compile — the fixed 128-row block OOMed VMEM at [16384, 32768].
    Returns None when even 8 rows exceed the budget (vocab > 128k) — the
    caller then falls back to the jnp loss, which is numerically the same."""
    budget = 4 * 1024 * 1024
    rows = (budget // (cp * 4) // 8) * 8
    return min(_BLOCK_N, rows) if rows >= 8 else None


def _forward(logits, labels, interpret):
    n, c = logits.shape
    interpret = default_interpret(interpret)
    cp = _round_up(c, _LANE)
    block_n = _block_rows(cp)
    if block_n is None:  # vocab too wide for one VMEM row-block
        # plain optax directly — NOT losses.cross_entropy_loss, whose
        # LM-vocab dispatch would re-enter this function forever
        import optax

        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels).mean()
    np_ = _round_up(n, block_n)
    # pad in the INPUT dtype — the f32 promotion happens inside the kernel
    # per block, so no [N, C] f32 copy ever lands in HBM
    logits_p = jnp.pad(
        logits, ((0, np_ - n), (0, cp - c)),
        constant_values=jnp.asarray(_NEG, logits.dtype),
    )
    # padded rows: give them label 0 and a 0-logit at class 0 so their loss
    # is finite garbage; they are sliced off below
    logits_p = logits_p.at[n:, 0].set(0.0)
    labels_p = jnp.pad(labels.astype(jnp.int32), (0, np_ - n))[:, None]

    grid = (np_ // block_n,)
    with kernel_site("ce_fwd"):
        per_row = pl.pallas_call(
            _ce_kernel,
            out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_n, cp), lambda i: (i, 0)),
                pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            interpret=interpret,
        )(logits_p, labels_p)
    return jnp.mean(per_row[:n, 0])


def _fwd(logits, labels, interpret):
    return _forward(logits, labels, interpret), (logits, labels)


def _bwd(interpret, res, g):
    logits, labels = res
    n = logits.shape[0]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[1], dtype=jnp.float32)
    dlogits = (g / n) * (probs - onehot)
    return dlogits.astype(logits.dtype), None


pallas_cross_entropy.defvjp(_fwd, _bwd)
