"""Bucketed gradient synchronization — DDP's reducer discipline, XLA-side.

PyTorch DDP's C++ reducer groups gradients into ~25MB buckets and launches
one async NCCL all-reduce per bucket as soon as the bucket's grads are
produced, hiding comm under the rest of backward. Here the buckets are
structural: one independent collective per bucket over a flat buffer,
issued in the order the backward pass produces them and chained by
``optimization_barrier`` so that the all-reduce combiner cannot merge them
again. What that buys on a TPU has never been measured, and the schedules
say less than this docstring once claimed: this libtpu prints no
``all-reduce-start`` / ``-done`` pair for a bucket, only a synchronous
``all-reduce`` placed among the backward operations
(``tools/hlo_schedule.py``: 3 of 4 before the last of them, on the MNIST
ConvNet), and where one leaf carries the step's bytes, as in the 3000^2
ConvNet, bucketing changes nothing: XLA already takes that leaf's
all-reduce apart from the small ones and schedules it, with its producer,
behind the whole backward pass (PERF.md section 6, PR 34; what does move
it there is ``data_parallel._pmean_largest_first``).

Composition: each bucket goes through one :class:`CompressedAllReduce`
exchange — ``none`` stays a plain ``lax.pmean`` of the flat buffer
(elementwise, so bitwise-equal to the per-leaf spelling), bf16/int8
quantize per bucket with per-bucket error-feedback residuals. Residuals
stay LEAF-shaped in ``TrainState`` (checkpoint layout unchanged from the
monolithic path); they are concatenated into the bucket buffer on entry and
split back on exit, so quantization block boundaries are genuinely
per-bucket while elastic resume remains bitwise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from tpu_sandbox.parallel.collectives import as_compress_policy

# PyTorch DDP's bucket_cap_mb default — the same trade: big enough that
# per-collective latency amortizes, small enough that the first bucket is
# ready well before backward finishes.
DEFAULT_BUCKET_MB = 25.0


def plan_buckets(nbytes, bucket_bytes, keys=None):
    """Greedily group CONSECUTIVE leaves into size-targeted buckets.

    ``nbytes``: per-leaf byte sizes in flatten order. ``bucket_bytes``: the
    target; a bucket closes when adding the next leaf would push it past
    the target (a single over-target leaf still gets its own bucket).
    ``keys``: optional per-leaf grouping keys (dtypes) — a key change
    forces a bucket boundary so flat buffers never mix dtypes.

    Returns a list of ``(start, stop)`` index spans covering every leaf
    exactly once, in order. Leaf ORDER inside the flattened pytree is taken
    as given; :func:`sync_buckets` applies DDP's reverse-autograd heuristic
    by ISSUING the buckets in reversed span order instead of reordering
    leaves (flatten order ~ forward order, so backward produces the last
    spans' cotangents first).
    """
    nbytes = [int(b) for b in nbytes]
    bucket_bytes = int(bucket_bytes)
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    if keys is not None and len(keys) != len(nbytes):
        raise ValueError(
            f"keys length {len(keys)} != nbytes length {len(nbytes)}"
        )
    spans = []
    start, acc = 0, 0
    for i, b in enumerate(nbytes):
        boundary = i > start and (
            acc + b > bucket_bytes or (keys is not None and keys[i] != keys[start])
        )
        if boundary:
            spans.append((start, i))
            start, acc = i, 0
        acc += b
    if start < len(nbytes):
        spans.append((start, len(nbytes)))
    return spans


def sync_buckets(grads, axis_name, size, compress=None, residuals=None,
                 bucket_bytes=int(DEFAULT_BUCKET_MB * 2 ** 20)):
    """Mean ``grads`` across ``axis_name`` (inside ``shard_map``) as one
    independent collective per size-targeted bucket.

    ``compress``: a :class:`CompressedAllReduce` (or mode string / None);
    each bucket's flat buffer goes through one ``compress.pmean`` exchange.
    ``residuals``: leaf-shaped error-feedback pytree matching ``grads``
    (honored only when the policy :attr:`needs_residual`, mirroring
    ``pmean_tree``); returns ``(means, new_residuals)`` with
    ``new_residuals is None`` iff no residual was threaded in.
    """
    compress = as_compress_policy(compress)
    leaves, treedef = jax.tree.flatten(grads)
    if not leaves:
        return grads, None
    use_res = compress.needs_residual and residuals is not None
    if use_res:
        res_leaves = treedef.flatten_up_to(residuals)
    else:
        res_leaves = [None] * len(leaves)
    spans = plan_buckets(
        [g.size * jnp.dtype(g.dtype).itemsize for g in leaves],
        bucket_bytes,
        keys=[jnp.dtype(g.dtype) for g in leaves],
    )
    # Exchange phase: one compress.pmean per bucket, issued in REVERSED
    # leaf order — cotangent production order (backward visits layers in
    # reverse, so the last leaves' grads are ready first), the same order
    # DDP's reducer fires its buckets. Consecutive issues are
    # dependency-chained through an optimization_barrier (identity on
    # values): each bucket's input depends on the previously issued
    # bucket's mean, so XLA's all-reduce combiner — which merges any
    # INDEPENDENT same-shaped collectives — cannot re-fuse the buckets
    # into one monolithic sync (observed on TPU compiles: without the
    # chain the combiner undoes the bucketing entirely). Backward compute
    # stays free to interleave: the chain only orders collectives against
    # each other, DDP's NCCL-stream discipline exactly.
    results = [None] * len(spans)  # per-span (mean, new_residual)
    prev_k = None
    for k in range(len(spans) - 1, -1, -1):
        start, stop = spans[k]
        group = leaves[start:stop]
        rgroup = res_leaves[start:stop]
        if len(group) == 1:
            # no reshape churn for a lone (usually over-target) leaf
            buf, rbuf = group[0], rgroup[0]
        else:
            buf = jnp.concatenate([g.reshape(-1) for g in group])
            rbuf = (
                jnp.concatenate([r.reshape(-1) for r in rgroup])
                if use_res else None
            )
        if prev_k is not None:
            buf, chained = lax.optimization_barrier(
                (buf, results[prev_k][0])
            )
            results[prev_k] = (chained, results[prev_k][1])
        results[k] = compress.pmean(buf, axis_name, size, rbuf)
        prev_k = k

    # Split phase: scatter each bucket's mean back into leaf shapes.
    out = [None] * len(leaves)
    new_res = [None] * len(leaves)
    for (start, stop), (mean, rmean) in zip(spans, results):
        group = leaves[start:stop]
        rgroup = res_leaves[start:stop]
        if len(group) == 1:
            out[start], new_res[start] = mean, rmean
            continue
        off = 0
        for j, g in enumerate(group):
            n = g.size
            out[start + j] = lax.slice_in_dim(mean, off, off + n).reshape(
                g.shape
            )
            if use_res:
                new_res[start + j] = lax.slice_in_dim(
                    rmean, off, off + n
                ).reshape(rgroup[j].shape)
            off += n
    means = treedef.unflatten(out)
    if not use_res:
        return means, None
    return means, treedef.unflatten(new_res)
