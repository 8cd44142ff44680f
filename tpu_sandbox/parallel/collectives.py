"""Raw collectives over a device mesh — the TPU-native L3 layer.

Capability parity with the reference's collective surface
(``dist.all_reduce(SUM)`` at allreduce_toy.py:31, ``dist.barrier()`` at
allreduce_toy.py:33, implicit DDP param broadcast at mnist_distributed.py:67,
``dist.new_group`` at allreduce_toy.py:27 / mnist_distributed.py:100),
re-expressed the XLA way: a :class:`CollectiveGroup` binds a mesh axis once
(fixing the reference's group-per-step leak), and each collective is a jit'd
``shard_map`` whose body is a ``lax`` collective. XLA compiles these into
ICI/DCN ring or torus collectives — there is no user-level communicator
management, which is the point.

Data model: a "per-rank value" is an array whose leading dimension is the
group size, sharded over the group axis — rank i's tensor is row i. This is
the single-controller analogue of torch's one-tensor-per-process model; it
works identically on 8 virtual CPU devices, one real chip, or a pod slice.
"""

from __future__ import annotations

import dataclasses
from functools import cached_property, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class CollectiveGroup:
    """A set of devices that communicate — created once, reused every step.

    The reference creates a fresh ``dist.new_group`` every iteration
    (allreduce_toy.py:26-27); communicator setup is never free, so here the
    group (mesh axis binding + compiled collectives) is built once and every
    call reuses the jit cache.
    """

    def __init__(self, mesh: Mesh, axis: str | None = None):
        if axis is None:
            if len(mesh.axis_names) != 1:
                raise ValueError(
                    f"mesh has axes {mesh.axis_names}; pass axis= explicitly"
                )
            axis = mesh.axis_names[0]
        if axis not in mesh.axis_names:
            raise ValueError(f"axis {axis!r} not in mesh axes {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis
        self.size = mesh.shape[axis]

    # -- sharding helpers ---------------------------------------------------

    @cached_property
    def ranked_sharding(self) -> NamedSharding:
        """Leading dim = rank over the group axis."""
        return NamedSharding(self.mesh, P(self.axis))

    def put(self, values) -> jax.Array:
        """Place a host array of per-rank values (leading dim == group size)."""
        values = jnp.asarray(values)
        if values.shape[0] % self.size:
            raise ValueError(
                f"leading dim {values.shape[0]} not divisible by group size {self.size}"
            )
        return jax.device_put(values, self.ranked_sharding)

    def _smap(self, f, out_specs, check_vma: bool = True):
        # check_vma=False where the body provably replicates its output
        # (all_gather/broadcast) but jax's varying-mesh-axes analysis can't
        # statically see it.
        return jax.jit(
            jax.shard_map(
                f,
                mesh=self.mesh,
                in_specs=P(self.axis),
                out_specs=out_specs,
                check_vma=check_vma,
            )
        )

    # -- collectives --------------------------------------------------------

    @cached_property
    def _all_reduce_fns(self):
        def make(reducer):
            return self._smap(partial(reducer, axis_name=self.axis), P(self.axis))

        return {
            "sum": make(lax.psum),
            "mean": make(lax.pmean),
            "max": make(lax.pmax),
            "min": make(lax.pmin),
        }

    def all_reduce(self, values, op: str = "sum") -> jax.Array:
        """Elementwise reduce across ranks; every rank sees the result.

        Parity: ``dist.all_reduce(tensor, ReduceOp.SUM)`` (allreduce_toy.py:31)
        and the dead commented-out AVG loss reduce (mnist_distributed.py:102).
        """
        if op not in self._all_reduce_fns:
            raise ValueError(f"op {op!r} not in {sorted(self._all_reduce_fns)}")
        return self._all_reduce_fns[op](self.put(values))

    @cached_property
    def _all_gather_fn(self):
        return self._smap(
            lambda x: lax.all_gather(x, self.axis, axis=0, tiled=True),
            P(),
            check_vma=False,
        )

    def all_gather(self, values) -> jax.Array:
        """Every rank receives the concatenation of all ranks' rows."""
        return self._all_gather_fn(self.put(values))

    @cached_property
    def _reduce_scatter_fn(self):
        return self._smap(
            lambda x: lax.psum_scatter(x, self.axis, scatter_dimension=1, tiled=True),
            P(self.axis),
        )

    def reduce_scatter(self, values) -> jax.Array:
        """Each rank contributes a full payload (its row); the rows are
        summed and rank i keeps the i-th 1/size slice of the sum.

        ``values``: shape ``(size, m)`` with ``m % size == 0``; returns
        shape ``(size, m // size)`` where row i is slice i of the sum.
        """
        values = jnp.asarray(values)
        if values.ndim != 2 or values.shape[1] % self.size:
            raise ValueError(
                f"reduce_scatter wants shape (size, m) with m % {self.size} == 0, "
                f"got {values.shape}"
            )
        return self._reduce_scatter_fn(self.put(values))

    @cached_property
    def _broadcast_fn(self):
        def body(x, root):
            full = lax.all_gather(x, self.axis, axis=0, tiled=True)
            return lax.dynamic_index_in_dim(full, root, axis=0, keepdims=False)

        return jax.jit(
            jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(P(self.axis), P()),
                out_specs=P(),
                check_vma=False,
            )
        )

    def broadcast(self, values, root: int = 0) -> jax.Array:
        """All ranks receive rank ``root``'s row — DDP's initial param sync
        (mnist_distributed.py:67) as an explicit collective."""
        return self._broadcast_fn(self.put(values), jnp.asarray(root))

    def _shift_fn(self, offset: int):
        cache = self.__dict__.setdefault("_shift_cache", {})
        if offset not in cache:
            perm = [(i, (i + offset) % self.size) for i in range(self.size)]
            cache[offset] = self._smap(
                lambda x: lax.ppermute(x, self.axis, perm), P(self.axis)
            )
        return cache[offset]

    def shift(self, values, offset: int = 1) -> jax.Array:
        """Ring permute: rank i's row moves to rank (i+offset) % size.

        The primitive under ring attention / pipeline p2p — no torch analogue
        in the reference (it has no send/recv), included because rings are
        how TPU ICI wants to move data."""
        return self._shift_fn(offset)(self.put(values))

    @cached_property
    def _all_to_all_fn(self):
        # local block is [1, size, ...]; drop the sharded leading dim, trade
        # sub-row j to rank j, restack what arrived, restore the leading dim
        return self._smap(
            lambda x: lax.all_to_all(
                x[0], self.axis, split_axis=0, concat_axis=0, tiled=True
            )[None],
            P(self.axis),
        )

    def all_to_all(self, values) -> jax.Array:
        """Transpose rows across ranks: rank i sends chunk j of its row-block
        to rank j. ``values``: shape ``(size, size, ...)`` — rank i holds
        block ``values[i]`` whose j-th sub-row goes to rank j; returns the
        same shape with ``out[j, i] = values[i, j]``.

        The primitive under expert dispatch (MoE) and Ulysses-style
        sequence parallelism; maps to one XLA AllToAll on the ICI fabric.
        No torch analogue in the reference (SURVEY §2.2 "EP: no all_to_all").
        """
        values = jnp.asarray(values)
        if values.ndim < 2 or values.shape[0] != self.size or (
            values.shape[1] != self.size
        ):
            raise ValueError(
                f"all_to_all wants shape (size, size, ...), got {values.shape}"
            )
        return self._all_to_all_fn(self.put(values))

    def compressed_all_reduce(self, values, policy) -> jax.Array:
        """Mean across ranks under a :class:`CompressedAllReduce` policy
        (stateless surface — no error-feedback residual is carried here;
        the engines thread that through :class:`TrainState`)."""
        policy = as_compress_policy(policy)
        cache = self.__dict__.setdefault("_compress_cache", {})
        if policy not in cache:
            def body(x):
                mean, _ = policy.pmean(x[0], self.axis, self.size, None)
                return mean[None]

            cache[policy] = self._smap(body, P(self.axis), check_vma=False)
        return cache[policy](self.put(values))

    @cached_property
    def _barrier_fn(self):
        return self._smap(lambda x: lax.psum(x, self.axis), P())

    def barrier(self) -> None:
        """Block the host until every device in the group has participated.

        Parity: ``dist.barrier()`` (allreduce_toy.py:33). A psum of a unit
        token; host-blocks on the result.
        """
        token = self.put(jnp.ones((self.size,), jnp.int32))
        self._barrier_fn(token).block_until_ready()

    # -- microbenchmark -----------------------------------------------------

    def allreduce_bandwidth(self, nbytes: int = 1 << 26, iters: int = 10) -> dict:
        """All-reduce bus bandwidth — the north-star metric BASELINE.md names.

        Returns algorithm bandwidth (payload/time) and bus bandwidth
        (algbw * 2*(n-1)/n — the standard ring-allreduce accounting, which
        is what NCCL reports for the reference's fabric).

        Timing is fetch-synced and differential (see
        utils/profiling.py::measure_per_step): each iteration's input is the
        previous iteration's output (mean keeps values stable), so no
        iteration can be elided, and a device->host scalar fetch ends
        each timed run.
        """
        from tpu_sandbox.utils.profiling import measure_per_step

        n = self.size
        elems = max(nbytes // 4, n)
        elems -= elems % n
        x = self.put(jnp.ones((n, elems // n), jnp.float32))
        fn = self._all_reduce_fns["mean"]

        # k collectives chained INSIDE one program per timed call: a
        # Python-level launch loop dispatches k separate multi-device
        # programs back-to-back, and XLA:CPU's per-launch participant
        # rendezvous can deadlock when the host has fewer cores than
        # devices (~30% of runs on a 1-core/8-device box). In-program
        # collectives are cooperative — the same shape as a train step —
        # and fori_loop keeps every iteration data-dependent, so no
        # iteration can be elided.
        @partial(jax.jit, static_argnums=0)
        def run_k(k, v):
            return jax.lax.fori_loop(0, k, lambda _, o: fn(o), v)

        def run(k):
            return run_k(k, x)

        timing = measure_per_step(run, iters)
        if timing["sec_per_step"] <= 0:
            # tiny payloads + timing noise can turn the differential
            # negative; amortize over more iterations before giving up
            timing = measure_per_step(run, iters * 8)
        dt = timing["sec_per_step"]
        ok = dt > 0
        algbw = elems * 4 / dt if ok else 0.0
        busbw = algbw * (2 * (n - 1) / n)
        result = {
            "bytes": elems * 4,
            "seconds": dt,
            "algbw_GBps": algbw / 1e9,
            "busbw_GBps": busbw / 1e9,
            "timing_method": timing["timing_method"],
        }
        if not ok:
            result["degraded"] = (
                f"non-positive differential ({dt:.3e}s) even at "
                f"{iters * 8} iters; no bandwidth published"
            )
        return result


# -- compressed gradient synchronization ------------------------------------
#
# At pod scale the gradient all-reduce is DCN-bandwidth-bound while the chip
# idles (EQuARX, arxiv 2506.17615). These helpers shrink the wire payload:
# a bf16 cast (2x) or an int8 block-scaled two-shot exchange (~4x), with an
# optional error-feedback residual so quantization error is re-injected into
# the next step's gradient instead of lost.

_COMPRESS_MODES = ("none", "bf16", "int8")


def _quantize_int8_blocks(v):
    """Symmetric per-block int8: ``v`` is fp32 ``[..., block]``; returns
    ``(q int8, scale fp32 [..., 1])`` with scale = blockwise absmax / 127
    (guarded so an all-zero block dequantizes to exact zeros)."""
    s = jnp.max(jnp.abs(v), axis=-1, keepdims=True) / 127.0
    safe = jnp.where(s > 0, s, 1.0)
    q = jnp.clip(jnp.round(v / safe), -127, 127).astype(jnp.int8)
    return q, s


def int8_block_pmean(value, residual, axis_name, size: int, block: int):
    """Block-quantized mean over ``axis_name`` for one array, inside
    ``shard_map``. Returns ``(mean, new_residual)``.

    Two-shot exchange so accumulation happens in fp32 master precision,
    never int8:

    1. flatten + residual, pad to ``size * chunk`` (chunk block-aligned),
       quantize ``[size, nb, block]`` and ``all_to_all`` — the quantized
       spelling of reduce-scatter: rank j receives every rank's chunk j;
    2. dequantize, accumulate the mean in fp32, REquantize the owned chunk
       and ``all_gather`` it back — the second shot.

    Error feedback (``residual`` not None): the returned residual carries
    rank-local shot-1 error plus ``size *`` shot-2 error injected only at
    this rank's own chunk, so summing residuals across ranks next step
    re-injects exactly what this step's mean dropped — the compression
    telescopes instead of biasing the trajectory.
    """
    shape, dtype = value.shape, value.dtype
    flat = value.astype(jnp.float32).reshape(-1)
    n = flat.size
    if residual is not None:
        flat = flat + residual.reshape(-1).astype(jnp.float32)
    chunk = -(-n // (size * block)) * block
    pad = size * chunk - n
    v = jnp.pad(flat, (0, pad)).reshape(size, chunk // block, block)
    q, s = _quantize_int8_blocks(v)
    qx = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=True)
    sx = lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0, tiled=True)
    red = jnp.sum(qx.astype(jnp.float32) * sx, axis=0) / size  # [nb, block]
    q2, s2 = _quantize_int8_blocks(red)
    q2g = lax.all_gather(q2, axis_name, axis=0, tiled=True)
    s2g = lax.all_gather(s2, axis_name, axis=0, tiled=True)
    mean = (
        (q2g.astype(jnp.float32) * s2g).reshape(-1)[:n]
        .reshape(shape).astype(dtype)
    )
    if residual is None:
        return mean, None
    err1 = v - q.astype(jnp.float32) * s
    err2 = red - q2.astype(jnp.float32) * s2
    rows = lax.broadcasted_iota(jnp.int32, (size, 1, 1), 0)
    inj = jnp.where(rows == lax.axis_index(axis_name), err2[None] * size, 0.0)
    new_res = (
        (err1 + inj).reshape(-1)[: n + pad][:n]
        .reshape(shape).astype(residual.dtype)
    )
    return mean, new_res


@dataclasses.dataclass(frozen=True)
class CompressedAllReduce:
    """Gradient-sync compression policy, shared by the parallel engines.

    ``mode``:
      - ``"none"``: plain fp32 ``lax.pmean`` — byte-for-byte today's path;
      - ``"bf16"``: cast to bf16, pmean, cast back (2x payload reduction,
        no state);
      - ``"int8"``: :func:`int8_block_pmean` (~4x payload reduction;
        pair with ``error_feedback`` for fp32-tracking convergence).

    ``block``: int8 scale granularity; one fp32 scale per ``block`` elements
    (overhead ``4 / block`` bytes/element on the wire). Chunks are sized to
    the group axis so every rank owns an aligned slice in shot 2.

    ``error_feedback``: only meaningful for int8 — the engine must then
    carry a param-shaped residual pytree across steps
    (:attr:`needs_residual`).
    """

    mode: str = "none"
    block: int = 256
    error_feedback: bool = True

    def __post_init__(self):
        if self.mode not in _COMPRESS_MODES:
            raise ValueError(
                f"grad_compress mode {self.mode!r} not in {_COMPRESS_MODES}"
            )
        if self.block < 1:
            raise ValueError(f"block must be positive, got {self.block}")

    @property
    def needs_residual(self) -> bool:
        return self.mode == "int8" and self.error_feedback

    def block_for(self, n: int, size: int) -> int:
        """Per-leaf int8 block size over the sync axis (``size`` is the
        mesh's slowest — DCN at pod scale — axis, the one the exchange
        crosses). ``block`` is the ceiling; leaves whose per-rank chunk is
        smaller than one block shrink it by halving (floor 8), because
        :func:`int8_block_pmean` pads each rank's chunk to a block multiple
        and a 16-element bias padded to 256 would ship 16x its payload in
        alignment zeros. Leaves at or above one block per rank keep the
        configured granularity (and its ``4 / block`` scale overhead)."""
        per_rank = -(-int(n) // size)
        b = self.block
        while b > 8 and b > per_rank:
            b //= 2
        return b

    def pmean(self, value, axis_name, size: int, residual=None):
        """Compressed mean of one array across ``axis_name`` (inside
        ``shard_map``). Returns ``(mean, new_residual)``."""
        if self.mode == "none":
            return lax.pmean(value, axis_name), residual
        if self.mode == "bf16":
            return (
                lax.pmean(value.astype(jnp.bfloat16), axis_name)
                .astype(value.dtype),
                residual,
            )
        if not self.error_feedback:
            residual = None
        return int8_block_pmean(
            value, residual, axis_name, size, self.block_for(value.size, size)
        )

    def pmean_tree(self, grads, axis_name, size: int, residuals=None):
        """:meth:`pmean` over a pytree. ``residuals`` is None (no error
        feedback) or a pytree matching ``grads``; returns
        ``(means, new_residuals)`` with ``new_residuals is None`` iff
        no residual was threaded in."""
        if self.mode != "int8" or not self.error_feedback:
            residuals = None
        leaves, treedef = jax.tree.flatten(grads)
        if residuals is None:
            res_leaves = [None] * len(leaves)
        else:
            res_leaves = treedef.flatten_up_to(residuals)
        pairs = [
            self.pmean(g, axis_name, size, r)
            for g, r in zip(leaves, res_leaves)
        ]
        means = treedef.unflatten([m for m, _ in pairs])
        if residuals is None:
            return means, None
        return means, treedef.unflatten([r for _, r in pairs])

    def wire_bytes(self, leaf_sizes, size: int) -> dict:
        """Analytic per-participant bytes contributed to the fabric per
        step for gradients of the given element counts — the chipless
        counterpart of the HLO-derived number in
        ``tools/hlo_traffic.collective_bytes``.

        Returns ``{"total", "payload", "overhead"}``: ``payload`` is the
        gradient elements themselves at the compressed width (4n fp32 /
        2n bf16 / n int8 — the headline 2x / 4x), ``overhead`` is what
        int8 adds on top (fp32 block scales, ``4 / block`` per element,
        plus block/axis-alignment padding on both shots), so the all-in
        ``total`` never hides it. fp32/bf16 count the all-reduce operand;
        int8 counts both shots' operands (all_to_all + re-quantized
        all_gather). Block sizes follow :meth:`block_for` per leaf, the
        same rule the on-wire path uses, so this stays the HLO's mirror."""
        payload = total = 0
        for n in leaf_sizes:
            n = int(n)
            if self.mode == "none":
                payload += 4 * n
                total += 4 * n
            elif self.mode == "bf16":
                payload += 2 * n
                total += 2 * n
            else:
                block = self.block_for(n, size)
                chunk = -(-n // (size * block)) * block
                nb = chunk // block
                # shot 1 (q + scales) + shot 2 (q2 + scales); payload is
                # the unpadded elements crossing once per shot pair
                payload += n + -(-n // size)
                total += size * chunk + size * nb * 4
                total += chunk + nb * 4
        return {"total": total, "payload": payload,
                "overhead": total - payload}


def as_compress_policy(policy) -> CompressedAllReduce:
    """Coerce a CLI string / None / policy object to a policy."""
    if isinstance(policy, CompressedAllReduce):
        return policy
    return CompressedAllReduce(mode=str(policy) if policy else "none")


def world_group(mesh: Mesh | None = None, axis: str = "data") -> CollectiveGroup:
    """The default all-devices group (the reference's implicit WORLD)."""
    if mesh is None:
        from tpu_sandbox.runtime.mesh import make_mesh

        mesh = make_mesh({axis: -1})
    return CollectiveGroup(mesh, axis)


def sub_groups(mesh: Mesh, axis: str) -> CollectiveGroup:
    """Collectives over one axis of a multi-axis mesh: every slice along the
    other axes forms an independent group — the once-created analogue of
    ``dist.new_group(range(args.gpus))`` (mnist_distributed.py:100)."""
    return CollectiveGroup(mesh, axis)
