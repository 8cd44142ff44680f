"""ConvNetS2DT: the space-to-depth ConvNet in TRANSPOSED layout
[N, H, C, W] — round 3's production execution plan.

Same function as models.convnet.ConvNet and models.convnet_s2d.ConvNetS2D
(reference mnist_onegpu.py:11-31), exactly — forward, gradients, and
batch-stats updates agree to float tolerance (tests/test_convnet_s2d_t.py)
— and the parameter/batch_stats tree is bit-compatible with both, so
checkpoints, TrainState, and every engine accept any of the three.

Why a third plan: round 3's on-chip micro-benchmarks (tools/conv_micro.py)
showed the NHWC s2d Pallas convs running at 19-27 TF/s — below the XLA
convs they replaced — because with channels on the 128-lane minor dim the
[W, 9C] im2col tile build wastes 7/8 of every VPU op at C=16 and the
operands are lane-padded up to 8x in HBM. Putting channels on SUBLANES
and W on lanes (ops/pallas_conv_t.py) made the tile build tile-aligned
sublane stacking: conv1 fwd 24.6 -> 15.3 ms, conv1 fwd+BN-stats
29.1 -> 15.3 ms (the stats fusion became free), conv2 bwd
57.6 -> 27.3-41.1 ms at bs=16 (the range spans the two recorded r03
sweeps — 25-50% run-to-run spread), with the
fused tail pair (ops/pallas_bn_tail_t.py) keeping the BN/ReLU/pool chain
at one HBM pass per direction.

Layout plumbing (the only places the transpose exists):
- input: ``space_to_depth_t`` emits [N, H/4, 16, W/4] straight from the
  [N, H, W] image — one device transpose of the raw input;
- output: pool2's [N, H/4, f2, W/4] feeds the fc directly — the
  framework-canonical fc row order is (h, c, w), this plan's native
  feature order (models/convnet.py), so no transpose exists here at all
  and fc weights stay interchangeable with ConvNet's.
Channel indexing within C is identical to ConvNetS2D (co minor, (a,b)
block-position major), so BN grouping, pooling pairs, and the kernel
scatter are shared unchanged.
"""

from __future__ import annotations

import functools
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tpu_sandbox.models.convnet_s2d import scatter_kernel


@functools.lru_cache(maxsize=8)
def resize_weights(src: int, dst: int) -> np.ndarray:
    """[dst, src] bilinear interpolation matrix with EXACTLY
    jax.image.resize's weights (train.resize_on_device's method): resize
    is linear and separable, so resizing the identity yields its weight
    matrix. Host-cached f32 constant (a few hundred KB at 28->3000: small
    enough to close over, unlike a full-size image)."""
    with jax.ensure_compile_time_eval():  # concrete even mid-trace
        eye = jnp.eye(src, dtype=jnp.float32)
        w = jax.image.resize(eye, (dst, src), method="bilinear")
        return np.asarray(jax.device_get(w))


def _as_nhw(x: jnp.ndarray) -> jnp.ndarray:
    """[N,H,W,1] or [N,H,W] -> [N,H,W] (shared by __call__ and
    fused_input_stage)."""
    if x.ndim == 4:
        assert x.shape[-1] == 1, "s2d plan is for the 1-channel CNN"
        x = x[..., 0]
    return x


def space_to_depth_t(x: jnp.ndarray, r: int) -> jnp.ndarray:
    """[N,H,W] -> [N, H/r, r*r, W/r] (channel index a*r+b, channels on
    the sublane dim)."""
    n, h, w = x.shape
    x = x.reshape(n, h // r, r, w // r, r)
    return x.transpose(0, 1, 2, 4, 3).reshape(n, h // r, r * r, w // r)


def block_max_pool_t(y: jnp.ndarray, blk: int, co: int) -> jnp.ndarray:
    """2x2/2 max-pool inside the channel (sublane) dim: y
    [..., blk*blk*co, W] with ordering (a*blk+b)*co+c; pool pairs are the
    LOW bits of (a, b). Returns [..., (blk//2)**2*co, W]. Slice/maximum
    form for the same layout reason as block_max_pool."""
    *lead, c, w = y.shape
    assert c == blk * blk * co, (c, blk, co)
    y = y.reshape(*lead, blk // 2, 2, blk // 2, 2, co, w)
    m = jnp.maximum(
        jnp.maximum(y[..., :, 0, :, 0, :, :], y[..., :, 0, :, 1, :, :]),
        jnp.maximum(y[..., :, 1, :, 0, :, :], y[..., :, 1, :, 1, :, :]),
    )
    return m.reshape(*lead, (blk // 2) ** 2 * co, w)


class _ConvT(nn.Module):
    """Same canonical [5,5,ci,co] kernel + bias variables as ConvNet /
    ConvNetS2D. conv1 (r=4, 1-channel input) runs the sparse-tap
    union-tile kernel (ops/pallas_conv5_t.py: K=64 -> half the MXU
    passes of the scattered-3x3 form, whose weight is only 25/144
    dense); conv2 (r=2, 16-channel input, 69%-dense scatter) keeps the
    scattered-3x3 kernel (ops/pallas_conv_t.py).
    TPU_SANDBOX_NO_SPARSE_CONV1=1 reverts conv1 to the scattered-3x3
    kernel — the whole-model A/B lever for the first on-chip runs of the
    r04 kernel (tools/conv_micro.py races the two directly)."""

    shape: tuple[int, ...]
    r: int
    dtype: jnp.dtype
    sparse: bool = True  # conv1's union-tile kernel (in-process A/B lever)

    @nn.compact
    def __call__(self, x, want_stats: bool = False,
                 params_only: bool = False):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), self.shape, jnp.float32
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (self.shape[-1],), jnp.float32
        )
        if params_only:
            # the conv1+tail fused-backward composite (pallas_conv1_tail_t)
            # spans this module's params and bn1's — the parent fetches
            # them here (declared under the same names, so the tree is
            # unchanged) and calls the composite itself
            return kernel.astype(self.dtype), bias.astype(self.dtype)
        # env var read at TRACE time: set it before the process first
        # traces the step (each run is its own process
        # under the one-chip-process discipline); flipping it after a
        # jitted step compiled is a no-op — the jit cache key ignores
        # env. In-process A/B goes through the `sparse` field instead
        # (ConvNetS2DT(sparse_conv1=False) retraces properly).
        no_sparse = os.environ.get("TPU_SANDBOX_NO_SPARSE_CONV1") == "1"
        if (self.r == 4 and self.shape[2] == 1 and self.sparse
                and not no_sparse):
            from tpu_sandbox.ops.pallas_conv5_t import (
                conv1_s2d_t,
                conv1_s2d_t_stats,
            )

            k5 = kernel.astype(self.dtype)
            b = bias.astype(self.dtype)
            if want_stats:
                y, s, ss = conv1_s2d_t_stats(x, k5, b)
                return y, (s, ss)
            return conv1_s2d_t(x, k5, b)
        from tpu_sandbox.ops.pallas_conv_t import conv3x3_t, conv3x3_t_stats

        wg = scatter_kernel(kernel.astype(self.dtype), self.r)
        reps = wg.shape[-1] // self.shape[-1]
        bias_g = jnp.tile(bias.astype(self.dtype), reps)
        if want_stats:
            y, s, ss = conv3x3_t_stats(x, wg, bias_g)
            return y, (s, ss)
        return conv3x3_t(x, wg, bias_g)


class _GroupedBNT(nn.Module):
    """_GroupedBN semantics (models/convnet_s2d.py) over the transposed
    layout [..., g*co, W]; identical variable names/shapes."""

    features: int  # co
    dtype: jnp.dtype
    momentum: float = 0.9
    epsilon: float = 1e-5

    def setup(self):
        co = self.features
        self.scale = self.param(
            "scale", nn.initializers.ones, (co,), jnp.float32
        )
        self.offset = self.param(
            "bias", nn.initializers.zeros, (co,), jnp.float32
        )
        self.ra_mean = self.variable(
            "batch_stats", "mean", lambda s: jnp.zeros(s, jnp.float32), (co,)
        )
        self.ra_var = self.variable(
            "batch_stats", "var", lambda s: jnp.ones(s, jnp.float32), (co,)
        )

    def _update_running(self, mu, var):
        if not self.is_initializing():
            m = self.momentum
            self.ra_mean.value = m * self.ra_mean.value + (1 - m) * mu
            self.ra_var.value = m * self.ra_var.value + (1 - m) * var

    def __call__(self, y, train: bool):
        co = self.features
        *lead, c, w = y.shape
        yg = y.reshape(*lead, c // co, co, w)
        if train:
            yf = yg.astype(jnp.float32)
            red = tuple(i for i in range(yf.ndim) if i != yf.ndim - 2)
            mu = jnp.mean(yf, axis=red)
            mu2 = jnp.mean(jnp.square(yf), axis=red)
            var = jnp.maximum(0.0, mu2 - jnp.square(mu))
            self._update_running(mu, var)
        else:
            mu, var = self.ra_mean.value, self.ra_var.value
        out = (yg.astype(jnp.float32) - mu[:, None]) * (
            jax.lax.rsqrt(var + self.epsilon) * self.scale
        )[:, None] + self.offset[:, None]
        return out.astype(self.dtype).reshape(*lead, c, w)

    def fused(self, y, blk: int, ysums=None):
        from tpu_sandbox.ops.pallas_bn_tail_t import fused_bn_relu_pool_t

        out, mu, var = fused_bn_relu_pool_t(
            y, self.scale, self.offset, self.features, blk, self.epsilon,
            None, ysums,
        )
        self._update_running(mu, var)
        return out

    def fused_conv1(self, x, k5, cbias, blk: int):
        """conv1 + this BN's tail as ONE differentiable unit: the r05
        backward fusion (ops/pallas_conv1_tail_t.py) — conv1's ~4.7 GB
        output cotangent never round-trips HBM (its only consumer is
        the conv wgrad; dx is dead). Forward identical to
        _ConvT(sparse) + self.fused."""
        from tpu_sandbox.ops.pallas_conv1_tail_t import conv1_tail_t

        out, mu, var = conv1_tail_t(
            x, k5, cbias, self.scale, self.offset, self.features, blk,
            self.epsilon,
        )
        self._update_running(mu, var)
        return out


class _DenseT(nn.Module):
    """nn.Dense over the transposed feature map. The kernel variable stays
    [h*w*c, k] with rows flattened in canonical (h, c, w) order — the
    parameter tree is bit-identical to ConvNet's fc: same init path (so
    the same values under the same key), rows in the framework-canonical
    (h, c, w) order that all three plans share — see models/convnet.py
    (the torch reference's own NCHW flatten is (c, h, w); utils/parity.py
    re-blocks between the conventions). On the v5e the [F, k] parameter
    is stored k-major ([k, F], classes on sublanes), and ops/pallas_fc_t
    contracts against exactly that: the activation is flattened to
    [N, F] (and its gradient un-flattened) inside two small Pallas
    kernels, the weight and its gradient never change layout. The
    [k, h, c, w] view this class took before PR 24 was a copy of the
    whole weight each way, 36 ms of an 89.7 ms step on the chip (PERF.md
    section 6, PR 24); the kill-switch branch below still takes it."""

    features: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, y: jnp.ndarray) -> jnp.ndarray:
        n, h, c, w = y.shape
        kernel = self.param(
            "kernel", nn.linear.default_kernel_init,
            (h * w * c, self.features), jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (self.features,), jnp.float32
        )
        # ops/pallas_fc_t: Pallas flatten + input-grad kernels around
        # XLA contractions against kernel.T. The env kill switch (the
        # plain einsum, the tests' oracle) reads at trace time like
        # TPU_SANDBOX_NO_SPARSE_CONV1.
        if os.environ.get("TPU_SANDBOX_NO_PALLAS_FC") != "1":
            from tpu_sandbox.ops.pallas_fc_t import fc_t

            return fc_t(y, kernel, bias, self.dtype)
        k4 = kernel.astype(self.dtype).reshape(h, c, w, self.features)
        out = jnp.einsum("nhcw,hcwk->nk", y, k4)
        return out + bias.astype(self.dtype)


class ConvNetS2DT(nn.Module):
    """Drop-in ConvNet with the transposed space-to-depth execution plan.

    Always runs the Pallas conv kernels; ``fused_tail=True`` (the TPU
    default via ``pick_convnet``) additionally fuses each BN/ReLU/pool
    tail and rides the conv kernels' fused BN statistics. Requires H, W
    divisible by 4 and one input channel (the reference's 3000x3000
    MNIST qualifies); other configs use models.convnet.ConvNet.
    """

    num_classes: int = 10
    features: tuple[int, ...] = (16, 32)
    dtype: jnp.dtype = jnp.float32  # compute dtype; params stay fp32
    use_bn: bool = True
    fused_tail: bool = False
    sparse_conv1: bool = True  # False: scattered-3x3 conv1 (A/B lever)
    fused_conv1_bwd: bool = True  # False: unfused conv1/tail backward

    def fused_input_stage(self, images: jnp.ndarray,
                          image_size: tuple[int, int]) -> jnp.ndarray:
        """Bilinear resize (exactly train.resize_on_device's weights, see
        ``resize_weights``) fused with ``space_to_depth_t``: two small
        contractions against the interpolation matrices emit
        [N, H/4, 16, W/4] straight from the raw [N, h0, w0(, 1)] batch.
        The full-size [N, H, W] image never materializes — in the r03
        step that intermediate cost two whole-image relayout copies
        (~55 ms/step at bs=16, the largest single residue in the 199 ms
        step by round 4's AOT estimate). Feed the result to
        ``__call__``, which detects the pre-s2d shape."""
        H, W = image_size
        assert H % 4 == 0 and W % 4 == 0, (H, W)
        images = _as_nhw(images)
        n, h0, w0 = images.shape
        ah4 = jnp.asarray(resize_weights(h0, H)).reshape(H // 4, 4, h0)
        aw4 = jnp.asarray(resize_weights(w0, W)).reshape(W // 4, 4, w0)
        x = images.astype(jnp.float32)
        u = jnp.einsum("nij,wbj->nibw", x, aw4)          # [N, h0, 4, W/4]
        # The 5D->4D (a,b)->16 merge costs one whole-tensor retiling
        # copy (~6 ms est at bs=16, copy.67 in round 4's AOT estimate; real
        # bytes ~0.6 GB). A per-a-slice + channel-concat variant was
        # AOT-raced in r05 and came out est-neutral (47.8 vs 48.0 ms:
        # the concat just splits the same relayout into four slice
        # copies + a pad fusion, identical traffic) — recorded here so
        # it isn't retried.
        v = jnp.einsum("hai,nibw->nhabw", ah4, u)
        return v.reshape(n, H // 4, 16, W // 4).astype(self.dtype)

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = True) -> jnp.ndarray:
        """x: [N,H,W,1] NHWC, [N,H,W], or a pre-s2d [N,H/4,16,W/4] from
        ``fused_input_stage`` (distinguished by its non-1 trailing dim).
        Returns logits [N, num_classes]."""
        assert len(self.features) == 2, "s2d plan is the 2-block parity CNN"
        f1, f2 = self.features

        if x.ndim == 4 and x.shape[-1] != 1:             # pre-s2d input
            # pre-s2d tensors come only from fused_input_stage
            if x.shape[2] != 16:
                raise ValueError(
                    "expected [N,H,W,1]/[N,H,W] (the s2d plan is the "
                    "1-channel CNN) or a fused_input_stage output "
                    f"[N,H/4,16,W/4]; got {x.shape}"
                )
            x = x.astype(self.dtype)
            n = x.shape[0]
        else:
            x = _as_nhw(x)
            n, h, w = x.shape
            assert h % 4 == 0 and w % 4 == 0, (h, w)
            x = space_to_depth_t(x, 4).astype(self.dtype)  # [N,H/4,16,W/4]

        fuse_stats = self.fused_tail and self.use_bn and train
        conv1 = _ConvT((5, 5, 1, f1), r=4, dtype=self.dtype,
                       sparse=self.sparse_conv1, name="conv1")
        # r05 fused conv1/tail BACKWARD: requires the sparse conv1 and
        # the fused tail both active (the composite is built from those
        # kernels). Trace-time env kill switch like the other levers.
        sparse_on = (self.sparse_conv1
                     and os.environ.get("TPU_SANDBOX_NO_SPARSE_CONV1")
                     != "1")
        fully_fused = (
            fuse_stats and sparse_on and self.fused_conv1_bwd
            and os.environ.get("TPU_SANDBOX_NO_FUSED_CONV1_BWD") != "1"
        )
        if fully_fused:
            k5, cbias = conv1(x, params_only=True)
            y = _GroupedBNT(f1, self.dtype, name="bn1").fused_conv1(
                x, k5, cbias, 4)                         # [N,H/4,4*f1,W/4]
        else:
            y = conv1(x, fuse_stats)
            y, ysums = y if fuse_stats else (y, None)
            y = self._tail(y, f1, 4, "bn1", train, ysums)  # [N,H/4,4*f1,W/4]

        y = _ConvT((5, 5, f1, f2), r=2, dtype=self.dtype,
                   name="conv2")(y, fuse_stats)
        y, ysums = y if fuse_stats else (y, None)
        y = self._tail(y, f2, 2, "bn2", train, ysums)    # [N,H/4,f2,W/4]

        # fc contracts the transposed map in place; the kernel variable
        # keeps the canonical (h, c, w) row order all plans share (_DenseT)
        y = _DenseT(self.num_classes, self.dtype, name="fc")(y)
        return jnp.asarray(y, jnp.float32)

    def _tail(self, y, co: int, blk: int, name: str, train: bool,
              ysums=None):
        """BN + ReLU + 2x2 block pool — fused Pallas pair when enabled."""
        if self.use_bn and self.fused_tail and train:
            return _GroupedBNT(co, self.dtype, name=name).fused(
                y, blk, ysums)
        if self.use_bn:
            y = _GroupedBNT(co, self.dtype, name=name)(y, train)
        y = nn.relu(y)
        return block_max_pool_t(y, blk, co)
