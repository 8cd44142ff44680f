"""Analytic FLOP accounting + per-chip peak table => MFU.

VERDICT r01 found the benchmark reported ~10x a v5e's bf16 peak because
nothing in the repo cross-checked achieved FLOP/s against the hardware
ceiling. This module is that cross-check: a hand-derived FLOP model for the
reference-parity ConvNet (reference mnist_onegpu.py:11-31 defines the
architecture; SURVEY §2.1 C11), a peak-FLOPs table keyed on
``jax.Device.device_kind``, and an MFU helper that flags physically
impossible numbers instead of publishing them.

Conventions (stated so the numbers are auditable):
- Model FLOPs count matmul/conv multiply-adds as 2 FLOPs; elementwise work
  (BN, ReLU, pooling, the on-device 28->3000 resize) is excluded — standard
  MFU accounting, which therefore *understates* utilization slightly.
- Training = forward + backward. Backward of a conv/matmul costs 2x its
  forward (grad wrt input + grad wrt weights), except the first conv, whose
  grad wrt the *input image* is never needed — we subtract that term rather
  than quoting the usual flat 3x.
- MFU is computed against the chip's *bf16 systolic-array peak* regardless
  of the run dtype; fp32 runs will show lower MFU by construction (TPUs
  have no faster fp32 path than bf16).
"""

from __future__ import annotations

from dataclasses import dataclass

#: bf16 peak matmul TFLOP/s per chip, keyed by the EXACT
#: ``jax.Device.device_kind`` string (spellings as in the installed jax's
#: own table, jax/_src/pallas/mosaic/tpu_info.py). Figures: Google Cloud
#: TPU documentation, the "System architecture" page of each generation
#: (cloud.google.com/tpu/docs/v5e: 197 TFLOP/s bf16 per v5e chip).
#: A kind that is not listed has no peak here: it is an error, never a
#: guess ("TPU v5" is a v5p to jax, not a v5e).
PEAK_BF16_TFLOPS: dict[str, float] = {
    "TPU v6 lite": 918.0,  # v6e
    "TPU v5p": 459.0,
    "TPU v5 lite": 197.0,  # v5e
    "TPU v4": 275.0,
    "TPU v3": 123.0,
    "TPU v2": 46.0,
}


def device_peak_tflops(device_kind: str) -> float:
    """bf16 peak for an exact ``device_kind``. An unlisted kind (``cpu``
    included) raises: a utilization against an assumed peak is not a
    measurement."""
    try:
        return PEAK_BF16_TFLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published bf16 peak for device_kind {device_kind!r}; "
            f"known kinds: {sorted(PEAK_BF16_TFLOPS)}. Add it to "
            "PEAK_BF16_TFLOPS with its source before measuring on it."
        ) from None


def conv2d_flops(h: int, w: int, c_in: int, c_out: int, k: int) -> float:
    """'same'-padded stride-1 conv forward FLOPs at output h x w."""
    return 2.0 * h * w * c_out * k * k * c_in


@dataclass(frozen=True)
class ConvNetFlops:
    """Per-image FLOP breakdown for the parity ConvNet at a given input size.

    Architecture (models/convnet.py, mirroring reference mnist_onegpu.py:14-24):
    conv 1->16 k5 same; pool /2; conv 16->32 k5 same; pool /2; dense -> 10.
    """

    conv1: float
    conv2: float
    fc: float

    @property
    def forward(self) -> float:
        return self.conv1 + self.conv2 + self.fc

    @property
    def train(self) -> float:
        """fwd + bwd; conv1's grad-wrt-input term is excluded (the input is
        data, its gradient is never formed)."""
        return 3.0 * self.forward - self.conv1


def convnet_flops(image_size: int, num_classes: int = 10) -> ConvNetFlops:
    h = w = image_size
    conv1 = conv2d_flops(h, w, 1, 16, 5)
    conv2 = conv2d_flops(h // 2, w // 2, 16, 32, 5)
    features = 32 * (h // 4) * (w // 4)
    fc = 2.0 * features * num_classes
    return ConvNetFlops(conv1=conv1, conv2=conv2, fc=fc)


#: per-(output element) matmul contraction depths of the s2d-plan Pallas
#: kernels at the production geometry (H=W=image/4): EXECUTED flops per
#: custom call = 2 * B * H * W * _S2D_KERNEL_K[class]. conv taps run the
#: scattered 3x3 at the s2d channel widths (conv1: 16 in -> 256 out;
#: conv2: 64 in -> 128 out); the bn tails' matmuls are the pool
#: compaction/scatter selections (bn1: [64,256] sel; bn2: [32,128]).
#: ``bn1.fused_conv1`` is the scope of the conv1+tail composite
#: (ops/pallas_conv1_tail_t.py). Forward: the sparse conv1 (64 tap rows
#: x 256) and the tail (256 x 64 selection) — the same depth. ``fc`` is
#: the head's Pallas input-grad (ops/pallas_fc_t.py: 10 classes x f2 = 32
#: channels per output position; its forward and wgrad are XLA dots, and
#: its forward Pallas call is the flatten, a copy: _S2D_KERNEL_K_FWD).
_S2D_KERNEL_K = {
    "/bn1.fused_conv1/": 64 * 256,
    "/conv1/": 9 * 16 * 256,   # in 16 (s2d image), out blk^2*f1 = 256
    "/conv2/": 9 * 64 * 128,   # in 4*f1 = 64 (pool1), out blk^2*f2 = 128
    "/bn1.fused/": 256 * 64,   # pool compaction/scatter selection matmuls
    "/bn2.fused/": 128 * 32,
    "/fc/": 10 * 32,
}

#: backward calls whose depth differs from the class's forward: the
#: composite's backward is the tail's reduce pass (one selection matmul)
#: plus ONE kernel doing the selection matmul AND the conv1 wgrad dot —
#: three 64x256-deep contractions over two calls, so 1.5 per call.
_S2D_KERNEL_K_BWD = {"/bn1.fused_conv1/": 1.5 * 64 * 256}

#: forward calls whose depth differs from the class's: the head's forward
#: kernel only flattens the activation
_S2D_KERNEL_K_FWD = {"/fc/": 0}

#: the transposed plan's conv1 runs the sparse-tap union-tile kernel
#: since r04 (ops/pallas_conv5_t.py): K = 64 tap rows, not 9C = 144
_S2DT_OVERRIDES = {"/conv1/": 64 * 256}


def pallas_call_paths(hlo_text: str) -> list[str]:
    """The ``op_name`` path of every Pallas kernel instruction in an HLO
    module: ``%name = <shape> custom-call(...)`` lines whose metadata path
    ends in .../pallas_call (plain XLA gather/scatter ops under the same
    module paths, and non-Pallas custom calls, are not kernels)."""
    import re

    paths = []
    for line in hlo_text.splitlines():
        if not re.search(r"= [^=]*custom-call\(", line):
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        if m and "/pallas_call" in m.group(1):
            paths.append(m.group(1))
    return paths


def model_runs_sparse_conv1(model) -> bool:
    """Whether this model instance will EXECUTE the sparse-tap conv1
    kernel, accounting for both the ``sparse_conv1`` field and the
    TPU_SANDBOX_NO_SPARSE_CONV1 kill switch (read at trace time by
    models/convnet_s2d_t.py::_ConvT). The FLOP cross-check must key on
    this, never on the class name alone."""
    import os

    return (type(model).__name__ == "ConvNetS2DT"
            and getattr(model, "sparse_conv1", False)
            and os.environ.get("TPU_SANDBOX_NO_SPARSE_CONV1") != "1")


def s2d_custom_call_flops(hlo_text: str, batch: int, image_size: int,
                          plan: str = "s2dt",
                          sparse_conv1: bool | None = None) -> dict:
    """Analytic EXECUTED flops of the Pallas custom calls in a compiled
    s2d/s2dt train step, counted from the optimized HLO (VERDICT r03
    weak-7: XLA's cost analysis cannot see into custom calls, so
    ``flops_per_step_xla`` silently undercounts exactly when the
    production kernels are in play; composing it with this makes the
    cross-check real). Counts every custom-call line whose op_name names
    a model kernel; per-call flops are the kernel's one matmul over the
    full [B, H, W] geometry, which holds for fwd, dgrad, wgrad, and the
    tail kernels alike (same contraction per output element).

    ``sparse_conv1`` is the EXECUTED conv1 kernel choice, not the model
    class: ConvNetS2DT can run the scattered-3x3 conv1 (K = 9*16) via
    ``sparse_conv1=False`` or TPU_SANDBOX_NO_SPARSE_CONV1=1, in which
    case keying the K table on the class name would undercount every
    conv1 call by 2.25x while ``unmatched_pallas_calls`` stayed 0 —
    exactly the silent-wrong-cross-check this function exists to prevent
    (ADVICE r04 medium). Callers that know the model should pass
    ``model_runs_sparse_conv1(model)``; None falls back to the plan-name
    heuristic for HLO-only callers."""
    h = w = image_size // 4
    base = 2.0 * batch * h * w
    table = dict(_S2D_KERNEL_K)
    if sparse_conv1 is None:
        sparse_conv1 = "s2dt" in plan.lower()
    if sparse_conv1:
        table.update(_S2DT_OVERRIDES)
    per_class: dict[str, float] = {}
    count = unmatched = 0
    for path in pallas_call_paths(hlo_text):
        for tag, k in table.items():
            if tag in path:
                if "transpose(" in path:
                    k = _S2D_KERNEL_K_BWD.get(tag, k)
                else:
                    k = _S2D_KERNEL_K_FWD.get(tag, k)
                key = tag.strip("/")
                per_class[key] = per_class.get(key, 0.0) + base * k
                count += 1
                break
        else:
            unmatched += 1  # a Pallas call this table doesn't know
    return {
        "total": sum(per_class.values()),
        "per_class": per_class,
        "custom_calls_counted": count,
        "unmatched_pallas_calls": unmatched,
    }


def transformer_flops(
    n_layers: int, d_model: int, d_ff: int, seq: int, vocab: int
) -> dict[str, float]:
    """Per-token forward FLOPs for the TransformerLM (models/transformer.py):
    the standard 2*params matmul accounting + attention score/value terms."""
    per_layer = (
        2.0 * 4 * d_model * d_model  # qkv + out projections
        + 2.0 * 2 * d_model * d_ff  # mlp up + down
        + 2.0 * 2 * seq * d_model  # QK^T and PV, amortized per token
    )
    head = 2.0 * d_model * vocab
    fwd = n_layers * per_layer + head
    return {"forward": fwd, "train": 3.0 * fwd}


def mfu(flops_per_step: float, sec_per_step: float, device_kind: str,
        n_devices: int = 1) -> dict:
    """Achieved TFLOP/s + model-FLOPs utilization, with a sanity verdict.

    Returns achieved_tflops, peak_tflops_bf16, mfu, and plausible=False
    when mfu > 1 — the r01 failure mode this module exists to catch.
    Raises ``ValueError`` for a ``device_kind`` with no published peak.
    """
    achieved = flops_per_step / sec_per_step / 1e12
    total_peak = device_peak_tflops(device_kind) * n_devices
    util = achieved / total_peak
    return {
        "achieved_tflops": achieved,
        "peak_tflops_bf16": total_peak,
        "mfu": util,
        "plausible": 0.0 < util <= 1.0,
    }
