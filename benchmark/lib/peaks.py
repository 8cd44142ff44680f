"""Published peaks of the chips, and the operations a step requires.

Copied from ``tpu_sandbox/utils/flops.py`` (peaks, ``convnet_flops``,
``transformer_flops``) and extended with memory and interconnect peaks:
later PRs may change the program, not the yardstick. The original is
listed under Open questions in PERF.md for deletion.
"""

from __future__ import annotations

#: Keyed by the exact ``jax.Device.device_kind``. Source: Google Cloud
#: documentation, "TPU v5e" system architecture page
#: (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 16 GB of HBM2e at
#: 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect per chip. A kind
#: that is not listed is an error, never a default ("TPU v5" is a v5p).
PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add it to benchmark/lib/peaks.py with its "
            "source before measuring on it.") from None


def convnet_train_flops(image_size: int, num_classes: int = 10) -> float:
    """Model FLOPs of one training step on ONE image of the source paper's
    ConvNet (conv 1->16 k5 same, pool /2, conv 16->32 k5 same, pool /2,
    dense -> classes). A multiply-add is 2 FLOPs; elementwise work (BN,
    ReLU, pooling, the 28->3000 resize) is not counted. Backward costs
    twice the forward, except conv1's gradient with respect to the image,
    which is never formed."""
    h = w = image_size
    conv1 = 2.0 * h * w * 16 * 25 * 1
    conv2 = 2.0 * (h // 2) * (w // 2) * 32 * 25 * 16
    fc = 2.0 * 32 * (h // 4) * (w // 4) * num_classes
    return 3.0 * (conv1 + conv2 + fc) - conv1


def transformer_train_flops_per_token(n_layers: int, d_model: int, d_ff: int,
                                      seq: int, vocab: int) -> float:
    """Model FLOPs of one training step per token of a dense decoder:
    2 x parameters for the matmuls (qkv + out, mlp up + down, the head),
    plus the attention scores and values at full (unmasked) length, times
    three for forward + backward. Recomputed operations do not count."""
    per_layer = (2.0 * 4 * d_model * d_model + 2.0 * 2 * d_model * d_ff
                 + 2.0 * 2 * seq * d_model)
    return 3.0 * (n_layers * per_layer + 2.0 * d_model * vocab)


def causal_attention_train_flops(batch: int, heads: int, seq: int,
                                 head_dim: int, layers: int) -> float:
    """FLOPs of the attention proper (scores and values, no projections)
    that one training step NEEDS: the causal half of the two ``seq x seq``
    matmuls a head, forward once (2 matmuls) and backward 2.5 times that (5
    matmuls: scores again inside the kernel, dV, dP, dQ, dK) -- 3.5 x
    (2 x 2 x B x H x S^2 x D / 2) a layer. What a kernel does beyond that
    does not count: the masked half of a block it does not skip, the
    forward pass repeated under ``remat``. So the share of the peak this
    gives stays under 100 % whatever a later kernel skips or repeats. At
    S 1024, D 64 the FLOPs bind, not the bytes: a layer's 60 GFLOP want
    0.31 ms of the bf16 peak where its q, k, v, o and their gradients (0.2
    GB) want 0.25 ms of HBM; a configuration where the bytes bind needs
    their count too."""
    forward = 2.0 * 2.0 * batch * heads * seq * seq * head_dim / 2.0
    return 3.5 * forward * layers


def mfu_pct(flops_per_step: float, step_s: float, device_kind: str,
            chips: int) -> float:
    """Model FLOP/s utilisation, in percent of ``chips`` x the bf16 peak."""
    return 100.0 * flops_per_step / step_s / (
        chips * peak(device_kind)["bf16_flops_per_s"])
