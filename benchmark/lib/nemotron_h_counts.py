"""The operations and bytes a training step of the Nemotron-H configuration
NEEDS, from its shapes: what the roofline shares and ``mfu_pct`` of the
``nemotron_h_train`` runner divide by. Beside ``lib/peaks.py`` (which later
PRs do not edit). Recomputation under ``remat``, masked attention blocks,
tile padding, the experts' alignment tiles and whatever a scan's
implementation moves beyond its operands are work the program does and the
algorithm does not need: none of it is counted, so no share can pass 100 %.
"""

from __future__ import annotations

from benchmark.lib.xing4_counts import causal_attention_train_flops  # noqa: F401


def expert_flops(rows: int, latent: int, d_ff: int, layers: int) -> float:
    """The two expert products (up, down; relu**2 between, no gate) over
    ``rows`` buffer rows, forward and twice that backward."""
    return 2 * 2.0 * latent * d_ff * rows * 3 * layers


def ssd_flops(tokens: int, heads: int, head_dim: int, state: int,
              groups: int, chunk: int, layers: int) -> float:
    """The chunked scan's four products a chunk -- ``C B^T`` (a group),
    ``(L o C B^T)(dt x)``, the chunk's state ``B^T (decay o dt x)`` and
    ``C h`` (a head each) -- forward and twice that backward. The recurrence
    over the chunk states and the decays are elementwise and not counted."""
    a_chunk = (2.0 * chunk * chunk * state * groups
               + 2.0 * chunk * chunk * head_dim * heads
               + 2 * 2.0 * chunk * head_dim * state * heads)
    return a_chunk * (tokens / chunk) * 3 * layers


def ssd_bytes(tokens: int, heads: int, head_dim: int, state: int,
              groups: int, layers: int, itemsize: int = 2) -> float:
    """Bytes the scan needs to move: forward it reads x, B, C (compute
    dtype) and dt (float32) and writes y; backward twice that (the operands
    again, their cotangents out)."""
    a_token = ((2 * heads * head_dim + 2 * groups * state) * itemsize
               + heads * 4)
    return 3.0 * a_token * tokens * layers


def layer_counts(pattern: str) -> dict:
    return {kind: pattern.count(kind) for kind in "M*E"}


def train_flops(config: dict, batch: int, seq: int, local_rows: int) -> float:
    """Model FLOPs of one training step: every matrix product a token needs
    (2 x weights, x 3 for forward + backward), the causal attention, the
    routed experts over ``local_rows`` rows a layer, and the scan."""
    c = config["hidden_size"]
    h, p = config["mamba_num_heads"], config["mamba_head_dim"]
    g, n = config["n_groups"], config["ssm_state_size"]
    hq, hkv, d = (config["num_attention_heads"], config["num_key_value_heads"],
                  config["head_dim"])
    latent = config["moe_latent_size"]
    routed_total = config["deployment"].get("routed_experts_total",
                                            config["n_routed_experts"])
    layers = layer_counts(config["hybrid_override_pattern"])
    mamba = c * (2 * h * p + 2 * g * n + h) + h * p * c
    attn = c * hq * d + c * 2 * hkv * d + hq * d * c
    moe = (c * routed_total + 2 * c * latent
           + 2 * c * config["moe_shared_expert_intermediate_size"])
    per_token = (layers["M"] * mamba + layers["*"] * attn + layers["E"] * moe
                 + c * config["vocab_size"])
    tokens = batch * seq
    return (3 * 2.0 * per_token * tokens
            + causal_attention_train_flops(batch, hq, seq, d, d, layers["*"])
            + expert_flops(local_rows, latent, config["moe_intermediate_size"],
                           layers["E"])
            + ssd_flops(tokens, h, p, n, g, config["chunk_size"], layers["M"]))
