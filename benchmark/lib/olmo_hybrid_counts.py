"""The operations and bytes a training step of the Olmo-Hybrid configuration
NEEDS, from its shapes: what the roofline shares and ``mfu_pct`` of the
``olmo_hybrid_train`` runner divide by. Beside ``lib/peaks.py`` (which later
PRs do not edit). Recomputation under ``remat``, masked attention blocks,
tile padding, and everything a chunked form of the delta rule computes
beyond the recurrence (the keys' Gram matrix, the triangular inverse, the
products inside a chunk) are work the program does and the algorithm does
not need: none of it is counted, so no share can pass 100 %.
"""

from __future__ import annotations

from benchmark.lib.xing4_counts import causal_attention_train_flops  # noqa: F401

KINDS = ("linear_attention", "full_attention")


def delta_rule_flops(tokens: int, heads: int, key_dim: int, value_dim: int,
                     layers: int) -> float:
    """The recurrence's own work, a token a head on a ``[d_v, d_k]`` state:
    the decay (1 a state entry), what the state holds for the key (2), the
    rank-one write (2) and the read by the query (2) -- 7 d_k d_v forward,
    and twice that backward."""
    return 7.0 * key_dim * value_dim * heads * tokens * 3 * layers


def delta_rule_bytes(tokens: int, heads: int, key_dim: int, value_dim: int,
                     layers: int, itemsize: int = 2) -> float:
    """Bytes the rule needs to move: forward it reads q, k, v (compute dtype)
    and g, beta (float32) and writes o; backward twice that (the operands
    again, their cotangents out). The state lives on the chip's fast memory
    in a kernel that needs nothing else: it is not counted."""
    a_token = heads * ((2 * key_dim + 2 * value_dim) * itemsize + 2 * 4)
    return 3.0 * a_token * tokens * layers


def layer_counts(layer_types) -> dict:
    return {kind: list(layer_types).count(kind) for kind in KINDS}


def train_flops(config: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: every matrix product a token needs
    (2 x weights, x 3 for forward + backward), the causal attention of the
    full layers, and the delta rule's recurrence of the linear ones."""
    c, ff = config["hidden_size"], config["intermediate_size"]
    h, dk, dv = (config["linear_num_key_heads"], config["linear_key_head_dim"],
                 config["linear_value_head_dim"])
    hq = config["num_attention_heads"]
    layers = layer_counts(config["layer_types"])
    linear = c * (2 * h * dk + 2 * h * dv + 2 * h) + h * dv * c
    per_token = (layers["linear_attention"] * linear
                 + layers["full_attention"] * 4 * c * c
                 + len(config["layer_types"]) * 3 * c * ff
                 + c * config["vocab_size"])
    tokens = batch * seq
    return (3 * 2.0 * per_token * tokens
            + causal_attention_train_flops(batch, hq, seq, c // hq, c // hq,
                                           layers["full_attention"])
            + delta_rule_flops(tokens, h, dk, dv, layers["linear_attention"]))
