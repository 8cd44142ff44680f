"""The operations and bytes a training step of the Xing4.0 configuration
NEEDS, from its shapes: what the roofline shares and ``mfu_pct`` of the
``xing4_train`` runner divide by. Beside ``lib/peaks.py`` (which later PRs
do not edit). Recomputation under ``remat``, masked attention blocks, tile
padding and the experts' alignment tiles are work the program does and the
algorithm does not need: none of it is counted, so no share can pass 100 %.
"""

from __future__ import annotations


def causal_attention_train_flops(batch: int, heads: int, seq: int, d_qk: int,
                                 d_v: int, layers: int) -> float:
    """The causal half of the attention proper with one head size for q.k
    and another for v: forward ``q k^T`` (d_qk) and ``p v`` (d_v); backward
    the scores again, dQ and dK (d_qk each), dV and dP (d_v each). With
    d_qk = d_v = D this is ``peaks.causal_attention_train_flops``."""
    pair = 2.0 * batch * heads * seq * seq / 2.0   # one S x S product per unit width
    return pair * (4 * d_qk + 3 * d_v) * layers


def mhc_bytes(tokens: int, streams: int, width: int, sublayers: int,
              itemsize: int = 2) -> float:
    """Bytes one step's hyper-connections need to move, streams in bf16.
    A sub-layer forward: the streams read twice (once for the token-wide
    norm, the coefficients and the mixed input; once for the residual mix,
    which needs the sub-layer's output first), the mixed input written, the
    sub-layer's output read, the new streams written: 3 n + 2 rows of
    ``width`` a token. Backward the same count (streams and their cotangent
    read, the streams' cotangent written, the two narrow rows). The
    coefficients themselves (24 numbers a token) are not counted."""
    return 2.0 * (3 * streams + 2) * tokens * width * itemsize * sublayers


def expert_flops(rows: int, d_model: int, d_ff: int, layers: int) -> float:
    """The three expert products (gate, up, down) over ``rows`` buffer rows,
    forward and twice that backward."""
    return 3 * 2.0 * d_model * d_ff * rows * 3 * layers


def train_flops(config: dict, batch: int, seq: int, local_rows: int) -> float:
    """Model FLOPs of one training step: every matrix product a token needs
    (2 x weights, x 3 for forward + backward), the causal attention, and
    the routed experts over ``local_rows`` rows a layer."""
    c, h = config["hidden_size"], config["num_attention_heads"]
    d_qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    d_v, n = config["v_head_dim"], config["hc_mult"]
    layers, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    width = config["moe_intermediate_size"]
    routed_total = config["deployment"].get("routed_experts_total",
                                            config["n_routed_experts"])
    mla = (c * config["q_lora_rank"] + config["q_lora_rank"] * h * d_qk
           + c * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
           + config["kv_lora_rank"] * h * (config["qk_nope_head_dim"] + d_v)
           + h * d_v * c)
    mhc = 2 * n * c * (2 * n + n * n)
    per_token = (layers * (mla + mhc) + dense * 3 * c * config["intermediate_size"]
                 + (layers - dense) * (config["n_shared_experts"] * 3 * c * width
                                       + c * routed_total)
                 + c * config["vocab_size"])
    return (3 * 2.0 * per_token * batch * seq
            + causal_attention_train_flops(batch, h, seq, d_qk, d_v, layers)
            + expert_flops(local_rows, c, width, layers - dense))
