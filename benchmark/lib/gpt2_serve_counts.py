"""What one decode step of a GPT-2-shaped model **needs**, counted on what
the traffic drew: the FLOPs and the bytes of one new token for each live
session, given each session's live context (tokens whose keys and values
the new token attends to, itself included). ``decode_mfu_pct`` holds the
whole step against the larger of the two times these give; nothing an
implementation does beyond them counts (a gather of the padded context, a
float32 copy of a weight, a query computed twice), so no implementation can
pass 100 %. Beside ``lib/peaks.py``, which a later PR may not edit.
"""

from __future__ import annotations


def matmul_params(config: dict) -> int:
    """Parameters that stand in a matrix product for every token: qkv and
    the output projection, the MLP's two, the head. The embeddings are
    looked up, not multiplied; biases and LayerNorms are elementwise."""
    d, inner = config["n_embd"], config["n_inner"]
    return (config["n_layer"] * (4 * d * d + 2 * d * inner)
            + d * config["vocab_size"])


def decode_step_flops(config: dict, contexts) -> float:
    """A multiply-add is 2 FLOPs: every matrix parameter once a session,
    and in every layer the new token's scores against its live keys and
    the weighted sum of its live values (2 x 2 x context x d_model)."""
    d, layers = config["n_embd"], config["n_layer"]
    return float(sum(2.0 * matmul_params(config) + layers * 4.0 * c * d
                     for c in contexts))


#: bytes of a weight as it is multiplied, and of a cached key or value: bf16
WEIGHT_BYTES = CACHE_BYTES = 2


def decode_step_bytes(config: dict, contexts) -> float:
    """The matrix weights once a step in the type they are multiplied in,
    each session's live keys and values once in the cache's type, the new
    token's key and value written, its embedding rows read and its float32
    logits written."""
    d, layers = config["n_embd"], config["n_layer"]
    contexts = list(contexts)
    kv_read = sum(layers * 2 * c * d * CACHE_BYTES for c in contexts)
    kv_write = len(contexts) * layers * 2 * d * CACHE_BYTES
    ends = len(contexts) * (2 * d * WEIGHT_BYTES + 4 * config["vocab_size"])
    return float(matmul_params(config) * WEIGHT_BYTES + kv_read + kv_write
                 + ends)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the FLOPs over the
    bf16 peak and the bytes over the memory's peak."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
