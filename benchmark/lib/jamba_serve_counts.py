"""What one decode step of a Jamba-shaped model **needs**, counted on what
the traffic drew, as ``lib/gpt2_serve_counts.py`` counts GPT-2's: the FLOPs
and the bytes of one new token for each live session, given each session's
live context in the attention layers, and the recurrent state every live
session's Mamba layers read and write. ``decode_mfu_pct`` holds the whole
step against the larger of the two times these give,
``mamba_state_roofline`` the state's update against its own bytes; nothing
an implementation does beyond them counts (a gather of the padded context,
a copy of the state, a float32 copy of a weight), so no implementation can
pass 100 %. Beside ``lib/peaks.py``, which a later PR may not edit.
"""

from __future__ import annotations

#: bytes of a weight as it is multiplied, of a cached key or value, of an
#: activation: bf16; of the scan's state and of what the update keeps in
#: float32 (dt, y)
WEIGHT_BYTES = CACHE_BYTES = ACT_BYTES = 2
STATE_BYTES = 4


def layer_counts(config: dict) -> tuple[int, int]:
    """``(mamba layers, attention layers)`` by the published rule."""
    attn = sum(i % config["attn_layer_period"] == config["attn_layer_offset"]
               for i in range(config["num_hidden_layers"]))
    return config["num_hidden_layers"] - attn, attn


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def d_inner(config: dict) -> int:
    return config["mamba_expand"] * config["hidden_size"]


def mlp_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["intermediate_size"]


def mamba_matmul_params(config: dict) -> int:
    """A Mamba mixer's matrices: in, x, dt and out projections."""
    c, d = config["hidden_size"], d_inner(config)
    r, n = config["mamba_dt_rank"], config["mamba_d_state"]
    return c * 2 * d + d * (r + 2 * n) + r * d + d * c


def attn_matmul_params(config: dict) -> int:
    c, hd = config["hidden_size"], head_dim(config)
    return (2 * c * config["num_attention_heads"] * hd
            + 2 * c * config["num_key_value_heads"] * hd)


def matmul_params(config: dict) -> int:
    """Parameters that stand in a matrix product for every token: the
    mixers' projections, every layer's MLP, and the tied embedding as the
    head. Vectors (norms, ``A_log``, ``D``, the convolution, biases) are
    elementwise."""
    mamba, attn = layer_counts(config)
    return (mamba * mamba_matmul_params(config)
            + attn * attn_matmul_params(config)
            + config["num_hidden_layers"] * mlp_params(config)
            + config["hidden_size"] * config["vocab_size"])


def parameters(config: dict) -> int:
    """Every parameter of the model, the vectors among them."""
    c, d = config["hidden_size"], d_inner(config)
    r, n, k = (config["mamba_dt_rank"], config["mamba_d_state"],
               config["mamba_d_conv"])
    mamba, attn = layer_counts(config)
    # the convolution and its bias, b_dt, A_log, D, the three inner norms
    mamba_vectors = (k * d + d) + d + n * d + d + (r + 2 * n)
    return (matmul_params(config) + mamba * mamba_vectors
            + config["num_hidden_layers"] * 2 * c + c)


def slot_state_bytes(config: dict) -> int:
    """One sequence's recurrent state: every Mamba layer's ``[N, D]``
    float32 scan state and its convolution's last ``K - 1`` inputs."""
    mamba, _ = layer_counts(config)
    d = d_inner(config)
    return mamba * (config["mamba_d_state"] * d * STATE_BYTES
                    + (config["mamba_d_conv"] - 1) * d * ACT_BYTES)


def state_update_flops(config: dict, sessions: int) -> float:
    """The scan's update of one token a session a layer: the decay's
    exponent, the state's multiply-add, the input's outer product and the
    contraction with ``C`` -- about 7 operations a state element."""
    mamba, _ = layer_counts(config)
    return 7.0 * sessions * mamba * config["mamba_d_state"] * d_inner(config)


def state_update_bytes(config: dict, sessions: int) -> float:
    """What ``/ssm_step`` has to move: every live session's scan state
    read and written once in every Mamba layer, its ``x`` (bf16), ``dt``
    (float32), ``B`` and ``C`` (bf16) read and ``y`` (float32) written."""
    mamba, _ = layer_counts(config)
    d, n = d_inner(config), config["mamba_d_state"]
    a_slot = (2 * n * d * STATE_BYTES + d * ACT_BYTES + d * STATE_BYTES
              + 2 * n * ACT_BYTES + d * STATE_BYTES)
    return float(sessions * mamba * a_slot)


def decode_step_flops(config: dict, contexts) -> float:
    """A multiply-add is 2 FLOPs: every matrix parameter once a session;
    in every attention layer the new token's scores against its live keys
    and the weighted sum of its live values (2 x 2 x context x heads x
    head_dim); the scans' updates."""
    _, attn = layer_counts(config)
    wide = config["num_attention_heads"] * head_dim(config)
    contexts = list(contexts)
    return float(sum(2.0 * matmul_params(config) + attn * 4.0 * c * wide
                     for c in contexts)
                 + state_update_flops(config, len(contexts)))


def decode_step_bytes(config: dict, contexts) -> float:
    """The matrix weights once a step in the type they are multiplied in;
    each session's recurrent state read and written once (the scan's and
    the convolution's); its live keys and values once in the cache's type
    (at the key/value heads: one for twenty here), the new token's written;
    its embedding row read and its float32 logits written."""
    _, attn = layer_counts(config)
    kv = config["num_key_value_heads"] * head_dim(config)
    contexts = list(contexts)
    kv_read = sum(attn * 2 * c * kv * CACHE_BYTES for c in contexts)
    kv_write = len(contexts) * attn * 2 * kv * CACHE_BYTES
    state = 2 * len(contexts) * slot_state_bytes(config)
    ends = len(contexts) * (config["hidden_size"] * WEIGHT_BYTES
                            + 4 * config["vocab_size"])
    return float(matmul_params(config) * WEIGHT_BYTES + state + kv_read
                 + kv_write + ends)
