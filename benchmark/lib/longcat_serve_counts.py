"""What one decode step of a LongCat-Flash-shaped model **needs**, counted on
what the traffic drew and on what the router chose, as
``lib/jamba_serve_counts.py`` counts Jamba's: the FLOPs and the bytes of one
new token for each live session, given each session's live context in the
latent cache and the rows the held experts were given. ``decode_mfu_pct``
holds the whole step against the larger of the two times these give,
``latent_ctx_roofline`` the read of the context against its own,
``serve_moe_experts_roofline`` the held experts' products against theirs;
nothing an implementation does beyond them counts (the cache row's padding
lanes, a tile of zero rows, an expert's matrices read a second time), so no
implementation can pass 100 %. Beside ``lib/peaks.py``, which a later PR may
not edit.
"""

from __future__ import annotations

#: bytes of a weight as it is multiplied, of a cached latent value, of an
#: activation: bf16; the router's matrix is float32
WEIGHT_BYTES = CACHE_BYTES = ACT_BYTES = 2
ROUTER_BYTES = 4


def router_width(config: dict) -> int:
    dep = config.get("deployment", {})
    return (dep.get("routed_experts_total", config["n_routed_experts"])
            + config["zero_expert_num"])


def held_experts(config: dict) -> int:
    dep = config.get("deployment", {})
    return len(dep.get("held", range(config["n_routed_experts"])))


def latent_dim(config: dict) -> int:
    """The values a position leaves in the cache, an attention sub-layer."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def mla_matmul_params(config: dict) -> int:
    """An attention sub-layer's matrices. In the absorbed form a token's
    products with ``W_kvb`` (``q~ = q_nope W^K``, ``o = ctx W^V``) are as
    many multiply-adds as its parameters."""
    c, h = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return (c * config["q_lora_rank"] + config["q_lora_rank"] * h * qk
            + c * latent_dim(config)
            + config["kv_lora_rank"] * h * (config["qk_nope_head_dim"]
                                            + config["v_head_dim"])
            + h * config["v_head_dim"] * c)


def mlp_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["ffn_hidden_size"]


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["expert_ffn_hidden_size"]


def router_params(config: dict) -> int:
    return config["hidden_size"] * router_width(config)


def dense_matmul_params(config: dict) -> int:
    """A double layer's matrices outside the router and the routed experts:
    two attention sub-layers and two dense MLPs."""
    return 2 * mla_matmul_params(config) + 2 * mlp_params(config)


def layer_parameters(config: dict) -> int:
    """A double layer outside its routed experts, every vector counted: the
    router with its bias, the two inner norms a sub-layer, the four norms."""
    norms = 2 * (config["q_lora_rank"] + config["kv_lora_rank"]) \
        + 4 * config["hidden_size"]
    return (dense_matmul_params(config) + router_params(config)
            + router_width(config) + norms)


def parameters(config: dict) -> int:
    """Every parameter held here: the layers with their held experts, the
    embedding and the untied head (``vocab_size`` rows each), the last
    norm."""
    return (config["num_layers"] * (layer_parameters(config)
                                    + held_experts(config)
                                    * expert_params(config))
            + 2 * config["vocab_size"] * config["hidden_size"]
            + config["hidden_size"])


def mean_held_rows(config: dict, sessions: int) -> float:
    """The rows the held experts see in a step, a layer, where the router
    chooses evenly: ``sessions x top_k x held / router outputs``."""
    return (sessions * config["moe_topk"] * held_experts(config)
            / router_width(config))


def latent_ctx_flops(config: dict, contexts) -> float:
    """The attention core of the absorbed form: every head's score against
    a cached row (``latent_dim`` multiply-adds) and its weighted sum of the
    row's first ``kv_lora_rank`` values, a context token a sub-layer."""
    per_token = 2.0 * config["num_attention_heads"] * (
        latent_dim(config) + config["kv_lora_rank"])
    return float(2 * config["num_layers"] * per_token * sum(contexts))


def latent_ctx_bytes(config: dict, contexts) -> float:
    """Every live cached row read once a sub-layer: ``latent_dim`` values
    in the cache's type (1152 B), not the lanes a layout pads them to."""
    return float(2 * config["num_layers"] * latent_dim(config) * CACHE_BYTES
                 * sum(contexts))


def experts_flops(config: dict, rows: float) -> float:
    """The held experts' three products on the ``rows`` they were given, a
    step, all layers."""
    return 2.0 * config["num_layers"] * rows * expert_params(config)


def experts_bytes(config: dict, rows: float) -> float:
    """The held experts' matrices once and their rows in and out, a step,
    all layers."""
    return float(config["num_layers"] * (
        held_experts(config) * expert_params(config) * WEIGHT_BYTES
        + rows * 2 * config["hidden_size"] * ACT_BYTES))


def decode_step_flops(config: dict, contexts, rows: float | None = None
                      ) -> float:
    """A multiply-add is 2 FLOPs: the dense matrices, the router and the
    head once a session; the held experts on the rows they were given
    (``rows`` a layer; the even router's mean where None); the attention
    core on every live cached row."""
    contexts = list(contexts)
    n = len(contexts)
    rows = mean_held_rows(config, n) if rows is None else rows
    per_session = 2.0 * (config["num_layers"] * (
        dense_matmul_params(config) + router_params(config))
        + config["hidden_size"] * config["vocab_size"])
    return float(n * per_session + experts_flops(config, rows)
                 + latent_ctx_flops(config, contexts))


def decode_step_bytes(config: dict, contexts, rows: float | None = None
                      ) -> float:
    """The matrices once a step in the type they are multiplied in (the
    held experts' among them, the router's in float32, the head; of the
    embedding a row a session); every live latent row once a sub-layer and
    the new token's written; the experts' rows in and out; the float32
    logits written."""
    contexts = list(contexts)
    n = len(contexts)
    rows = mean_held_rows(config, n) if rows is None else rows
    weights = config["num_layers"] * (
        dense_matmul_params(config) * WEIGHT_BYTES
        + router_params(config) * ROUTER_BYTES) \
        + config["hidden_size"] * config["vocab_size"] * WEIGHT_BYTES
    writes = n * 2 * config["num_layers"] * latent_dim(config) * CACHE_BYTES
    ends = n * (config["hidden_size"] * WEIGHT_BYTES
                + 4 * config["vocab_size"])
    return float(weights + experts_bytes(config, rows)
                 + latent_ctx_bytes(config, contexts) + writes + ends)
