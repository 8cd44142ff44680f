"""What one decode step of a Laguna-shaped model **needs**, counted on what
the traffic drew and on what the router chose, as
``lib/longcat_serve_counts.py`` counts LongCat's: the FLOPs and the bytes of
one new token for each live session, given each session's live context and
the rows the held experts were given. A *full* layer reads all of a
session's cached keys and values, a *window* layer its last
``sliding_window`` (``min(length, window)`` rows), 4096 B a row a layer (K
and V, 8 heads of 128, bfloat16). ``decode_mfu_pct`` holds the whole step
against the larger of the two times these give, ``full_ctx_roofline`` and
``window_ctx_roofline`` each kind's read against its own,
``serve_moe_experts_roofline`` the held experts' products against theirs;
nothing an implementation does beyond them counts (a block's rows before the
window, a tile of zero rows, queries laid out block-diagonally), so no
implementation can pass 100 %. Beside ``lib/peaks.py``, which a later PR may
not edit.
"""

from __future__ import annotations

#: bytes of a weight as it is multiplied, of a cached value, of an
#: activation: bf16; the router's matrix is float32
WEIGHT_BYTES = CACHE_BYTES = ACT_BYTES = 2
ROUTER_BYTES = 4

KINDS = {"full_attention": "full", "sliding_attention": "window"}


def router_width(config: dict) -> int:
    return config.get("deployment", {}).get("routed_experts_total",
                                            config["num_experts"])


def held_experts(config: dict) -> int:
    dep = config.get("deployment", {})
    return len(dep.get("held", range(config["num_experts"])))


def layers(config: dict, kind: str) -> list[int]:
    """The layers of ``kind`` (``full`` | ``window``)."""
    return [i for i, t in enumerate(config["layer_types"])
            if KINDS[t] == kind]


def sparse_layers(config: dict) -> int:
    return sum(t != "dense" for t in config["mlp_layer_types"])


def kv_row_bytes(config: dict) -> int:
    """What a position leaves in a layer's cache: a key and a value."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * CACHE_BYTES


def attention_params(config: dict, layer: int) -> int:
    """A layer's attention matrices: q, k, v, the per-head gate, o."""
    c, d = config["hidden_size"], config["head_dim"]
    h = config["num_attention_heads_per_layer"][layer]
    return c * h * d + 2 * c * config["num_key_value_heads"] * d + c * h \
        + h * d * c


def mlp_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["intermediate_size"]


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["shared_expert_intermediate_size"]


def router_params(config: dict) -> int:
    return config["hidden_size"] * router_width(config)


def dense_matmul_params(config: dict) -> int:
    """Every layer's matrices outside the routers and the routed experts:
    attention, the dense MLPs, the shared experts."""
    n = config["num_hidden_layers"]
    sparse = sparse_layers(config)
    return (sum(attention_params(config, i) for i in range(n))
            + (n - sparse) * mlp_params(config)
            + sparse * shared_params(config))


def parameters(config: dict) -> int:
    """Every parameter held here: the layers with their held experts, every
    vector counted (two norms a layer, the router's bias), the embedding
    and the untied head, the last norm."""
    n, c = config["num_hidden_layers"], config["hidden_size"]
    sparse = sparse_layers(config)
    return (dense_matmul_params(config)
            + sparse * (router_params(config) + router_width(config)
                        + held_experts(config) * expert_params(config))
            + 2 * n * c + 2 * config["vocab_size"] * c + c)


def mean_held_rows(config: dict, sessions: int) -> float:
    """The rows the held experts see in a step, a layer, where the router
    chooses evenly: ``sessions x top_k x held / router outputs``."""
    return (sessions * config["num_experts_per_tok"] * held_experts(config)
            / router_width(config))


def live_rows(config: dict, contexts, kind: str) -> float:
    """The cached rows a layer of ``kind`` reads in a step: every session's
    context, or its last ``sliding_window`` positions."""
    if kind == "full":
        return float(sum(contexts))
    return float(sum(min(n, config["sliding_window"]) for n in contexts))


def ctx_flops(config: dict, contexts, kind: str) -> float:
    """The attention core over the cached rows of the layers of ``kind``:
    every query head's score against a row's key (``head_dim``
    multiply-adds) and its weighted sum of the row's value."""
    heads = sum(config["num_attention_heads_per_layer"][i]
                for i in layers(config, kind))
    return 4.0 * heads * config["head_dim"] * live_rows(config, contexts, kind)


def ctx_bytes(config: dict, contexts, kind: str) -> float:
    """Every live cached row read once a layer of ``kind``, K and V."""
    return float(len(layers(config, kind)) * kv_row_bytes(config)
                 * live_rows(config, contexts, kind))


def experts_flops(config: dict, rows: float) -> float:
    """The held experts' three products on the ``rows`` they were given, a
    step, all sparse layers."""
    return 2.0 * sparse_layers(config) * rows * expert_params(config)


def experts_bytes(config: dict, rows: float) -> float:
    """The held experts' matrices once and their rows in and out, a step,
    all sparse layers."""
    return float(sparse_layers(config) * (
        held_experts(config) * expert_params(config) * WEIGHT_BYTES
        + rows * 2 * config["hidden_size"] * ACT_BYTES))


def decode_step_flops(config: dict, contexts, rows: float | None = None
                      ) -> float:
    """A multiply-add is 2 FLOPs: the dense matrices, the routers and the
    head once a session; the held experts on the rows they were given
    (``rows`` a layer; the even router's mean where None); the attention
    core on every live cached row of either kind."""
    contexts = list(contexts)
    n = len(contexts)
    rows = mean_held_rows(config, n) if rows is None else rows
    per_session = 2.0 * (dense_matmul_params(config)
                         + sparse_layers(config) * router_params(config)
                         + config["hidden_size"] * config["vocab_size"])
    return float(n * per_session + experts_flops(config, rows)
                 + ctx_flops(config, contexts, "full")
                 + ctx_flops(config, contexts, "window"))


def decode_step_bytes(config: dict, contexts, rows: float | None = None
                      ) -> float:
    """The matrices once a step in the type they are multiplied in (the held
    experts' among them, the routers' in float32, the head; of the
    embedding a row a session); every live cached row once a layer of its
    kind and the new token's written; the experts' rows in and out; the
    float32 logits written."""
    contexts = list(contexts)
    n = len(contexts)
    rows = mean_held_rows(config, n) if rows is None else rows
    weights = (dense_matmul_params(config) * WEIGHT_BYTES
               + sparse_layers(config) * router_params(config) * ROUTER_BYTES
               + config["hidden_size"] * config["vocab_size"] * WEIGHT_BYTES)
    writes = n * config["num_hidden_layers"] * kv_row_bytes(config)
    ends = n * (config["hidden_size"] * WEIGHT_BYTES
                + 4 * config["vocab_size"])
    return float(weights + experts_bytes(config, rows)
                 + ctx_bytes(config, contexts, "full")
                 + ctx_bytes(config, contexts, "window") + writes + ends)
