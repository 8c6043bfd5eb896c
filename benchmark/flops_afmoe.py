"""Operations and bytes of the AFMoE decode step and of its paged
attention kernel, from shapes and from the window's counters.

Only bytes that MUST be read are counted, so that no roofline share can
pass 100%: a routed expert's weights count only if a token of the step was
routed to it (`decode.moe_experts_hit`), K/V only for the keys a row
attends (`decode.kv_tokens_attended`: a ring layer reads min(context,
window)), the embedding only for the rows that are looked up. `m` is the
configuration file's dict; heads, experts and vocabulary rows are the held
ones.
"""

from __future__ import annotations


def _dtype_bytes(m: dict) -> int:
    return {"bfloat16": 2, "float32": 4}[m["dtype"]]


def moe_layers(m: dict) -> int:
    return len(m["layers_held"]) - m["num_dense_layers"]


def attention_weight_params(m: dict) -> int:
    """Wq, Wg [hidden, q heads x hd], Wk, Wv [hidden, kv heads x hd],
    Wo [q heads x hd, hidden]."""
    d, hd = m["hidden_size"], m["head_dim"]
    return d * hd * (3 * m["q_heads_held"] + 2 * m["kv_heads_held"])


def expert_params(m: dict) -> int:
    """One SwiGLU expert (routed or shared): three hidden x width matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def non_expert_weight_bytes(m: dict) -> float:
    """What every decode step reads whatever it routes: attention of every
    layer, the dense MLPs, the shared experts and routers, the output
    head's held slice (the norm gains are float32 and counted too)."""
    d, b = m["hidden_size"], _dtype_bytes(m)
    layers, dense = len(m["layers_held"]), m["num_dense_layers"]
    matrices = layers * attention_weight_params(m) \
        + dense * 3 * d * m["intermediate_size"] \
        + moe_layers(m) * (expert_params(m) * m["num_shared_experts"]
                           + d * m["num_experts"]) \
        + d * m["vocab_size"]
    gains = layers * (4 * d + 2 * m["head_dim"]) + d \
        + moe_layers(m) * m["num_experts"]
    return float(matrices * b + gains * 4)


def expert_bytes(m: dict) -> float:
    return float(expert_params(m) * _dtype_bytes(m))


def kv_bytes_per_token_layer(m: dict) -> float:
    """K and V of one cached token in one layer."""
    return float(2 * m["kv_heads_held"] * m["head_dim"] * _dtype_bytes(m))


def step_bytes(m: dict, experts_hit: float, kv_tokens: float,
               rows: float) -> float:
    """Least bytes of one decode step: the non-expert weights once, the
    weights of each routed expert that was hit (summed over MoE layers),
    the embedding rows of the live slots, and the K/V of every key
    attended (summed over rows and layers)."""
    return (non_expert_weight_bytes(m) + experts_hit * expert_bytes(m)
            + rows * m["hidden_size"] * _dtype_bytes(m)
            + kv_tokens * kv_bytes_per_token_layer(m))


def paged_gqa_bytes(m: dict, kv_tokens: float) -> float:
    """Least bytes of the paged_gqa_attention kernel over one step: the
    K/V of the keys attended; queries, tables and outputs are small
    beside them and left out, so the share is counted low, never high."""
    return kv_tokens * kv_bytes_per_token_layer(m)


def params_held(m: dict) -> int:
    """Parameters this chip holds (for the configuration's arithmetic)."""
    d = m["hidden_size"]
    return (len(m["layers_held"]) * attention_weight_params(m)
            + m["num_dense_layers"] * 3 * d * m["intermediate_size"]
            + moe_layers(m) * (expert_params(m) * (m["num_shared_experts"]
                                                   + m["experts_held"][1])
                               + d * m["num_experts"])
            + 2 * d * m["vocab_size"])
