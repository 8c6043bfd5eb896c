"""Parameters, bytes and operations of the LFM2-MoE decode step and of its
prefill, from shapes and from the window's counters.

Only bytes that MUST be read are counted, so that no roofline share can pass
100%: the mixers, norms, routers and both dense SwiGLUs once, a routed
expert's weights only if a live row of the step was routed to it
(`decode.moe_experts_hit`), the embedding ONCE as the head (the rows looked
up are rows of the same array: not counted twice), K and V only of the keys
a row attends (`decode.kv_tokens_attended`: the attention layers alone keep
pages), a conv tail only for the rows that were live
(`decode.conv_rows_updated`: live rows x convolution layers a step; read
once: the write-back is left out, counted low). `m` is the configuration
file's dict; `layers_held` index its published `layer_types`.
"""

from __future__ import annotations


def _dtype_bytes(name: str) -> int:
    return {"bfloat16": 2, "float32": 4}[name]


def layer_kinds(m: dict):
    """The held layers' `layer_types`."""
    return [m["layer_types"][i] for i in m["layers_held"]]


def layers_of(m: dict):
    """(convolution layers, attention layers) among the layers held."""
    kinds = layer_kinds(m)
    attn = sum(1 for k in kinds if k == "full_attention")
    return len(kinds) - attn, attn


def dense_layers(m: dict) -> int:
    """The held layers whose feed-forward is the dense SwiGLU."""
    return sum(1 for i in m["layers_held"] if i < m["num_dense_layers"])


def conv_params(m: dict) -> int:
    """in_proj hidden x 3 hidden, out_proj hidden x hidden, the depthwise
    convolution's taps."""
    d = m["hidden_size"]
    return 3 * d * d + d * d + m["conv_L_cache"] * d


def head_dim(m: dict) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def attention_params(m: dict) -> int:
    """q and o hidden x (heads x hd), k and v hidden x (kv heads x hd), the
    two head norms."""
    d, hd = m["hidden_size"], head_dim(m)
    return d * hd * (2 * m["num_attention_heads"]
                     + 2 * m["num_key_value_heads"]) + 2 * hd


def dense_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def expert_params(m: dict) -> int:
    """One routed SwiGLU expert: three hidden x width matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def router_params(m: dict) -> int:
    """The router over all experts and the expert bias."""
    return m["hidden_size"] * m["num_experts"] + m["num_experts"]


def non_expert_params(m: dict) -> int:
    """What a step reads whatever it routes: the mixers, two norms a layer,
    the dense SwiGLUs, the routers, the final norm and the embedding (read
    as the head)."""
    conv, attn = layers_of(m)
    d, n = m["hidden_size"], len(m["layers_held"])
    dense = dense_layers(m)
    return conv * conv_params(m) + attn * attention_params(m) + 2 * d * n \
        + dense * dense_params(m) + (n - dense) * router_params(m) + d \
        + d * m["vocab_size"]


def params_held(m: dict) -> int:
    """Parameters this chip holds: `non_expert_params` and the held experts
    of every routed layer. The head is the embedding: one array."""
    routed = len(m["layers_held"]) - dense_layers(m)
    return non_expert_params(m) \
        + routed * m["experts_held"][1] * expert_params(m)


def non_expert_weight_bytes(m: dict) -> float:
    """Counted at the matrices' dtype (the float32 gains, taps and expert
    biases are a few hundred KB more: counted low)."""
    return float(_dtype_bytes(m["dtype"]) * non_expert_params(m))


def expert_bytes(m: dict) -> float:
    return float(expert_params(m) * _dtype_bytes(m["dtype"]))


def kv_bytes_per_token_layer(m: dict) -> float:
    """K and V of one cached token in one attention layer."""
    return float(2 * m["num_key_value_heads"] * head_dim(m)
                 * _dtype_bytes(m["dtype"]))


def tail_bytes(m: dict) -> float:
    """One slot's conv tail of ONE convolution layer."""
    return float((m["conv_L_cache"] - 1) * m["hidden_size"]
                 * _dtype_bytes(m["dtype"]))


def step_bytes(m: dict, experts_hit: float, kv_tokens: float,
               conv_rows: float) -> float:
    """Least bytes of one decode step: the non-expert weights once (the
    embedding among them, as the head), the weights of each held expert
    that was hit (summed over layers), the K/V of every key attended
    (summed over rows and attention layers) and the tail of every live row
    of every convolution layer."""
    return (non_expert_weight_bytes(m) + experts_hit * expert_bytes(m)
            + kv_tokens * kv_bytes_per_token_layer(m)
            + conv_rows * tail_bytes(m))


def prefill_flops(m: dict, tokens: int) -> float:
    """Multiply-adds x 2 of one whole-prompt prefill of `tokens` real tokens
    on this chip: the projections and the head's one row, the routed layer
    at top-k experts a token, the convolution's taps and gates, and the
    causal half of attention."""
    conv, attn = layers_of(m)
    d, t = m["hidden_size"], float(tokens)
    dense = dense_layers(m)
    routed = len(m["layers_held"]) - dense
    per_token = conv * 4 * d * d + attn * (attention_params(m)
                                           - 2 * head_dim(m)) \
        + dense * dense_params(m) + routed * (
            d * m["num_experts"]
            + m["num_experts_per_tok"] * expert_params(m))
    matmuls = 2.0 * t * per_token + 2.0 * d * m["vocab_size"]
    taps = conv * t * d * (2.0 * m["conv_L_cache"] + 2.0)
    attention = attn * 2.0 * 2.0 * m["num_attention_heads"] * head_dim(m) \
        * t * (t + 1) / 2
    return matmuls + taps + attention
