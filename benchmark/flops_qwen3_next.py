"""Parameters, bytes and operations of the Qwen3-Next decode step, of its
state kernel and of its prefill, from shapes and from the window's counters.

Only bytes that MUST move are counted, so that no roofline share can pass
100%: the non-expert weights once, a routed expert's weights only if a token
of the step was routed to it (`decode.moe_experts_hit`), K/V only for the
keys a row attends (`decode.kv_tokens_attended`: the attention layers
alone keep pages), a matrix state only for the rows that were live
(`decode.state_rows_updated`: live rows x DeltaNet layers a step; each is
read once and written once), the embedding only for the rows looked up. `m`
is the configuration file's dict; heads, experts and vocabulary rows are the
held ones.
"""

from __future__ import annotations


def _dtype_bytes(name: str) -> int:
    return {"bfloat16": 2, "float32": 4}[name]


def layers_of(m: dict):
    """(DeltaNet layers, attention layers) among the layers held."""
    n, every = m["num_hidden_layers"], m["full_attention_interval"]
    attn = sum(1 for i in range(n) if (i + 1) % every == 0)
    return n - attn, attn


def key_dim(m: dict) -> int:
    return m["linear_key_heads_held"] * m["linear_key_head_dim"]


def value_dim(m: dict) -> int:
    return m["linear_value_heads_held"] * m["linear_value_head_dim"]


def conv_dim(m: dict) -> int:
    return 2 * key_dim(m) + value_dim(m)


def delta_net_params(m: dict) -> int:
    """in_proj_qkvz hidden x (2 key + 2 value widths), in_proj_ba hidden x
    2 value heads, out_proj value width x hidden, the depthwise
    convolution, dt_bias and A_log a value head, the gated norm's gain."""
    d, nv = m["hidden_size"], m["linear_value_heads_held"]
    return d * (2 * key_dim(m) + 2 * value_dim(m)) + d * 2 * nv \
        + value_dim(m) * d + m["linear_conv_kernel_dim"] * conv_dim(m) \
        + 2 * nv + m["linear_value_head_dim"]


def attention_params(m: dict) -> int:
    """q_proj hidden x (q heads x 2 x hd: query and gate), k and v hidden x
    (kv heads x hd), o_proj (q heads x hd) x hidden, the two head norms."""
    d, hd = m["hidden_size"], m["head_dim"]
    return d * hd * (3 * m["q_heads_held"] + 2 * m["kv_heads_held"]) \
        + 2 * hd


def expert_params(m: dict) -> int:
    """One routed SwiGLU expert: three hidden x width matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def moe_common_params(m: dict) -> int:
    """What every chip holds of a routed layer whatever it routes: the
    router over all experts, the shared expert and its one-column gate."""
    d = m["hidden_size"]
    return d * m["num_experts"] \
        + 3 * d * m["shared_expert_intermediate_size"] + d


def params_held(m: dict) -> int:
    """Parameters this chip holds: its layers' mixers, routed layers (the
    held experts) and norms, the held rows of embedding and head."""
    linear, attn = layers_of(m)
    d = m["hidden_size"]
    return linear * delta_net_params(m) + attn * attention_params(m) \
        + m["num_hidden_layers"] * (
            moe_common_params(m) + m["experts_held"][1] * expert_params(m)
            + 2 * d) + d + 2 * d * m["vocab_size"]


def non_expert_weight_bytes(m: dict) -> float:
    """What every decode step reads whatever it routes: every layer's
    mixer, router, shared expert and norms, the head's held slice (the
    embedding is read by row). Counted at the matrices' dtype (the float32
    gains and convolution are a few KB: counted low)."""
    linear, attn = layers_of(m)
    d = m["hidden_size"]
    return float(_dtype_bytes(m["dtype"]) * (
        linear * delta_net_params(m) + attn * attention_params(m)
        + m["num_hidden_layers"] * (moe_common_params(m) + 2 * d) + d
        + d * m["vocab_size"]))


def expert_bytes(m: dict) -> float:
    return float(expert_params(m) * _dtype_bytes(m["dtype"]))


def kv_bytes_per_token_layer(m: dict) -> float:
    """K and V of one cached token in one attention layer."""
    return float(2 * m["kv_heads_held"] * m["head_dim"]
                 * _dtype_bytes(m["dtype"]))


def state_bytes(m: dict) -> float:
    """Bytes the state kernel must move for ONE live row of ONE DeltaNet
    layer: the row's matrix state read once and written once. The row's q,
    k, v, gates and o (a few KB) are left out: counted low, never high."""
    return 2.0 * m["linear_value_heads_held"] * m["linear_key_head_dim"] \
        * m["linear_value_head_dim"] * _dtype_bytes(m["linear_state_dtype"])


def state_slot_bytes(m: dict) -> float:
    """What one slot keeps beside its pages, over the DeltaNet layers held:
    the matrix state and the conv tail."""
    state = state_bytes(m) / 2.0
    tail = conv_dim(m) * (m["linear_conv_kernel_dim"] - 1) \
        * _dtype_bytes(m["dtype"])
    return float(layers_of(m)[0] * (state + tail))


def step_bytes(m: dict, experts_hit: float, kv_tokens: float,
               state_rows: float, rows: float) -> float:
    """Least bytes of one decode step: the non-expert weights once, the
    weights of each held expert that was hit (summed over layers), the
    embedding rows of the live slots, the K/V of every key attended (summed
    over rows and attention layers) and the state of every live row of
    every DeltaNet layer, read and written."""
    return (non_expert_weight_bytes(m) + experts_hit * expert_bytes(m)
            + rows * m["hidden_size"] * _dtype_bytes(m["dtype"])
            + kv_tokens * kv_bytes_per_token_layer(m)
            + state_rows * state_bytes(m))


def prefill_flops(m: dict, tokens: int) -> float:
    """Multiply-adds x 2 of one whole-prompt prefill of `tokens` real
    tokens on this chip: the projections and the head's one row, the routed
    layer at top-k x the held share of the experts (even routing), the
    causal half of attention, and the chunked rule's products (a chunk of C
    tokens a value head: the C x C key and query products over dk, the
    triangular solve's C^3 / 3, three C x C products into dv or dk, and
    four products against the carried [dk, dv] state)."""
    linear, attn = layers_of(m)
    d, t = m["hidden_size"], float(tokens)
    held = m["experts_held"][1] / m["num_experts"]
    per_token = linear * (delta_net_params(m)
                          - m["linear_conv_kernel_dim"] * conv_dim(m)) \
        + attn * attention_params(m) \
        + m["num_hidden_layers"] * (
            moe_common_params(m)
            + m["num_experts_per_tok"] * held * expert_params(m))
    matmuls = 2.0 * t * per_token + 2.0 * d * m["vocab_size"]
    hd = m["head_dim"]
    attention = attn * 2.0 * 2.0 * m["q_heads_held"] * hd * t * (t + 1) / 2
    c = float(m["linear_chunk_size"])
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    a_chunk = 2.0 * (2 * c * c * dk + c ** 3 / 3 + c * c * (2 * dv + dk)
                     + 4 * c * dk * dv)
    rule = linear * m["linear_value_heads_held"] * (t / c) * a_chunk
    return matmuls + attention + rule
