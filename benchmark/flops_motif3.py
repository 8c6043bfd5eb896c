"""Operations and bytes of the Motif-3 decode step, prefill attention,
residual kernels and grouped expert kernel, from shapes and from the
window's counters.

Only bytes that MUST be moved and operations that MUST be done are counted,
so that no roofline share can pass 100%: a routed expert's weights count
only if a token of the step was routed to it (`decode.moe_experts_hit`), a
latent row only for a position a row attends (`decode.kv_tokens_attended`,
the rings' part of it `decode.ring_latent_rows_attended`) and at the 576
values it means, not the 640 lanes a page carries them in; the embedding
only for the rows looked up; a window layer's prefill only the band's
pairs. `m` is the configuration file's dict; experts and vocabulary rows
are the held ones, every head is held.
"""

from __future__ import annotations


def _dtype_bytes(m: dict) -> int:
    return {"bfloat16": 2, "float32": 4}[m["dtype"]]


def layers(m: dict) -> int:
    return len(m["layers_held"])


def moe_layers(m: dict) -> int:
    return sum(1 for i in m["layers_held"] if i >= m["n_dense_first_layers"])


def full_layers(m: dict) -> int:
    return sum(1 for i in m["layers_held"]
               if (i + 1) % m["sliding_window_period"] == 0)


def nope_dim(m: dict) -> int:
    return m["head_dim"] - m["qk_rope_head_dim"]


def latent_dim(m: dict) -> int:
    """Values a latent row holds of a token: the latent, the shared key."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def signal_heads(m: dict) -> int:
    return m["num_attention_heads"] - m["num_noise_heads"]


def n_maps(m: dict) -> int:
    n = m["mhc_expansion_rate"]
    return 2 * n + n * n


def attention_weight_params(m: dict) -> int:
    """W_qa, W_qb [q rank, heads x head], W_kva [hidden, rank + rope],
    W_kvb [rank, kv heads x (nope + v)], W_lam [hidden, signal heads], W_g
    and W_o [hidden, signal heads x v]."""
    d, n = m["hidden_size"], m["num_attention_heads"]
    return (d * m["q_lora_rank"] + m["q_lora_rank"] * n * m["head_dim"]
            + d * latent_dim(m)
            + m["kv_lora_rank"] * m["num_key_value_heads"]
            * (nope_dim(m) + m["v_head_dim"])
            + d * signal_heads(m)
            + 2 * d * signal_heads(m) * m["v_head_dim"])


def phi_params(m: dict) -> int:
    """The two sublayers' map projections of one layer."""
    return 2 * m["mhc_expansion_rate"] * m["hidden_size"] * n_maps(m)


def expert_params(m: dict) -> int:
    """One expert (routed or shared): three hidden x width matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_bytes(m: dict) -> float:
    return float(expert_params(m) * _dtype_bytes(m))


def non_expert_weight_bytes(m: dict) -> float:
    """What every decode step reads whatever it routes: attention and the
    maps' projections of every layer, the dense MLPs, the shared experts
    and routers, the head's held slice; the float32 gains (sublayer norms,
    the streams' norm, the latent norms) counted too."""
    d, b = m["hidden_size"], _dtype_bytes(m)
    dense = layers(m) - moe_layers(m)
    matrices = layers(m) * (attention_weight_params(m) + phi_params(m)) \
        + dense * 3 * d * m["intermediate_size"] \
        + moe_layers(m) * (expert_params(m) * m["num_shared_experts"]
                           + d * m["num_experts"]) \
        + d * m["vocab_size"]
    gains = layers(m) * (2 * d + 2 * m["mhc_expansion_rate"] * d
                         + m["q_lora_rank"] + m["kv_lora_rank"]) + d
    return float(matrices * b + gains * 4)


def latent_row_bytes(m: dict) -> float:
    """The latent row of one cached token in one layer."""
    return float(latent_dim(m) * _dtype_bytes(m))


def step_bytes(m: dict, experts_hit: float, latent_rows: float,
               rows: float) -> float:
    """Least bytes of one decode step: the non-expert weights once, each
    routed expert that was hit (summed over MoE layers), the embedding rows
    of the live slots, and the latent row of every position attended, ring
    and pages alike (summed over rows and layers). The streams' traffic
    (224 KB a row a sublayer) is left out: counted low, never high."""
    return (non_expert_weight_bytes(m) + experts_hit * expert_bytes(m)
            + rows * m["hidden_size"] * _dtype_bytes(m)
            + latent_rows * latent_row_bytes(m))


def prefill_pairs(m: dict, tokens: int) -> float:
    """(query, key) pairs the prefill kernel is GIVEN for one bucket of
    `tokens` positions, summed over the layers held: the causal triangle
    with its diagonal in a full layer, the band (a query's last
    `sliding_window` keys, fewer at the start) in a window layer."""
    w = min(m["sliding_window"], tokens)
    band = tokens * w - w * (w - 1) / 2.0
    return full_layers(m) * tokens * (tokens + 1) / 2.0 \
        + (layers(m) - full_layers(m)) * band


def mla_prefill_flops(m: dict, pairs: float) -> float:
    """Least operations of the prefill kernel for `pairs` pairs: every
    query head's score (over nope + rope) and its weighted sum of values
    (over v), 2 operations a multiply-add; softmax and masked parts left
    out."""
    return pairs * 2.0 * m["num_attention_heads"] * (
        m["head_dim"] + m["v_head_dim"])


def mhc_pre_bytes(m: dict, rows: float) -> float:
    """Least bytes of `mhc_pre` over `rows` tokens of ONE sublayer: the
    streams read once (n x C float32), u written (C float32); Phi, the
    gain and the maps' lane tile left out."""
    return rows * 4.0 * m["hidden_size"] * (m["mhc_expansion_rate"] + 1)


def mhc_post_bytes(m: dict, rows: float) -> float:
    """Least bytes of `mhc_post`: the streams and y read once, the streams
    written once."""
    return rows * 4.0 * m["hidden_size"] * (2 * m["mhc_expansion_rate"] + 1)


def sublayers(m: dict) -> int:
    return 2 * layers(m)


def params_held(m: dict) -> int:
    """Parameters this chip holds (for the configuration's arithmetic)."""
    d = m["hidden_size"]
    return (layers(m) * (attention_weight_params(m) + phi_params(m))
            + (layers(m) - moe_layers(m)) * 3 * d * m["intermediate_size"]
            + moe_layers(m) * (expert_params(m) * (m["num_shared_experts"]
                                                   + m["experts_held"][1])
                               + d * m["num_experts"])
            + 2 * d * m["vocab_size"])
