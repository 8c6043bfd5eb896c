"""Operations and bytes of the Kimi-K2 decode step and of its paged latent
attention kernel, from shapes and from the window's counters.

Only bytes that MUST be read are counted, so that no roofline share can
pass 100%: a routed expert's weights count only if a token of the step was
routed to it (`decode.moe_experts_hit`), a latent row only for a position a
row attends (`decode.kv_tokens_attended`) and at the 576 values it means,
not the 640 lanes a page carries them in; the embedding only for the rows
looked up. The kernel's operations are the two products alone (scores over
576, values over 512), no softmax. `m` is the configuration file's dict;
experts and vocabulary rows are the held ones, every head is held.
"""

from __future__ import annotations


def _dtype_bytes(m: dict) -> int:
    return {"bfloat16": 2, "float32": 4}[m["dtype"]]


def moe_layers(m: dict) -> int:
    return sum(1 for i in m["layers_held"] if i >= m["first_k_dense_replace"])


def latent_dim(m: dict) -> int:
    """Values a latent page holds of a token: the latent, the shared key."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def attention_weight_params(m: dict) -> int:
    """W_qa [hidden, q rank], W_qb [q rank, heads x (nope + rope)], W_kva
    [hidden, rank + rope], W_kvb [rank, heads x (nope + v)], W_o
    [heads x v, hidden]."""
    d, n = m["hidden_size"], m["num_attention_heads"]
    return (d * m["q_lora_rank"]
            + m["q_lora_rank"] * n * (m["qk_nope_head_dim"]
                                      + m["qk_rope_head_dim"])
            + d * latent_dim(m)
            + m["kv_lora_rank"] * n * (m["qk_nope_head_dim"]
                                       + m["v_head_dim"])
            + n * m["v_head_dim"] * d)


def expert_params(m: dict) -> int:
    """One SwiGLU expert (routed or shared): three hidden x width matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def non_expert_weight_bytes(m: dict) -> float:
    """What every decode step reads whatever it routes: attention of every
    layer, the dense MLPs, the shared experts and routers, the output
    head's held slice (the norm gains and selection bias are float32 and
    counted too)."""
    d, b = m["hidden_size"], _dtype_bytes(m)
    layers = len(m["layers_held"])
    dense = layers - moe_layers(m)
    matrices = layers * attention_weight_params(m) \
        + dense * 3 * d * m["intermediate_size"] \
        + moe_layers(m) * (expert_params(m) * m["n_shared_experts"]
                           + d * m["n_routed_experts"]) \
        + d * m["vocab_size"]
    gains = layers * (2 * d + m["q_lora_rank"] + m["kv_lora_rank"]) + d \
        + moe_layers(m) * m["n_routed_experts"]
    return float(matrices * b + gains * 4)


def expert_bytes(m: dict) -> float:
    return float(expert_params(m) * _dtype_bytes(m))


def latent_bytes_per_token_layer(m: dict) -> float:
    """The latent row of one cached token in one layer."""
    return float(latent_dim(m) * _dtype_bytes(m))


def step_bytes(m: dict, experts_hit: float, latent_tokens: float,
               rows: float) -> float:
    """Least bytes of one decode step: the non-expert weights once, the
    weights of each routed expert that was hit (summed over MoE layers),
    the embedding rows of the live slots, and the latent row of every
    position attended (summed over rows and layers)."""
    return (non_expert_weight_bytes(m) + experts_hit * expert_bytes(m)
            + rows * m["hidden_size"] * _dtype_bytes(m)
            + latent_tokens * latent_bytes_per_token_layer(m))


def paged_mla_bytes(m: dict, latent_tokens: float) -> float:
    """Least bytes of the paged_mla_attention kernel over one step: the
    latent rows attended; queries, tables and outputs are small beside
    them and left out, so the share is counted low, never high."""
    return latent_tokens * latent_bytes_per_token_layer(m)


def paged_mla_flops(m: dict, latent_tokens: float) -> float:
    """Least operations of the kernel over one step: every head's score
    against each attended row (a product over rank + rope) and its
    weighted sum of the rows' latents (over rank), 2 operations a
    multiply-add; the softmax is left out."""
    return latent_tokens * 2.0 * m["num_attention_heads"] \
        * (latent_dim(m) + m["kv_lora_rank"])


def prefill_pairs(m: dict, tokens: int) -> float:
    """(query, key) pairs of one causal pass over `tokens` positions,
    summed over the layers held: the triangle with its diagonal."""
    return len(m["layers_held"]) * tokens * (tokens + 1) / 2.0


def mla_prefill_flops(m: dict, pairs: float) -> float:
    """Least operations of the mla_prefill_attention kernel for `pairs`
    (query, key) pairs attended (`prefill_pairs`): every head's score (a
    product over nope + rope) and its weighted sum of values (over v), 2
    operations a multiply-add; the softmax and the upper halves of the
    blocks on the diagonal, which the kernel computes and masks, are left
    out."""
    return pairs * 2.0 * m["num_attention_heads"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])


def params_held(m: dict) -> int:
    """Parameters this chip holds (for the configuration's arithmetic)."""
    d = m["hidden_size"]
    layers = len(m["layers_held"])
    return (layers * attention_weight_params(m)
            + (layers - moe_layers(m)) * 3 * d * m["intermediate_size"]
            + moe_layers(m) * (expert_params(m) * (m["n_shared_experts"]
                                                   + m["experts_held"][1])
                               + d * m["n_routed_experts"])
            + 2 * d * m["vocab_size"])
