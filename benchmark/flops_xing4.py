"""Operations and bytes of the Xing4.0 drafting decode step, from shapes and
from the window's counters.

Only bytes that MUST be read are counted, so that no roofline share can
pass 100%: a routed expert's weights count only if a token of the step was
routed to it (`decode.moe_experts_hit` of the held layers,
`decode.draft_moe_experts_hit` of the module's layer), a latent row once a
ROW a step (both positions of a pair read the same rows:
`decode.kv_tokens_attended` counts a row's context once a step, over the
held layers and the module's) and at the 576 values it means, not the 640
lanes a page carries them in; the head ONCE (the held layers' two positions
and the module's one read the same matrix, and a step that read it three
times would not be at its roofline); the embedding only for the rows looked
up. The streams' traffic and the sampler's passes over the logits are left
out: counted low, never high. `m` is the configuration file's dict; every
expert, head and vocabulary row is held.
"""

from __future__ import annotations

from benchmark.flops_kimi_k2 import (_dtype_bytes, attention_weight_params,
                                     expert_bytes, expert_params,
                                     latent_bytes_per_token_layer,
                                     moe_layers)

MODULE_LAYERS = 1       # the draft module's one MoE decoder layer


def n_maps(m: dict) -> int:
    n = m["hc_mult"]
    return 2 * n + n * n


def phi_params(m: dict) -> int:
    """The two sublayers' map projections of one layer."""
    return 2 * m["hc_mult"] * m["hidden_size"] * n_maps(m)


def layer_gains(m: dict) -> int:
    """float32 values a layer reads beside its matrices: two sublayer norms,
    the two streams' norms, the latent attention's two norms."""
    d = m["hidden_size"]
    return 2 * d + 2 * m["hc_mult"] * d + m["q_lora_rank"] \
        + m["kv_lora_rank"]


def moe_layer_fixed_params(m: dict) -> int:
    """What an MoE layer reads whatever it routes: attention, the maps'
    projections, the shared expert, the router."""
    return attention_weight_params(m) + phi_params(m) \
        + expert_params(m) * m["n_shared_experts"] \
        + m["hidden_size"] * m["n_routed_experts"]


def non_expert_weight_bytes(m: dict) -> float:
    """What every step reads whatever it routes: the held layers' and the
    module's attention, maps, dense MLP, shared experts and routers,
    `eh_proj`, the head once; the float32 gains counted too."""
    d, b = m["hidden_size"], _dtype_bytes(m)
    held = len(m["layers_held"])
    dense = held - moe_layers(m)
    matrices = dense * (attention_weight_params(m) + phi_params(m)
                        + 3 * d * m["intermediate_size"]) \
        + (moe_layers(m) + MODULE_LAYERS) * moe_layer_fixed_params(m) \
        + 2 * d * d + d * m["vocab_size"]
    gains = (held + MODULE_LAYERS) * layer_gains(m) + 4 * d \
        + (moe_layers(m) + MODULE_LAYERS) * m["n_routed_experts"]
    return float(matrices * b + gains * 4)


def step_bytes(m: dict, experts_hit: float, latent_rows: float,
               rows: float) -> float:
    """Least bytes of one drafting step: the non-expert weights once, each
    routed expert that was hit (held layers and module), the embedding
    rows looked up (two a row for the held layers, two for the module),
    and the latent row of every position a ROW attends, once for its two
    positions, over the held layers and the module's."""
    return (non_expert_weight_bytes(m) + experts_hit * expert_bytes(m)
            + 4 * rows * m["hidden_size"] * _dtype_bytes(m)
            + latent_rows * latent_bytes_per_token_layer(m))


def params_held(m: dict) -> int:
    """Parameters this chip holds (for the configuration's arithmetic)."""
    d = m["hidden_size"]
    held = len(m["layers_held"])
    dense = held - moe_layers(m)
    return (dense * (attention_weight_params(m) + phi_params(m)
                     + 3 * d * m["intermediate_size"])
            + (moe_layers(m) + MODULE_LAYERS) * (
                moe_layer_fixed_params(m)
                + expert_params(m) * m["n_routed_experts"])
            + 2 * d * d + 2 * d * m["vocab_size"])
