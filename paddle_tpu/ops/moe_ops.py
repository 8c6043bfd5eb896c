"""MoE op — Switch FFN with expert parallelism (parallel/moe.py).

Greenfield vs the reference (SURVEY.md §2.7: EP absent). The op flattens
[B,S,H] to tokens, routes top-1 with capacity, and runs the expert shard
held by this rank ('ep' mesh axis); outputs the combined tokens plus the
load-balancing aux loss (add it to the training loss scaled by
aux_weight, Switch Transformer recipe).
"""

from __future__ import annotations

from ..core.registry import register_op


@register_op("switch_moe", is_collective=True, skip_infer_shape=True)
def switch_moe_op(ins, attrs):
    from ..parallel.moe import switch_moe

    x = ins["X"][0]
    gate_w = ins["GateW"][0]
    w1, b1 = ins["W1"][0], ins["B1"][0]
    w2, b2 = ins["W2"][0], ins["B2"][0]
    h = x.shape[-1]
    flat = x.reshape(-1, h)
    out, aux = switch_moe(
        flat, gate_w, w1, b1, w2, b2,
        capacity_factor=float(attrs.get("capacity_factor", 1.25)),
        axis_name=attrs.get("axis_name", "ep"),
        activation=attrs.get("activation", "gelu"),
        tokens_sharded=bool(attrs.get("tokens_sharded", False)))
    return {"Out": out.reshape(x.shape), "AuxLoss": aux}


def _poly_experts(ins, attrs):
    """`routed_experts_share`'s `poly` from the op's attr `activation` and
    its PolyNorm input and attrs; None for SwiGLU experts."""
    activation = attrs.get("activation", "silu")
    if activation == "silu":
        return None
    if activation != "poly_norm":
        raise ValueError(f"routed_experts: activation {activation!r}")
    return (ins["PN"][0], float(attrs["pn_eps"]),
            float(attrs["pn_out_scale"]), float(attrs["pn_bias_clamp"]))


@register_op("routed_experts", non_diff_inputs=("SelectBias", "Live", "PN"),
             required_attrs=("top_k", "held_lo"))
def routed_experts_op(ins, attrs):
    """One chip's share of a dropless top-k routed expert layer
    (parallel/moe.py routed_experts_share; attr `score_func` "sigmoid" or
    "softmax" over all experts; attr `norm_eps`, 1e-20 by default, is what
    the kept scores' sum is added to before it divides them; attr
    `trainable` gives the held experts'
    part its backward and Counts a fourth entry, the largest group's
    rows): X [..., H] float32, RouterW
    [H, E] over ALL experts, SelectBias [E] (optional: a router without
    one), W1/W3 [E_held, H, F] and W2 [E_held, F, H] of the experts `held_lo` .. held here, optional Live
    bool, one a row of X (the rows that carry a token; the rest join no
    group). The held experts' SwiGLUs run as one grouped kernel over the
    pairs sorted by expert (ops/pallas/grouped_swiglu.py; three
    ragged_dots where kernel_mode() is off). Out like X, float32; Counts
    int32 [3] (live pairs, those on held experts, held experts hit);
    Chosen int32 [..., top_k], the experts each row chose.

    Attr `activation` "poly_norm" (default "silu") makes the held experts
    PolyNorm ones: input PN [E_held, 4] float32 (three weights and a bias
    an expert) and attrs `pn_eps`, `pn_out_scale`, `pn_bias_clamp`; the
    grouped kernel is then `grouped_polyglu` of the same module."""
    import jax.numpy as jnp

    from ..parallel.moe import routed_experts_share

    live = ins["Live"][0] if ins.get("Live") else None
    x = ins["X"][0]
    router_w = ins["RouterW"][0]
    # a router without a selection bias selects by its scores alone
    bias = ins["SelectBias"][0] if ins.get("SelectBias") \
        else jnp.zeros((router_w.shape[1],), jnp.float32)
    out, counts, chosen = routed_experts_share(
        x.reshape(-1, x.shape[-1]), router_w, bias,
        ins["W1"][0], ins["W3"][0], ins["W2"][0],
        top_k=int(attrs["top_k"]), held_lo=int(attrs["held_lo"]),
        route_scale=float(attrs.get("route_scale", 1.0)),
        route_norm=bool(attrs.get("route_norm", True)), live=live,
        score_func=attrs.get("score_func", "sigmoid"),
        trainable=bool(attrs.get("trainable", False)), with_chosen=True,
        poly=_poly_experts(ins, attrs),
        norm_eps=float(attrs.get("norm_eps", 1e-20)))
    return {"Out": out.reshape(x.shape), "Counts": counts,
            "Chosen": chosen.reshape(x.shape[:-1] + (-1,))}
