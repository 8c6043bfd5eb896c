"""Operations of one chip's share of a Mellum decoder's training step, from
shapes (and the window's routing counter). `m` is the configuration
file's dict; heads, experts and vocabulary rows are the held ones.

Model FLOPs: what the forward and the backward REQUIRE, a multiply-add as
two. A matmul's backward is two products of its size (one for the
activation's gradient, one for the weight's); attention's forward is two
score-sized products (q k^T, p v) and its backward four (dV, dP, dQ, dK)
over the (query, key) pairs INSIDE the causal window only. What an
implementation computes again (a flash backward's scores, the routed
layer's gate and up products) is not counted, so a share of the peak reads
low for it and none can pass 100%.
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def layer_types_held(m: dict) -> list:
    return [m["layer_types"][i] for i in m["layers_held"]]


def projection_flops_per_token(m: dict) -> float:
    """Wq, Wo [hidden x q heads x hd], Wk, Wv [hidden x kv heads x hd] and
    the router [hidden x experts], one layer, forward."""
    d, hd = m["hidden_size"], m["head_dim"]
    return 2.0 * d * (hd * (2 * m["q_heads_held"] + 2 * m["kv_heads_held"])
                      + m["num_experts"])


def expert_pair_flops(m: dict) -> float:
    """One (token, expert) pair through a SwiGLU expert, forward: three
    hidden x width products."""
    return 2.0 * 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expected_held_pairs_per_token(m: dict) -> float:
    """Pairs a token sends to the held experts of one layer when routing
    is even."""
    return m["num_experts_per_tok"] * m["experts_held"][1] / m["num_experts"]


def window_pairs(seq: int, window: int) -> int:
    """(query, key) pairs of one head over one sequence with
    t - window < s <= t (all s <= t with window 0)."""
    w = min(window or seq, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def attention_flops_per_sequence(m: dict, seq: int, sliding: bool,
                                 products: int) -> float:
    """`products` score-sized products of one layer over one sequence."""
    pairs = window_pairs(seq, m["sliding_window"] if sliding else 0)
    return 2.0 * products * m["q_heads_held"] * m["head_dim"] * pairs


def head_flops_per_token(m: dict) -> float:
    return 2.0 * m["hidden_size"] * m["vocab_size"]


def forward_flops_per_token(m: dict, seq: int,
                            held_pairs_per_token_layer=None) -> float:
    """`held_pairs_per_token_layer`: the pairs on held experts a token and
    layer, as the window's counter read them; the even expectation where
    None."""
    pairs = expected_held_pairs_per_token(m) \
        if held_pairs_per_token_layer is None else held_pairs_per_token_layer
    kinds = layer_types_held(m)
    attn = sum(attention_flops_per_sequence(m, seq, k == SLIDING, 2)
               for k in kinds) / seq
    return (len(kinds) * (projection_flops_per_token(m)
                          + pairs * expert_pair_flops(m))
            + attn + head_flops_per_token(m))


def train_flops_per_token(m: dict, seq: int,
                          held_pairs_per_token_layer=None) -> float:
    """Forward plus backward (2 x forward: attention's four products
    against its two, a matmul's two against its one). Optimizer
    arithmetic is elementwise and left out, as in every MFU definition."""
    return 3.0 * forward_flops_per_token(m, seq, held_pairs_per_token_layer)


def window_attention_step_flops(m: dict, batch: int, seq: int,
                                products: int) -> float:
    """`products` score-sized products of every held layer over a step's
    sequences: 2 for the forward kernel, 4 for the backward's two."""
    return batch * sum(attention_flops_per_sequence(m, seq, k == SLIDING,
                                                    products)
                       for k in layer_types_held(m))


def routed_train_step_flops(m: dict, held_pairs_per_step: float) -> float:
    """The nine products of a step's held pairs (summed over the layers):
    three forward, six backward."""
    return 3.0 * held_pairs_per_step * expert_pair_flops(m)


def parameters(m: dict) -> int:
    d, hd, f = m["hidden_size"], m["head_dim"], m["moe_intermediate_size"]
    layer = d * hd * (2 * m["q_heads_held"] + 2 * m["kv_heads_held"]) \
        + d * m["num_experts"] + m["experts_held"][1] * 3 * d * f \
        + 2 * d + 2 * hd
    return len(m["layers_held"]) * layer + 2 * m["vocab_size"] * d + d
