"""Parameters and bytes of the Falcon-H1 decode step and of its state
kernel, from shapes and from the window's counters.

Only bytes that MUST move are counted, so that no roofline share can pass
100%: the weights once, K/V only for the keys a row attends
(`decode.kv_tokens_attended`), a recurrent state only for the rows that
were live (`decode.state_rows_updated`: live rows x state layers a step;
each is read once and written once), the embedding only for the rows
looked up. `m` is the configuration file's dict.
"""

from __future__ import annotations


def _dtype_bytes(name: str) -> int:
    return {"bfloat16": 2, "float32": 4}[name]


def conv_dim(m: dict) -> int:
    return m["mamba_d_ssm"] + 2 * m["mamba_n_groups"] * m["mamba_d_state"]


def mixer_params(m: dict) -> int:
    """in_proj hidden x (z + xBC + dt), out_proj d_ssm x hidden, the
    depthwise convolution and its bias, dt_bias, A_log, D a head, the gated
    norm's gain."""
    d, ds, h = m["hidden_size"], m["mamba_d_ssm"], m["mamba_n_heads"]
    return d * (ds + conv_dim(m) + h) + ds * d \
        + (m["mamba_d_conv"] + 1) * conv_dim(m) + 3 * h + ds


def attention_params(m: dict) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    return d * hd * 2 * (m["num_attention_heads"]
                         + m["num_key_value_heads"])


def mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def layer_params(m: dict) -> int:
    """One block: mixer, attention, MLP and its two norms."""
    return mixer_params(m) + attention_params(m) + mlp_params(m) \
        + 2 * m["hidden_size"]


def params_held(m: dict) -> int:
    """Parameters this chip holds: its layers, the held rows of embedding
    and head."""
    return m["num_hidden_layers"] * layer_params(m) \
        + 2 * m["hidden_size"] * m["vocab_size"]


def weight_bytes_a_step(m: dict) -> float:
    """What every decode step reads whatever its rows: every layer and the
    head's held slice (the embedding is read by row)."""
    return float(_dtype_bytes(m["dtype"]) * (
        m["num_hidden_layers"] * layer_params(m)
        + m["hidden_size"] * m["vocab_size"]))


def kv_bytes_per_token_layer(m: dict) -> float:
    """K and V of one cached token in one layer."""
    return float(2 * m["num_key_value_heads"] * m["head_dim"]
                 * _dtype_bytes(m["dtype"]))


def ssm_state_bytes(m: dict) -> float:
    """Bytes the state kernel must move for ONE live row of ONE layer: the
    row's recurrent state read once and written once. The row's x, B, C,
    dt and y (a few KB) are left out: counted low, never high."""
    return 2.0 * m["mamba_n_heads"] * m["mamba_d_head"] \
        * m["mamba_d_state"] * _dtype_bytes(m["ssm_state_dtype"])


def state_slot_bytes(m: dict) -> float:
    """What one slot keeps beside its pages, over the held layers: the
    recurrent state and the conv tail."""
    state = m["mamba_n_heads"] * m["mamba_d_head"] * m["mamba_d_state"] \
        * _dtype_bytes(m["ssm_state_dtype"])
    tail = conv_dim(m) * (m["mamba_d_conv"] - 1) * _dtype_bytes(m["dtype"])
    return float(m["num_hidden_layers"] * (state + tail))


def step_bytes(m: dict, kv_tokens: float, state_rows: float,
               rows: float) -> float:
    """Least bytes of one decode step: the weights once, the embedding rows
    of the live slots, the K/V of every key attended (summed over rows and
    layers) and the state of every live row of every layer, read and
    written."""
    return (weight_bytes_a_step(m)
            + rows * m["hidden_size"] * _dtype_bytes(m["dtype"])
            + kv_tokens * kv_bytes_per_token_layer(m)
            + state_rows * ssm_state_bytes(m))
