"""Operations and bytes the models need, computed from shapes alone.

These are the algorithm's counts (no recomputation, no padding), the
numerators of every roofline share the benchmark reports. The peaks they
are divided by come from peaks.json, keyed by `device_kind`; a device that
is not in the table is an error, never a default.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peak figures for device kind {device_kind!r}; "
                       f"peaks.json knows {sorted(table)}")
    return table[device_kind]


# -- BERT / ERNIE encoder, MLM + NSP pretraining -----------------------------

def bert_forward_flops_per_token(hidden: int, layers: int, ffn: int,
                                 vocab: int, seq: int, max_preds: int) -> float:
    """Multiply-adds x 2 of one forward pass, per input token.

    Per layer: Q, K, V and output projections (4 x 2 h^2), the two FFN
    matmuls (2 x 2 h ffn), and full bidirectional attention (QK^T and PV,
    2 x 2 seq h). The MLM head (h x h transform, h x vocab decoder) runs on
    the `max_preds` gathered positions of each sequence only; the pooler
    and NSP head (2 h^2 + 4 h per sequence) are counted too."""
    per_layer = 8 * hidden * hidden + 4 * hidden * ffn + 4 * seq * hidden
    mlm = (2 * hidden * hidden + 2 * hidden * vocab) * max_preds / seq
    nsp = (2 * hidden * hidden + 4 * hidden) / seq
    return float(layers * per_layer + mlm + nsp)


def bert_train_flops_per_token(**sizes) -> float:
    """Forward plus backward (2 x forward: a gradient for the activation
    and one for the weight of every matmul). Optimizer arithmetic is
    elementwise and left out, as in every MFU definition."""
    return 3.0 * bert_forward_flops_per_token(**sizes)


# -- decoder LM (models/decoder_lm.py geometry) ------------------------------

def decoder_weight_bytes(d_model: int, layers: int, ffn: int, vocab: int,
                         dtype_bytes: int = 4) -> float:
    """Bytes of every tensor a decode step must read once: per layer the
    four attention projections and two FFN matrices with biases and two
    layer norms, plus the token embedding, which is also the output head
    (tied) and is read whole for the logits."""
    per_layer = (4 * d_model * d_model + 2 * d_model * ffn     # matrices
                 + 4 * d_model + ffn + d_model                  # biases
                 + 4 * d_model)                                 # 2 norms
    return float(dtype_bytes * (layers * per_layer + vocab * d_model))


def decoder_kv_bytes_per_token(d_model: int, layers: int,
                               dtype_bytes: int = 4) -> float:
    """K and V of one cached token over all layers."""
    return float(2 * d_model * layers * dtype_bytes)


def decoder_step_bytes(d_model: int, layers: int, ffn: int, vocab: int,
                       live_context_tokens: float,
                       dtype_bytes: int = 4) -> float:
    """Least bytes one decode step reads: the weights once plus the cached
    K/V of every live context (`live_context_tokens` summed over slots)."""
    return (decoder_weight_bytes(d_model, layers, ffn, vocab, dtype_bytes)
            + live_context_tokens
            * decoder_kv_bytes_per_token(d_model, layers, dtype_bytes))


def decoder_forward_flops_per_token(d_model: int, layers: int, ffn: int,
                                    vocab: int, context: float) -> float:
    """One token through the stack against `context` cached tokens."""
    per_layer = 8 * d_model * d_model + 4 * d_model * ffn + 4 * context * d_model
    return float(layers * per_layer + 2 * d_model * vocab)
