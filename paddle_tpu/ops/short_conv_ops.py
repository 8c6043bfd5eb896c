"""The gated short convolution of models/lfm2.py: a layer whose whole mixer
is a causal depthwise convolution of a few taps between two gates, and whose
only state is the convolution's tail.

From the layer's input projection ``[B | C | X] = u W_in`` (three equal
parts, in that order), a channel at a time, in float32:

    z_t = B_t * X_t
    c_t = sum_{k=0..K-1} w[k] * z_{t-(K-1)+k}      (no bias, no activation)
    y_t = C_t * c_t

What a request carries from token to token is ``z_{t-1} .. z_{t-(K-1)}``:
``conv_tail_<l>`` [slots + 1, K - 1, channels] in the pages' dtype, the tail
of serving/kv_cache.py's TAIL-ONLY state layer (no recurrent state, no
pages). The tail itself (the gather at a row's slot, the shift, the inputs
of a prompt's last REAL tokens, the write-back) is ops/ssm_ops.py's, shared
with the `ssm_conv_*` pair; the gates, and that nothing follows the sum, are
this file's.

* decode step: `gated_short_conv_update`, one token a row against the tail
  at the row's slot (``Slots``; a padding row names the scratch slot), the
  tail shifted by one in place.
* whole prompt: `gated_short_conv_prefill`, a padded prompt from a zero
  tail, the slot's tail WRITTEN with z of the last ``K - 1`` real tokens
  (zeros before a prompt shorter than that).

Both are XLA element-wise work: a step's is 3 x [B, channels] multiplies a
layer beside the layer's two projections, nothing a kernel would win.
"""

from __future__ import annotations

from ..core.registry import register_op
from .ssm_ops import (conv_prompt, conv_prompt_tail, conv_tail_write,
                      conv_window, row_slots)


def _gates(bcx):
    """[..., 3 C] float32 -> (z = B * X, C)."""
    import jax.numpy as jnp

    b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    return b * x, c


@register_op("gated_short_conv_update")
def gated_short_conv_update_op(ins, attrs):
    """BCX [B, 3 C] float32, ConvTail [slots + 1, K - 1, C], Slots [B]
    int32, W [K, C] -> Y [B, C] float32 = C * sum_k W[k] window[k] over the
    row's tail and this token's z; ConvTailOut, the tail shifted by one."""
    import jax.numpy as jnp

    z, gate = _gates(ins["BCX"][0])
    pool, slots = ins["ConvTail"][0], row_slots(ins)
    win = conv_window(pool, slots, z)                         # [B, K, C]
    conv = jnp.sum(win * ins["W"][0].astype(jnp.float32)[None], axis=1)
    return {"Y": gate * conv,
            "ConvTailOut": conv_tail_write(pool, slots, win[:, 1:])}


@register_op("gated_short_conv_prefill")
def gated_short_conv_prefill_op(ins, attrs):
    """BCX [B, S, 3 C], Lengths [B], Slots [B], ConvTail, W [K, C] -> Y
    [B, S, C] float32; ConvTailOut with the slot's tail written: z of the
    last ``K - 1`` REAL tokens, not of the padded bucket's end."""
    import jax.numpy as jnp

    z, gate = _gates(ins["BCX"][0])
    pool, slots = ins["ConvTail"][0], row_slots(ins)
    lengths = ins["Lengths"][0].reshape(-1).astype(jnp.int32)
    w = ins["W"][0].astype(jnp.float32)
    zp, conv = conv_prompt(z, w)
    tail = conv_prompt_tail(zp, lengths, w.shape[0])
    return {"Y": gate * conv,
            "ConvTailOut": conv_tail_write(pool, slots, tail)}
