"""The decode step's token choice, on the device.

``sample_tokens`` is the last stage of the jitted decode-step program
(``DecodeEngine._entry("step", b)``): the program returns ``[slots]`` int32
token ids, and the ``[slots, vocab]`` logits never leave the device. Row by
row it is the mathematics of ``GenerationRequest.sample`` (the host function
that still chooses a request's FIRST token from its prefill logits): argmax
where ``temperature <= 0``, else softmax at the row's temperature and the
inverse CDF at the row's uniform. The uniform is drawn on the host from the
request's own pinned ``np.random.RandomState``, one per token in token
order, so the random stream (and with it the session journal's
``rng_state``) stays where it was; only the arithmetic over the vocabulary
moved.

Nothing is truncated (no top-k, no top-p) and a row reads its own logits,
temperature and uniform only. Sums accumulate in float32 where the host
used float64 scratch: the chosen token can differ from the float64 choice
only where the uniform lies within float32 summation error of a CDF
boundary.
"""

from __future__ import annotations

import jax.numpy as jnp

# the vocabulary axis is summed in two levels, blocks of this many entries:
# a running sum over the ~V/BLOCK block totals picks the block, a running sum
# inside that one block picks the token. Both running sums stay short (their
# rounding error grows with their length) and the inner comparison is made at
# one block's magnitude; a flat cumsum over 256k entries was never tried on
# the chip.
BLOCK = 1024


def sample_tokens(logits, temperature, uniform):
    """``(logits [B, V] f32, temperature [B] f32, uniform [B] f32) ->
    int32 [B]``.

    Greedy rows (``temperature <= 0``): the lowest index of the maximum.
    Sampled rows: the count of CDF entries below ``uniform``, clamped to
    ``V - 1``; the unnormalised running sum is compared against
    ``uniform x total`` (no division), so the tail cannot fall short of the
    uniform."""
    b, v = logits.shape
    greedy = jnp.argmax(logits, axis=-1)
    nb = -(-v // BLOCK)
    t = jnp.where(temperature > 0, temperature, 1.0)[:, None]
    z = (logits - jnp.max(logits, axis=-1, keepdims=True)) / t
    # -inf padding: exp gives the pad entries no mass
    z = jnp.pad(z, ((0, 0), (0, nb * BLOCK - v)),
                constant_values=-jnp.inf)
    e = jnp.exp(z).reshape(b, nb, BLOCK)
    block_cdf = jnp.cumsum(jnp.sum(e, axis=-1), axis=-1)       # [B, nb]
    target = uniform * block_cdf[:, -1]
    k = jnp.minimum(jnp.sum(block_cdf < target[:, None], axis=-1), nb - 1)
    # mass before block k (0 before the first); the residual keeps the inner
    # comparison at the block's own magnitude, not the whole row's
    before = jnp.take_along_axis(jnp.pad(block_cdf, ((0, 0), (1, 0))),
                                 k[:, None], axis=-1)[:, 0]
    inner = jnp.cumsum(jnp.take_along_axis(
        e, k[:, None, None], axis=1)[:, 0, :], axis=-1)        # [B, BLOCK]
    j = jnp.sum(inner < (target - before)[:, None], axis=-1)
    sampled = jnp.minimum(k * BLOCK + jnp.minimum(j, BLOCK - 1), v - 1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)
