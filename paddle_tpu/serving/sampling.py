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

A model with a draft module is stepped two positions a row
(serving/decode.py): `draft_tokens` draws the module's proposal FROM its
own distribution q, `verify_tokens` holds it against the model's p by the
exact acceptance rule of speculative sampling, so that the delivered tokens
are distributed as `sample_tokens`' and depend on the request's seed and
the logits alone. A row a step takes FOUR uniforms of the request's stream,
in this order: accept, redraw (on rejection, from norm(max(p - q, 0))),
second position (on acceptance, from that position's p), next draft (from
q); all four are drawn whichever are used, so the stream's state after a
step does not depend on its outcome.

Nothing is truncated (no top-k, no top-p) and a row reads its own logits,
temperature and uniforms only. Sums accumulate in float32 where the host
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


def _pick(e, uniform):
    """The inverse CDF of unnormalised masses ``e [B, V]`` (>= 0) at
    ``uniform [B]``: the count of CDF entries below ``uniform x total``,
    clamped to ``V - 1`` (no division, so the tail cannot fall short of
    the uniform)."""
    b, v = e.shape
    nb = -(-v // BLOCK)
    # zero padding: the pad entries have no mass
    e = jnp.pad(e, ((0, 0), (0, nb * BLOCK - v))).reshape(b, nb, BLOCK)
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
    return jnp.minimum(k * BLOCK + jnp.minimum(j, BLOCK - 1), v - 1)


def _masses(logits, temperature):
    """exp((logits - max) / t) [B, V]: the row's softmax at its temperature,
    unnormalised (a greedy row's at 1)."""
    t = jnp.where(temperature > 0, temperature, 1.0)[:, None]
    return jnp.exp((logits - jnp.max(logits, axis=-1, keepdims=True)) / t)


def sample_tokens(logits, temperature, uniform):
    """``(logits [B, V] f32, temperature [B] f32, uniform [B] f32) ->
    int32 [B]``.

    Greedy rows (``temperature <= 0``): the lowest index of the maximum.
    Sampled rows: `_pick` of the row's softmax at its temperature."""
    greedy = jnp.argmax(logits, axis=-1)
    sampled = _pick(_masses(logits, temperature), uniform)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


def probabilities(logits, temperature):
    """The row's softmax at its temperature, float32 [B, V], normalised:
    the p and the q of the acceptance rule."""
    e = _masses(logits, temperature)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def draft_tokens(logits, temperature, uniform):
    """The draft module's proposal a row: ``(draft int32 [B], q [B, V])``
    with q = `probabilities` of its logits and the draft DRAWN FROM q at the
    row's uniform (a greedy row's: q's argmax)."""
    q = probabilities(logits, temperature)
    draft = jnp.where(temperature > 0, _pick(q, uniform),
                      jnp.argmax(logits, axis=-1))
    return draft.astype(jnp.int32), q


def verify_tokens(logits, logits_after, q, draft, has_draft, temperature,
                  uniforms):
    """Speculative sampling of one draft a row (Leviathan et al.
    arXiv:2211.17192; Chen et al. arXiv:2302.01318).

    ``logits [B, V]`` are the model's at the row's position (the
    distribution p of the token the draft stands for), ``logits_after`` at
    the position after it with the draft fed, ``q [B, V]`` the distribution
    the draft was drawn from, ``has_draft [B]`` bool (a row's first step has
    none), ``uniforms [B, 3]``: accept, redraw, second position.
    -> ``(tokens int32 [B, 2], count int32 [B])``: the draft is accepted
    with probability ``min(1, p(d) / q(d))`` (``u q(d) < p(d)``) and
    followed by a draw from the second position's distribution (count 2);
    rejected, the ONE token is drawn from ``norm(max(p - q, 0))``, which
    without a draft (q = 0) is p itself. So a row's tokens are distributed
    exactly as `sample_tokens` would have drawn them one by one. Greedy
    rows: the draft is accepted where it is p's argmax, and the tokens are
    the two argmaxes."""
    p = probabilities(logits, temperature)
    q = jnp.where(has_draft[:, None], q, 0.0)
    at = draft[:, None]
    p_d = jnp.take_along_axis(p, at, axis=-1)[:, 0]
    q_d = jnp.take_along_axis(q, at, axis=-1)[:, 0]
    greedy = jnp.argmax(logits, axis=-1)
    sampled = temperature > 0
    accept = has_draft & jnp.where(sampled, uniforms[:, 0] * q_d < p_d,
                                   draft == greedy)
    redrawn = _pick(jnp.maximum(p - q, 0.0), uniforms[:, 1])
    first = jnp.where(accept, draft, jnp.where(sampled, redrawn, greedy))
    second = sample_tokens(logits_after, temperature, uniforms[:, 2])
    return (jnp.stack([first, second], axis=1).astype(jnp.int32),
            1 + accept.astype(jnp.int32))
