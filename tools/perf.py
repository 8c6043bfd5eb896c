"""Slope timing of a chained step.

Chain calls with data dependence (each dispatch's input is the prior
output), close each window with a sync, and report the SLOPE between a
short and a long window: whatever is fixed per window (dispatch ramp-up,
the closing sync) cancels.

On the TPU runtime ``block_until_ready`` blocks and a scalar host fetch
costs about a millisecond (checked on a v5e chip, PR 21: a 40-matmul
chain dispatched in 0.2 ms, blocked for 28.8 ms, and its fetch then took
1.7 ms). ``sync`` closes a window with a host fetch of a reduction, which
is a valid sync too; the benchmark PR (ROADMAP A1) owns the timing method.
"""

from __future__ import annotations

import time

import numpy as np


def sync(x):
    import jax.numpy as jnp

    return np.asarray(jnp.sum(x.astype(jnp.float32)))


def _window(step, x0, iters):
    x = x0
    t0 = time.perf_counter()
    for _ in range(iters):
        x = step(x)
    sync(x)
    return time.perf_counter() - t0


def time_chain(step, x0, *, n1=10, n2=40, repeats=2):
    """ms per call of step (x -> x), fixed overhead cancelled by slope.

    step must map x to a same-shape/dtype x (chain-able).
    """
    x = step(x0)
    sync(x)  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t1 = _window(step, x0, n1)
        t2 = _window(step, x0, n2)
        slope = (t2 - t1) / (n2 - n1)
        best = min(best, slope)
    return best * 1e3


def time_chain_device(step, x0, *, n1=8, n2=40, repeats=5):
    """ms per step with the iteration loop INSIDE jit (lax.fori_loop):
    per-dispatch host time would swamp a sub-ms kernel in a Python loop.
    Fresh input per window. step must map x -> same-aval x."""
    import functools

    import jax
    from jax import lax
    import jax.numpy as jnp

    @functools.lru_cache(maxsize=None)
    def runner(n):
        @jax.jit
        def run(x):
            return lax.fori_loop(0, n, lambda i, xx: step(xx), x)

        return run

    rng = np.random.RandomState(7)

    def window(n):
        x = jax.tree_util.tree_map(
            lambda a: a * (1.0 + 0.001 * float(rng.rand())), x0)
        sync(jax.tree_util.tree_leaves(x)[0])
        t0 = time.perf_counter()
        y = runner(n)(x)
        sync(jax.tree_util.tree_leaves(y)[0])
        return time.perf_counter() - t0

    window(n1), window(n2)      # compile both
    slopes = []
    for _ in range(repeats):
        t1, t2 = window(n1), window(n2)
        slopes.append((t2 - t1) / (n2 - n1))
    est = float(np.median(slopes))
    if est * (n2 - n1) < 0.02:
        # sub-ms kernel: the window difference is under ~20 ms and host
        # jitter dominates (negative slopes) — rescale the windows so
        # the slope term is >= 20 ms and re-measure
        n2b = int(min(max(0.02 / max(est, 1e-7), 200), 4000))
        n1b = max(n2b // 5, 1)
        window(n1b), window(n2b)
        slopes = []
        for _ in range(repeats):
            t1, t2 = window(n1b), window(n2b)
            slopes.append((t2 - t1) / (n2b - n1b))
        est = float(np.median(slopes))
    return est * 1e3
