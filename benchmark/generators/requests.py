"""Generation requests for a decode server: lengths, token ids, arrivals.

Parameters (the traffic file):
  arrival        {"kind": "closed", "clients": n}          n callers, each
                   sending its next request when the last one returned
                 {"kind": "poisson", "rate": r}            independent users,
                   r requests per second
  ramp_s         seconds of the same traffic before the window opens
  prompt_tokens, new_tokens
                 {"median", "sigma", "min", "max"}: lognormal, clipped
  max_context    prompt + new tokens never exceed it (new tokens give way)
  lengths_seed   fixes the SET of lengths and gaps; `distinct_lengths` of
                 them for a closed loop, one per scheduled request otherwise
  temperature    0 = greedy; above, every request carries its own seed

Every seed gets the same multiset of lengths and of inter-arrival gaps in
another order, and its own token ids: a seed changes what is asked, not how
much work it is.
"""

from __future__ import annotations

import numpy as np

FIRST_TOKEN_ID = 3        # ids below are pad / bos / eos in decoder_lm


def _clipped_lognormal(rng, spec, n):
    raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def lengths(traffic: dict, n: int):
    """The fixed set of (prompt, new) pairs: a function of the file alone."""
    rng = np.random.RandomState(traffic["lengths_seed"])
    prompt = _clipped_lognormal(rng, traffic["prompt_tokens"], n)
    new = _clipped_lognormal(rng, traffic["new_tokens"], n)
    new = np.minimum(new, traffic["max_context"] - prompt)
    if (new < 1).any():
        raise ValueError("a prompt leaves no room for a new token")
    return prompt, new


def _arrivals(n: int, span_s: float, fixed_rng, order_rng):
    """n arrival times in [0, span_s): a fixed multiset of exponential
    gaps, scaled to fill the span and permuted by the seed."""
    if n == 0:
        return np.zeros(0)
    gaps = fixed_rng.exponential(1.0, n + 1)
    gaps = order_rng.permutation(gaps * (span_s / gaps.sum()))
    return np.cumsum(gaps)[:n]


def make(traffic: dict, seed: int, seconds: float, vocab: int) -> dict:
    """-> {"requests": [...], "clients": n or None, "ramp_s": s}

    A request is {"prompt_ids", "max_new_tokens", "temperature", "seed",
    "due_s"}; due_s is relative to the window's opening (negative inside
    the ramp) and None for a closed loop, whose callers take the requests in
    order."""
    arrival = traffic["arrival"]
    ramp_s = float(traffic.get("ramp_s", 0.0))
    order = np.random.RandomState(seed % (2 ** 32))
    fixed = np.random.RandomState(traffic["lengths_seed"] + 1)
    if arrival["kind"] == "closed":
        n = int(traffic["distinct_lengths"])
        due = [None] * n
        clients = int(arrival["clients"])
    elif arrival["kind"] == "poisson":
        n_window = int(round(arrival["rate"] * seconds))
        n_ramp = int(round(arrival["rate"] * ramp_s))
        in_window = _arrivals(n_window, seconds, fixed, order)
        in_ramp = _arrivals(n_ramp, ramp_s, fixed, order) - ramp_s
        due = [float(t) for t in np.concatenate([in_ramp, in_window])]
        n, clients = len(due), None
    else:
        raise ValueError(f"no arrival kind {arrival['kind']!r}")
    prompt_len, new_len = lengths(traffic, n)
    perm = order.permutation(n)
    prompt_len, new_len = prompt_len[perm], new_len[perm]
    temperature = float(traffic.get("temperature", 0.0))
    requests = []
    for i in range(n):
        ids = order.randint(FIRST_TOKEN_ID, vocab, int(prompt_len[i]))
        requests.append({
            "prompt_ids": ids.tolist(),
            "max_new_tokens": int(new_len[i]),
            "temperature": temperature,
            "seed": int((seed * 1000003 + i) % (2 ** 31)),
            "due_s": due[i]})
    return {"requests": requests, "clients": clients, "ramp_s": ramp_s}
