"""A ring of seeded host batches for next-token pretraining.

Parameters (the traffic file): batch_per_replica, seq_len, ring,
warmup_steps, loss_every. Batch i of the ring comes from `seed + i`: ids
uniform over the `vocab` rows that are held, [batch, seq_len + 1] of them,
of which `tokens` is all but the last of a row and `labels` all but the
first (the ids shifted by one): fixed, unpacked rows with a target at
every position. The trainer is fed numpy arrays, as a user's loop feeds
it, so the host-to-device copy is inside the step.
"""

from __future__ import annotations

import numpy as np


def batch(vocab: int, batch_size: int, seq_len: int, seed: int) -> dict:
    ids = np.random.RandomState(seed % (2 ** 32)).randint(
        0, vocab, (batch_size, seq_len + 1)).astype(np.int64)
    return dict(tokens=np.ascontiguousarray(ids[:, :-1]),
                labels=np.ascontiguousarray(ids[:, 1:]))


def make(traffic: dict, seed: int, vocab: int, replicas: int = 1) -> list:
    size = traffic["batch_per_replica"] * replicas
    return [batch(vocab, size, traffic["seq_len"], seed + i)
            for i in range(traffic["ring"])]
