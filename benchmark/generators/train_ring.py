"""A ring of seeded host batches for MLM + NSP pretraining.

Parameters (the traffic file): batch_per_replica, seq_len, ring,
max_predictions_per_seq, warmup_steps, loss_every. Batch i of the ring
comes from `seed + i`; the trainer is fed numpy arrays, as a user's loop
feeds it, so the host-to-device copy is inside the step.

`batch` is a copy of models/bert.py:synthetic_pretraining_batch, kept here
so that the inputs cannot move with the program.
"""

from __future__ import annotations

import numpy as np


def batch(vocab: int, type_vocab: int, batch_size: int, seq_len: int,
          seed: int, max_preds: int) -> dict:
    rng = np.random.RandomState(seed % (2 ** 32))
    shape = (batch_size, seq_len)
    src = rng.randint(0, vocab, shape).astype(np.int64)
    sent = rng.randint(0, type_vocab, shape).astype(np.int64)
    pos = np.tile(np.arange(seq_len, dtype=np.int64), (batch_size, 1))
    mask = np.ones(shape, np.float32)
    labels = rng.randint(0, vocab, shape).astype(np.int64)
    weight = (rng.rand(*shape) < 0.15).astype(np.float32)
    for row in weight:               # keep the first max_preds masked positions
        hits = np.flatnonzero(row)
        row[hits[max_preds:]] = 0.0
    nsp = rng.randint(0, 2, (batch_size, 1)).astype(np.int64)
    return dict(src_ids=src, sent_ids=sent, pos_ids=pos, input_mask=mask,
                mask_labels=labels, mask_weight=weight, nsp_labels=nsp)


def make(traffic: dict, seed: int, vocab: int, type_vocab: int,
         replicas: int = 1) -> list:
    size = traffic["batch_per_replica"] * replicas
    return [batch(vocab, type_vocab, size, traffic["seq_len"], seed + i,
                  traffic["max_predictions_per_seq"])
            for i in range(traffic["ring"])]
