"""Batches of the expert trainer's cells: rows of token ids with the
look-ahead columns a multi-token-prediction loss needs. `traffic.py`'s
`train_batch` draws ids and labels apart; here one row gives the inputs and
every head's labels, as a document does.
"""
from __future__ import annotations

import numpy as np


def train_rows(vocab: int, batch: int, seq: int, ahead: int, seed: int,
               step: int):
    """[batch, seq + 1 + ahead] fresh uniform ids below `vocab` of training
    step `step` (0, 1, ...), int32 on the host: the inputs are
    rows[:, :seq], the main head's labels rows[:, 1:seq+1] (module k embeds
    rows[:, 1+k:seq+1+k]), module k's labels rows[:, 2+k:seq+2+k]."""
    rng = np.random.default_rng([int(seed), 7, int(step)])
    return rng.integers(0, vocab, (batch, seq + 1 + ahead), dtype=np.int32)


def split(rows, seq: int, ahead: int):
    """(what the model takes, the main head's labels, each module's labels)"""
    return (rows[:, :seq + ahead], rows[:, 1:seq + 1]) + tuple(
        rows[:, 2 + k:seq + 2 + k] for k in range(ahead))
