"""Training token batches: the seeded Zipf-Markov stream of the
program's ``train/data.py``, copied so that the yardstick stays put.

A fixed per-seed table maps each of ``n_states`` Markov states to
``fanout`` candidate tokens drawn uniformly from the vocabulary; each row
walks the chain, so the loss can fall below ln(V).  Batch ``i`` of a seed
is its own random stream: every row of every step differs.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def make(mix: dict, seed: int, batch: int, vocab: int):
    """``batch_at(i) -> {"inputs", "targets"}``, int32 ``[batch, T]``."""
    T, S, F = mix["seq_len"], mix["n_states"], mix["fanout"]
    table = np.random.RandomState(seed).randint(0, vocab, size=(S, F))

    def batch_at(i: int) -> Dict[str, np.ndarray]:
        rng = np.random.RandomState((seed * 1_000_003 + i) % (2**31 - 1))
        state = rng.randint(0, S, size=(batch,))
        toks = np.empty((batch, T + 1), np.int32)
        for t in range(T + 1):
            toks[:, t] = table[state, rng.randint(0, F, size=(batch,))]
            state = (state * 31 + toks[:, t]) % S
        return {"inputs": toks[:, :-1], "targets": toks[:, 1:]}

    return batch_at
