"""The synthetic token stream the cells train on, from the seed alone.

A copy of the repository's ``data/pipeline.py::_batch_np`` recipe (a
learnable Markov-like stream, each client's rows drawn from a shifted slice
of the vocabulary), so that the reference rebuilds every batch itself."""
from __future__ import annotations

import numpy as np


def batch(seed: int, step: int, global_batch: int, seq_len: int,
          vocab: int, clients: int, heterogeneity: float):
    """(tokens, labels), each int32 (global_batch, seq_len): the rows of
    client i are rows [i·B/n, (i+1)·B/n)."""
    B, S, V = global_batch, seq_len, vocab
    rng = np.random.RandomState((seed * 1_000_003 + step) % (2 ** 31))
    group = np.arange(B) * clients // B
    width = max(16, int(V * (1.0 - heterogeneity * (1 - 1 / clients))))
    base = (group * (V - width) // max(clients - 1, 1)).astype(np.int64)
    toks = np.empty((B, S + 1), np.int64)
    toks[:, 0] = rng.randint(0, width, size=B)
    noise = rng.randint(0, 3, size=(B, S))
    for t in range(S):
        toks[:, t + 1] = (toks[:, t] * 31 + 17 + noise[:, t]) % width
    toks = toks + base[:, None]
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)
