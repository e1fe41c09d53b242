"""The training data as the reference sees it: a copy of the program's
deterministic synthetic token stream (``repro.data.pipeline``), so the
reference reads the same rows without importing the program.

``batch_at(step)`` is a pure function of (seed, step).  Each next token
follows the current one through a fixed random permutation, replaced by
a uniform draw with probability ``noise``.
"""
from __future__ import annotations

import numpy as np


def synthetic_batch(vocab: int, batch: int, seq: int, step: int, *,
                    seed: int, noise: float = 0.1) -> dict[str, np.ndarray]:
    perm = np.random.default_rng(seed).permutation(vocab)
    rng = np.random.default_rng((seed << 20) ^ step)
    toks = np.empty((batch, seq + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=batch)
    noise_mask = rng.random((batch, seq)) < noise
    noise_tok = rng.integers(0, vocab, size=(batch, seq))
    for t in range(seq):
        nxt = perm[toks[:, t]]
        toks[:, t + 1] = np.where(noise_mask[:, t], noise_tok[:, t], nxt)
    return {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
