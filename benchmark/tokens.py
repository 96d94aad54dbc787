"""Seeded token sequences for the language-model cells: a first-order chain
in which every token has a few likely successors, so that a model can learn
(the loss can fall from ``ln vocab``) and no two sequences are alike.  One
general generator; a traffic file only sets its parameters (``data``:
``successors``, ``p_likely``)."""

from __future__ import annotations

import numpy as np


def token_rows(seed: int, rows: int, length: int, vocab: int,
               p: dict) -> np.ndarray:
    """``rows`` chains of ``length`` ids over ``vocab`` tokens, int32: the
    first of each uniform over the vocabulary, each next one of the previous
    token's ``p['successors']`` likely successors with probability
    ``p['p_likely']``, else uniform.  The table of successors hangs on the
    seed, like everything else."""
    rng = np.random.RandomState(seed % (2 ** 32))
    fan, likely = int(p['successors']), float(p['p_likely'])
    succ = rng.randint(0, vocab, (vocab, fan))
    out = np.empty((rows, length), np.int32)
    out[:, 0] = rng.randint(0, vocab, rows)
    pick = rng.randint(0, fan, (rows, length))
    stray = rng.random_sample((rows, length)) >= likely
    anywhere = rng.randint(0, vocab, (rows, length))
    for t in range(1, length):
        out[:, t] = np.where(stray[:, t], anywhere[:, t],
                             succ[out[:, t - 1], pick[:, t]])
    return out
