"""Synthetic token sequences for language-model confs (``iter =
synth_tokens``): a seeded first-order chain in which every token has a few
likely successors, so that a model can learn and the loss can fall from
``ln vocab``.  No file is read: the machine a conf trains on may be sealed.

A batch is ``data`` = ``(batch, 1, 1, seq_len + 1)`` int32 ids and ``label``
= ``(batch, 2 * seq_len)`` float32: the next token of every position, then
the one after (a multi-token-prediction head's target; the last of them has
no token to show and repeats the one before).  The conf names both halves
as one field, ``label_vec[0,2*seq_len) = label``, of which ``lm_head_loss``
gives each head its ``seq_len`` columns (doc/sequence.md).
"""

from __future__ import annotations

import numpy as np

from .data import DataBatch, IIterator


def markov_tokens(rng: np.random.RandomState, succ: np.ndarray, rows: int,
                  length: int, p_likely: float) -> np.ndarray:
    """``rows`` chains of ``length`` ids over ``succ.shape[0]`` tokens: the
    first uniform, each next one of the previous token's ``succ.shape[1]``
    likely successors with probability ``p_likely``, else uniform."""
    vocab, fan = succ.shape
    out = np.empty((rows, length), np.int32)
    out[:, 0] = rng.randint(0, vocab, rows)
    pick = rng.randint(0, fan, (rows, length))
    stray = rng.random_sample((rows, length)) >= p_likely
    anywhere = rng.randint(0, vocab, (rows, length))
    for t in range(1, length):
        nxt = succ[out[:, t - 1], pick[:, t]]
        out[:, t] = np.where(stray[:, t], anywhere[:, t], nxt)
    return out


def token_batch(ids: np.ndarray) -> DataBatch:
    """``ids``: (batch, seq_len + 2) -> the batch described above."""
    seq = ids.shape[1] - 2
    label = np.concatenate([ids[:, 1:seq + 1], ids[:, 2:seq + 2]], axis=1)
    return DataBatch(np.ascontiguousarray(ids[:, None, None, :seq + 1]),
                     label.astype(np.float32))


class SynthTokenIterator(IIterator):
    def __init__(self):
        self.batch_size = 0
        self.seq_len = 0
        self.vocab = 0
        self.successors = 4
        self.p_likely = 0.9
        self.num_batches = 8
        self.seed_data = 0
        self.silent = 0
        self._batches = None

    def set_param(self, name, val):
        if name == 'batch_size':
            self.batch_size = int(val)
        if name == 'seq_len':
            self.seq_len = int(val)
        if name == 'vocab':
            self.vocab = int(val)
        if name == 'successors':
            self.successors = int(val)
        if name == 'p_likely':
            self.p_likely = float(val)
        if name == 'num_batches':
            self.num_batches = int(val)
        if name == 'seed_data':
            self.seed_data = int(val)
        if name == 'silent':
            self.silent = int(val)

    def init(self):
        if self._batches is not None:
            return
        assert min(self.batch_size, self.seq_len, self.vocab) > 0, \
            'synth_tokens: set batch_size, seq_len and vocab'
        rng = np.random.RandomState(self.seed_data % (2 ** 32))
        succ = rng.randint(0, self.vocab, (self.vocab, self.successors))
        ids = markov_tokens(rng, succ, self.num_batches * self.batch_size,
                            self.seq_len + 2, self.p_likely)
        self._batches = [
            token_batch(ids[i * self.batch_size:(i + 1) * self.batch_size])
            for i in range(self.num_batches)]
        if self.silent == 0:
            print(f'SynthTokenIterator: {self.num_batches} batches of '
                  f'{self.batch_size} x {self.seq_len} tokens over '
                  f'{self.vocab} ids')

    def __iter__(self):
        return iter(self._batches)
