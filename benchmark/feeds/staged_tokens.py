"""Feed kind ``staged_tokens``: the ``staged`` feed for a conf whose input is
token ids.  A ring of distinct seeded sequences (``tokens.token_rows``), each
put on the device once in set-up through the trainer's own ``stage_batch``
(ids int32, labels the next token of every position and then the one after,
for a multi-token-prediction head), then ``update_staged(ring[i % n])`` a
step.  ``samples_per_step`` counts sequences; a sample is ``graph.seq``
tokens.  (``feeds/staged.py`` makes images from ``confnet.build_graph``'s
input shape, and that graph knows no sequence node.)
"""

from __future__ import annotations

import numpy as np

from .. import cxx, harness, tokens


class Feed:
    def __init__(self, cell, seed: int, spans):
        import jax
        from cxxnet_tpu.io.data import DataBatch
        reference = harness.load_module('references',
                                        cell.config['reference'])
        self.spans = spans
        pairs = cxx.conf_pairs(cell, seed, keep_data=False)
        self.graph = reference.build_graph(pairs)
        self.task = cxx.build_task(pairs)
        self.trainer = self.task.net_trainer
        self.samples_per_step = cell.batch_per_chip * cell.chips
        self.rounds = 0
        self.trainer.start_round(1)
        seq, n = self.graph.seq, int(cell.t('ring_batches'))
        ids = tokens.token_rows(seed, n * self.samples_per_step, seq + 2,
                                self.graph.vocab, cell.t('data'))
        self.ring = []
        for i in range(n):
            rows = ids[i * self.samples_per_step:(i + 1)
                       * self.samples_per_step]
            label = reference.label_matrix(self.graph,
                                           rows).astype(np.float32)
            self.ring.append(jax.block_until_ready(self.trainer.stage_batch(
                DataBatch(np.ascontiguousarray(
                    rows[:, None, None, :seq + 1]), label))))
        self.i = 0

    def advance(self) -> None:
        with self.spans.span('step.dispatch'):
            self.trainer.update_staged(self.ring[self.i % len(self.ring)])
        self.i += 1

    def close(self) -> None:
        pass
