"""Feed kind ``staged``: a ring of distinct seeded batches, each put on the
device once in set-up through the trainer's own ``stage_batch`` (so type,
sharding and mask are the program's), then ``update_staged(ring[i % n])`` a
step: the step program the CLI dispatches, with no input work in the window
(the step does not donate its inputs).  With ``chips`` > 1 the conf gets
``dev = tpu:0-<chips-1>`` and ``chips`` times the batch, so every chip runs
the shapes of the one-chip cell.
"""

from __future__ import annotations

from .. import confnet, cxx, synth


class Feed:
    def __init__(self, cell, seed: int, spans):
        import jax
        import jax.numpy as jnp
        from cxxnet_tpu.io.data import DataBatch
        self.spans = spans
        pairs = cxx.conf_pairs(cell, seed, keep_data=False)
        self.task = cxx.build_task(pairs)
        self.trainer = self.task.net_trainer
        self.graph = confnet.build_graph(pairs)
        self.samples_per_step = cell.batch_per_chip * cell.chips
        self.rounds = 0
        wire = (jnp.bfloat16 if self.trainer.compute_dtype == jnp.bfloat16
                else jnp.float32)
        self.trainer.start_round(1)
        self.ring = []
        for data, label in synth.learnable_batches(
                seed, int(cell.t('ring_batches')), self.samples_per_step,
                self.graph.input_shape, self.graph.num_classes, wire,
                cell.t('data'), jax.devices()[:cell.chips]):
            # one at a time: staged all at once, the transfers' transient
            # copies pile up on device 0 by the luck of the timing, and the
            # memory peak with them (11.5 and 13.6 GiB in two runs on four
            # chips)
            self.ring.append(jax.block_until_ready(
                self.trainer.stage_batch(DataBatch(data, label))))
        self.i = 0

    def advance(self) -> None:
        with self.spans.span('step.dispatch'):
            self.trainer.update_staged(self.ring[self.i % len(self.ring)])
        self.i += 1

    def close(self) -> None:
        pass
