"""Feed kind ``imgbin``: the conf's own ``data = train`` section (imgbin ->
augment -> threadbuffer, unchanged but for its three paths) over a packed
set of seeded JPEGs, ``LearnTask``'s own iterator, and a fresh
``ExecutionPlan`` stepper each round as ``main._round`` has it; rounds are
repeated until the clock runs out.  No ``nworker``, no ``device_normalize``:
the defaults are the program's to change.
"""

from __future__ import annotations

import os
import subprocess

from .. import confnet, cxx, synth

_PATH_KEYS = {'image_list': 'list', 'image_bin': 'bin', 'image_mean': 'mean'}


class Feed:
    def __init__(self, cell, seed: int, spans):
        from cxxnet_tpu.nnet.execution import ExecutionPlan
        self.spans = spans
        # make's own timestamps decide whether the native reader is stale
        subprocess.run(['make', '-s', '-C', os.path.join(synth.ROOT,
                                                         'runtime')],
                       check=True, stdout=subprocess.DEVNULL)
        pairs = cxx.conf_pairs(cell, seed, keep_data=True)
        self.graph = confnet.build_graph(pairs)
        paths = synth.packed_jpegs(dict(cell.t('dataset'),
                                        num_classes=self.graph.num_classes))
        pairs = [(k, paths[_PATH_KEYS[k]]) if k in _PATH_KEYS else (k, v)
                 for k, v in pairs]
        if not os.path.exists(paths['mean']):
            # a checkout's first run: the mean image's pass goes through a
            # chain of its own.  Left to the training chain's init it leaves
            # that chain's readers ahead, and the run reads 616 img/s where
            # every later one reads 487 (PERF.md, Findings)
            cxx.prime_data_chain(pairs)
        self.task = cxx.build_task(pairs)
        self.trainer = self.task.net_trainer
        self.samples_per_step = cell.batch_per_chip * cell.chips
        self.plan = ExecutionPlan.resolve(
            requested_k=self.task.steps_per_dispatch, silent=True)
        self.rounds = 0
        self._begin_round()

    def _begin_round(self) -> None:
        self.rounds += 1
        self.trainer.start_round(self.rounds)
        self.stepper = self.plan.round_stepper(self.trainer)
        self.batches = iter(self.task.itr_train)

    def advance(self) -> None:
        with self.spans.span('io.wait'):
            batch = next(self.batches, None)
        with self.spans.span('step.stage'):
            if batch is not None:
                self.stepper.feed(batch)
            else:
                self.stepper.finish()
        if batch is None:
            self._begin_round()

    def close(self) -> None:
        closer = getattr(self.task.itr_train, 'close', None)
        if closer is not None:
            closer(timeout=5.0)
