"""The door between the benchmark and the system under test.

From ``cxxnet_tpu`` the benchmark takes the program (``LearnTask`` built
from a conf, its ``NetTrainer``, its iterators, its ``ExecutionPlan``), its
spans (the telemetry hub), its counters (the program ledger) and nothing
that decides a number.  Every feed for a conf-driven trainer goes through
here, so that one place knows which of the program's names the benchmark
leans on; a feed for another kind of program brings a door of its own.
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

from . import confnet

CONFIG_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          'configs')


def load_conf(config: dict) -> str:
    with open(os.path.join(CONFIG_DIR, config['conf'])) as f:
        return f.read()


def conf_pairs(cell, seed: int, keep_data: bool) -> confnet.Pairs:
    """The cell's conf as it is run: the copied conf, without its ``eval``
    section (the harness owns the loop: no evaluation pass, no
    ``save_model`` in the window) and, for a staged feed, without its
    ``data`` section; then the keys the configuration sets, the seed, the
    devices, the global batch, and what the traffic mix (and a rehearsal)
sets besides."""
    pairs = confnet.parse_conf(load_conf(cell.config))
    pairs = confnet.drop_sections(
        pairs, ('eval', 'pred') + (() if keep_data else ('data',)))
    sets = {k: str(v) for k, v in cell.config.get('set', {}).items()}
    sets.update({'seed': str(seed), 'seed_data': str(seed), 'silent': '1',
                 'batch_size': str(cell.batch_per_chip * cell.chips)})
    if cell.chips > 1:
        sets['dev'] = f'tpu:0-{cell.chips - 1}'
    sets.update(cell.conf_extra())
    return pairs + list(sets.items())


def build_task(pairs: confnet.Pairs):
    """``LearnTask`` as ``python -m cxxnet_tpu.main <conf>`` would have it
    after ``init()``: trainer initialised from the seed, iterators made."""
    from cxxnet_tpu.main import LearnTask
    task = LearnTask()
    for name, val in pairs:
        task.set_param(name, val)
    task.init()
    return task


def prime_data_chain(pairs: confnet.Pairs) -> None:
    """Create, initialise and close the ``data`` section's iterator chain,
    as ``LearnTask._create_iterators`` would: the chain's ``init`` computes
    and saves the mean image when its file is missing."""
    from cxxnet_tpu.io.data import create_iterator
    section, rest, inside = [], [], False
    for name, val in pairs:
        if name == 'data':
            inside = True
        elif inside:
            section.append((name, val))
            inside = (name, val) != ('iter', 'end')
        else:
            rest.append((name, val))
    chain = create_iterator(section)
    for name, val in rest:
        chain.set_param(name, val)
    chain.init()
    closer = getattr(chain, 'close', None)
    if closer is not None:
        closer(timeout=5.0)


class LossTap:
    """Every dispatched step's loss scalar, still on the device.

    ``update_staged`` does not return the loss, and hands it only to the
    trainer's own ``_observe_loss``; the tap wraps that method on this one
    trainer instance.  (A public per-step hook is on PERF.md's list for the
    tracing issue.)"""

    def __init__(self, trainer):
        self.losses: List = []
        inner = trainer._observe_loss

        def observe(loss):
            self.losses.append(loss)
            return inner(loss)

        trainer._observe_loss = observe


def eval_outputs(trainer, data: np.ndarray,
                 nodes: List[str]) -> Dict[str, np.ndarray]:
    """The program's evaluation-mode value of each named node for ``data``
    (NCHW float32), through its own forward step."""
    from cxxnet_tpu.io.data import DataBatch
    batch = DataBatch(data, np.zeros((data.shape[0], 1), np.float32))
    return {n: np.asarray(trainer.extract_feature(batch, n), np.float32)
            for n in nodes}


def host_params(trainer) -> Dict[str, Dict[str, np.ndarray]]:
    import jax
    return jax.device_get(trainer.params)


def ledger_compiles() -> int:
    """Compilations the program's own ledger has seen in this process."""
    from cxxnet_tpu.obs.programs import get_ledger
    return int(get_ledger().summary()['compiles_total'])


def hub_spans(name: str, t0_ns: int, t1_ns: int) -> List[tuple]:
    """(start_ns, dur_ns) of the program's own spans called ``name`` that
    began inside ``[t0_ns, t1_ns)`` (``time.monotonic_ns`` clock)."""
    from cxxnet_tpu.obs import get_hub
    return [(e['t_start_ns'], e['dur_ns']) for e in get_hub().events()
            if e['name'] == name and t0_ns <= e['t_start_ns'] < t1_ns]


def hlo_flops_per_step(trainer) -> float:
    """The compiler's own count for the step program (an AOT probe through
    the ledger).  Printed beside the analytic count, never used for MFU."""
    return float(trainer.train_step_flops())
