#!/usr/bin/env python
"""Benchmark: per-step checkpoint save stall, sync vs async, at
``save_every=1`` (the aggressive cadence the preemptible-fleet story
wants).  CPU platform — the stall under measure is host/storage work, so
no accelerator is needed and the ledger is reproducible anywhere.

Prints ONE JSON line (the BENCH_CKPT_rNN.json ledger shape):

  {"metric": "ckpt_save_stall_ms_per_step", "value": <async ms>,
   "sync_ms_per_step": S, "async_ms_per_step": A, "stall_ratio": S/A, ...}

*stall* is the wall time the STEP LOOP is blocked by the save boundary:
the full serialize+fsync+commit for the synchronous path
(``trainer.save_training_state``), versus snapshot+submit (plus any
double-buffer backpressure) for ``runtime.async_ckpt.AsyncCheckpointer``.
Every save leg gets one untimed warmup save (orbax/pool setup is one-time
cost, not per-step stall), and the bench restores both legs' final
checkpoints and asserts they are BITWISE equal before emitting — a ledger
entry can never describe an async path that drifted from sync bytes.

CLI overrides (``k=v``): ``steps=``, ``batch=``, ``nhidden=``,
``workers=`` (parsed with ``utils.config.cfg_get_int``).
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault('JAX_PLATFORMS', 'cpu')

import numpy as np

MLP_CONF = """
netconfig=start
layer[+1] = fullc:fc1
  nhidden = {nhidden}
layer[+1] = relu
layer[+1] = fullc:fc2
  nhidden = {nhidden}
layer[+1] = relu
layer[+1] = fullc:fc3
  nhidden = 10
layer[+0] = softmax
netconfig=end
input_shape = 1,1,784
batch_size = {batch}
dev = cpu
eta = 0.01
momentum = 0.9
metric = error
eval_train = 0
random_type = xavier
"""


def _fresh_trainer(batch: int, nhidden: int):
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string
    tr = NetTrainer(parse_config_string(
        MLP_CONF.format(batch=batch, nhidden=nhidden)))
    tr.init_model()
    return tr


def _batches(n: int, batch: int):
    from cxxnet_tpu.io.data import DataBatch
    rng = np.random.RandomState(0)
    return [DataBatch(rng.randn(batch, 1, 1, 784).astype(np.float32),
                      rng.randint(0, 10, (batch, 1)).astype(np.float32))
            for _ in range(n)]


def _state_bytes(tr) -> int:
    return sum(tr.resident_state_bytes().values())


def _params_host(tr):
    import jax
    return [np.asarray(x) for x in jax.tree.leaves(tr.params)]


def main() -> int:
    from cxxnet_tpu.utils.config import apply_cli_overrides, cfg_get_int
    cfg = apply_cli_overrides([], sys.argv[1:])
    steps = cfg_get_int(cfg, 'steps', 12)
    batch = cfg_get_int(cfg, 'batch', 200)
    nhidden = cfg_get_int(cfg, 'nhidden', 512)
    workers = cfg_get_int(cfg, 'workers', 8)

    import tempfile

    import jax

    from cxxnet_tpu.runtime.async_ckpt import AsyncCheckpointer
    from cxxnet_tpu.utils.backend import enable_compile_cache
    enable_compile_cache()

    batches = _batches(steps + 2, batch)   # 2 warmup + `steps` timed

    with tempfile.TemporaryDirectory() as tmp:
        # --- baseline step time (no saves), warmup/compile included up
        # front so neither leg pays tracing inside its timed region
        tr_sync = _fresh_trainer(batch, nhidden)
        tr_async = _fresh_trainer(batch, nhidden)
        tr_sync.update(batches[0])
        tr_async.update(batches[0])
        t0 = time.perf_counter()
        tr_sync.update(batches[1])
        step_ms = (time.perf_counter() - t0) * 1e3
        tr_async.update(batches[1])

        # --- sync leg: save_training_state at EVERY step --------------
        sdir = os.path.join(tmp, 'sync')
        tr_sync.save_training_state(sdir, 0)        # warmup (orbax setup)
        stall_sync = []
        for i, b in enumerate(batches[2:2 + steps]):
            tr_sync.update(b)
            t0 = time.perf_counter()
            tr_sync.save_training_state(sdir, tr_sync.sample_counter)
            stall_sync.append(time.perf_counter() - t0)

        # --- async leg: snapshot+submit at EVERY step -----------------
        adir = os.path.join(tmp, 'async')
        ck = AsyncCheckpointer(workers=workers)
        ck.save_sharded_async(adir, 0, tr_async.snapshot_training_state())
        ck.wait()                                   # warmup (pool spinup)
        stall_async = []
        for i, b in enumerate(batches[2:2 + steps]):
            tr_async.update(b)
            t0 = time.perf_counter()
            ck.save_sharded_async(adir, tr_async.sample_counter,
                                  tr_async.snapshot_training_state())
            stall_async.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        ck.wait()                                   # final save barriers
        final_barrier_ms = (time.perf_counter() - t0) * 1e3

        # --- the trust gate: async bytes must restore bitwise-equal ---
        last = tr_sync.sample_counter
        probe_s = _fresh_trainer(batch, nhidden)
        probe_a = _fresh_trainer(batch, nhidden)
        probe_s.load_training_state(sdir, step=last, restore_params=True)
        probe_a.load_training_state(adir, step=last, restore_params=True)
        bitwise = all((x == y).all() for x, y in
                      zip(_params_host(probe_s), _params_host(probe_a)))
        if not bitwise:
            raise AssertionError(
                'async-written checkpoint restored different bytes than '
                'its sync twin — ledger not emitted')
        state_mb = _state_bytes(tr_sync) / 1e6
        ck.close()

    sync_ms = 1e3 * sum(stall_sync) / len(stall_sync)
    async_ms = 1e3 * sum(stall_async) / len(stall_async)
    print(json.dumps({
        'metric': 'ckpt_save_stall_ms_per_step',
        'value': round(async_ms, 3),
        'unit': 'ms/step',
        'sync_ms_per_step': round(sync_ms, 3),
        'async_ms_per_step': round(async_ms, 3),
        'stall_ratio': round(sync_ms / async_ms, 2),
        'step_ms_nosave': round(step_ms, 3),
        'save_every': 1,
        'steps': steps,
        'state_mb': round(state_mb, 2),
        'workers': workers,
        'bitwise_restore_equal': True,
        'platform': jax.devices()[0].platform,
        'timing': 'mean stall over timed steps, one untimed warmup save '
                  'per leg; stall = wall time the step loop is blocked '
                  'at the save boundary',
    }))
    return 0


if __name__ == '__main__':
    sys.exit(main())
