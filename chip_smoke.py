#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Trains AlexNet (``example/ImageNet/ImageNet.conf`` as written: batch 256,
3x227x227, bf16, momentum SGD, dropout, LRN, grouped convs, ``dev = tpu``)
through ``cxxnet_tpu.main.LearnTask().run(argv)``, which is what
``python -m cxxnet_tpu.main <conf> [k=v ...]`` runs, on synthetic JPEGs
packed with ``tools/im2bin.py`` and fed by the imgbin -> augment ->
threadbuffer chain: two rounds of two batches with an eval section and
``save_model``, then ``task = pred`` from the saved model over the eval
list.  Only ``num_round``, the data paths and ``model_dir`` differ from the
example.

Both phases run in THIS process, the one that holds the chip; the only
children are ``make`` and ``tools/im2bin.py``, which never touch JAX.
Exits non-zero with one line saying why unless everything checks out; the
last stdout line is then ``{"ok": true, "device": {...}}``.  Nothing is
caught: a failed phase is a failed run.

    python chip_smoke.py
"""

from __future__ import annotations

import contextlib
import io
import json
import math
import os
import re
import subprocess
import sys
import tempfile
import time
from importlib import metadata

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

BATCH = 256
TRAIN_BATCHES = 2
EVAL_BATCHES = 1
ROUNDS = 2
_COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'


def fail(why: str):
    raise SystemExit(f'chip_smoke: FAIL — {why}')


class Tee(io.TextIOBase):
    """stderr that keeps a copy: the eval and train-mfu lines are checked."""

    def __init__(self, stream):
        self.stream, self.kept = stream, []

    def write(self, s):
        self.kept.append(s)
        return self.stream.write(s)

    def flush(self):
        self.stream.flush()


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def build_runtime() -> None:
    """Always from source: a stale git-ignored .so must never be what ran."""
    subprocess.run(['make', '-B', '-C', os.path.join(ROOT, 'runtime')],
                   check=True, stdout=subprocess.DEVNULL)


def pack_synthetic(tmp: str, name: str, n: int, seed: int):
    """``n`` seeded low-frequency 256x256 JPEGs (photo-like decode cost)
    packed by the in-tree packer; returns (list_path, bin_path)."""
    import numpy as np
    from PIL import Image
    rng = np.random.RandomState(seed)
    root = os.path.join(tmp, name)
    os.makedirs(root)
    lst = os.path.join(tmp, f'{name}.lst')
    with open(lst, 'w') as f:
        for i in range(n):
            small = rng.randint(0, 255, (16, 16, 3), dtype=np.uint8)
            Image.fromarray(small).resize((256, 256), Image.BILINEAR).save(
                os.path.join(root, f'{i}.jpg'), quality=85)
            f.write(f'{i}\t{int(rng.randint(0, 1000))}\t{i}.jpg\n')
    binpath = os.path.join(tmp, f'{name}.bin')
    subprocess.run([sys.executable, os.path.join(ROOT, 'tools', 'im2bin.py'),
                    lst, root, binpath], check=True,
                   stdout=subprocess.DEVNULL)
    return lst, binpath


def write_confs(tmp: str, train, test):
    """The example conf with its dataset paths pointed at ``tmp``; the pred
    conf is the same text with the eval section turned into ``pred =``."""
    with open(os.path.join(ROOT, 'example', 'ImageNet',
                           'ImageNet.conf')) as f:
        text = f.read()
    model_dir = os.path.join(tmp, 'models')
    os.makedirs(model_dir)
    for old, new in (('../../NameList.train', train[0]),
                     ('../../TRAIN.BIN', train[1]),
                     ('../../NameList.test', test[0]),
                     ('../../TEST.BIN', test[1]),
                     ('models/image_net_mean.bin',
                      os.path.join(model_dir, 'image_net_mean.bin'))):
        if old not in text:
            fail(f'example/ImageNet/ImageNet.conf no longer names {old}')
        text = text.replace(old, new)
    pred_out = os.path.join(tmp, 'pred.txt')
    paths = {'train': os.path.join(tmp, 'train.conf'),
             'pred': os.path.join(tmp, 'pred.conf')}
    with open(paths['train'], 'w') as f:
        f.write(text)
    with open(paths['pred'], 'w') as f:
        f.write(text.replace('eval = test', f'pred = {pred_out}'))
    return paths, model_dir, pred_out


class Phases:
    """Wall and compile seconds of each phase; compile time is what JAX
    itself reports (backend compile or persistent-cache retrieval)."""

    def __init__(self):
        import jax.monitoring
        self.compile_s = 0.0
        self.rows = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == _COMPILE_EVENT:
            self.compile_s += secs

    @contextlib.contextmanager
    def phase(self, name: str):
        t0, c0 = time.perf_counter(), self.compile_s
        yield
        row = (name, time.perf_counter() - t0, self.compile_s - c0)
        self.rows.append(row)
        print(f'chip_smoke: phase {row[0]}: wall {row[1]:.1f}s, '
              f'compile {row[2]:.1f}s', flush=True)


def check_train(stderr_text: str, task, model_dir: str, batch: int) -> None:
    import jax
    import numpy as np
    for rnd in range(1, ROUNDS + 1):
        line = next((ln for ln in stderr_text.splitlines()
                     if ln.startswith(f'[{rnd}]')), None)
        if line is None:
            fail(f'no eval line for round {rnd}')
        vals = dict(re.findall(r'(\S+?):(\S+)', line))
        for key in ('train-error', 'test-error'):
            if key not in vals or not math.isfinite(float(vals[key])):
                fail(f'round {rnd} eval line lacks a finite {key}: {line!r}')
    mfus = [float(v) for v in re.findall(r'train-mfu:(\S+)', stderr_text)]
    if len(mfus) != ROUNDS or not all(0.0 < m <= 1.0 for m in mfus):
        fail(f'train-mfu not in (0, 1] for every round: {mfus}')
    steps = task.net_trainer.epoch_counter
    if steps < ROUNDS * TRAIN_BATCHES:
        fail(f'{steps} optimizer steps, expected {ROUNDS * TRAIN_BATCHES}')
    for leaf in jax.tree.leaves(task.net_trainer.params):
        if not np.all(np.isfinite(np.asarray(leaf, np.float32))):
            fail('non-finite parameters after the last step')
    if not os.path.exists(os.path.join(model_dir, f'{ROUNDS:04d}.model')):
        fail(f'{ROUNDS:04d}.model was not saved')
    print(f'chip_smoke: {steps} optimizer steps at batch {batch}, '
          f'train-mfu per round {mfus}', flush=True)


def check_pred(pred_out: str, want: int) -> None:
    with open(pred_out) as f:
        labels = f.read().split()
    if len(labels) != want:
        fail(f'pred wrote {len(labels)} labels for {want} eval instances')
    if not all(v.isdigit() and int(v) < 1000 for v in labels):
        fail('pred labels are not all integers in [0, 1000)')


def check_pallas_compiled() -> None:
    """The gates the traced steps consulted, read in the same process."""
    from cxxnet_tpu.ops import pallas_kernels as PK
    got = {'interpret': PK._interpret(),
           'fc8 eval': PK.fullc_use_pallas(BATCH, 4096, 1000,
                                           is_train=False)}
    want = {'interpret': False, 'fc8 eval': True}
    if got != want:
        fail(f'Pallas gates {got}, expected {want}')


def drive(phases: Phases, batch: int = BATCH):
    """Both phases and their checks.  ``batch`` is only ever narrowed by
    the CPU dry run of this function (tests/test_chip_smoke.py)."""
    from cxxnet_tpu.main import LearnTask
    with tempfile.TemporaryDirectory(prefix='chip_smoke_') as tmp:
        with phases.phase('build+data'):
            build_runtime()
            train = pack_synthetic(tmp, 'train', batch * TRAIN_BATCHES, 0)
            test = pack_synthetic(tmp, 'test', batch * EVAL_BATCHES, 1)
            confs, model_dir, pred_out = write_confs(tmp, train, test)
        common = [f'model_dir={model_dir}', f'batch_size={batch}']
        tee = Tee(sys.stderr)
        task = LearnTask()
        with phases.phase('train'), contextlib.redirect_stderr(tee):
            rc = task.run([confs['train'], f'num_round={ROUNDS}',
                           f'max_round={ROUNDS}'] + common)
        if rc != 0:
            fail(f'task=train returned {rc}')
        check_train(''.join(tee.kept), task, model_dir, batch)
        model = os.path.join(model_dir, f'{ROUNDS:04d}.model')
        with phases.phase('pred'):
            rc = LearnTask().run([confs['pred'], 'task=pred',
                                  f'model_in={model}'] + common)
        if rc != 0:
            fail(f'task=pred returned {rc}')
        check_pred(pred_out, batch * EVAL_BATCHES)


def main() -> int:
    t_start = time.perf_counter()
    # the smoke checks the peak table itself, not an override of it
    os.environ.pop('CXXNET_PEAK_TFLOPS', None)
    import jax

    from cxxnet_tpu.utils.backend import enable_compile_cache, meet_backend
    cache_dir = enable_compile_cache()
    backend = meet_backend()
    if backend != 'tpu':
        fail(f'JAX backend is {backend!r} ({jax.devices()[0]}), not a TPU')
    from cxxnet_tpu.obs.programs import peak_flops
    dev = jax.devices()[0]
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': len(jax.devices())}
    peak = peak_flops(dev)        # raises when the kind has no table row
    print(f'chip_smoke: {device}, peak {peak / 1e12:g} bf16 TFLOP/s; '
          f'jax {jax.__version__}, jaxlib {metadata.version("jaxlib")}, '
          f'libtpu {metadata.version("libtpu")}', flush=True)
    n_before = cache_entries(cache_dir)
    print(f'chip_smoke: compile cache {cache_dir}: {n_before} entries',
          flush=True)
    phases = Phases()
    drive(phases)
    check_pallas_compiled()
    print(f'chip_smoke: compile cache {cache_dir}: {n_before} -> '
          f'{cache_entries(cache_dir)} entries; compile '
          f'{sum(r[2] for r in phases.rows):.1f}s of '
          f'{time.perf_counter() - t_start:.1f}s wall', flush=True)
    print(json.dumps({'ok': True, 'device': device}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
