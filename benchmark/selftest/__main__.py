"""The benchmark's own checks, none of which needs the chip:

    JAX_PLATFORMS=cpu python3 -m benchmark.selftest

Not under ``tests/``: the yardstick is checked where it lives, and tier-1's
count is untouched.  Each check is a function; one that fails prints why and
the command exits non-zero.
"""

from __future__ import annotations

import gzip
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
FIX = os.path.join(HERE, 'fixtures')
sys.path.insert(0, ROOT)

from benchmark import confnet, harness, trace as T  # noqa: E402


def near(got, want, rel=1e-9):
    assert abs(got - want) <= rel * max(1.0, abs(want)), (got, want)


# --- BENCHMARK.json and the files it names ----------------------------------

def check_contract_shape():
    b = harness.load_json(ROOT, 'BENCHMARK.json')
    assert sorted(b) == sorted(['command', 'paths', 'run_seconds', 'configs',
                                'workloads', 'end_to_end', 'per_layer'])
    name = re.compile(r'^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$')
    names = [x['name'] for k in ('configs', 'workloads', 'end_to_end',
                                 'per_layer') for x in b[k]]
    assert all(name.match(n) for n in names), names
    assert len(names) == len(set(names)), 'a name is used twice'
    assert all(len(x['why']) <= 200 for x in b['configs'] + b['workloads'])
    four = [w for w in b['workloads'] if w['chips'] == 4]
    assert len(four) <= max(1, len(b['workloads']) // 4)
    e2e = {m['name'] for m in b['end_to_end']}
    assert 'setup_s' in e2e
    assert all(0 < m['bound'] <= 0.1 for m in b['end_to_end'])
    for w in b['workloads']:
        cell = harness.load_cell(w['name'])
        mine = {m['name'] for m in cell.end_to_end}
        assert 'setup_s' in mine and len(mine) >= 2, w['name']
        assert cell.per_layer, w['name']
        for m in cell.per_layer:
            assert m['moves'] in mine, (w['name'], m['name'], m['moves'])
        harness.load_module('feeds', cell.traffic['feed'])
        harness.load_module('references', cell.config['reference'])
    for m in b['end_to_end']:
        harness.load_module('e2e_metrics', m['name'])
    for m in b['per_layer']:
        mod = harness.load_module('layer_metrics', m['name'])
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
            m['layer'], m['unit'], m['moves']), m['name']


def check_conf_copies():
    """The copied confs are still the example's: a drift is said, not
    failed, because the example is the program's to change."""
    import hashlib
    for c in harness.load_json(ROOT, 'BENCHMARK.json')['configs']:
        cfg = harness.load_json(ROOT, c['file'])
        src = os.path.join(ROOT, cfg['copied_from'])
        with open(os.path.join(BENCH, 'configs', cfg['conf']), 'rb') as f:
            mine = hashlib.sha256(f.read()).hexdigest()
        assert mine == cfg['copied_sha256'], f'{cfg["conf"]} was edited'
        if os.path.exists(src):
            with open(src, 'rb') as f:
                if hashlib.sha256(f.read()).hexdigest() != mine:
                    print(f'    note: {cfg["copied_from"]} has moved on from '
                          f'the copy in benchmark/configs')


# --- operation counts against hand counts -----------------------------------

def check_flops():
    def graph(name):
        with open(os.path.join(BENCH, 'configs', name + '.conf')) as f:
            return confnet.build_graph(confnet.parse_conf(f.read()))
    alex = graph('alexnet')
    # by hand, multiply-accumulates an image: out positions x out channels x
    # (in channels / groups) x kernel area
    hand = {'conv1': 55 * 55 * 96 * 3 * 121, 'conv2': 27 * 27 * 256 * 48 * 25,
            'conv3': 13 * 13 * 384 * 256 * 9, 'conv4': 13 * 13 * 384 * 192 * 9,
            'conv5': 13 * 13 * 256 * 192 * 9, 'fc6': 9216 * 4096,
            'fc7': 4096 * 4096, 'fc8': 4096 * 1000}
    macs = confnet.forward_macs(alex)
    got = {l.name: macs[l.index] for l in alex.layers if l.index in macs}
    assert got == hand, (got, hand)
    assert sum(hand.values()) == 724_406_816       # 724.4 M an image
    # forward + weight gradient + input gradient, none of the last for conv1
    assert confnet.train_flops_per_sample(alex) == 2 * (
        3 * sum(hand.values()) - hand['conv1'])
    near(confnet.train_flops_per_sample(alex) * 256, 1.0587e12, 1e-4)
    goog = graph('googlenet')
    macs = confnet.forward_macs(goog)
    got = {l.name: macs[l.index] for l in goog.layers if l.index in macs}
    assert got['conv1'] == 112 * 112 * 64 * 3 * 49
    assert got['conv2'] == 56 * 56 * 192 * 64 * 9
    # inception 3a: 28x28, 192 in -> 64 | 96>128 | 16>32 | pool>32
    in3a = 28 * 28 * (192 * 64 + 192 * 96 + 96 * 128 * 9 + 192 * 16
                      + 16 * 32 * 25 + 192 * 32)
    assert sum(v for k, v in got.items() if k.startswith('in3a')) == in3a
    assert got['loss3_fc'] == 1024 * 1000
    assert got['aux1_conv'] == 4 * 4 * 128 * 512 and got['aux1_fc1'] == 2048 * 1024
    assert len(got) == 59 + 5 and sum(got.values()) == 1_591_044_096
    assert goog.loss_nodes() == ['aux1_fc2', 'aux2_fc2', 'fc']
    assert goog.shapes['in5b_out'] == (1024, 7, 7)


# --- the trace reduction ----------------------------------------------------

def check_trace_arithmetic():
    assert T.union([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)]) == [(1, 4), (5, 8)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert T.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert T.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
    from jax.profiler import ProfileData
    with open(os.path.join(FIX, 'hand_made.textproto')) as f:
        planes = T.load(ProfileData.from_text_proto(f.read()))
    r = T.reduce(planes, chips=2)
    assert r.devices == [0, 1] and r.window == (1000.0, 11000.0)
    near(r.window_s, 10000e-9)
    near(r.busy_s, 4750e-9)                    # mean of 5500 and 4000
    assert r.steps == 2
    near(r.step_busy_ms[0], 3500e-6)
    near(r.step_busy_ms[1], 2000e-6)
    near(r.collective_s, 3500e-9)
    near(r.collective_exposed_s, 2500e-9)
    near(r.pallas_s, 1000e-9)
    cats = dict(r.by_category_s)
    assert set(cats) == {'convolution/dot fusion', 'collective',
                         'mosaic custom call', 'loop fusion'}, cats
    near(cats['convolution/dot fusion'], 2000e-9)
    near(cats['loop fusion'], 1000e-9)     # its operands' names do not count
    gaps = dict(r.idle_gaps_s)
    assert set(gaps) == {'step.stage', 'io.wait', 'harness'}, gaps
    near(gaps['io.wait'], 1900e-9)
    near(gaps['step.stage'], 1200e-9)
    near(gaps['harness'], 1400e-9)
    one = T.reduce(planes, chips=1)            # a one-chip cell on that host
    near(one.busy_s, 5500e-9)


def check_trace_recorded():
    """A trace recorded on the chip (two steps of a cell, trimmed): the
    names the reduction leans on are the ones the runtime writes."""
    from jax.profiler import ProfileData
    found = sorted(f for f in os.listdir(FIX) if f.endswith('.textproto.gz'))
    assert found, 'no recorded trace beside the self-test'
    for name in found:
        with gzip.open(os.path.join(FIX, name), 'rt') as f:
            planes = T.load(ProfileData.from_text_proto(f.read()))
        with open(os.path.join(FIX, name.replace('.textproto.gz',
                                                 '.expect.json'))) as f:
            want = json.load(f)
        r = T.reduce(planes, chips=want['chips'])
        assert r.steps == want['steps'], (name, r.steps)
        near(r.busy_s, want['busy_s'], 1e-6)
        near(r.window_s, want['window_s'], 1e-6)
        near(r.collective_s, want['collective_s'], 1e-6)
        near(r.collective_exposed_s, want['collective_exposed_s'], 1e-6)
        near(r.pallas_s, want['pallas_s'], 1e-6)
        assert [k for k, _ in r.by_category_s] == want['categories'], (
            name, r.by_category_s)
        assert 0 < r.busy_s <= r.window_s


# --- the plain reference against the program --------------------------------

_ZOO = '''
netconfig=start
layer[0->c1] = conv:c1
  kernel_size = 5
  stride = 2
  pad = 1
  nchannel = 16
  init_bias = 0.5
layer[c1->c1] = relu
layer[c1->p1] = max_pooling
  kernel_size = 3
  stride = 2
layer[p1->p1] = lrn
  local_size = 5
  alpha = 0.1
  beta = 0.75
  knorm = 1
layer[p1->a,b] = split
layer[a->a1] = conv:a1
  kernel_size = 3
  pad = 1
  ngroup = 2
  nchannel = 8
  init_bias = 0.5
layer[a1->a1] = batch_norm
layer[b->b1] = avg_pooling
  kernel_size = 3
  stride = 1
  pad = 1
layer[a1,b1->cat] = ch_concat
layer[cat->cat] = relu
layer[cat->f] = flatten
layer[f->f] = dropout
  threshold = 0.5
layer[f->h] = fullc:h
  nhidden = 32
  init_bias = 0.5
layer[h->h] = sigmoid
layer[h->out] = fullc:out
  nhidden = 10
layer[out->out] = softmax
netconfig=end
input_shape = 3,23,23
batch_size = 8
dev = cpu
eta = 0.01
random_type = xavier
metric = error
'''


def _program(conf_text, compute):
    from cxxnet_tpu.nnet.trainer import NetTrainer
    pairs = confnet.parse_conf(conf_text) + [('compute_type', compute),
                                             ('seed', '5')]
    trainer = NetTrainer(pairs)
    trainer.init_model()
    return trainer, confnet.build_graph(pairs)


def check_reference_against_program():
    from benchmark import cxx
    from benchmark.references import confnet as R
    rng = np.random.RandomState(3)
    data = (4.0 * rng.standard_normal((8, 3, 23, 23))).astype(np.float32)
    trainer, graph = _program(_ZOO, 'float32')
    params = cxx.host_params(trainer)
    got = cxx.eval_outputs(trainer, data, graph.loss_nodes())['out']
    want = R.forward(graph, params, data)['out']
    assert got.shape == want.shape == (8, 10)
    exact = R.log_prob_error(got, want)
    assert exact < 1e-4, exact                 # same arithmetic, float32
    # the program in bfloat16 is inside the tolerance; the reference with an
    # LRN layer left out, or with the biases zeroed, is outside it
    half, _ = _program(_ZOO, 'bfloat16')
    low = R.log_prob_error(
        cxx.eval_outputs(half, data, graph.loss_nodes())['out'], want)
    assert exact < low < R.TOLERANCE, low
    no_lrn = R.log_prob_error(
        got, R.forward(graph, params, data, skip=('lrn',))['out'])
    no_bias = R.log_prob_error(got, R.forward(graph, {
        k: {f: (np.zeros_like(v) if f == 'bias' else v)
            for f, v in d.items()} for k, d in params.items()}, data)['out'])
    assert no_lrn > R.TOLERANCE and no_bias > R.TOLERANCE, (no_lrn, no_bias)
    print(f'    float32 {exact:.2e}, bfloat16 {low:.4f}, LRN dropped '
          f'{no_lrn:.3f}, biases zeroed {no_bias:.3f}, tolerance '
          f'{R.TOLERANCE}')


# --- a later PR adds a cell with files only ---------------------------------

def _run_added_cell(new_files, entries, workload, joins=()):
    """Copy the benchmark, drop ``new_files`` ({source: directory under
    benchmark/}) in, append ``entries`` to a copy of BENCHMARK.json and
    ``workload`` to the cells of the metrics it ``joins``, and run it there
    as a rehearsal, untraced and traced.  Returns the two result lines;
    fails if a file that was there changed, or if the same command without
    ``--rehearse`` runs off the chip."""
    with tempfile.TemporaryDirectory(prefix='benchmark_selftest_') as tmp:
        put = os.path.join(tmp, 'benchmark')
        shutil.copytree(BENCH, put,
                        ignore=shutil.ignore_patterns('.cache', '__pycache__'))
        before = _digest(put)
        # a checkout has the program's runtime/ and tools/ beside benchmark/
        for beside in ('runtime', 'tools'):
            os.symlink(os.path.join(ROOT, beside), os.path.join(tmp, beside))
        for src, where in new_files.items():
            shutil.copy(src, os.path.join(put, where))
        b = harness.load_json(ROOT, 'BENCHMARK.json')
        for key, rows in entries.items():
            b[key].extend(rows)
        for m in b['end_to_end'] + b['per_layer']:
            if m['name'] in joins:
                m['workloads'].append(workload)
        with open(os.path.join(tmp, 'BENCHMARK.json'), 'w') as f:
            json.dump(b, f)
        env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=ROOT,
                   JAX_COMPILATION_CACHE_DIR=os.path.join(tmp, 'jax_cache'))
        command = [sys.executable, '-m', 'benchmark.run', '--workload',
                   workload, '--seed', '3', '--seconds', '1']
        lines = {}
        for traced in (0, 1):
            r = subprocess.run(
                command + ['--trace', str(traced), '--rehearse', '1'],
                cwd=tmp, env=env, capture_output=True, text=True, timeout=900)
            assert r.returncode == 0, r.stderr[-2000:] + r.stdout[-2000:]
            lines[traced] = json.loads(r.stdout.strip().splitlines()[-1])
        assert lines[0]['correct'] and lines[1]['correct'], lines
        assert lines[0]['device']['platform'] == 'cpu'
        after = _digest(put)
        assert all(after[k] == v for k, v in before.items()), \
            'a file that was there was changed'
        # and the same command, without --rehearse, refuses to run off the
        # chip: non-zero, and no result line
        r = subprocess.run(command + ['--trace', '0'], cwd=tmp, env=env,
                           capture_output=True, text=True, timeout=600)
        assert r.returncode != 0 and '"correct"' not in r.stdout, r.stdout
    return lines


def check_add_a_cell():
    """One new configuration, traffic mix and per-layer metric as files,
    their entries appended, and the new cell runs: no file that was there is
    edited."""
    src = os.path.join(FIX, 'add_a_cell')
    lines = _run_added_cell(
        {os.path.join(src, 'tinynet.conf'): 'configs',
         os.path.join(src, 'tinynet.json'): 'configs',
         os.path.join(src, 'tiny.json'): 'traffic',
         os.path.join(src, 'selftest.steps_per_round.py'): 'layer_metrics'},
        {'configs': [{'name': 'tinynet', 'source': 'selftest',
                      'file': 'benchmark/configs/tinynet.json',
                      'reduced': [], 'why': 'a later PR\'s'}],
         'workloads': [{'name': 'tinynet-tiny', 'config': 'tinynet',
                        'traffic': 'tiny', 'chips': 1, 'why': 'new'}],
         'per_layer': [{
             'name': 'selftest.steps_per_round', 'unit': 'steps',
             'better': 'higher', 'source': 'program_counter', 'layer': 'step',
             'moves': 'samples_per_s', 'workloads': ['tinynet-tiny']}]},
        'tinynet-tiny', joins=('samples_per_s',))
    # (a CPU reports no device memory, so peak_hbm_gib's reader finds
    # nothing to read and the metric is left out of the line)
    assert set(lines[0]['metrics']) == {'samples_per_s', 'setup_s'}
    assert lines[1]['metrics']['selftest.steps_per_round']['value'] \
        == lines[1]['attempted'] > 0


def check_fed_cell_as_entries():
    """The host-fed cell, which no entry of BENCHMARK.json switches on yet:
    its feed, traffic file and readers are under benchmark/, and the entries
    kept beside this check are all a later PR appends."""
    entries = harness.load_json(FIX, 'fed_cell', 'entries.json')
    entries.pop('what')
    lines = _run_added_cell({}, entries, 'alexnet-imgbin')
    assert set(lines[0]['metrics']) == {'fed_samples_per_s', 'setup_s'}
    assert {'io.batch_wait_ms_p50', 'step.stage_ms_p50',
            'entry.compile_s'} <= set(lines[1]['metrics']), lines[1]


def _digest(top):
    import hashlib
    out = {}
    for base, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d not in ('.cache', '__pycache__')]
        for name in files:
            path = os.path.join(base, name)
            with open(path, 'rb') as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


CHECKS = [check_contract_shape, check_conf_copies, check_flops,
          check_trace_arithmetic, check_trace_recorded,
          check_reference_against_program, check_add_a_cell,
          check_fed_cell_as_entries]


def main(argv) -> int:
    wanted = [c for c in CHECKS if not argv or c.__name__ in argv]
    failed = 0
    for check in wanted:
        print(f'selftest: {check.__name__} ...', flush=True)
        try:
            check()
        except Exception:                  # a check's failure is the report
            failed += 1
            traceback.print_exc()
            print(f'selftest: {check.__name__} FAILED', flush=True)
    print(f'selftest: {len(wanted) - failed} of {len(wanted)} passed')
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
