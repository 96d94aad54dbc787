"""The checks of the ``solar-open2-ep40tp4`` configuration and its cell,
none of which needs the chip (``__main__.py``'s, ``glm.py``'s and
``laguna.py``'s are those of the rest):

    JAX_PLATFORMS=cpu python3 -m benchmark.selftest.solar

Each check is a function; one that fails prints why and the command exits
non-zero.  The last one rehearses the cell on the CPU with the tiny twin's
conf in the place of the configuration's (the real widths hold 906 M
parameters, some 15 GB of host memory in a rehearsal).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import confnet, harness, kda_costs, tokens      # noqa: E402
from benchmark.references import solar_open2 as R              # noqa: E402

CELL = 'solar-open2-ep40tp4-seq8k'
CONFIG = 'solar-open2-ep40tp4'
TINY = os.path.join(ROOT, 'example', 'LM', 'tiny-solar.conf')
DATA = {'successors': 4, 'p_likely': 0.9}


def _pairs(path):
    with open(path) as f:
        return confnet.drop_sections(confnet.parse_conf(f.read()),
                                     ('data', 'eval', 'pred'))


def check_flops_and_costs_by_hand():
    graph = R.build_graph(_pairs(os.path.join(BENCH, 'configs',
                                              CONFIG + '.conf')))
    s, d, hd, w, c = 8192, 4096, 128, 16 * 128, 64
    assert (graph.seq, graph.width, graph.vocab) == (s, d, 24576)
    # the delta rule at chunks of 64: pairs (2 C^2 dk), U and W (C^2 (dk +
    # dv)), the scan and the outputs' Q S (3 C dk dv), pairs times Delta
    per_chunk = 2 * c * c * hd + 2 * c * c * hd + 3 * c * hd * hd \
        + c * c * hd
    assert kda_costs.recurrence_macs(s, 16, hd, hd) == 128 * 16 * per_chunk
    assert kda_costs.recurrence_macs(s + 1, 16, hd, hd) \
        == 129 * 16 * per_chunk
    kda = s * (3 * d * w + 2 * (d * hd + hd * w) + d * 16 + w * d) \
        + 128 * 16 * per_chunk
    macs = R.forward_macs(graph)
    assert [macs[l.index] for l in graph.of_type('kda')] == [kda] * 3
    cfg = harness.load_json(BENCH, 'configs', CONFIG + '.json')
    assert R.train_flops_per_sequence(graph) \
        == cfg['train_flops_per_sequence']          # 16.48 TFLOP a step
    # the roofline's numerator: four forward passes a layer, the
    # convolutions in, bytes of six float32 arrays of the heads' width
    (l, *_) = graph.of_type('kda')
    assert kda_costs.parameters(graph, l) == 35_225_744
    one = kda_costs.layer_forward(s, d, 16, hd, 35_225_744)
    assert one['flops'] == 2.0 * (kda + 3 * 4 * w * s)
    assert one['bytes'] == s * (4 * d + 48 * w) + 2 * 35_225_744
    step = kda_costs.step_cost(graph, 1)
    assert step['flops'] == 3 * kda_costs.PASSES * one['flops']
    assert abs(step['flops'] - 7.2e12) < 0.1e12


def check_configuration_keeps_the_published_numbers():
    """Every number of the catalog's ``config`` is in the file under its
    key, the cut ones named in ``reduced``, no width among them; the nested
    group whole but for its head count; the conf's layers as the file
    says."""
    cfg = harness.load_json(BENCH, 'configs', CONFIG + '.json')
    published = {
        'model_type': 'solar_open2', 'partial_rotary_factor': 1,
        'hidden_size': 4096, 'head_dim': 128, 'intermediate_size': 10240,
        'moe_intermediate_size': 1280, 'rms_norm_eps': 1e-05,
        'rope_theta': 10000, 'tie_word_embeddings': False,
        'max_position_embeddings': 1048576, 'first_k_dense_replace': 0,
        'use_rope': False, 'gqa_interval': 3, 'use_gqa_gate': True,
        'kda_use_full_proj': False, 'kda_allow_neg_eigval': True,
        'n_shared_experts': 1, 'norm_topk_prob': True,
        'routed_scaling_factor': 1, 'num_experts_per_tok': 8,
        'gqa_layers': list(range(0, 48, 4))}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg['linear_attn_config'] == {
        'short_conv_kernel_size': 4, 'head_dim': 128, 'num_heads': 16,
        'num_kv_heads': None}
    assert cfg['reduced'] == ['num_hidden_layers', 'n_routed_experts',
                              'vocab_size', 'num_attention_heads',
                              'num_key_value_heads', 'linear_attn_config']
    assert (cfg['num_hidden_layers'], cfg['n_routed_experts'],
            cfg['vocab_size'], cfg['num_attention_heads'],
            cfg['num_key_value_heads']) == (4, 8, 24576, 16, 2)
    assert cfg['published'] == {
        'num_hidden_layers': 48, 'n_routed_experts': 320,
        'vocab_size': 196608, 'num_attention_heads': 64,
        'num_key_value_heads': 8,
        'linear_attn_config': dict(cfg['linear_attn_config'], num_heads=64)}
    graph = R.build_graph(_pairs(os.path.join(BENCH, 'configs',
                                              cfg['conf'])))
    attn, kda, moe = (graph.of_type(t) for t in ('gqa', 'kda', 'moe'))
    kinds = [l.type for l in graph.layers if l.type in ('gqa', 'kda')]
    assert kinds == ['gqa' if i in cfg['gqa_layers'] else 'kda'
                     for i in cfg['layers_run']]
    (a,) = attn
    assert (a.geti('nhead'), a.geti('nkvhead'), a.geti('head_dim'),
            a.geti('nhead_published'), a.geti('head_first'),
            a.geti('use_rope'), a.cfg['gate'], a.geti('window')) == (
        16, 2, 128, 64, 0, 0, 'elementwise', 0)
    for l in kda:
        assert (l.geti('nhead'), l.geti('head_dim'),
                l.geti('nhead_published')) == (16, 128, 64)
    for l in graph.layers:
        if l.type in ('gqa', 'kda', 'moe', 'rmsnorm'):
            assert l.getf('eps', 0) == cfg['rms_norm_eps']
    assert len(moe) == 4 and all(
        l.geti('experts_published') == 320
        and l.geti('experts_held') == cfg['n_routed_experts']
        and l.geti('experts_per_token') == cfg['num_experts_per_tok']
        and l.geti('nhidden') == cfg['moe_intermediate_size']
        and l.geti('shared_experts') == cfg['n_shared_experts']
        and l.cfg.get('router_score', 'sigmoid') == 'sigmoid'
        and l.getf('routed_scaling_factor', 0) == 1.0 for l in moe)


def check_reference_against_program():
    """Float32 program = reference: forward, delta rule and step; bf16
    inside every limit; each variant of the sensitivity probe outside at
    least one, at the tiny size (the limits are set from the chip's readings at the
    cell's size: ``solar_sensitivity.py``, PERF.md 6)."""
    import jax
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer
    pairs = _pairs(TINY)
    graph = R.build_graph(pairs)
    ids = tokens.token_rows(11, 2, graph.seq + 2, graph.vocab, DATA)
    batch = DataBatch(ids[:, None, None, :graph.seq + 1],
                      np.zeros((2, graph.seq), np.float32))
    ring = [tokens.token_rows(100 + i, 2, graph.seq + 2, graph.vocab, DATA)
            for i in range(4)]

    def program(compute):
        tr = NetTrainer(pairs + [('compute_type', compute), ('seed', '5')])
        tr.init_model()
        # a few steps first, as a run's comparison comes after its window:
        # from zero moments Adam's first change is the gradient's sign
        for rows in ring * 2:
            tr.update_staged(tr.stage_batch(DataBatch(
                rows[:, None, None, :graph.seq + 1],
                R.label_matrix(graph, rows).astype(np.float32))))
        got = {n: np.asarray(tr.extract_feature(batch, n)).reshape(
            2, graph.seq, -1) for n in graph.loss_nodes()}
        params = jax.device_get(tr.params)
        sides = {what: R.reference_side(graph, params, ids, got, v)
                 for what, v in {'as is': R.MODEL, **R.PROBE}.items()}
        step = R.program_step(tr, graph, ids)
        out = {}
        for what, side in sides.items():
            found, ok = R.judge(graph, side, step)
            n = side['numbers']['logits']
            out[what] = (n['largest'], n['mean'], n['median_position'],
                         n['loss'], max(side['recurrence'].values()),
                         found['loss'], max(found['update'].values()),
                         max(found['kda_update'].values()), ok)
        return out

    head = ('largest / mean / median position / loss / delta rule / step '
            'loss / update / delta layer update')
    exact = program('float32')
    e = exact.pop('as is')
    assert e[0] < 1e-3 and e[1] < 1e-4 and e[3] < 1e-5 and e[4] < 1e-5 \
        and e[5] < 1e-5 and e[6] < 1e-3 and e[7] < 1e-3 and e[8], e
    h = program('bfloat16')['as is']
    print(f'    {head}: float32 ' + ' / '.join(f'{v:.2e}' for v in e[:8])
          + ', bfloat16 ' + ' / '.join(f'{v:.2e}' for v in h[:8]))
    assert h[8], h
    inside = []
    for name, v in sorted(exact.items()):
        print(f'    {name}: ' + ' / '.join(f'{x:.2e}' for x in v[:8])
              + (' INSIDE' if v[8] else ''))
        if v[8]:
            inside.append(name)
    assert not inside, inside
    jax.clear_caches()


def check_cell_rehearses():
    """The cell end to end on the CPU, untraced and traced: feed, taps,
    reference, window, readers, in a copy of the tree whose configuration
    conf is the tiny twin's.  A rehearsal, never a measurement."""
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=ROOT)
    with tempfile.TemporaryDirectory(prefix='selftest_solar_') as tmp:
        shutil.copytree(BENCH, os.path.join(tmp, 'benchmark'),
                        ignore=shutil.ignore_patterns('.cache', '__pycache__'))
        shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), tmp)
        shutil.copy(TINY, os.path.join(tmp, 'benchmark', 'configs',
                                       CONFIG + '.conf'))
        for beside in ('runtime', 'tools'):
            os.symlink(os.path.join(ROOT, beside), os.path.join(tmp, beside))
        env['JAX_COMPILATION_CACHE_DIR'] = os.path.join(tmp, 'jax_cache')
        for traced in (0, 1):
            r = subprocess.run(
                [sys.executable, '-m', 'benchmark.run', '--workload', CELL,
                 '--seed', '3000000019', '--seconds', '1', '--trace',
                 str(traced), '--rehearse', '1'], cwd=tmp, env=env,
                capture_output=True, text=True, timeout=900)
            assert r.returncode == 0, r.stderr[-2000:] + r.stdout[-2000:]
            line = json.loads(r.stdout.strip().splitlines()[-1])
            assert line['rehearsal'] and line['correct'], line
            assert line['attempted'] > 0 and line['failed'] == 0, line
            if traced:
                # no device plane on the CPU: the readers of a trace leave
                # their metrics out, the counters and the spans are there
                assert {'kda.chunk_log_decay_min',
                        'moe.local_assignment_share', 'entry.compile_s',
                        'step.host_self_ms_p50'} <= set(line['metrics'])
                assert not {'net.kda_ms_per_step', 'net.kda_roofline_pct',
                            'net.gqa_full_ms_per_step'} \
                    & set(line['metrics']), line
            else:
                assert set(line['metrics']) == {'samples_per_s', 'setup_s'}


CHECKS = [check_flops_and_costs_by_hand,
          check_configuration_keeps_the_published_numbers,
          check_reference_against_program, check_cell_rehearses]


def main(argv) -> int:
    wanted = [c for c in CHECKS if not argv or c.__name__ in argv]
    failed = 0
    for check in wanted:
        print(f'selftest.solar: {check.__name__} ...', flush=True)
        try:
            check()
        except Exception:                  # a check's failure is the report
            failed += 1
            traceback.print_exc()
            print(f'selftest.solar: {check.__name__} FAILED', flush=True)
    print(f'selftest.solar: {len(wanted) - failed} of {len(wanted)} passed')
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
