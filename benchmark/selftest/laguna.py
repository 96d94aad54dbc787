"""The checks of what PR 36 added to the benchmark, none of which needs the
chip (``__main__.py``'s and ``glm.py``'s stay as they are; this file is
theirs for the ``laguna-s21-ep32`` configuration and its cell):

    JAX_PLATFORMS=cpu python3 -m benchmark.selftest.laguna

Each check is a function; one that fails prints why and the command exits
non-zero.  The last one rehearses the cell on the CPU at the model's real
widths and a 64-token sequence (811 M parameters: a minute or two).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import attention_costs, confnet, harness, tokens  # noqa: E402
from benchmark.references import laguna_moe as R                 # noqa: E402

CELL = 'laguna-s21-ep32-seq8k'
TINY = os.path.join(ROOT, 'example', 'LM', 'tiny-laguna.conf')
DATA = {'successors': 4, 'p_likely': 0.9}
#: faults of the probe that 64 positions of a tiny model barely show: YaRN
#: by 8 over an original context of 32 changes three of four frequencies of
#: heads that turn 8 of 16 dims (on the chip the variant reads a mean of
#: 0.97 against bf16's 0.018); a sigmoid's weights over 16 experts of small
#: logits are near a softmax's once normalised over the chosen (0.053 there)
WEAK_AT_THE_TINY_SIZE = {'YaRN left out', 'sigmoid router'}


def _pairs(path):
    with open(path) as f:
        return confnet.drop_sections(confnet.parse_conf(f.read()),
                                     ('data', 'eval', 'pred'))


def check_flops_and_kernel_costs_by_hand():
    graph = R.build_graph(_pairs(os.path.join(BENCH, 'configs',
                                              'laguna-s21-ep32.conf')))
    s, d, hd = 8192, 3072, 128
    assert (graph.seq, graph.width, graph.vocab) == (s, d, 12544)
    full, window = s * (s + 1) // 2, 4_063_488

    def attn(heads, kept):
        return s * (d * heads * hd + 2 * d * 8 * hd + d * heads
                    + heads * hd * d) + kept * heads * 2 * hd
    expert = 3 * d * 1024
    moe = s * (d * 256 + expert) + (s * 10 * 8 / 256) * expert
    macs = R.forward_macs(graph)
    by_type = {}
    for l in graph.layers:
        if l.index in macs:
            by_type.setdefault(l.type, []).append(macs[l.index])
    assert by_type['gqa'] == [attn(48, full)] + [attn(72, window)] * 3 \
        + [attn(48, full)]
    assert by_type['moe'] == [moe] * 4
    assert by_type['swiglu'] == [s * 3 * d * 12288]
    assert by_type['lm_head_loss'] == [s * d * 12544]
    cfg = harness.load_json(BENCH, 'configs', 'laguna-s21-ep32.json')
    assert R.train_flops_per_sequence(graph) \
        == cfg['train_flops_per_sequence']          # 30.00 TFLOP a step
    # the kernels' pairs against pairs counted by a loop, at paper size and
    # at the published window
    for seq, w in ((40, 8), (40, 0), (6, 8), (8, 8), (s, 512)):
        counted = sum(1 for i in range(seq) for j in range(seq)
                      if j <= i and (not w or i - j < w)) if seq < 100 \
            else sum(min(i + 1, w) for i in range(seq))
        assert attention_costs.pairs(seq, w) == R.attended_pairs(seq, w) \
            == counted, (seq, w)
    assert attention_costs.pairs(s, 512) == window
    fwd = attention_costs.cost('fwd', s, 72, 8, hd, 512)
    assert fwd['flops'] == 2 * window * 72 * (hd + hd)
    assert fwd['bytes'] == s * hd * 2 * (2 * 72 + 2 * 8)
    dq = attention_costs.cost('dq', s, 48, 8, hd, 0)
    assert dq['flops'] == 2 * full * 48 * 3 * hd
    assert dq['bytes'] == s * hd * 2 * (3 * 48 + 2 * 8)
    dkv = attention_costs.cost('dkv', s, 48, 8, hd, 0)
    assert dkv['flops'] == 2 * full * 48 * 4 * hd
    assert dkv['bytes'] == s * hd * 2 * (2 * 48 + 4 * 8)
    # the three kernels' operations are the attention's share of the step's
    kernels = sum(attention_costs.cost(k, **attention_costs.layer_shape(
        graph, l))['flops'] for l in graph.of_type('gqa')
        for k in ('fwd', 'dq', 'dkv'))
    in_step = 6 * sum((full if not l.geti('window') else window)
                      * l.geti('nhead') * 2 * hd for l in graph.of_type('gqa'))
    assert kernels == in_step * 9 // 6          # 9 products, 6 of them counted
    assert abs(in_step - 6.3e12) < 0.1e12       # ISSUE 36's 6.3 of 30 TFLOP


def check_configuration_keeps_the_published_numbers():
    """Every number of the catalog's ``config`` is in the file under its
    key, the three cut ones named in ``reduced``, no width among them; the
    nested groups whole."""
    cfg = harness.load_json(BENCH, 'configs', 'laguna-s21-ep32.json')
    published = {
        'hidden_size': 3072, 'intermediate_size': 12288,
        'num_attention_heads': 48, 'num_key_value_heads': 8, 'head_dim': 128,
        'max_position_embeddings': 1048576, 'rms_norm_eps': 1e-06,
        'num_experts_per_tok': 10, 'moe_intermediate_size': 1024,
        'shared_expert_intermediate_size': 1024, 'decoder_sparse_step': 1,
        'sliding_window': 512, 'moe_routed_scaling_factor': 2.5,
        'moe_router_logit_softcapping': 0, 'mlp_only_layers': [0],
        'gating': 'per-head', 'norm_topk_prob': True,
        'attention_bias': False, 'tie_word_embeddings': False}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg['rope_parameters'] == {
        'full_attention': {
            'rope_theta': 500000, 'rope_type': 'yarn', 'factor': 128,
            'original_max_position_embeddings': 8192, 'beta_slow': 1,
            'beta_fast': 32, 'attention_factor': 1.4852030263919618,
            'partial_rotary_factor': 0.5},
        'sliding_attention': {'rope_type': 'default', 'rope_theta': 10000,
                              'partial_rotary_factor': 1}}
    period = ['full_attention'] + ['sliding_attention'] * 3
    assert cfg['layer_types'] == period * 12
    assert cfg['num_attention_heads_per_layer'] == [48, 72, 72, 72] * 12
    assert cfg['mlp_layer_types'] == ['dense'] + ['sparse'] * 47
    assert cfg['reduced'] == ['num_hidden_layers', 'num_experts',
                              'vocab_size']
    assert (cfg['num_hidden_layers'], cfg['num_experts'],
            cfg['vocab_size']) == (5, 8, 12544)
    assert cfg['published'] == {'num_hidden_layers': 48, 'num_experts': 256,
                                'vocab_size': 100352}
    graph = R.build_graph(_pairs(os.path.join(BENCH, 'configs',
                                              cfg['conf'])))
    attn, moe = graph.of_type('gqa'), graph.of_type('moe')
    run = cfg['layers_run']
    assert len(attn) == len(run) == 5 and len(moe) == 4
    full = cfg['rope_parameters']['full_attention']
    for l, i in zip(attn, run):
        sliding = cfg['layer_types'][i] == 'sliding_attention'
        assert l.geti('nhead') == cfg['num_attention_heads_per_layer'][i]
        assert l.geti('window') == (cfg['sliding_window'] if sliding else 0)
        assert (l.geti('nkvhead'), l.geti('head_dim')) == (8, 128)
        assert l.getf('eps', 0) == cfg['rms_norm_eps']
        if sliding:
            assert (l.geti('rotary_dims'), l.getf('rope_theta', 0),
                    l.getf('rope_factor', 1.0)) == (128, 10000, 1.0)
        else:
            assert (l.geti('rotary_dims'), l.getf('rope_theta', 0),
                    l.getf('rope_factor', 1.0),
                    l.geti('rope_original_positions'),
                    l.getf('rope_beta_fast', 0), l.getf('rope_beta_slow', 0),
                    l.getf('rope_attention_factor', 0)) == (
                64, full['rope_theta'], full['factor'],
                full['original_max_position_embeddings'], full['beta_fast'],
                full['beta_slow'], full['attention_factor'])
    assert all(l.geti('experts_published') == 256
               and l.geti('experts_held') == cfg['num_experts']
               and l.geti('experts_per_token') == cfg['num_experts_per_tok']
               and l.geti('nhidden') == cfg['moe_intermediate_size']
               and l.cfg['router_score'] == 'softmax'
               and l.getf('routed_scaling_factor', 0) == 2.5 for l in moe)
    assert graph.of_type('swiglu')[0].geti('nhidden') == 12288


def check_reference_against_program():
    """Float32 program = reference, forward and step; bf16 inside every
    limit; each variant of the sensitivity probe outside at least one, at
    the tiny size (the limits are set from the chip's readings at the
    cell's size: ``laguna_sensitivity.py``, PERF.md 6)."""
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
        sides = {what: R.reference_side(graph, tr.params, ids, got, v)
                 for what, v in {'as is': R.MODEL, **R.PROBE}.items()}
        step = R.program_step(tr, graph, ids)
        out = {}
        for what, side in sides.items():
            found, ok = R.judge(graph, side, step)
            n = side['numbers']['logits']
            out[what] = (n['largest'], n['mean'], n['median_position'],
                         n['loss'], found['loss'],
                         max(found['update'].values()), ok)
        return out

    head = 'largest / mean / median position / loss / step loss / update'
    exact = program('float32')
    e = exact.pop('as is')
    assert e[0] < 1e-3 and e[1] < 1e-4 and e[3] < 1e-5 and e[4] < 1e-5 \
        and e[5] < 1e-3 and e[6], e
    h = program('bfloat16')['as is']
    print(f'    {head}: float32 ' + ' / '.join(f'{v:.2e}' for v in e[:6])
          + ', bfloat16 ' + ' / '.join(f'{v:.2e}' for v in h[:6]))
    assert h[6], h
    inside = []
    for name, v in sorted(exact.items()):
        print(f'    {name}: ' + ' / '.join(f'{x:.2e}' for x in v[:6])
              + (' INSIDE' if v[6] else ''))
        if v[6]:
            inside.append(name)
        if name in WEAK_AT_THE_TINY_SIZE:
            # here the variant only has to stand well clear of bf16's
            # reading; that it leaves the limits is shown at the cell's size
            # on the chip (laguna_sensitivity.py; PERF.md 6)
            assert v[1] > 2 * h[1], (name, v[1], h[1])
    assert set(inside) <= WEAK_AT_THE_TINY_SIZE, inside
    jax.clear_caches()


def check_cell_rehearses():
    """The cell end to end on the CPU, untraced and traced: feed, taps,
    reference, window, readers.  A rehearsal, never a measurement."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    for traced in (0, 1):
        r = subprocess.run(
            [sys.executable, '-m', 'benchmark.run', '--workload', CELL,
             '--seed', '3000000019', '--seconds', '1', '--trace',
             str(traced), '--rehearse', '1'], cwd=ROOT, env=env,
            capture_output=True, text=True, timeout=1500)
        assert r.returncode == 0, r.stderr[-2000:] + r.stdout[-2000:]
        line = json.loads(r.stdout.strip().splitlines()[-1])
        assert line['rehearsal'] and line['attempted'] > 0, line
        assert line['failed'] == 0, line
        if traced:
            # no device plane on the CPU: the readers of a trace leave their
            # metrics out, the counters and the spans are there
            assert {'moe.local_assignment_share', 'moe.load_max_over_mean',
                    'moe.full_buffer_share', 'entry.compile_s', 'net.init_s',
                    'entry.backend_s', 'step.stage_setup_s'} \
                <= set(line['metrics']), line
            assert not {'net.gqa_full_ms_per_step',
                        'kernels.gqa_fwd_roofline_pct'} & set(line['metrics'])
        else:
            assert set(line['metrics']) == {'samples_per_s', 'setup_s'}


CHECKS = [check_flops_and_kernel_costs_by_hand,
          check_configuration_keeps_the_published_numbers,
          check_reference_against_program, check_cell_rehearses]


def main(argv) -> int:
    wanted = [c for c in CHECKS if not argv or c.__name__ in argv]
    failed = 0
    for check in wanted:
        print(f'selftest.laguna: {check.__name__} ...', flush=True)
        try:
            check()
        except Exception:                  # a check's failure is the report
            failed += 1
            traceback.print_exc()
            print(f'selftest.laguna: {check.__name__} FAILED', flush=True)
    print(f'selftest.laguna: {len(wanted) - failed} of {len(wanted)} passed')
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
