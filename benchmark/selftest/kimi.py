"""The checks of the ``kimi-linear-ep32`` configuration and its cell, none
of which needs the chip (``__main__.py``'s and the other selftests' are
those of the rest):

    JAX_PLATFORMS=cpu python3 -m benchmark.selftest.kimi

Each check is a function; one that fails prints why and the command exits
non-zero.  The last one rehearses the cell on the CPU with the tiny twin's
conf in the place of the configuration's (the real widths hold 602 M
parameters, some 10 GB of host memory in a rehearsal).
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

from benchmark import (confnet, harness, kda_costs, kernel_costs,  # noqa: E402
                       mla_costs, tokens)
from benchmark.references import kimi_linear as R              # noqa: E402

CELL = 'kimi-linear-ep32-seq8k'
CONFIG = 'kimi-linear-ep32'
TINY = os.path.join(ROOT, 'example', 'LM', 'tiny-kimi.conf')
DATA = {'successors': 4, 'p_likely': 0.9}


def _pairs(path):
    with open(path) as f:
        return confnet.drop_sections(confnet.parse_conf(f.read()),
                                     ('data', 'eval', 'pred'))


def check_flops_and_costs_by_hand():
    graph = R.build_graph(_pairs(os.path.join(BENCH, 'configs',
                                              CONFIG + '.conf')))
    s, d, nh, hd = 8192, 2304, 32, 128
    w = nh * hd
    assert (graph.seq, graph.width, graph.vocab) == (s, d, 20480)
    macs = R.forward_macs(graph)
    # a delta layer: q, k, v, the two low-rank pairs, beta, W_o, then the
    # rule at chunks of 64 (kda_costs, checked by hand in selftest.solar)
    kda = s * (3 * d * w + 2 * (d * hd + hd * w) + d * nh + w * d) \
        + kda_costs.recurrence_macs(s, nh, hd, hd)
    assert [macs[l.index] for l in graph.of_type('kda')] == [kda] * 4
    # the latent layer: the full-rank query (2304 x 32 x 192), W_kva (2304
    # x 576), W_kvb (512 x 32 x 256), W_o (4096 x 2304); 192 + 128 a pair
    (mla,) = graph.of_type('mla')
    pairs = s * (s + 1) // 2
    proj = d * nh * 192 + d * 576 + 512 * nh * 256 + w * d
    assert macs[mla.index] == s * proj + pairs * nh * (192 + 128)
    (ffn,) = graph.of_type('swiglu')
    assert macs[ffn.index] == s * 3 * d * 9216
    for l in graph.of_type('moe'):     # a balanced router's 1/32 here
        assert macs[l.index] == s * d * 256 + s * 8 * 8 / 256 * 3 * d \
            * 1024 + s * 3 * d * 1024
    cfg = harness.load_json(BENCH, 'configs', CONFIG + '.json')
    assert R.train_flops_per_sequence(graph) \
        == cfg['train_flops_per_sequence']          # 19.12 TFLOP a step
    assert abs(sum(macs.values()) / s - 389.08e6) < 0.01e6
    # the three kernels' numerators at 192 / 128, whatever the padding
    for kernel, products, arrays in (('fwd', 192 + 128, 2 * 192 + 2 * 128),
                                     ('dq', 2 * 192 + 128, 3 * 192 + 2 * 128),
                                     ('dkv', 2 * 192 + 2 * 128,
                                      3 * 192 + 3 * 128)):
        one = mla_costs.cost(kernel, graph, mla)
        assert one == {'flops': 2 * pairs * nh * products,
                       'bytes': s * nh * arrays * 2}, kernel
        assert mla_costs.COSTS[kernel] is getattr(
            kernel_costs, 'flash_attention' + ('' if kernel == 'fwd'
                                               else '_' + kernel))


def check_configuration_keeps_the_published_numbers():
    """Every number of the catalog's ``config`` is in the file under its
    key, the cut ones named in ``reduced``, no width among them; the nested
    group whole; the conf's layers as the file says."""
    cfg = harness.load_json(BENCH, 'configs', CONFIG + '.json')
    published = {
        'first_k_dense_replace': 1, 'head_dim': 72, 'hidden_act': 'silu',
        'hidden_size': 2304, 'intermediate_size': 9216, 'kv_lora_rank': 512,
        'mla_use_nope': True, 'model_max_length': 1048576,
        'model_type': 'kimi_linear', 'moe_intermediate_size': 1024,
        'moe_layer_freq': 1, 'moe_renormalize': True,
        'moe_router_activation_func': 'sigmoid', 'num_attention_heads': 32,
        'num_expert_group': 1, 'num_experts_per_token': 8,
        'num_key_value_heads': 32, 'num_nextn_predict_layers': 0,
        'num_shared_experts': 1, 'q_lora_rank': None,
        'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64,
        'rms_norm_eps': 1e-05, 'rope_scaling': None, 'rope_theta': 10000,
        'routed_scaling_factor': 2.446, 'tie_word_embeddings': False,
        'topk_group': 1, 'use_grouped_topk': True, 'v_head_dim': 128}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg['linear_attn_config'] == {
        'full_attn_layers': [4, 8, 12, 16, 20, 24, 27], 'head_dim': 128,
        'kda_layers': [i for i in range(1, 27) if i % 4], 'num_heads': 32,
        'short_conv_kernel_size': 4}
    assert cfg['reduced'] == ['num_hidden_layers', 'num_experts',
                              'vocab_size']
    assert (cfg['num_hidden_layers'], cfg['num_experts'],
            cfg['vocab_size']) == (5, 8, 20480)
    assert cfg['published'] == {'num_hidden_layers': 27, 'num_experts': 256,
                                'vocab_size': 163840}
    graph = R.build_graph(_pairs(os.path.join(BENCH, 'configs',
                                              cfg['conf'])))
    lac = cfg['linear_attn_config']
    mixers = [l for l in graph.layers if l.type in ('mla', 'kda')]
    assert [l.type for l in mixers] == [
        'mla' if i + 1 in lac['full_attn_layers'] else 'kda'
        for i in cfg['layers_run']]
    assert [l.type for l in graph.layers if l.type in ('swiglu', 'moe')] \
        == ['swiglu'] * cfg['first_k_dense_replace'] + ['moe'] * (
            len(cfg['layers_run']) - cfg['first_k_dense_replace'])
    (m,) = graph.of_type('mla')
    assert (m.geti('nhead'), m.geti('q_lora_rank'), m.geti('use_rope'),
            m.geti('kv_lora_rank'), m.geti('qk_nope_head_dim'),
            m.geti('qk_rope_head_dim'), m.geti('v_head_dim')) == (
        32, 0, 0, 512, 128, 64, 128)
    for l in graph.of_type('kda'):
        assert (l.geti('nhead'), l.geti('head_dim'),
                l.geti('allow_neg_eigval', 1)) == (32, 128, 0)
    assert graph.of_type('swiglu')[0].geti('nhidden') == 9216
    for l in graph.layers:
        if l.type in ('mla', 'kda', 'moe', 'rmsnorm', 'swiglu'):
            assert l.getf('eps', 0) == cfg['rms_norm_eps']
    moe = graph.of_type('moe')
    assert len(moe) == 4 and all(
        l.geti('experts_published') == 256
        and l.geti('experts_held') == cfg['num_experts']
        and l.geti('experts_per_token') == cfg['num_experts_per_token']
        and l.geti('nhidden') == cfg['moe_intermediate_size']
        and l.geti('shared_experts') == cfg['num_shared_experts']
        and l.cfg.get('router_score', 'sigmoid') == 'sigmoid'
        and l.getf('routed_scaling_factor', 0) == 2.446 for l in moe)


def check_reference_against_program():
    """Float32 program = reference: forward, delta rule and step; bf16
    inside every limit; each variant of the sensitivity probe outside at
    least one, at the tiny size (the limits are set from the chip's readings
    at the cell's size: ``kimi_sensitivity.py``, PERF.md 6)."""
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
                         max(found['kda_update'].values()),
                         max(found['mla_update'].values()), ok)
        return out

    head = ('largest / mean / median position / loss / delta rule / step '
            'loss / update / delta layer update / latent layer update')
    exact = program('float32')
    e = exact.pop('as is')
    assert e[0] < 1e-3 and e[1] < 1e-4 and e[3] < 1e-5 and e[4] < 1e-5 \
        and e[5] < 1e-5 and e[6] < 1e-3 and e[7] < 1e-3 and e[8] < 1e-3 \
        and e[9], e
    h = program('bfloat16')['as is']
    print(f'    {head}: float32 ' + ' / '.join(f'{v:.2e}' for v in e[:9])
          + ', bfloat16 ' + ' / '.join(f'{v:.2e}' for v in h[:9]))
    inside = []
    for name, v in sorted(exact.items()):
        print(f'    {name}: ' + ' / '.join(f'{x:.2e}' for x in v[:9])
              + (' INSIDE' if v[9] else ''))
        if v[9]:
            inside.append(name)
    assert h[9], h
    assert not inside, inside
    jax.clear_caches()


def check_cell_rehearses():
    """The cell end to end on the CPU, untraced and traced: feed, taps,
    reference, window, readers, in a copy of the tree whose configuration
    conf is the tiny twin's.  A rehearsal, never a measurement."""
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=ROOT)
    with tempfile.TemporaryDirectory(prefix='selftest_kimi_') as tmp:
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
                        'net.init_s', 'step.stage_setup_s',
                        'step.host_self_ms_p50'} <= set(line['metrics'])
                assert not {'net.kda_ms_per_step', 'net.mla_ms_per_step',
                            'kernels.mla_fwd_roofline_pct',
                            'kernels.mla_dq_roofline_pct',
                            'kernels.mla_dkv_roofline_pct'} \
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
        print(f'selftest.kimi: {check.__name__} ...', flush=True)
        try:
            check()
        except Exception:                  # a check's failure is the report
            failed += 1
            traceback.print_exc()
            print(f'selftest.kimi: {check.__name__} FAILED', flush=True)
    print(f'selftest.kimi: {len(wanted) - failed} of {len(wanted)} passed')
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
