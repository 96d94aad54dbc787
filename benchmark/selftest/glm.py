"""The checks of what PR 29 added to the benchmark, none of which needs the
chip (``__main__.py``'s eight stay as they are; this file is theirs for the
language-model cell):

    JAX_PLATFORMS=cpu python3 -m benchmark.selftest.glm

Each check is a function; one that fails prints why and the command exits
non-zero.  The last one rehearses the cell on the CPU at the model's real
widths and a 64-token sequence (706 M parameters: a minute or two).
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

from benchmark import confnet, harness, kernel_costs, tokens  # noqa: E402
from benchmark.references import glm_moe_lite as R             # noqa: E402

CELL = 'glm47flash-ep8-seq8k'
TINY = os.path.join(ROOT, 'example', 'LM', 'tiny-glm.conf')


def _graph(path):
    with open(path) as f:
        return R.build_graph(confnet.drop_sections(
            confnet.parse_conf(f.read()), ('data', 'eval', 'pred')))


def check_flops_by_hand():
    graph = _graph(os.path.join(BENCH, 'configs', 'glm47flash-ep8.conf'))
    s, d = 8192, 2048
    assert (graph.seq, graph.width, graph.vocab) == (s, d, 19360)
    attn = s * (d * 768 + 768 * 20 * 256 + d * 576 + 512 * 20 * 448
                + 20 * 256 * d) + (s * (s + 1) // 2) * 20 * 512
    expert = 3 * d * 1536
    moe = s * (d * 64 + expert) + (s * 4 * 8 / 64) * expert
    macs = R.forward_macs(graph)
    by_type = {}
    for l in graph.layers:
        if l.index in macs:
            by_type.setdefault(l.type, []).append(macs[l.index])
    assert by_type['mla'] == [attn] * 6 and by_type['moe'] == [moe] * 5
    assert by_type['swiglu'] == [s * 3 * d * 10240]
    assert by_type['lm_head_loss'] == [2 * s * d * 19360]
    assert by_type['mtp_join'] == [s * 2 * d * d]
    cfg = harness.load_json(BENCH, 'configs', 'glm47flash-ep8.json')
    assert R.train_flops_per_sequence(graph) \
        == cfg['train_flops_per_sequence']          # 29.70 TFLOP a step
    # the kernels' own counts, against the same hand counts
    fwd = kernel_costs.flash_attention(s, 20, 256, 256)
    assert fwd['flops'] == 2 * (s * (s + 1) // 2) * 20 * 512
    one = kernel_costs.grouped_product(4096, 2048, 1536, 8)
    assert one['flops'] == 2 * 4096 * 2048 * 1536


def check_configuration_keeps_the_published_numbers():
    """Every number of the catalog's ``config`` is in the file under its
    key, the three cut ones named in ``reduced``, no width among them."""
    cfg = harness.load_json(BENCH, 'configs', 'glm47flash-ep8.json')
    published = {
        'hidden_size': 2048, 'intermediate_size': 10240,
        'moe_intermediate_size': 1536, 'num_attention_heads': 20,
        'num_key_value_heads': 20, 'n_shared_experts': 1,
        'routed_scaling_factor': 1.8, 'num_experts_per_tok': 4,
        'first_k_dense_replace': 1, 'num_nextn_predict_layers': 1,
        'q_lora_rank': 768, 'kv_lora_rank': 512, 'qk_nope_head_dim': 192,
        'qk_rope_head_dim': 64, 'v_head_dim': 256, 'rope_theta': 1000000,
        'rms_norm_eps': 1e-05, 'n_group': 1, 'topk_group': 1,
        'max_position_embeddings': 202752, 'partial_rotary_factor': 1}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg['reduced'] == ['num_hidden_layers', 'n_routed_experts',
                              'vocab_size']
    assert (cfg['num_hidden_layers'], cfg['n_routed_experts'],
            cfg['vocab_size']) == (5, 8, 19360)
    assert cfg['published'] == {'num_hidden_layers': 47,
                                'n_routed_experts': 64,
                                'vocab_size': 154880,
                                'num_nextn_predict_layers': 1}
    graph = _graph(os.path.join(BENCH, 'configs', cfg['conf']))
    attn, moe = graph.of_type('mla'), graph.of_type('moe')
    assert len(attn) == 6 and len(moe) == 5      # 1 + 4 layers and the MTP's
    assert all(l.geti('experts_published') == 64
               and l.geti('experts_held') == cfg['n_routed_experts']
               and l.geti('experts_per_token') == cfg['num_experts_per_tok']
               and l.geti('nhidden') == cfg['moe_intermediate_size']
               for l in moe)
    assert all((l.geti('nhead'), l.geti('q_lora_rank'),
                l.geti('kv_lora_rank'), l.geti('qk_nope_head_dim'),
                l.geti('qk_rope_head_dim'), l.geti('v_head_dim'))
               == (20, 768, 512, 192, 64, 256) for l in attn)


def check_reference_against_program():
    """Float32 program = reference, forward and step; bf16 inside every
    limit; each variant of the sensitivity probe outside at least one (the
    limits are set from the chip's readings at the cell's size; one variant
    is too weak at the tiny size to leave them, and is held to less here)."""
    import jax
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer
    with open(TINY) as f:
        pairs = confnet.drop_sections(confnet.parse_conf(f.read()),
                                      ('data', 'eval', 'pred'))
    graph = R.build_graph(pairs)
    ids = tokens.token_rows(11, 2, graph.seq + 2, graph.vocab,
                            {'successors': 4, 'p_likely': 0.9})
    batch = DataBatch(ids[:, None, None, :graph.seq + 1],
                      np.zeros((2, 2 * graph.seq), np.float32))
    ring = [tokens.token_rows(100 + i, 2, graph.seq + 2, graph.vocab,
                              {'successors': 4, 'p_likely': 0.9})
            for i in range(4)]

    def program(compute):
        """-> {what: (largest, mean, loss, step loss, largest update,
        inside?)} for the reference as it is and each of its variants"""
        tr = NetTrainer(pairs + [('compute_type', compute), ('seed', '5')])
        tr.init_model()
        # a few steps first, as a run's comparison comes after its window:
        # from zero moments Adam's first change is the gradient's sign
        # alone, and bf16 flips the sign of the smallest entries
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
            heads = side['numbers'].values()
            out[what] = (max(n['largest'] for n in heads),
                         max(n['mean'] for n in heads),
                         max(n['loss'] for n in heads), found['loss'],
                         max(found['update'].values()), ok)
        return out

    exact = program('float32')
    e_max, e_mean, e_loss, e_step, e_update, _ = exact.pop('as is')
    assert e_max < 1e-3 and e_mean < 1e-4 and e_loss < 1e-5, (e_max, e_mean)
    assert e_step < 1e-5 and e_update < 1e-3, (e_step, e_update)
    h_max, h_mean, h_loss, h_step, h_update, h_ok = \
        program('bfloat16')['as is']
    assert h_ok, (h_max, h_mean, h_loss, h_step, h_update)
    print(f'    float32 {e_max:.2e} / {e_mean:.2e} / {e_loss:.2e} / '
          f'{e_step:.2e} / {e_update:.2e}, bfloat16 {h_max:.4f} / '
          f'{h_mean:.5f} / {h_loss:.2e} / {h_step:.2e} / {h_update:.4f} '
          f'(largest / mean / loss / step loss / largest update; limits '
          f'{R.TOLERANCE} / {R.MEAN_TOLERANCE} / {R.LOSS_TOLERANCE} / '
          f'{R.STEP_LOSS_TOLERANCE} / {R.UPDATE_TOLERANCE})')
    for name, (v_max, v_mean, v_loss, v_step, v_update, v_ok) in sorted(
            exact.items()):
        print(f'    {name}: {v_max:.3f} / {v_mean:.4f} / {v_loss:.2e} / '
              f'{v_step:.2e} / {v_update:.4f}')
        if name == 'rotary left out':
            # 32 positions of a tiny model barely tell positions apart:
            # here the variant only has to stand well clear of bf16's
            # reading; that it leaves the limits is shown at the cell's
            # size on the chip (glm_sensitivity.py; PERF.md 6: 0.245
            # against 0.014)
            assert v_mean > 2.5 * h_mean, (v_mean, h_mean)
        else:
            assert not v_ok, name
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
            assert {'moe.local_assignment_share', 'moe.load_max_over_mean',
                    'entry.compile_s', 'net.init_s', 'entry.backend_s',
                    'step.stage_setup_s'} <= set(line['metrics']), line
        else:
            assert set(line['metrics']) == {'samples_per_s', 'setup_s'}


CHECKS = [check_flops_by_hand,
          check_configuration_keeps_the_published_numbers,
          check_reference_against_program, check_cell_rehearses]


def main(argv) -> int:
    wanted = [c for c in CHECKS if not argv or c.__name__ in argv]
    failed = 0
    for check in wanted:
        print(f'selftest.glm: {check.__name__} ...', flush=True)
        try:
            check()
        except Exception:                  # a check's failure is the report
            failed += 1
            traceback.print_exc()
            print(f'selftest.glm: {check.__name__} FAILED', flush=True)
    print(f'selftest.glm: {len(wanted) - failed} of {len(wanted)} passed')
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
