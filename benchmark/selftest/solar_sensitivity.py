"""How sharp the comparison with the plain reference is for the
``solar-open2-ep40tp4`` cell, on the chip:

    python3 -m benchmark.selftest.solar_sensitivity [--seed n] [--steps n]
        [--probe name ...]

Trains the cell ``--steps`` steps, takes the program's evaluation-mode
probabilities on the check sequence once, puts that sequence through one
real training step, and prints the comparison's numbers against the
reference as it is and against each variant of its probe
(``references/solar_open2.PROBE``, or the ``--probe`` names alone): ``beta``
up to 1 (no negative eigenvalue), the short convolution left out, the delta
rule without its erase, the delta layer's gate left out, rotary in the
softmax layer, that layer's gate left out, a softmax router, the delta rule
as a bfloat16 kernel would run it (its products' operands and the state it
carries between chunks rounded), the delta rule fed bfloat16 ``q``, ``k``,
``v`` (both where the configuration states float32), every product's
operands rounded to float8 (the nearest precision below the bf16 the
configuration states for them); and, for the step alone, an eighth of the
tokens dropped from the loss, the loss over every other token, no gradient
through the delta rule.  Last, a fault of the program itself: the same step
with the last delta layer's leaves left as they were.  The limits have to
hold the reference as it is and refuse each of the others.  Not part of a
run; PERF.md records what it printed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from .. import cxx, harness

CELL = 'solar-open2-ep40tp4-seq8k'


def _short(x):
    if isinstance(x, float):
        return float(f'{x:.5g}')
    if isinstance(x, dict):
        return {k: _short(v) for k, v in x.items()}
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', default=CELL)
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--steps', type=int, default=0)
    ap.add_argument('--probe', action='append', default=[])
    ap.add_argument('--rehearse', type=int, default=0)
    args = ap.parse_args(argv)
    from cxxnet_tpu.utils.backend import enable_compile_cache, require_chip
    enable_compile_cache()
    if require_chip() != 'tpu' and not args.rehearse:
        raise SystemExit('solar_sensitivity: not on a TPU')
    cell = harness.load_cell(args.workload, bool(args.rehearse))
    feed = harness.load_module('feeds', cell.traffic['feed']).Feed(
        cell, args.seed, harness.Spans())
    ref = harness.load_module('references', cell.config['reference'])
    probe = {k: v for k, v in ref.PROBE.items()
             if not args.probe or k in args.probe}
    if set(args.probe) - set(probe):
        raise SystemExit(f'solar_sensitivity: no probe '
                         f'{sorted(set(args.probe) - set(probe))}')
    for _ in range(args.steps):
        feed.advance()
    graph = feed.graph
    ids = ref.check_ids(graph, cell, args.seed)
    data = ids[:, None, None, :graph.seq + 1]
    got = {n: g.reshape(len(ids), graph.seq, -1) for n, g in
           cxx.eval_outputs(feed.trainer, data, graph.loss_nodes()).items()}
    # every variant's side first: the program's step moves the parameters
    sides = {}
    for what, variant in {'as is': ref.MODEL, **probe}.items():
        t0 = time.perf_counter()
        sides[what] = ref.reference_side(graph, feed.trainer.params, ids,
                                         got, variant)
        print(f'sensitivity: reference {what} in '
              f'{time.perf_counter() - t0:.1f} s', flush=True)
    step = ref.program_step(feed.trainer, graph, ids)
    last = ref.kda_tail(graph)[0].primary
    frozen = dict(step, after={k: step['w'][k] if k[0] == last else a
                               for k, a in step['after'].items()})
    runs = [(what, side, step) for what, side in sides.items()]
    runs.append(('as is, the program\'s last delta layer frozen',
                 sides['as is'], frozen))
    for what, side, s in runs:
        found, ok = ref.judge(graph, side, s)
        print(f'sensitivity: {args.workload} seed {args.seed} after '
              f'{args.steps} steps, reference {what}: '
              f'{"INSIDE" if ok else "outside"} the limits; near ties '
              f'{side["tie_share"]:.4f}; delta rule '
              f'{json.dumps(_short(side["recurrence"]))}; step '
              f'{json.dumps(_short(found))}; '
              + json.dumps(_short(side['numbers'])), flush=True)
    import jax
    print(f'sensitivity: device memory {jax.devices()[0].memory_stats()}',
          flush=True)
    feed.close()
    return 0


if __name__ == '__main__':
    sys.exit(main())
