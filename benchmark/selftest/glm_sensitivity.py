"""How sharp the comparison with the plain reference is for a sequence cell,
on the chip:

    python3 -m benchmark.selftest.glm_sensitivity --workload glm47flash-ep8-seq8k

Trains the cell ``--steps`` steps, takes the program's evaluation-mode
probabilities of both heads on the check sequence once and puts that
sequence through one real training step, and prints the comparison's numbers
against the reference as it is (what a run's ``correct`` rests on) and
against each variant of the reference's probe
(``references/glm_moe_lite.PROBE``: the shared expert left out,
``routed_scaling_factor`` 1, rotary positions left out, top-3 routing, every
product's operands rounded to float8, the nearest precision below the bf16
the configuration states; and, for the step alone, an eighth of the tokens
dropped from the loss and the loss over every other token).  The limits have
to hold the first and refuse each of the others.  Not part of a run; PERF.md records what it printed.
"""

from __future__ import annotations

import argparse
import json
import sys

from .. import cxx, harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--steps', type=int, default=0)
    ap.add_argument('--rehearse', type=int, default=0)
    args = ap.parse_args(argv)
    from cxxnet_tpu.utils.backend import enable_compile_cache, require_chip
    enable_compile_cache()
    if require_chip() != 'tpu' and not args.rehearse:
        raise SystemExit('glm_sensitivity: not on a TPU')
    cell = harness.load_cell(args.workload, bool(args.rehearse))
    feed = harness.load_module('feeds', cell.traffic['feed']).Feed(
        cell, args.seed, harness.Spans())
    ref = harness.load_module('references', cell.config['reference'])
    for _ in range(args.steps):
        feed.advance()
    graph = feed.graph
    ids = ref.check_ids(graph, cell, args.seed)
    data = ids[:, None, None, :graph.seq + 1]
    got = {n: g.reshape(len(ids), graph.seq, -1) for n, g in
           cxx.eval_outputs(feed.trainer, data, graph.loss_nodes()).items()}
    # every variant's side first: the program's step moves the parameters
    sides = {what: ref.reference_side(graph, feed.trainer.params, ids, got,
                                      variant)
             for what, variant in {'as is': ref.MODEL, **ref.PROBE}.items()}
    step = ref.program_step(feed.trainer, graph, ids)
    for what, side in sides.items():
        found, ok = ref.judge(graph, side, step)
        print(f'sensitivity: {args.workload} seed {args.seed} after '
              f'{args.steps} steps, reference {what}: '
              f'{"INSIDE" if ok else "outside"} the limits; near ties '
              f'{side["tie_share"]:.4f}; step {json.dumps(found)}; '
              + json.dumps({n: {k: (round(v, 5) if isinstance(v, float)
                                    else v) for k, v in d.items()}
                            for n, d in side['numbers'].items()}),
              flush=True)
    feed.close()
    return 0


if __name__ == '__main__':
    sys.exit(main())
