"""How sharp the comparison with the plain reference is, on the chip:

    python3 -m benchmark.selftest.sensitivity --workload alexnet-staged

Trains the cell ``--steps`` steps, then prints the program's error against
the reference (what a run's ``correct`` rests on) beside the error of a
reference with every LRN layer left out, and of one with the biases zeroed.
The tolerance in ``references/confnet.py`` has to lie between the first and
the other two.  Not part of a run; PERF.md records what it printed.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

from .. import cxx, harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--steps', type=int, default=100)
    ap.add_argument('--rehearse', type=int, default=0)
    args = ap.parse_args(argv)
    from cxxnet_tpu.utils.backend import enable_compile_cache, require_chip
    enable_compile_cache()
    if require_chip() != 'tpu' and not args.rehearse:
        raise SystemExit('sensitivity: not on a TPU')
    cell = harness.load_cell(args.workload, bool(args.rehearse))
    feed = harness.load_module('feeds', cell.traffic['feed']).Feed(
        cell, args.seed, harness.Spans())
    ref = harness.load_module('references', cell.config['reference'])
    for _ in range(args.steps):
        feed.advance()
    graph, nodes = feed.graph, feed.graph.loss_nodes()
    data = ref.check_batch(feed, cell, args.seed)
    params = cxx.host_params(feed.trainer)
    got = cxx.eval_outputs(feed.trainer, data, nodes)
    no_bias = {k: {f: (np.zeros_like(v) if f == 'bias' else v)
                   for f, v in d.items()} for k, d in params.items()}
    rows = {'as is': ref.forward(graph, params, data),
            'LRN left out': ref.forward(graph, params, data, skip=('lrn',)),
            'biases zeroed': ref.forward(graph, no_bias, data)}
    for what, want in rows.items():
        errs = {n: round(ref.log_prob_error(got[n], want[n]), 4)
                for n in nodes}
        print(f'sensitivity: {args.workload} after {args.steps} steps, '
              f'reference {what}: {errs} (tolerance {ref.TOLERANCE})',
              flush=True)
    feed.close()
    return 0


if __name__ == '__main__':
    sys.exit(main())
