"""``step.stage_ms_p50`` - LAYER step loop (``nnet/trainer.stage_batch``);
UNIT ms; MOVES ``fed_samples_per_s``; fed cells.

Median, over the window, of the harness's span around ``stepper.feed`` less
the program's own ``train.dispatch`` spans that began inside it: the host
cast and the enqueue of the transfer."""

from benchmark import cxx
from benchmark.harness import median

LAYER, UNIT, MOVES = 'step', 'ms', 'fed_samples_per_s'


def read(run):
    w = run.window
    stages = [(s, e) for n, s, e in run.spans.rows
              if n == 'step.stage' and w.t_open_ns <= s < w.t_close_ns]
    inner = cxx.hub_spans('train.dispatch', w.t_open_ns, w.t_close_ns)
    return median([(e - s - sum(d for t, d in inner if s <= t < e)) / 1e6
                   for s, e in stages])
