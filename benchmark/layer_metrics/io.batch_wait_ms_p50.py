"""``io.batch_wait_ms_p50`` - LAYER input pipeline (``io/``,
``utils/thread_buffer``); UNIT ms; MOVES ``fed_samples_per_s``; fed cells.

Median, over the window, of the harness's span around ``next()`` on the
program's train iterator: how long the step loop waited for a batch."""

from benchmark.harness import median

LAYER, UNIT, MOVES = 'io', 'ms', 'fed_samples_per_s'


def read(run):
    w = run.window
    return median(run.spans.durations_ms('io.wait', w.t_open_ns,
                                         w.t_close_ns))
