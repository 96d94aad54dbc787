"""``step.dispatch_ms_p50`` - LAYER step loop (``nnet/execution.py``,
``trainer.update_staged``); UNIT ms; MOVES ``samples_per_s``, and only once
it nears the device's step; staged cells.

Median, over the window, of the host time of one dispatch: the harness's
span around ``update_staged``, which is where ``WindowedStepper`` puts the
program's ``train.dispatch`` span.  The staged mixes set ``eval_train = 0``,
so no readback of the previous step sits inside it; in a mix that scores the
train metrics it holds that wait and the scoring as well."""

from benchmark.harness import median

LAYER, UNIT, MOVES = 'step', 'ms', 'samples_per_s'


def read(run):
    w = run.window
    return median(run.spans.durations_ms('step.dispatch', w.t_open_ns,
                                         w.t_close_ns))
