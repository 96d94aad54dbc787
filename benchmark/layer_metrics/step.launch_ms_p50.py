"""``step.launch_ms_p50`` - LAYER step loop (``trainer.update_staged``); UNIT
ms; MOVES ``samples_per_s``, and only once it nears the device's step; staged
cells.

Median of the program's ``train.launch`` spans that began inside the timed
window: the call of the jitted step program alone (argument flattening, the
enqueue), inside ``update_staged``.  ``step.dispatch_ms_p50`` times the whole
of ``update_staged`` from outside, so this nests inside it and the difference
is what the method does around the call (the eager ``fold_in`` of the step's
key, the loss gate, the train metrics' bookkeeping)."""

from benchmark import program_spans
from benchmark.harness import median

LAYER, UNIT, MOVES = 'step', 'ms', 'samples_per_s'


def read(run):
    found = program_spans.spans(run, 'train.launch', in_window=True)
    return None if found is None else median([d / 1e6 for _, d in found])
