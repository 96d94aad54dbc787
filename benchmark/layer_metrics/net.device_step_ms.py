"""``net.device_step_ms`` - LAYER net/layers (``nnet/net.py``, ``layers/*``);
UNIT ms; MOVES ``samples_per_s``; staged cells.

From the trace, device 0: the time in which an operation ran inside one
execution of the step program, median over the traced steps.  The four-chip
cell's against the one-chip cell's is what the mesh adds to the step: LRN on
XLA in place of the Mosaic kernels, and the exposed collectives."""

from benchmark.harness import median

LAYER, UNIT, MOVES = 'net', 'ms', 'samples_per_s'


def read(run):
    return median(run.trace.step_busy_ms) if run.trace else None
