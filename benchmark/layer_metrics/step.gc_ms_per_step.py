"""``step.gc_ms_per_step`` - LAYER step loop; UNIT ms; MOVES
``samples_per_s``; staged cells.

Milliseconds a step that Python's collector held the process over the timed
window: ``gc_ns`` of the window's last ``train.dispatch`` record less its
first's, over the steps between.  The hub's total counts every collection on
every thread (``gc.callbacks``), the young generation's too."""

from benchmark import step_records

LAYER, UNIT, MOVES = 'step', 'ms', 'samples_per_s'


def read(run):
    found = step_records.dispatches(run)
    if found is None:
        return None
    first, last = found[0]['attrs'], found[-1]['attrs']
    return (last['gc_ns'] - first['gc_ns']) / 1e6 / (len(found) - 1)
