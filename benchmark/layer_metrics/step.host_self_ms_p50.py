"""``step.host_self_ms_p50`` - LAYER step loop (``trainer.update_staged``); UNIT
ms; MOVES ``samples_per_s``, and only once it nears the device's step; staged
cells.

Median over the timed window of the program's ``train.dispatch`` span less the
``train.launch`` inside it: the step loop's own host code around the jitted
call (the accumulator sync, the key's ``fold_in``, the loss gate, the
listeners, the step record itself).  ``step.launch_ms_p50`` is the other
part; the two add up to the harness's ``step.dispatch`` span."""

from benchmark import step_records
from benchmark.harness import median

LAYER, UNIT, MOVES = 'step', 'ms', 'samples_per_s'


def read(run):
    outer = step_records.dispatches(run)
    inner = step_records.events(run, 'train.launch')
    if outer is None or not inner:
        return None
    own, j = [], 0
    for e in outer:
        t0, t1 = e['t_start_ns'], e['t_start_ns'] + e['dur_ns']
        while j < len(inner) and inner[j]['t_start_ns'] < t0:
            j += 1
        held = 0
        while j < len(inner) and inner[j]['t_start_ns'] < t1:
            held += inner[j]['dur_ns']
            j += 1
        own.append((e['dur_ns'] - held) / 1e6)
    return median(own)
