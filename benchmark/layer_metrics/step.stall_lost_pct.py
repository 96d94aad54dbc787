"""``step.stall_lost_pct`` - LAYER step loop; UNIT %; MOVES ``samples_per_s``;
staged cells.

The share of the timed window's wall the device stood idle for steps
dispatched late, **as the host's clock lets one estimate it**: it is worked
out from the start-to-start intervals of ``train.dispatch``, not from a
device trace (the timed window has none).  For every interval over 1.5
medians: the sum of (interval - median) over it and the next ``max_inflight``
intervals (the cell's traffic parameter: the steps the harness keeps in
flight), floored at nought, each interval in one sum only; all such sums over
the window's wall.  A stall that the queue of steps in flight absorbed is made
good by the short intervals after it, while the host catches up, and reads
nought; one that ran the queue dry reads what the device stood idle.  Where
the window closes before the ``max_inflight`` intervals after a stall are out,
those still to come count as nought, the shortest they can be: the least the
device can have lost, so a stall the queue absorbed reads nought there too."""

from benchmark import step_records
from benchmark.harness import median

LAYER, UNIT, MOVES = 'step', '%', 'samples_per_s'


def read(run):
    iv = step_records.intervals_ms(run)
    if iv is None:
        return None
    mid, span = median(iv), 1 + int(run.cell.t('max_inflight'))
    lost, i = 0.0, 0
    while i < len(iv):
        if iv[i] > 1.5 * mid:
            lost += max(0.0, sum(iv[i:i + span]) - span * mid)
            i += span
        else:
            i += 1
    return 100.0 * lost / (run.window.wall_s * 1e3)
