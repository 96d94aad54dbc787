"""``step.interval_max_over_p50`` - LAYER step loop; UNIT x; MOVES
``samples_per_s``; staged cells.

The longest start-to-start interval between two ``train.dispatch`` spans of
the timed window over the median one.  In a closed loop the interval is the
device's step, so a quiet run reads 1.00-1.06 (up to 1.2 where a step's cost
depends on its data: an expert layer that takes its overflow branch); one
stall of the host in the window reads 2.5 and more, and the program's own
``train.stall`` line says of what kind."""

from benchmark import step_records
from benchmark.harness import median

LAYER, UNIT, MOVES = 'step', 'x', 'samples_per_s'


def read(run):
    iv = step_records.intervals_ms(run)
    return None if iv is None else max(iv) / median(iv)
