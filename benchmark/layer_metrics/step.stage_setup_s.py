"""``step.stage_setup_s`` - LAYER step loop (``NetTrainer.stage_batch``);
UNIT s; MOVES ``setup_s``; every cell.

Sum of the program's ``train.stage`` spans before the window: the host cast
and the enqueue of the transfer of every batch staged in set-up (a staged
feed's ring, and whatever the reference's comparison stages)."""

from benchmark import program_spans

LAYER, UNIT, MOVES = 'step', 's', 'setup_s'


def read(run):
    return program_spans.seconds(run, 'train.stage')
