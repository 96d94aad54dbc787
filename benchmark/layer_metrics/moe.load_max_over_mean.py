"""``moe.load_max_over_mean`` - LAYER net/layers (``layers/sequence.py``
``moe``); UNIT x; MOVES ``samples_per_s``; cells of a conf with expert
layers.

The largest held expert's load over the mean load of the held experts, the
worst expert layer of each step, mean over the run's steps: 1 when the held
experts share their tokens evenly.  Same counter route as
``moe.local_assignment_share``: the grouped products' tiles follow the
largest group."""

from benchmark import scope_times

LAYER, UNIT, MOVES = 'net', 'x', 'samples_per_s'


def read(run):
    return scope_times.mean_stat(run, 'moe.load_max_over_mean')
