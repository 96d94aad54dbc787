"""``moe.local_assignment_share`` - LAYER net/layers (``layers/sequence.py``
``moe``); UNIT %; MOVES ``samples_per_s``; cells of a conf with expert
layers.

Of the tokens' assignments (tokens x experts a token), the share that landed
on experts held here, mean over the expert layers and over the run's steps:
12.5% for 8 of 64 under a balanced router.  A counter of the program: the
step returns it as a device scalar beside the loss and
``NetTrainer.step_stats`` fetches what the newest steps counted, after the
window.  More assignments here are more grouped products here."""

from benchmark import scope_times

LAYER, UNIT, MOVES = 'net', '%', 'samples_per_s'


def read(run):
    share = scope_times.mean_stat(run, 'moe.local_assignment_share')
    return None if share is None else 100.0 * share
