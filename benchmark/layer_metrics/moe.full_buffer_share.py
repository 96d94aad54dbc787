"""``moe.full_buffer_share`` - LAYER net/layers (``layers/sequence.py``
``moe``, ``parallel/moe.held_experts_ffn``); UNIT %; MOVES ``samples_per_s``;
cells of a conf with expert layers.

Of the expert layers and steps of the run, the share that worked on the
whole sorted buffer (tokens x experts a token rows) because the assignments
that landed here passed the bounded one (``parallel/moe.bounded_rows``).
Nothing is dropped either way; the whole buffer costs what the worst case
costs, so a gain that it eats shows here.  Under 1% in a 10 s run of the
benchmark's cell, and climbing over a longer one as the routers drift toward
the held experts (PERF.md 6, PR 34).  Same counter route as ``moe.local_assignment_share``; a program
without the counter (before PR 34) reads nothing and the metric is left
out."""

from benchmark import scope_times

LAYER, UNIT, MOVES = 'net', '%', 'samples_per_s'


def read(run):
    share = scope_times.mean_stat(run, 'moe.full_buffer_share')
    return None if share is None else 100.0 * share
