"""Stands for a per-layer metric a later PR adds: a reader of its own."""

LAYER, UNIT, MOVES = 'step', 'steps', 'samples_per_s'


def read(run):
    return float(run.window.steps)
