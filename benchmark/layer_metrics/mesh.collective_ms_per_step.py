"""``mesh.collective_ms_per_step`` - LAYER mesh (``parallel/mesh.py``);
UNIT ms; MOVES ``samples_per_s``; cells on more than one chip.

From the trace, device 0: the time in which an all-reduce, all-gather,
reduce-scatter or the like was running, over the traced steps."""

LAYER, UNIT, MOVES = 'mesh', 'ms', 'samples_per_s'


def read(run):
    t = run.trace
    if not t or not t.steps or t.collective_s <= 0:
        return None
    return t.collective_s * 1e3 / t.steps
