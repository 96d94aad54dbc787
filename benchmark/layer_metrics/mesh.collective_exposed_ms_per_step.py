"""``mesh.collective_exposed_ms_per_step`` - LAYER mesh; UNIT ms; MOVES
``samples_per_s``; cells on more than one chip.

From the trace, device 0: the part of the collectives' time during which no
other operation ran, over the traced steps.  This is what the mesh costs the
step; the rest is hidden behind compute."""

LAYER, UNIT, MOVES = 'mesh', 'ms', 'samples_per_s'


def read(run):
    t = run.trace
    if not t or not t.steps or t.collective_s <= 0:
        return None
    return t.collective_exposed_s * 1e3 / t.steps
