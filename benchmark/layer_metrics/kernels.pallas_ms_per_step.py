"""``kernels.pallas_ms_per_step`` - LAYER Pallas kernels
(``ops/pallas_kernels.py``); UNIT ms; MOVES ``samples_per_s``; the one-chip
staged cells (under a mesh the gates hand LRN to XLA and nothing is there to
read).

From the trace, device 0: the summed device time of Mosaic custom calls,
over the traced steps."""

LAYER, UNIT, MOVES = 'kernels', 'ms', 'samples_per_s'


def read(run):
    t = run.trace
    if not t or not t.steps or t.pallas_s <= 0:
        return None
    return t.pallas_s * 1e3 / t.steps
