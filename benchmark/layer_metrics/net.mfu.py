"""``net.mfu`` - LAYER net/layers; UNIT %; MOVES ``samples_per_s``; staged
cells.

The operations the conf's forward and backward passes require for one step
(``confnet.train_flops_per_sample``: conv and fullc multiply-accumulates
times two, forward, weight gradient and input gradient, none of the last for
a layer fed by the input; nothing recomputed counts), times the steps a
second of the untraced window, over the chips of the cell times the peak of
``peaks.json``.  Throughput times a constant inside a cell, so it compares
cells and chips, not PRs."""

LAYER, UNIT, MOVES = 'net', '%', 'samples_per_s'


def read(run):
    if not run.peaks or not run.flops_per_step:
        return None
    steps_per_s = run.window.steps / run.window.wall_s
    return 100.0 * run.flops_per_step * steps_per_s / (
        run.cell.chips * run.peaks['bf16_flops_per_s'])
