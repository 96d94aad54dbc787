"""``kernels.delta_rule_ms_per_step`` - LAYER kernels
(``ops/delta_rule_kernel.py``: the chunked gated delta rule's Pallas kernels
``delta_rule_fwd`` and ``delta_rule_bwd``); UNIT ms; MOVES
``samples_per_s``; cells of a conf with ``kda`` layers on one chip.

Device ms a step of the Mosaic calls whose name starts ``delta_rule``: every
delta layer's forward kernel, again in the recomputation, and its backward
kernel.  They lie inside ``net.kda_ms_per_step``, which the trace cannot
split otherwise; the rest of that is the layers' products and elementwise
work.  A program that runs the rule in XLA reads nothing."""

from benchmark import scope_times

LAYER, UNIT, MOVES = 'kernels', 'ms', 'samples_per_s'
KERNEL = 'delta_rule'


def read(run):
    return scope_times.kernel_ms(run, KERNEL)
