"""``net.kda_roofline_pct`` - LAYER net (``layers/sequence.py`` ``kda``,
``ops/delta_rule.py``); UNIT %; MOVES ``samples_per_s``; cells of a conf
with Kimi delta-attention layers on one chip.

The ``kda`` layers' analytic operations and bytes a step
(``benchmark/kda_costs.py``: their products, short convolutions and the delta
rule in the chunkwise form at chunks of 64 whatever chunk the program runs,
four forward passes' worth a layer for the forward pass, the recomputation and
the backward pass), over ``net.kda_ms_per_step``, against the chip's peaks:
the larger of the two shares.  The trace keeps no scope inside a conf layer,
so this is the layers' share, elementwise work included; a later kernel is
read against the same work."""

from benchmark import kda_costs

LAYER, UNIT, MOVES = 'net', '%', 'samples_per_s'


def read(run):
    return kda_costs.roofline(run)
