"""``kernels.gqa_dkv_roofline_pct`` - LAYER Pallas kernels
(``ops/attention.py``: the backward kernel of keys and values of JAX's
block-sparse ``splash_attention``, which window and full ``gqa`` layers
share); UNIT %; MOVES ``samples_per_s``; cells of a conf with ``gqa`` layers
on one chip.

The scores again, ``dp``, ``dv = p^T do`` and ``dk = ds^T q`` over the pairs
each layer's mask keeps, summed over a group's query heads; ``q`` and ``do``
read once a query head, ``k`` and ``v`` read and ``dk``, ``dv`` written once
a key/value head (``attention_costs.cost``: a key block outside the window
is no work), summed over the conf's ``gqa`` layers at each one's heads and
window, once a layer as the compiled step holds the calls; over the kernel's
device time a step (events named ``splash_mqa_dkv...``), against the chip's
peaks: the larger of the two shares."""

from benchmark import attention_costs

LAYER, UNIT, MOVES = 'kernels', '%', 'samples_per_s'


def read(run):
    return attention_costs.roofline(run, 'dkv')
