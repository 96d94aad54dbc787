"""``kernels.gqa_fwd_roofline_pct`` - LAYER Pallas kernels
(``ops/attention.py``: the forward kernel of JAX's block-sparse
``splash_attention``, which window and full ``gqa`` layers share); UNIT %;
MOVES ``samples_per_s``; cells of a conf with ``gqa`` layers on one chip.

The scores and their product with the values over the pairs each layer's
mask keeps; ``q`` read and ``o`` written once a query head, ``k`` and ``v``
read once a key/value head (``attention_costs.cost``: a key block outside
the window is no work), summed over the conf's ``gqa`` layers at each one's
heads and window, twice a layer (once more in the backward pass's
recomputation) as the compiled step holds the calls; over the kernel's
device time a step (events named ``splash_mqa_fwd...``), against the chip's
peaks: the larger of the two shares."""

from benchmark import attention_costs

LAYER, UNIT, MOVES = 'kernels', '%', 'samples_per_s'


def read(run):
    return attention_costs.roofline(run, 'fwd')
