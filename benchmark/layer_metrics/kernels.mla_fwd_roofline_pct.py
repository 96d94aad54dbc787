"""``kernels.mla_fwd_roofline_pct`` - LAYER Pallas kernels
(``ops/attention.py``: JAX's block-sparse ``splash_attention`` on a latent-
attention layer whose value heads are narrower than its query/key heads);
UNIT %; MOVES ``samples_per_s``; cells of a conf with ``mla`` layers at
192/128 and no ``gqa`` layer, on one chip.

The forward kernel: the scores and their product with the values (192 + 128
a pair), q, k, v read and o written once a head; twice a layer, as the
compiled step holds it (once more in the backward pass's recomputation),
over every causal pair of every head (``mla_costs.cost``, at the conf's
widths whatever padding the program uses), over the kernel's device time a
step (events named ``splash_mqa_fwd...``), against the chip's peaks: the
larger of the two shares."""

from benchmark import mla_costs

LAYER, UNIT, MOVES = 'kernels', '%', 'samples_per_s'


def read(run):
    return mla_costs.roofline(run, 'fwd')
