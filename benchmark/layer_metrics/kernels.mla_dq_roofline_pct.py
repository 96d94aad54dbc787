"""``kernels.mla_dq_roofline_pct`` - LAYER Pallas kernels
(``ops/attention.py``: JAX's block-sparse ``splash_attention`` on a latent-
attention layer whose value heads are narrower than its query/key heads);
UNIT %; MOVES ``samples_per_s``; cells of a conf with ``mla`` layers at
192/128 and no ``gqa`` layer, on one chip.

The backward kernel of the queries: the scores again, do v^T and ds k (192 +
128 + 192 a pair); q, k, v, do read and dq written once a head; once a
layer, over every causal pair of every head (``mla_costs.cost``, at the
conf's widths whatever padding the program uses), over the kernel's device
time a step (events named ``splash_mqa_dq...``), against the chip's peaks:
the larger of the two shares."""

from benchmark import mla_costs

LAYER, UNIT, MOVES = 'kernels', '%', 'samples_per_s'


def read(run):
    return mla_costs.roofline(run, 'dq')
