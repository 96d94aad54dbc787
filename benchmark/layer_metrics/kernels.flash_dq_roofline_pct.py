"""``kernels.flash_dq_roofline_pct`` - LAYER Pallas kernels
(``ops/attention.py``: JAX's ``flash_attention`` backward kernel of the
queries); UNIT %; MOVES ``samples_per_s``; cells of a conf with ``mla``
layers on one chip.

``kernel_costs.flash_attention_dq`` (the scores again, ``do v^T`` and ``ds
k``: three products a pair) times one call a layer, over the device time a
step of the events named ``flash_mha_bwd_dq...``, against the chip's peaks.
Compute-bound."""

from benchmark import kernel_costs

LAYER, UNIT, MOVES = 'kernels', '%', 'samples_per_s'


def read(run):
    return kernel_costs.attention_roofline(
        run, 'flash_mha_bwd_dq', kernel_costs.flash_attention_dq, recomputed=False)
