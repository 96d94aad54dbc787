"""``kernels.flash_fwd_roofline_pct`` - LAYER Pallas kernels
(``ops/attention.py``: JAX's ``flash_attention`` forward kernel); UNIT %;
MOVES ``samples_per_s``; cells of a conf with ``mla`` layers on one chip.

The causal attention's operations and bytes (``kernel_costs.
flash_attention``: every query against every key up to it, two products;
q, k, v read and o written once) times the calls a step (one a layer, and
one more a layer for the recomputation in the backward pass), over the
kernel's device time a step from ``scope_times`` (events named
``flash_attention``), against the chip's peaks: the larger of the two
shares.  Compute-bound at these shapes (0.69 TFLOP against 0.17 GB)."""

from benchmark import kernel_costs

LAYER, UNIT, MOVES = 'kernels', '%', 'samples_per_s'


def read(run):
    return kernel_costs.attention_roofline(
        run, 'flash_attention', kernel_costs.flash_attention, recomputed=True)
