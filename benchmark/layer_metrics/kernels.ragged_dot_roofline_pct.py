"""``kernels.ragged_dot_roofline_pct`` - LAYER Pallas kernels
(``parallel/moe.grouped_swiglu``: the Mosaic kernel XLA makes of
``lax.ragged_dot``); UNIT %; MOVES ``samples_per_s``; cells of a conf with
``moe`` layers on one chip.

Twelve grouped products an expert layer and step (gate, up and down: forward,
the recomputation, the gradient to the rows and the gradient to the weights),
each ``2 x assignments x width x expert width`` operations over the
assignments that landed here (``moe.local_assignment_share`` of tokens x
experts a token, the run's mean: the kernel skips the buffer's other rows),
the held experts' matrices read once a call; over the device time a step of
the events named ``ragged-dot``, against the chip's peaks."""

from benchmark import kernel_costs, scope_times

LAYER, UNIT, MOVES = 'kernels', '%', 'samples_per_s'
KERNEL = 'ragged-dot'
PRODUCTS_A_LAYER = 12


def read(run):
    ms = scope_times.kernel_ms(run, KERNEL)
    share = scope_times.mean_stat(run, 'moe.local_assignment_share')
    if ms is None or share is None or not run.peaks:
        return None
    graph = run.feed.graph
    layers = graph.of_type('moe')
    l = layers[0]
    rows = share * graph.seq * l.geti('experts_per_token') \
        * run.feed.samples_per_step
    cost = kernel_costs.grouped_product(rows, graph.width, l.geti('nhidden'),
                                        l.geti('experts_held'))
    return kernel_costs.roofline_pct(cost, PRODUCTS_A_LAYER * len(layers),
                                     ms, run.peaks)
