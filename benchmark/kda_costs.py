"""Analytic operations and bytes of the Kimi delta-attention (``kda``)
layers of a conf's step, and the reader of their device time: the numerator
and denominator of ``net.kda_roofline_pct``.

What the algorithm needs, not what an implementation computes: a layer's
products (``q``, ``k``, ``v``, the decay's and the output gate's low-rank
pairs, ``beta``, ``W_o``), its short convolution, and the delta rule in the
chunkwise form at chunks of ``CHUNK`` positions whatever chunk the program
runs (a chunk's two matrices of pairs, ``U`` and ``W`` from its solve, the
scan's two products and the outputs' two), so that a later kernel is read
against the same work.  A training step runs each layer's forward pass, its
recomputation in the backward pass and the backward pass, which costs two
forward passes (the gradients of the inputs and of the weights): ``PASSES``
forward passes a layer.  Bytes, a forward pass: the layer's input and output
(bf16), its weights once (bf16), and six float32 arrays of ``heads *
head_dim`` a position (``q``, ``k``, ``v``, the decays, the gate, ``o``)
written once and read once.  The trace keeps no scope inside a conf layer,
so the time is the layers' own (``scope_times.scope_ms(run, 'kda')``) and
the share is the layers', elementwise work and all.
"""

from __future__ import annotations

from . import kernel_costs, scope_times

CHUNK = 64
PASSES = 4
TAPS = 4            # the short convolution's (Kimi Linear's kernel of 4)


def recurrence_macs(seq: int, heads: int, dk: int, dv: int,
                    chunk: int = CHUNK) -> float:
    """Multiply-accumulates of the delta rule's chunkwise form over ``seq``
    positions of ``heads`` heads: a chunk's two matrices of pairs (``C^2
    dk`` each), ``U`` and ``W`` from the solve (``C^2 (dv + dk)``), the
    scan's ``W S`` and state update and the outputs' ``Q S`` (``3 C dk
    dv``), the outputs' pairs times ``Delta`` (``C^2 dv``)."""
    c = chunk
    return float(-(-seq // c) * heads * (2 * c * c * dk + c * c * (dv + dk)
                                         + 3 * c * dk * dv + c * c * dv))


def projection_macs(d: int, heads: int, dk: int) -> int:
    """A position's products of a ``kda`` layer: ``q``, ``k``, ``v``, the
    decay's and the gate's low-rank pairs (``d x dk`` then ``dk x heads
    dk``), ``beta`` and ``W_o``."""
    width = heads * dk
    return 3 * d * width + 2 * (d * dk + dk * width) + d * heads + width * d


def layer_forward(seq: int, d: int, heads: int, dk: int, params: int,
                  batch: int = 1) -> dict:
    """One forward pass of one ``kda`` layer of ``params`` parameters over
    ``batch`` sequences."""
    width = heads * dk
    macs = seq * (projection_macs(d, heads, dk) + 3 * TAPS * width) \
        + recurrence_macs(seq, heads, dk, dk)
    return {'flops': 2.0 * batch * macs,
            'bytes': batch * seq * (2 * d * 2 + 6 * 2 * width * 4)
            + params * 2}


def step_cost(graph, batch: int) -> dict:
    """Every ``kda`` layer of ``graph`` (``references/glm_moe_lite.Graph``),
    ``PASSES`` passes each."""
    total = {'flops': 0.0, 'bytes': 0.0}
    for l in graph.of_type('kda'):
        one = layer_forward(graph.seq, graph.width, l.geti('nhead'),
                            l.geti('head_dim'), parameters(graph, l), batch)
        total = {k: total[k] + PASSES * one[k] for k in total}
    return total


def parameters(graph, l) -> int:
    """A ``kda`` layer's parameter count from its keys: the pre-norm, the
    seven products' matrices, three convolutions, ``dt_bias``, ``A_log``,
    the gate's bias and the output norm's gain."""
    d, nh, dk = graph.width, l.geti('nhead'), l.geti('head_dim')
    width = nh * dk
    return (d + projection_macs(d, nh, dk) + 3 * TAPS * width + width + nh
            + width + dk)


def roofline(run):
    """The ``kda`` layers' share of the chip's peaks over their device ms a
    step, every pass, the larger of the two shares; ``None`` where the run
    has no ``kda`` layer or no table by scope."""
    ms = scope_times.scope_ms(run, 'kda')
    graph = getattr(run.feed, 'graph', None)
    if ms is None or graph is None or not graph.of_type('kda') \
            or not run.peaks:
        return None
    cost = step_cost(graph, run.feed.samples_per_step)
    return kernel_costs.roofline_pct(cost, 1, ms, run.peaks)
