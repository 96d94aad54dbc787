"""Analytic operations and bytes of the attention kernels of a conf whose
latent attention runs on JAX's block-sparse kernel (``mla`` layers whose
value heads are narrower than their query/key heads: ``ops/attention.py``
hands them to ``splash_attention``, one forward, one dq and one dkv kernel),
and the reader of their share of the roofline: the numerators and
denominators of ``kernels.mla_*_roofline_pct``.

What the algorithm needs, not what a tiling or a padding computes: every
causal (query, key) pair of every head, ``qk_nope_head_dim +
qk_rope_head_dim`` a pair for the scores and ``v_head_dim`` for their
product with the values (192 and 128 at Kimi Linear's widths), whatever
width the kernel's blocks are laid out at.  The products and the arrays a
kernel moves are ``kernel_costs``' flash kernels' at those two widths
(forward: the scores and ``p v``; dq: the scores, ``do v^T`` and ``ds k``;
dkv: the scores, ``do v^T``, ``p^T do`` and ``ds^T q``); ``q``, ``o`` and
their gradients move once a head, ``k``, ``v`` and theirs once a head (the
layer hands the kernel one key head a query head).  The device time is the
events' whose name starts with the kernel's (``attention_costs.KERNELS``),
so a cell that also runs ``gqa`` layers, whose kernels share those names,
reads nothing.
"""

from __future__ import annotations

from . import attention_costs, kernel_costs, scope_times

COSTS = {'fwd': kernel_costs.flash_attention,
         'dq': kernel_costs.flash_attention_dq,
         'dkv': kernel_costs.flash_attention_dkv}


def cost(kernel: str, graph, l, batch: int = 1) -> dict:
    """One call of ``kernel`` (``fwd``, ``dq`` or ``dkv``) over latent
    layer ``l`` of ``graph`` (``references/glm_moe_lite.Graph``)."""
    return COSTS[kernel](
        seq=graph.seq, heads=l.geti('nhead'),
        dim_qk=l.geti('qk_nope_head_dim') + l.geti('qk_rope_head_dim'),
        dim_v=l.geti('v_head_dim'), batch=batch)


def roofline(run, kernel: str):
    """``kernel``'s share of its roofline over all ``mla`` layers of the
    step: each layer's cost, times the calls a layer the compiled step
    holds (the forward twice: once more in the backward pass's
    recomputation), over the device ms a step of the events named so,
    against the chip's peaks, the larger share.  ``None`` where the conf
    has no ``mla`` layer or has ``gqa`` layers, where the trace has no such
    event, or where the step's text is not there to count the calls."""
    graph = getattr(run.feed, 'graph', None)
    layers = graph.of_type('mla') if graph is not None else []
    if not layers or graph.of_type('gqa') or not run.peaks:
        return None
    ms = scope_times.kernel_ms(run, attention_costs.KERNELS[kernel])
    calls = attention_costs.calls_a_step(run).get(kernel, 0)
    if ms is None or not calls:
        return None
    total = {'flops': 0.0, 'bytes': 0.0}
    for l in layers:
        one = cost(kernel, graph, l, run.feed.samples_per_step)
        total = {k: total[k] + one[k] for k in total}
    return kernel_costs.roofline_pct(total, calls / len(layers), ms,
                                     run.peaks)
