"""Analytic operations and bytes of the attention kernels of a conf whose
attention is grouped and windowed (``gqa`` layers: ``ops/attention.py``
hands them to JAX's block-sparse ``splash_attention`` kernels, one forward,
one dq and one dkv kernel for window and full layers alike), and the
readers of their device time: the numerators and denominators of
``kernels.gqa_*_roofline_pct``, and the ``gqa`` layers' scopes split by
kind for ``net.gqa_full_ms_per_step`` / ``net.gqa_window_ms_per_step``.

What the algorithm needs, not what a tiling computes: a layer's (query,
key) pairs are those its mask keeps - every key up to the query, or the
last ``window`` of them - so a block the kernel skips is no work, and a
block it computes half masked counts for the kept half alone.  ``q``, ``o``
and their gradients move once a query head, ``k``, ``v`` and theirs once a
key/value head.  (``kernel_costs.attention_shape`` reads ``mla`` layers
only: equal heads, every position.)
"""

from __future__ import annotations

import re

from . import kernel_costs, scope_times

#: the start of the three kernels' names in a trace and in the compiled step
#: (``splash_mqa_fwd_residuals``, ``splash_mqa_dq_no_residuals``, ...)
KERNELS = {'fwd': 'splash_mqa_fwd', 'dq': 'splash_mqa_dq',
           'dkv': 'splash_mqa_dkv'}
#: products over the kept pairs, in units of ``2 * pairs * heads * dim``:
#: forward the scores and their product with the values; dq the scores
#: again, ``dp = do v^T`` and ``dq = ds k``; dkv the scores, ``dp``, ``dv =
#: p^T do`` and ``dk = ds^T q``
PRODUCTS = {'fwd': 2, 'dq': 3, 'dkv': 4}
#: arrays of ``seq * dim`` moved, (a query head, a key/value head): forward
#: reads q and writes o, reads k and v; dq reads q and do and writes dq,
#: reads k and v; dkv reads q and do, reads k and v and writes dk and dv
ARRAYS = {'fwd': (2, 2), 'dq': (3, 2), 'dkv': (2, 4)}


def pairs(seq: int, window: int) -> int:
    """(query, key) pairs a causal layer keeps: ``sum_i min(i + 1, window)``
    with a window, ``seq (seq + 1) / 2`` without."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def cost(kernel: str, seq: int, heads: int, kv_heads: int, dim: int,
         window: int, batch: int = 1, bytes_each: int = 2) -> dict:
    """One call of ``kernel`` (``fwd``, ``dq`` or ``dkv``) over one layer."""
    per_q, per_kv = ARRAYS[kernel]
    return {'flops': 2 * batch * pairs(seq, window) * heads * dim
            * PRODUCTS[kernel],
            'bytes': batch * seq * dim * (per_q * heads + per_kv * kv_heads)
            * bytes_each}


def layer_shape(graph, l) -> dict:
    return dict(seq=graph.seq, heads=l.geti('nhead'),
                kv_heads=l.geti('nkvhead'), dim=l.geti('head_dim'),
                window=l.geti('window'))


_calls = {}          # id(run) -> {kernel: calls a step in the compiled step}


def calls_a_step(run) -> dict:
    """How often the step calls each kernel, counted in its compiled text
    (none of them sits in a conditional, so every call of the text runs
    every step): instructions whose name starts with the kernel's.  ``{}``
    for a program without ``step_program_text``."""
    if id(run) not in _calls:
        text = getattr(run.feed.trainer, 'step_program_text', lambda: '')()
        names = re.findall(r'^\s+(?:ROOT )?%([\w.\-]+) = .*tpu_custom_call',
                           text, flags=re.M)
        _calls[id(run)] = {k: sum(n.startswith(prefix) for n in names)
                           for k, prefix in KERNELS.items()}
    return _calls[id(run)]


def roofline(run, kernel: str):
    """``kernel``'s share of its roofline over all ``gqa`` layers of the
    step: each layer's cost at its own heads and window, times the calls a
    layer the compiled step holds, over the device ms a step of the events
    named so, against the chip's peaks, the larger share."""
    ms = scope_times.kernel_ms(run, KERNELS[kernel])
    graph = getattr(run.feed, 'graph', None)
    layers = graph.of_type('gqa') if graph is not None else []
    calls = calls_a_step(run).get(kernel, 0) if layers else 0
    if ms is None or not calls or not run.peaks:
        return None
    total = {'flops': 0.0, 'bytes': 0.0}
    for l in layers:
        one = cost(kernel, batch=run.feed.samples_per_step,
                   **layer_shape(graph, l))
        total = {k: total[k] + one[k] for k in total}
    return kernel_costs.roofline_pct(total, calls / len(layers), ms,
                                     run.peaks)


def gqa_scope_ms(run, windowed: bool):
    """Device ms a step under the scopes of the ``gqa`` layers with
    (``windowed``) or without a window, every pass."""
    found = scope_times.table(run)
    graph = getattr(run.feed, 'graph', None)
    if found is None or graph is None:
        return None
    total = 0.0
    for l in graph.of_type('gqa'):
        if bool(l.geti('window')) != windowed:
            continue
        mine = re.compile(rf'^l0*{l.index}_gqa(_|$)')
        total += sum(ms for (scope, _), ms in found['scopes'].items()
                     if mine.match(scope))
    return total or None
