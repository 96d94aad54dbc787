"""``net.gqa_full_ms_per_step`` - LAYER net/layers (``layers/sequence.py``
``gqa``, ``ops/attention.py``); UNIT ms; MOVES ``samples_per_s``; cells of a
conf with ``gqa`` layers.

Device ms a step under the scopes of the conf's ``gqa`` layers
without a window (``window = 0``: every key up to the query),
every pass (forward, the recomputation in the backward pass, backward): the
pre-norm, the four products and the gate's, rotary positions, the blocked
attention.  Beside its twin a layer: if a window layer is not well under 3/2
of a full one (72 query heads against 48), nothing is skipped."""

from benchmark import attention_costs

LAYER, UNIT, MOVES = 'net', 'ms', 'samples_per_s'


def read(run):
    return attention_costs.gqa_scope_ms(run, windowed=False)
