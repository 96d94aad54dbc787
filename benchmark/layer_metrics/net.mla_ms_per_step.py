"""``net.mla_ms_per_step`` - LAYER net/layers (``layers/sequence.py``
``mla``, ``ops/attention.py``); UNIT ms; MOVES ``samples_per_s``; cells of a
conf with latent-attention layers.

Device ms a step under the scopes of the conf's ``mla`` layers, every pass
(forward, the recomputation in the backward pass, backward): the latent
projections, rotary positions and the blocked causal attention.  From
``scope_times`` (a short trace of its own after the run)."""

from benchmark import scope_times

LAYER, UNIT, MOVES = 'net', 'ms', 'samples_per_s'


def read(run):
    return scope_times.scope_ms(run, 'mla')
