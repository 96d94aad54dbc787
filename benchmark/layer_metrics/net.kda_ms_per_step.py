"""``net.kda_ms_per_step`` - LAYER net (``layers/sequence.py`` ``kda``,
``ops/delta_rule.py``); UNIT ms; MOVES ``samples_per_s``; cells of a conf
with Kimi delta-attention layers.

Device ms a step under the scopes of the conf's ``kda`` layers, every pass
(forward, the recomputation in the backward pass, backward): the pre-norm,
the products, the short convolutions, the decays and ``beta``, the chunked
delta rule (its matrices of pairs, the triangular solves, the scan over
chunks), the output norm and gate.  A loop's time is its body's, once.
From ``scope_times``; a program without ``kda`` layers reads nothing."""

from benchmark import scope_times

LAYER, UNIT, MOVES = 'net', 'ms', 'samples_per_s'


def read(run):
    return scope_times.scope_ms(run, 'kda')
