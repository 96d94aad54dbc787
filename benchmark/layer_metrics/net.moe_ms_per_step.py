"""``net.moe_ms_per_step`` - LAYER net/layers (``layers/sequence.py``
``moe``, ``parallel/moe.py``); UNIT ms; MOVES ``samples_per_s``; cells of a
conf with expert layers.

Device ms a step under the scopes of the conf's ``moe`` layers, every pass:
router, sort, the grouped products over the held experts, combine, the shared
expert.  From ``scope_times``.  Moves with the routing: beside it read
``moe.local_assignment_share``."""

from benchmark import scope_times

LAYER, UNIT, MOVES = 'net', 'ms', 'samples_per_s'


def read(run):
    return scope_times.scope_ms(run, 'moe')
