"""``net.head_loss_ms_per_step`` - LAYER net/layers (``layers/sequence.py``
``lm_head_loss``); UNIT ms; MOVES ``samples_per_s``; cells of a conf that
ends in the head-and-loss layer.

Device ms a step under the scope of the conf's ``lm_head_loss`` layer, every
head and both passes: the head's product and the chunked softmax
cross-entropy forward, and in the backward pass the logits' recomputation,
their gradient and the two products that read it.  From ``scope_times`` (a
short trace of its own after the run)."""

from benchmark import scope_times

LAYER, UNIT, MOVES = 'net', 'ms', 'samples_per_s'


def read(run):
    return scope_times.scope_ms(run, 'lm_head_loss')
