"""``kda.chunk_log_decay_min`` - LAYER net (``layers/sequence.py`` ``kda``);
UNIT ln; MOVES ``samples_per_s``; cells of a conf with Kimi delta-attention
layers.

The most negative summed log-decay of any chunk of 64 positions, over the
heads, channels, chunks and ``kda`` layers of a step, mean over the run's
steps: how near the chunked delta rule runs to float32's ``exp`` range
(``exp`` of a sum below about -88 underflows, and its inverse overflows),
and so which chunk a later kernel can afford without the sub-chunk
arithmetic of ``ops/delta_rule.py``.  A statistic of the model, not a cost:
its ``better`` is a field the format demands.  A counter of the program,
returned beside the loss (``NetTrainer.step_stats``); a program without
``kda`` layers reads nothing."""

from benchmark import scope_times

LAYER, UNIT, MOVES = 'net', 'ln', 'samples_per_s'


def read(run):
    return scope_times.mean_stat(run, 'kda.chunk_log_decay_min')
