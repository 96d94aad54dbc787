"""Device time of one step by conf layer and by kernel name, for the readers
in ``layer_metrics/`` that need more than ``trace.Reduced`` keeps (time by
kind of operation, and the sum of Mosaic calls only; ``run.py`` deletes the
raw trace).  After the run, a short trace of its own: ``STEPS`` more
``feed.advance()`` steps under the profiler, reduced by the program's own
``utils/profiler.device_time_by_scope`` with the step program's compiled
text, which names every instruction's conf layer.  Taken once a run and
shared by the readers.

A program without ``device_time_by_scope`` or ``step_program_text`` (an older
commit), an untraced run and a backend without a device plane (the CPU of a
rehearsal) all read ``None``: the readers then leave their metric out.
PERF.md 7 asks the next ``benchmark`` issue to fold this into ``trace.py``'s
own reduction, so that no second trace is needed.
"""

from __future__ import annotations

import os
import re
import shutil

from .harness import HERE

STEPS = 4
_found = {}          # id(run) -> table or None
_stats = {}          # id(run) -> rows of step statistics


def table(run):
    """``{'scopes': {(scope, pass): ms a step}, 'kernels': {name: ms a
    step}, 'steps': n}`` of this run's program, or ``None``."""
    if id(run) not in _found:
        _found[id(run)] = _take(run)
    return _found[id(run)]


def _take(run):
    trainer = run.feed.trainer
    if run.trace is None or not hasattr(trainer, 'step_program_text'):
        return None
    try:
        from cxxnet_tpu.utils.profiler import device_time_by_scope
    except ImportError:
        return None
    import jax
    from . import trace as T
    out = os.path.join(HERE, '.cache', 'scope_trace')
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.block_until_ready(trainer.params)
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        for _ in range(STEPS):
            run.feed.advance()
        jax.block_until_ready(trainer.params)
    finally:
        jax.profiler.stop_trace()
    try:
        found = device_time_by_scope(T.find_xplane(out),
                                     trainer.step_program_text)
    finally:
        shutil.rmtree(out, ignore_errors=True)
    keep = os.environ.get('BENCHMARK_KEEP_TRACE')    # run.py's, for reading
    if keep and found is not None:
        from cxxnet_tpu.utils.profiler import format_scope_table
        with open(os.path.join(keep, run.cell.name + '.scopes.txt'),
                  'w') as f:
            f.write('\n'.join(format_scope_table(found)) + '\n')
    return found


def step_stats(run):
    """What the run's steps counted beside their loss
    (``NetTrainer.step_stats``: one ``{name: value}`` a step), fetched once
    a run; ``[]`` for a program without such counters."""
    if id(run) not in _stats:
        fetch = getattr(run.feed.trainer, 'step_stats', None)
        _stats[id(run)] = fetch(clear=False) if fetch else []
    return _stats[id(run)]


def mean_stat(run, name: str):
    values = [row[name] for row in step_stats(run) if name in row]
    return sum(values) / len(values) if values else None


def scope_ms(run, layer_type: str):
    """Device ms a step under the scopes of the conf's layers of one type
    (``lNN_<type>`` or ``lNN_<type>_<name>``), every pass: forward,
    recomputation and backward."""
    found = table(run)
    if found is None:
        return None
    mine = re.compile(rf'^l\d+_{re.escape(layer_type)}(_|$)')
    total = sum(ms for (scope, _), ms in found['scopes'].items()
                if mine.match(scope))
    return total or None


def kernel_ms(run, prefix: str):
    """Device ms a step of the Mosaic calls whose name starts with
    ``prefix``."""
    found = table(run)
    if found is None:
        return None
    total = sum(ms for name, ms in found['kernels'].items()
                if name.startswith(prefix))
    return total or None
