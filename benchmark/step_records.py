"""The program's step records (``cxxnet_tpu/obs/step_record.py``), for the
readers in ``layer_metrics/`` that rank the steps of the timed window.

Since PR 38 ``update_staged`` opens the hub span ``train.dispatch`` around its
whole body, ``train.launch`` (the jitted call alone) inside it, and the
dispatch span's ``attrs`` carry the process's running totals, read once a
step: ``thread_cpu_ns``, ``process_cpu_ns``, ``gc_ns``, ``gc_n``, ``nivcsw``,
``majflt``, ``compiles``.  A collection of a millisecond or more is a
``host.gc`` event, a step that came late a ``train.stall`` event.  They are
read straight from ``get_hub().events()``, whole (``cxx.hub_spans`` keeps a
start and a length only), and always over the **timed window**, not the
traced sub-window after it.

``None`` under ``program_spans``' own rule: the ring no longer holds the
process's first span, ``entry.backend`` (it has wrapped, and a statistic over
what is left would be partial), or the program leaves no ``train.dispatch``
with these totals (a program before PR 38: the metric is then left out).
"""

from . import cxx

TOTALS = ('thread_cpu_ns', 'process_cpu_ns', 'gc_ns', 'gc_n', 'nivcsw',
          'majflt', 'compiles')


def events(run, name: str):
    """The hub events ``name`` that began inside the timed window, oldest
    first, as the hub keeps them (``t_start_ns``, ``dur_ns``, ``attrs``)."""
    from cxxnet_tpu.obs import get_hub
    w = run.window
    if not cxx.hub_spans('entry.backend', 0, w.t_open_ns):
        return None
    return [e for e in get_hub().events() if e['name'] == name
            and w.t_open_ns <= e['t_start_ns'] < w.t_close_ns]


def dispatches(run):
    """The window's ``train.dispatch`` events, ``None`` without at least two
    that carry the step record."""
    found = events(run, 'train.dispatch')
    if found is None or len(found) < 2 \
            or not all(k in e['attrs'] for e in found for k in TOTALS):
        return None
    return found


def intervals_ms(run):
    """Start-to-start intervals of the window's dispatches.  The window
    opens after a drain, so the first ``max_inflight`` are the short ones of
    the queue filling; they are few, and a median over all stands."""
    found = dispatches(run)
    if found is None:
        return None
    starts = [e['t_start_ns'] for e in found]
    return [(b - a) / 1e6 for a, b in zip(starts, starts[1:])]
