"""The program's own spans (``cxxnet_tpu/obs/hub.py``), for the readers in
``layer_metrics/`` that sum or rank them; read through ``cxx.hub_spans``.

The hub keeps the newest 4,096 events a thread and drops the oldest.  The
process's first span is ``entry.backend`` (the first touch of the backend),
on the thread that sets the run up and dispatches its steps: while the ring
still holds it, nothing recorded on that thread since has been dropped.  Once
it is gone the ring has wrapped, a sum over what is left would be partial,
and ``spans`` returns ``None`` - as it does for a program without these
spans."""

from . import cxx


def spans(run, name: str, in_window: bool):
    """``[(start_ns, dur_ns)]`` of the program's spans ``name`` that began
    before the timed window opened, or (``in_window``) inside it; ``None``
    where there are none, or where the ring no longer holds the process's
    first span."""
    w = run.window
    if not cxx.hub_spans('entry.backend', 0, w.t_open_ns):
        return None
    lo, hi = (w.t_open_ns, w.t_close_ns) if in_window else (0, w.t_open_ns)
    return cxx.hub_spans(name, lo, hi) or None


def seconds(run, name: str):
    found = spans(run, name, in_window=False)
    return None if found is None else sum(d for _, d in found) / 1e9
