"""The step record: what each ``train.dispatch`` span carries, and the
program's own verdict on a step that came late (doc/observability.md, "The
step record").

``NetTrainer.update_staged`` / ``update_staged_window`` open
``train.dispatch`` around their whole body, ``train.launch`` (the jitted
call alone) inside it.  :class:`StepSeries` rides those two spans:

* at the span's entry it reads the process's running totals once, on the
  dispatching thread (``TelemetryHub.host_totals`` and the program ledger's
  ``compiles_total``), and puts them into the span's ``attrs``: totals, not
  differences, so a reader subtracts two records and a dropped event costs
  one interval;
* it keeps the start-to-start intervals of the newest :data:`KEEP`
  dispatches, each split in three: the jitted call (``launch``), the
  program's own host code around it (``dispatch``), and the time between
  two calls (``caller``: the input and the staging in the CLI, the wait for
  a loss in a harness);
* an interval over :data:`FACTOR` medians and :data:`OVER_NS` over the
  median, once :data:`MIN` are known, is a ``train.stall`` hub event and a
  line on standard error: which of the three parts grew most over its own
  median, and the totals' differences over that interval, which tell a
  collection from a recompile, from a wait for the device, from a thread
  that was not run (the reading table is in the doc).  It is written when
  the next dispatch shows that the loop went on as before (an interval
  under half the long one): a loop that has filled its queue of steps in
  flight goes from the host's pace to the device's and stays there, and
  that is a change of pace, not a stall.

Nothing here fetches from the device or waits: some five clock reads a
step, and a sort of 64 numbers only for an interval over :data:`OVER_NS`.
"""

from __future__ import annotations

import collections
import sys
from statistics import median as _median

from .hub import get_hub

#: intervals kept; judged once MIN are known
KEEP, MIN = 64, 8
#: a stall is an interval over FACTOR medians and OVER_NS over the median
FACTOR, OVER_NS = 3, 50_000_000
#: stall lines a series writes to standard error; the next is a count, the
#: rest are hub events only
LINES = 8

_PARTS = ('launch', 'dispatch', 'caller')
_DIFFS = (('gc_ms', 'gc_ns', 1e-6), ('gc_n', 'gc_n', 1),
          ('thread_cpu_ms', 'thread_cpu_ns', 1e-6),
          ('process_cpu_ms', 'process_cpu_ns', 1e-6), ('nivcsw', 'nivcsw', 1),
          ('majflt', 'majflt', 1), ('compiles', 'compiles', 1))


class StepSeries:
    """One trainer's newest dispatches (module docstring).  Driven by the
    dispatching thread alone."""

    def __init__(self):
        self.stalls = 0
        self.launch_ns = 0       # the trainer notes its train.launch here
        self.reset()

    def reset(self) -> None:
        """Forget the series: a round's boundary, an evaluation or a
        checkpoint between two dispatches is no stall."""
        self._rows = collections.deque(maxlen=KEEP)
        # the dispatch that opened the running interval: (start_ns, totals,
        # its launch's length, its own), the lengths 0 until it ends
        self._prev = None
        # a long interval the next one has yet to confirm: (start_ns,
        # length, attrs)
        self._suspect = None

    def begin(self, sp) -> None:
        """At the entry of the ``train.dispatch`` span ``sp``."""
        t = sp.t_start_ns
        self.launch_ns = 0
        if not t:                # a disabled hub records nothing
            self._prev = None
            return
        from .programs import get_ledger
        totals = get_hub().host_totals()
        totals['compiles'] = get_ledger().compiles_total
        sp.attrs.update(totals)
        self.observe(t, totals, sp.attrs.get('update'))

    def observe(self, t_ns: int, totals: dict, update=None) -> None:
        """A dispatch began at ``t_ns`` with the running ``totals``: close
        the interval the previous one opened, judge it, open the next."""
        prev, self._prev = self._prev, (t_ns, totals, 0, 0)
        if prev is None:
            return
        t_prev, totals_prev, launch, whole = prev
        interval = t_ns - t_prev
        row = (interval, launch, whole - launch, interval - whole)
        suspect, self._suspect = self._suspect, None
        if suspect is not None and 2 * interval < suspect[1]:
            self._write(*suspect)
        if interval > OVER_NS and len(self._rows) >= MIN:
            attrs = self._judge(row, totals_prev, totals, update)
            if attrs is not None:
                self._suspect = (t_prev, interval, attrs)
        self._rows.append(row)

    def end(self, dispatch_ns: int) -> None:
        """After the exit of the ``train.dispatch`` span: its length, and
        its launch's (``launch_ns``), belong to the interval it opened."""
        if self._prev is not None:
            self._prev = self._prev[:2] + (self.launch_ns, dispatch_ns)

    def _judge(self, row, before, now, update):
        """The ``train.stall`` attributes of the interval ``row`` if it is
        long against the series, else ``None``."""
        median = _median(r[0] for r in self._rows)
        if row[0] <= FACTOR * median or row[0] - median < OVER_NS:
            return None
        grew = [row[i + 1] - _median(r[i + 1] for r in self._rows)
                for i in range(len(_PARTS))]
        attrs = {'update': update, 'interval_ms': row[0] / 1e6,
                 'median_ms': median / 1e6,
                 'where': _PARTS[grew.index(max(grew))]}
        for name, key, scale in _DIFFS:
            attrs[name] = (now[key] - before[key]) * scale
        return attrs

    def _write(self, t_ns: int, interval_ns: int, attrs: dict) -> None:
        self.stalls += 1
        get_hub().record_event('train.stall', 'train', t_start_ns=t_ns,
                               dur_ns=interval_ns, **attrs)
        if self.stalls <= LINES:
            sys.stderr.write('train.stall' + ''.join(
                f'\t{k}:{v:.3f}' if isinstance(v, float) else f'\t{k}:{v}'
                for k, v in attrs.items()) + '\n')
        elif self.stalls == LINES + 1:
            sys.stderr.write(
                f'train.stall\tcount:{self.stalls}\t(every further one is '
                f'a hub event only)\n')
