"""The parts every cell shares: the files a cell is made of, the harness's
own spans, the compile clock, the timed window, and what a run hands to the
metric readers."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'


# --- the files a cell is made of --------------------------------------------

def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by name: a feed, a reference or
    a metric reader that a later PR adds is a new file, never an edit."""
    path = os.path.join(HERE, kind, name + '.py')
    if not os.path.exists(path):
        raise FileNotFoundError(f'{kind} {name!r}: no file {path}')
    spec = importlib.util.spec_from_file_location(
        f'benchmark.{kind}.{name.replace(".", "_")}', path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # benchmark/configs/<config>.json
    traffic: dict          # benchmark/traffic/<traffic>.json
    end_to_end: List[dict]  # the metrics of BENCHMARK.json that this cell has
    per_layer: List[dict]
    own: dict              # benchmark/cells/<name>.json, where there is one:
    #                        what belongs to the pair and to neither part
    rehearsal: bool = False

    def t(self, key: str):
        """A traffic parameter; a rehearsal takes its tiny value where the
        traffic file gives one."""
        if self.rehearsal and key in self.traffic.get('rehearsal', {}):
            return self.traffic['rehearsal'][key]
        return self.traffic[key]

    def conf_extra(self) -> Dict[str, str]:
        """Conf keys the traffic mix sets (``set`` in its file: how the
        program is driven, never what it computes), and those a rehearsal
        sets besides (a tiny batch needs a tiny learning rate to stay
        finite)."""
        sets = dict(self.traffic.get('set', {}))
        if self.rehearsal:
            sets.update(self.traffic['rehearsal'].get('set', {}))
        return sets

    @property
    def batch_per_chip(self) -> int:
        if self.rehearsal:
            return int(self.traffic['rehearsal']['batch_per_chip'])
        return int(self.config['set']['batch_size'])


def load_cell(name: str, rehearsal: bool = False) -> Cell:
    bench = load_json(ROOT, 'BENCHMARK.json')
    entry = next((w for w in bench['workloads'] if w['name'] == name), None)
    if entry is None:
        raise SystemExit(f'benchmark: no workload {name!r} in BENCHMARK.json; '
                         f'it has {[w["name"] for w in bench["workloads"]]}')
    conf = next(c for c in bench['configs'] if c['name'] == entry['config'])

    def mine(metrics):
        return [m for m in metrics
                if name in m.get('workloads', [name])]

    own = os.path.join(HERE, 'cells', name + '.json')
    return Cell(name=name, chips=int(entry['chips']),
                own=load_json(own) if os.path.exists(own) else {},
                config=load_json(ROOT, conf['file']),
                traffic=load_json(HERE, 'traffic',
                                  entry['traffic'] + '.json'),
                end_to_end=mine(bench['end_to_end']),
                per_layer=mine(bench['per_layer']), rehearsal=rehearsal)


# --- spans and the compile clock --------------------------------------------

class Spans:
    """The harness's spans around its calls into the program: kept in
    memory on the ``time.monotonic_ns`` clock (the hub's), and written into
    the profiler's trace as well while one is being taken, so that idle gaps
    on the device can be laid against them."""

    def __init__(self):
        self.rows: List[tuple] = []       # (name, start_ns, end_ns)
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        with contextlib.ExitStack() as stack:
            if self.tracing:
                import jax
                stack.enter_context(
                    jax.profiler.TraceAnnotation('bench.' + name))
            t0 = time.monotonic_ns()
            try:
                yield
            finally:
                self.rows.append((name, t0, time.monotonic_ns()))

    def durations_ms(self, name: str, t0_ns: int, t1_ns: int) -> List[float]:
        return [(e - s) / 1e6 for n, s, e in self.rows
                if n == name and t0_ns <= s < t1_ns]


class CompileClock:
    """Seconds and count of what JAX itself reports as backend compilation
    (or retrieval from the persistent cache)."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.seconds += secs
            self.count += 1


# --- the timed window -------------------------------------------------------

@dataclasses.dataclass
class Window:
    t_open_ns: int
    t_close_ns: int
    first_step: int        # index, among all steps of the run, of the first
    steps: int             # steps dispatched (and completed) inside
    done_ns: List[int]     # when the harness saw each of them complete
    compiles: int          # compilations inside: ledger + JAX's own event

    @property
    def wall_s(self) -> float:
        return (self.t_close_ns - self.t_open_ns) / 1e9

    def intervals_ms(self) -> List[float]:
        d = self.done_ns
        return [(b - a) / 1e6 for a, b in zip(d, d[1:])]


class Pump:
    """Feeds the program and keeps its run-ahead bounded: after every feed
    it waits for the loss of the step ``inflight`` steps back, so every step
    gets a completion time and the closing drain is short."""

    def __init__(self, feed, tap, inflight: int):
        self.feed, self.tap, self.inflight = feed, tap, int(inflight)
        self.done_ns: List[int] = []

    def _settle(self, upto: int) -> None:
        import jax
        while len(self.done_ns) < upto:
            jax.block_until_ready(self.tap.losses[len(self.done_ns)])
            self.done_ns.append(time.monotonic_ns())

    def pump(self) -> None:
        self.feed.advance()
        self._settle(len(self.tap.losses) - self.inflight)

    def drain(self) -> None:
        import jax
        self._settle(len(self.tap.losses))
        jax.block_until_ready(self.feed.trainer.params)

    def window(self, compiles_now, keep_going) -> Window:
        """Open after a drain, pump while ``keep_going(steps, seconds)``,
        close at that step boundary with a drain inside the wall."""
        self.drain()
        first, c0 = len(self.tap.losses), compiles_now()
        t_open = time.monotonic_ns()
        while keep_going(len(self.tap.losses) - first,
                         (time.monotonic_ns() - t_open) / 1e9):
            self.pump()
        self.drain()
        t_close = time.monotonic_ns()
        steps = len(self.tap.losses) - first
        return Window(t_open, t_close, first, steps,
                      self.done_ns[first:first + steps],
                      compiles_now() - c0)


# --- what a run hands to the metric readers ---------------------------------

@dataclasses.dataclass
class Run:
    cell: Cell
    feed: object                   # trainer, samples_per_step, rounds, ...
    spans: Spans
    setup_s: float
    setup_compile_s: float
    window: Window
    traced: Optional[Window]       # the traced sub-window of a --trace 1 run
    trace: Optional[object]        # trace.Reduced of it
    memory_peak_bytes: int
    flops_per_step: float          # analytic (confnet.train_flops_per_sample)
    peaks: Optional[dict]          # this device's row of peaks.json

    def samples_per_s(self) -> float:
        return self.window.steps * self.feed.samples_per_step \
            / self.window.wall_s


def percentile(values: List[float], q: float) -> Optional[float]:
    if not values:
        return None
    v = sorted(values)
    pos = (len(v) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def median(values: List[float]) -> Optional[float]:
    return percentile(values, 0.5)


def read_metrics(kind: str, entries: List[dict], run: Run) -> Dict[str, dict]:
    """Each metric's reader, found by the metric's name; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        value = load_module(kind, m['name']).read(run)
        if value is not None:
            out[m['name']] = {'value': float(value), 'unit': m['unit']}
    return out
