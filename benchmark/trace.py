"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, device time of one step, time by kind of operation, collective time
and its exposed part, and the idle gaps laid against what the host was
doing.  Reads the trace with ``jax.profiler.ProfileData`` and nothing else.

A TPU's plane is ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event
for each HLO operation the core ran, one after the other (the event's name
is the instruction's whole text, and no stat says its kind), its line
``Async XLA Ops`` the start-to-done span of each asynchronous one, and its
line ``XLA Modules`` one event for each execution of a compiled program.  Host threads are lines
of ``/host:CPU``; the harness's own spans are ``TraceAnnotation`` events
there whose names start with ``bench.``, on the same clock.

The arithmetic (union of intervals, exposed collectives, gap attribution) is
checked by ``selftest`` on a hand-made trace whose answers can be worked out
on paper, and the names on a trace recorded on the chip.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]          # [start, end) in nanoseconds

OP_LINE = 'XLA Ops'
ASYNC_LINE = 'Async XLA Ops'      # start-to-done spans of asynchronous ops
MODULE_LINE = 'XLA Modules'
SPAN_PREFIX = 'bench.'
WINDOW_SPAN = SPAN_PREFIX + 'traced'
_DEVICE = re.compile(r'^/device:TPU:(\d+)$')
_COLLECTIVE = re.compile(
    r'all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute'
    r'|collective-broadcast')


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float
    stats: Dict[str, object]

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]

    def line(self, name: str) -> Optional[Line]:
        return next((l for l in self.lines if l.name == name), None)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not found:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    return found[-1]


def load(profile_data) -> List[Plane]:
    """The planes of a ``jax.profiler.ProfileData`` as plain objects."""
    planes = []
    for plane in profile_data.planes:
        lines = []
        for line in plane.lines:
            events = [Event(e.name, float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns),
                            dict(e.stats)) for e in line.events]
            lines.append(Line(line.name, events))
        planes.append(Plane(plane.name, lines))
    return planes


def load_file(path: str) -> List[Plane]:
    from jax.profiler import ProfileData
    return load(ProfileData.from_file(path))


# --- interval arithmetic ----------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """What of the merged intervals ``a`` the merged intervals ``b`` leave
    uncovered."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


# --- names ------------------------------------------------------------------

_DATA_MOVES = {'copy', 'copy-start', 'copy-done', 'transpose', 'reshape',
               'bitcast', 'pad', 'slice', 'dynamic-slice',
               'dynamic-update-slice', 'concatenate', 'broadcast', 'reverse',
               'async-start', 'async-done', 'gather', 'scatter'}


def instruction(ev: Event) -> Tuple[str, str]:
    """(name, opcode) of the HLO instruction an op event stands for.  On the
    TPU an event's name is the instruction's whole text, ``%name = shape
    opcode(operands), attributes``; a bare name is its own opcode."""
    text = ev.name
    if ' = ' not in text:
        bare = text.lstrip('%')
        return bare, re.sub(r'[.\d]+$', '', bare)
    name, rest = text.split(' = ', 1)
    if rest.startswith('('):                  # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == '(') - (ch == ')')
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:                                     # shape, then a space
        rest = rest.split(' ', 1)[1] if ' ' in rest else ''
    m = re.match(r'([\w\-]+)\(', rest)
    return name.lstrip('%'), (m.group(1) if m else '')


def is_collective(ev: Event) -> bool:
    name, opcode = instruction(ev)
    return bool(_COLLECTIVE.search(opcode) or _COLLECTIVE.search(name))


def category(ev: Event) -> str:
    """The kind of operation, in a handful of names that outlive a
    recompile (fusion numbers do not), read off the instruction: its opcode,
    a fusion's ``kind`` (on the TPU ``kOutput`` is a fusion rooted in a
    convolution or a dot, ``kLoop`` an elementwise loop, ``kInput`` a
    reduction) and what its name says it holds."""
    name, opcode = instruction(ev)
    if is_collective(ev):
        return 'collective'
    if opcode == 'custom-call':
        return ('mosaic custom call' if 'tpu_custom_call' in ev.name
                else 'other custom call')
    if opcode in ('reduce-window', 'select-and-scatter') \
            or 'reduce-window' in name or 'select-and-scatter' in name:
        return 'reduce-window'
    if opcode in ('convolution', 'dot') or 'convolution' in name:
        return 'convolution/dot fusion'
    if opcode == 'fusion':
        kind = re.search(r'kind=k(\w+)', ev.name)
        return {'Output': 'convolution/dot fusion', 'Loop': 'loop fusion',
                'Input': 'reduce fusion'}.get(
                    kind.group(1) if kind else '', 'other fusion')
    if opcode in _DATA_MOVES:
        return 'copy/transpose'
    return 'other'


# --- the reduction ----------------------------------------------------------

@dataclasses.dataclass
class Reduced:
    window: Interval
    devices: List[int]
    busy_s: float                      # mean over devices, inside the window
    window_s: float
    step_busy_ms: List[float]          # device 0: busy time inside each
    #                                    execution of the dominant program
    steps: int
    by_category_s: List[Tuple[str, float]]      # device 0, most time first
    pallas_s: float                    # device 0: Mosaic custom calls
    collective_s: float                # device 0
    collective_exposed_s: float        # device 0: no other op running
    idle_gaps_s: List[Tuple[str, float]]        # device 0, by host span


def device_planes(planes: List[Plane]) -> List[Tuple[int, Plane]]:
    found = []
    for p in planes:
        m = _DEVICE.match(p.name)
        if m:
            found.append((int(m.group(1)), p))
    return sorted(found)


def host_spans(planes: List[Plane]) -> List[Event]:
    """The harness's spans (``bench.*``) from the host's planes."""
    return sorted((ev for p in planes if p.name.startswith('/host:')
                   for l in p.lines for ev in l.events
                   if ev.name.startswith(SPAN_PREFIX)),
                  key=lambda ev: ev.start)


def _ops(plane: Plane) -> List[Event]:
    line = plane.line(OP_LINE)
    if line is None:
        raise ValueError(f'{plane.name} has no line {OP_LINE!r}: '
                         f'{[l.name for l in plane.lines]}')
    return line.events


def _top_seconds(ns_by_name: Dict[str, float]) -> List[Tuple[str, float]]:
    """The ten largest, in seconds, largest first (the contract's limit)."""
    return sorted(((k, v / 1e9) for k, v in ns_by_name.items()),
                  key=lambda kv: -kv[1])[:10]


def reduce(planes: List[Plane], chips: int) -> Reduced:
    devs = device_planes(planes)[:chips]
    if not devs:
        raise ValueError(
            f'no device plane in the trace: {[p.name for p in planes]}')
    spans = host_spans(planes)
    marker = next((s for s in spans if s.name == WINDOW_SPAN), None)
    all_ops = [ev for _, p in devs for ev in _ops(p)]
    if not all_ops:
        raise ValueError('no operation ran on the device inside the trace')
    window = ((marker.start, marker.end) if marker is not None else
              (min(e.start for e in all_ops), max(e.end for e in all_ops)))
    lo, hi = window
    busy = [total(clip(union((e.start, e.end) for e in _ops(p)), lo, hi))
            for _, p in devs]

    ops0 = [e for e in _ops(devs[0][1]) if e.end > lo and e.start < hi]
    busy0 = clip(union((e.start, e.end) for e in ops0), lo, hi)
    by_cat: Dict[str, float] = {}
    for e in ops0:
        kind = category(e)
        by_cat[kind] = by_cat.get(kind, 0.0) + e.dur
    # a collective is in flight from its start to its done: where the
    # runtime writes that span (the asynchronous line) it counts whole, and
    # what of it no other operation covers is what the step waits for
    background = devs[0][1].line(ASYNC_LINE)
    in_flight = [e for e in (background.events if background else [])
                 if e.end > lo and e.start < hi]
    coll = clip(union((e.start, e.end) for e in ops0 + in_flight
                      if is_collective(e)), lo, hi)
    rest = union((e.start, e.end) for e in ops0 if not is_collective(e))

    step_busy, mod = [], devs[0][1].line(MODULE_LINE)
    if mod is not None and mod.events:
        per_prog: Dict[str, float] = {}
        for e in mod.events:
            per_prog[e.name] = per_prog.get(e.name, 0.0) + e.dur
        main = max(per_prog, key=per_prog.get)
        step_busy = [total(clip(busy0, e.start, e.end)) / 1e6
                     for e in mod.events
                     if e.name == main and e.start >= lo and e.end <= hi]

    gaps = subtract([(lo, hi)], busy0)
    by_span: Dict[str, float] = {}
    inner = [s for s in spans if s.name != WINDOW_SPAN]
    for g in gaps:
        # each part of a gap goes to the harness span open at the time (the
        # feeds' spans follow one another, none holds another), and to the
        # harness itself where none is
        left = g[1] - g[0]
        for s in inner:
            cover = overlap(g, (s.start, s.end))
            if cover > 0:
                name = s.name[len(SPAN_PREFIX):]
                by_span[name] = by_span.get(name, 0.0) + cover
                left -= cover
        if left > 0:
            by_span['harness'] = by_span.get('harness', 0.0) + left

    return Reduced(
        window=window, devices=[n for n, _ in devs],
        busy_s=sum(busy) / len(busy) / 1e9, window_s=(hi - lo) / 1e9,
        step_busy_ms=step_busy, steps=len(step_busy),
        by_category_s=_top_seconds(by_cat),
        pallas_s=by_cat.get('mosaic custom call', 0.0) / 1e9,
        collective_s=total(coll) / 1e9,
        collective_exposed_s=total(subtract(coll, rest)) / 1e9,
        idle_gaps_s=_top_seconds(by_span))


def describe(planes: List[Plane], limit: int = 12) -> str:
    """What a trace holds, for reading by hand: planes, lines, event counts,
    stat names, and the first events of every line."""
    out = []
    for p in planes:
        out.append(f'PLANE {p.name}')
        for l in p.lines:
            keys = sorted({k for e in l.events[:2000] for k in e.stats})
            out.append(f'  LINE {l.name!r}: {len(l.events)} events, '
                       f'stats {keys}')
            for e in l.events[:limit]:
                stats = {k: (str(v)[:60]) for k, v in e.stats.items()}
                out.append(f'    {e.name[:200]!r} start={e.start:.0f} '
                           f'dur={e.dur:.0f} {stats}')
    return '\n'.join(out)
