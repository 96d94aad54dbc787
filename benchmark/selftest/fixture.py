"""Cut a trace taken on the chip down to a fixture small enough to keep: the
first ``steps`` executions of the step program on at most ``devices``
devices, their ``XLA Ops``, ``Async XLA Ops`` and ``XLA Modules`` lines, and
the harness's spans; written as a gzipped text-format
XSpace that ``ProfileData.from_text_proto`` reads back, with what the
reduction makes of it beside it (``.expect.json``).

``run.py`` calls this when ``BENCHMARK_KEEP_TRACE`` names a directory; the
result is copied to ``selftest/fixtures/`` by hand, once, after reading it.
"""

from __future__ import annotations

import gzip
import json
import os
from typing import List

from .. import trace as T


def _plane_text(pid: int, name: str, lines, base: float) -> str:
    names, out, body = {}, [], []
    for lid, (lname, events) in enumerate(lines, 1):
        rows = []
        for e in events:
            mid = names.setdefault(e.name, len(names) + 1)
            rows.append(
                f'    events {{ metadata_id: {mid} '
                f'offset_ps: {int(round((e.start - base) * 1000))} '
                f'duration_ps: {int(round(e.dur * 1000))} }}')
        body.append(f'  lines {{\n    id: {lid} name: "{lname}" '
                    f'timestamp_ns: 0\n' + '\n'.join(rows) + '\n  }')
    out.append(f'planes {{\n  id: {pid}\n  name: "{name}"')
    out.extend(body)
    for ename, mid in names.items():
        safe = ename.replace('\\', '\\\\').replace('"', '\\"')
        out.append(f'  event_metadata {{ key: {mid} value {{ id: {mid} '
                   f'name: "{safe}" }} }}')
    out.append('}')
    return '\n'.join(out)


def record(planes: List[T.Plane], chips: int, out_prefix: str,
           steps: int = 2, devices: int = 2) -> None:
    devs = T.device_planes(planes)[:min(chips, devices)]
    mod = devs[0][1].line(T.MODULE_LINE)
    totals = {}
    for e in mod.events:
        totals[e.name] = totals.get(e.name, 0.0) + e.dur
    main = max(totals, key=totals.get)
    runs = [e for e in mod.events if e.name == main]
    runs = runs[len(runs) // 2:][:steps]           # from the steady middle
    lo, hi = runs[0].start - 1000.0, runs[-1].end + 1000.0
    base = lo - 1000.0
    text = []
    for pid, (_, plane) in enumerate(devs, 1):
        lines = [(name, [e for e in plane.line(name).events
                         if e.start >= lo and e.end <= hi])
                 for name in (T.OP_LINE, T.ASYNC_LINE, T.MODULE_LINE)
                 if plane.line(name) is not None]
        text.append(_plane_text(pid, plane.name, lines, base))
    spans = [e for e in T.host_spans(planes)
             if e.name != T.WINDOW_SPAN and e.end > lo and e.start < hi]
    spans.append(T.Event(T.WINDOW_SPAN, lo, hi, {}))
    text.append(_plane_text(len(devs) + 1, '/host:CPU',
                            [('python3', spans)], base))
    with gzip.open(out_prefix + '.textproto.gz', 'wt') as f:
        f.write('\n'.join(text) + '\n')
    from jax.profiler import ProfileData
    with gzip.open(out_prefix + '.textproto.gz', 'rt') as f:
        again = T.load(ProfileData.from_text_proto(f.read()))
    r = T.reduce(again, chips=len(devs))
    with open(out_prefix + '.expect.json', 'w') as f:
        json.dump({'chips': len(devs), 'steps': r.steps, 'busy_s': r.busy_s,
                   'window_s': r.window_s, 'collective_s': r.collective_s,
                   'collective_exposed_s': r.collective_exposed_s,
                   'pallas_s': r.pallas_s,
                   'categories': [k for k, _ in r.by_category_s],
                   'by_category_s': r.by_category_s,
                   'idle_gaps_s': r.idle_gaps_s,
                   'source': os.path.basename(out_prefix)}, f, indent=1)
        f.write('\n')
