"""Profiling / tracing subsystem.

The reference has only wall-clock second counters printed every
``print_step`` batches (``cxxnet_main.cpp:376-387``, ``utils/timer.h:16-30``)
— no tracer, no per-op timing.  On TPU the idiomatic replacement is the JAX
profiler: it records an XLA trace (per-op device timing, HBM usage, fusion
boundaries) viewable in TensorBoard / Perfetto.

Config surface (global section)::

    profile_dir = traces        # enables tracing; directory for the trace
    profile_start_batch = 10    # first update() covered (default 10,
    profile_stop_batch = 20     #   skipping compile) .. last (exclusive)

The window is batch-based so the first (compiling) steps are excluded by
default.  When the window closes, :func:`device_time_by_scope` reads the
trace back and the table goes to stderr, a line a row: device time per step
by conf layer and pass, by Pallas kernel, and the device's idle time by the
program's own host span open at the time (doc/observability.md).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
import threading
from typing import Callable, Dict, List, Optional, Tuple

# --- single-flight arbitration ---------------------------------------------
# jax.profiler holds ONE global trace per process: the config-driven
# TraceWindow and the on-demand /profile session (obs/programs.py
# ProfilerSession) must never both start one.  Whoever acquires the
# slot owns the profiler until release; the loser observes busy.
_TRACE_LOCK = threading.Lock()
_TRACE_OWNER: Optional[str] = None        # guarded-by: _TRACE_LOCK


def acquire_trace(owner: str) -> bool:
    """Claim the process-wide profiler slot for ``owner``; False when
    ANY owner holds it — deliberately non-reentrant, so a stop racing
    a fresh start can never hand two sessions the same slot (the
    caller must not start a trace on False)."""
    global _TRACE_OWNER
    with _TRACE_LOCK:
        if _TRACE_OWNER is not None:
            return False
        _TRACE_OWNER = owner
        return True


def release_trace(owner: str) -> None:
    """Release the slot (no-op unless ``owner`` holds it)."""
    global _TRACE_OWNER
    with _TRACE_LOCK:
        if _TRACE_OWNER == owner:
            _TRACE_OWNER = None


def trace_owner() -> Optional[str]:
    with _TRACE_LOCK:
        return _TRACE_OWNER


# --- reading the program's own names back -----------------------------------
# Net.forward runs every conf layer in a ``jax.named_scope`` and train_step
# its ``nan_gate`` / ``update`` (and ``grad_acc``, which only an
# ``update_period > 1`` program has); every Pallas kernel has a
# ``name=`` (ops/pallas_kernels.KERNEL_NAMES).  The compiler keeps both: the
# scope in the instruction's ``op_name`` (``jit(train_step)/jvp(l03_lrn)/..``
# forward, ``transpose(jvp(l03_lrn))`` backward), the kernel's name as the
# custom call's own (``%lrn_fwd.1``).  A device event of the TPU's trace is
# named by its instruction's text and carries no ``op_name`` (looked at on the
# v5e, PR 27: its stats are offsets and durations), so the scope is found
# through the instruction's name in the compiled program's text.

_BWD = re.compile(r'transpose\(jvp\(([^()/]+)\)\)')
_FWD = re.compile(r'jvp\(([^()/]+)\)')
_CONTROL = re.compile(r'^(jit|pjit)\(.*\)$|^(while|body|cond|checkpoint)$'
                      r'|^branch_\d+_fun$')
_HLO_COMPUTATION = re.compile(r'^(?:ENTRY )?%([\w.\-]+) \(.*\{$')
_HLO_INSTRUCTION = re.compile(r'^\s+(?:ROOT )?%([\w.\-]+) = ')
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_HLO_CALLS = re.compile(r'(?:calls|to_apply)=%([\w.\-]+)')
_OP_LINE, _MODULE_LINE = 'XLA Ops', 'XLA Modules'


def scope_of(op_name: str) -> Tuple[str, str]:
    """(scope, pass) of an instruction's ``op_name``: pass ``bwd`` inside
    ``transpose(jvp(<scope>))``, ``fwd`` inside ``jvp(<scope>)``, ``-`` for a
    scope outside differentiation (``update``, or a layer of the forward-only
    program); ``('other', '-')`` where no scope holds the instruction."""
    m = _BWD.search(op_name)
    if m:
        return m.group(1), 'bwd'
    m = _FWD.search(op_name)
    if m:
        return m.group(1), 'fwd'
    parts = [p for p in op_name.split('/')[:-1] if not _CONTROL.match(p)]
    return (parts[0], '-') if parts else ('other', '-')


#: opcodes whose device event spans the events of the computation they run:
#: the body's events carry the time
_CONTAINERS = ('while', 'conditional', 'call')
_SPAN_PREFIX = 'cxxnet.'        # obs/hub.py: a hub span on the host plane


def _closing(text: str, at: int) -> int:
    """Index of the parenthesis that closes the one at ``at`` (the text's
    end where none does: an event's name may be cut)."""
    depth = 0
    for i in range(at, len(text)):
        depth += (text[i] == '(') - (text[i] == ')')
        if depth == 0:
            return i
    return len(text)


def parse_instruction(text: str) -> Tuple[str, str, Tuple[str, ...]]:
    """(name, opcode, operand names) of one HLO instruction's text, ``%name =
    shape opcode(operands), attributes``: a line of a compiled program's
    text, or a device event's name on the TPU.  A bare name has no opcode."""
    if ' = ' not in text:
        return text.strip().lstrip('%'), '', ()
    name, rest = text.split(' = ', 1)
    if rest.startswith('('):                  # a tuple shape: skip to its end
        rest = rest[_closing(rest, 0) + 1:].lstrip()
    else:                                     # shape, then a space
        rest = rest.split(' ', 1)[1] if ' ' in rest else ''
    m = re.match(r'([\w\-]+)\(', rest)
    if not m:
        return name.split('%')[-1], '', ()
    inside = rest[m.end():_closing(rest, m.end() - 1)]
    return (name.split('%')[-1], m.group(1),
            tuple(re.findall(r'%([\w.\-]+)', inside)))


def hlo_program(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """instruction name -> (opcode, ``op_name``) from a compiled program's
    text (``compiled.as_text()``).  The ``op_name`` by three rules, in order
    (the benchmark's ``trace.program_scopes`` has the same three):

    - the instruction's own;
    - the compiler's own rewrites lose the ``op_name`` of an instruction and
      keep it on what the instruction calls (LRN's channel cumsum becomes a
      windowed reduction in an unnamed fusion, 9 ms of GoogLeNet's step):
      such an instruction takes the first ``op_name`` inside the computation
      it calls;
    - a custom call whose ``op_name`` names no scope (XLA makes a Mosaic call
      of ``lax.ragged_dot`` and writes its own ``op_name="ragged-dot-none"``
      over the layer's path) takes the ``op_name`` of a neighbour in the
      scope most of what it reads and of what reads it have, what it reads
      first on a tie: a kernel belongs to the layer whose arrays it works
      on."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    opcode: Dict[str, str] = {}
    reads: Dict[str, Tuple[str, ...]] = {}
    inside: Dict[str, List[str]] = {}
    comp = ''
    for line in hlo_text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        if not _HLO_INSTRUCTION.match(line):
            continue
        name, opcode[name], reads[name] = parse_instruction(line)
        inside.setdefault(comp, []).append(name)
        op = _HLO_OP_NAME.search(line)
        if op:
            own[name] = op.group(1)
        called = _HLO_CALLS.search(line)
        if called:
            calls[name] = called.group(1)

    def resolve(name: str, depth: int) -> str:
        if name in own or depth > 4:
            return own.get(name, '')
        return next(filter(None, (resolve(n, depth + 1) for n in
                                  inside.get(calls.get(name, ''), ()))), '')

    found = {name: resolve(name, 0) for name in opcode}
    no_scope = scope_of('')
    read_by: Dict[str, List[str]] = {}
    for name, operands in reads.items():
        for o in dict.fromkeys(operands):
            read_by.setdefault(o, []).append(name)
    adopted = {}
    for name, op_name in found.items():
        if opcode[name] != 'custom-call' or scope_of(op_name) != no_scope:
            continue
        votes: Dict[Tuple[str, str], List[str]] = {}
        for n in list(dict.fromkeys(reads[name])) + read_by.get(name, []):
            near = found.get(n, '')
            if scope_of(near) != no_scope:
                votes.setdefault(scope_of(near), []).append(near)
        if votes:                        # max keeps the first on a tie
            adopted[name] = max(votes.values(), key=len)[0]
    found.update(adopted)
    return {name: (opcode[name], found[name]) for name in opcode}


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """instruction name -> ``op_name`` (:func:`hlo_program`'s rules)."""
    return {name: op for name, (_, op) in hlo_program(hlo_text).items()}


def device_time_by_scope(xplane_path: str, hlo_text: Callable[[], str]):
    """Device time of one step of the program a trace mostly ran, by this
    program's own names: ``{'module', 'steps', 'scopes': {(scope, pass):
    ms}, 'kernels': {name: ms}, 'idle': {host span: ms}, 'busy': ms}``,
    milliseconds a step on the first device plane (``busy``: the union of
    the step's events, which the scopes' rows add up to), or ``None`` where the trace has no
    device plane (a CPU's).
    ``hlo_text()`` is that program's compiled text
    (``NetTrainer.step_program_text``; asked for only once a device plane
    is found, it costs a compile): an event is given the ``op_name`` of the
    instruction of its name there, and is ``other`` without one."""
    from jax.profiler import ProfileData
    # a chip's plane is /device:TPU:<n>; the trace has other /device:
    # planes without an op line (/device:CUSTOM:Megascale Trace)
    planes = list(ProfileData.from_file(xplane_path).planes)
    found = sorted(
        (p.name, lines) for p in planes if p.name.startswith('/device:')
        for lines in [{l.name: l for l in p.lines}]
        if _OP_LINE in lines and _MODULE_LINE in lines)
    if not found:
        return None
    lines = found[0][1]
    return reduce_by_scope(list(lines[_OP_LINE].events),
                           list(lines[_MODULE_LINE].events), hlo_text(),
                           host_spans(planes))


def host_spans(planes) -> List[Tuple[str, int, int]]:
    """The hub's spans on a trace's host planes, ``(name, start_ns,
    end_ns)``, of the thread that dispatches the steps (a line that carries
    ``train.dispatch``), and ``host.gc`` of any thread: spans nest within
    one thread only, so another thread's (a checkpoint's writer, a batch's
    producer) would take the dispatching thread's idle gaps for its own,
    while a collection holds every thread wherever it runs."""
    out = []
    for p in planes:
        if not p.name.startswith('/host:'):
            continue
        for line in p.lines:
            spans = [(e.name[len(_SPAN_PREFIX):], e.start_ns,
                      e.start_ns + e.duration_ns) for e in line.events
                     if e.name.startswith(_SPAN_PREFIX)]
            if not any(n == 'train.dispatch' for n, _, _ in spans):
                spans = [x for x in spans if x[0] == 'host.gc']
            out += spans
    return out


def reduce_by_scope(ops, modules, hlo_text: str, host_spans=()):
    """The reduction behind :func:`device_time_by_scope`, over the events of
    one device's op line and module line and the hub's spans on the host's
    planes, ``(name, start_ns, end_ns)``.  The event of a ``while``, a
    ``conditional`` or a ``call`` is left out: it spans its body's events,
    which carry the time."""
    wall: Dict[str, float] = {}
    for m in modules:
        wall[m.name] = wall.get(m.name, 0.0) + m.duration_ns
    if not wall:
        return None
    main = max(wall, key=wall.get)
    runs = sorted((m.start_ns, m.start_ns + m.duration_ns)
                  for m in modules if m.name == main)
    program = hlo_program(hlo_text)
    scopes: Dict[Tuple[str, str], float] = {}
    kernels: Dict[str, float] = {}
    busy: List[Tuple[float, float]] = []
    step_busy, covered = 0.0, runs[0][0]
    i = 0
    for e in sorted(ops, key=lambda e: e.start_ns):
        end = e.start_ns + e.duration_ns
        if runs[0][0] <= e.start_ns < runs[-1][1]:
            busy.append((e.start_ns, end))
        while i < len(runs) and runs[i][1] <= e.start_ns:
            i += 1
        if i == len(runs) or e.start_ns < runs[i][0]:
            continue                   # ran outside the step program
        # the union of the step's events: what the rows below add up to if
        # nothing is counted twice
        step_busy += max(0.0, end - max(e.start_ns, covered))
        covered = max(covered, end)
        name, opcode, _ = parse_instruction(e.name)
        opcode, op_name = program.get(name, (opcode, ''))
        if opcode in _CONTAINERS:
            continue
        key = scope_of(op_name)
        scopes[key] = scopes.get(key, 0.0) + e.duration_ns
        if 'tpu_custom_call' in e.name:
            kernel = re.sub(r'[.\d]+$', '', name)
            kernels[kernel] = kernels.get(kernel, 0.0) + e.duration_ns
    per_step = 1e-6 / len(runs)
    idle = idle_by_host_span(busy, (runs[0][0], runs[-1][1]), host_spans)
    return {'module': main, 'steps': len(runs), 'busy': step_busy * per_step,
            'scopes': {k: v * per_step for k, v in scopes.items()},
            'kernels': {k: v * per_step for k, v in kernels.items()},
            'idle': {k: v * per_step for k, v in idle.items()}}


def idle_by_host_span(busy, window, host_spans) -> Dict[str, float]:
    """Nanoseconds inside ``window`` in which no interval of ``busy``
    (sorted by start) runs, by the host span ``(name, start, end)`` open at
    the time: the innermost where they nest (the one that began last),
    ``outside`` where none is.  ``train.launch`` says the device waited while
    the jitted call had not returned, ``train.dispatch`` for the step loop's
    own host code, ``host.gc`` for the collector, ``io.next`` for a batch,
    ``outside`` for whoever calls the trainer."""
    lo, hi = window
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        gaps.append((at, hi))
    # the spans' boundaries cut the window into pieces with one innermost
    # span each
    cuts = sorted({lo, hi, *(t for _, s, e in host_spans for t in (s, e)
                             if lo < t < hi)})
    spans = sorted(host_spans, key=lambda x: x[1])
    owner = []
    for a, b in zip(cuts, cuts[1:]):
        inner = [n for n, s, e in spans if s <= a and e >= b]
        owner.append(inner[-1] if inner else 'outside')
    out: Dict[str, float] = {}
    for s, e in gaps:
        k = max(bisect.bisect_right(cuts, s) - 1, 0)
        while k < len(owner) and cuts[k] < e:
            cover = min(e, cuts[k + 1]) - max(s, cuts[k])
            if cover > 0:
                out[owner[k]] = out.get(owner[k], 0.0) + cover
            k += 1
    return out


def format_scope_table(table) -> List[str]:
    """The table as lines for stderr: layers in conf order (their scope
    names sort that way), forward and backward side by side, then the
    kernels, then the device's idle time by host span, most first."""
    rows: Dict[str, Dict[str, float]] = {}
    for (scope, pas), ms in table['scopes'].items():
        rows.setdefault(scope, {})[pas] = ms
    total = sum(table['scopes'].values())
    out = [f'profile: {table["module"]}: {table["steps"]} steps traced, '
           f'{total:.3f} ms of device time a step (the device busy '
           f'{table["busy"]:.3f}); by scope '
           f'(fwd / bwd / outside differentiation, ms a step)']
    for scope in sorted(rows):
        r = rows[scope]
        out.append(f'profile-scope\t{scope}\t{r.get("fwd", 0.0):.3f}\t'
                   f'{r.get("bwd", 0.0):.3f}\t{r.get("-", 0.0):.3f}')
    for kernel in sorted(table['kernels']):
        out.append(f'profile-kernel\t{kernel}\t'
                   f'{table["kernels"][kernel]:.3f}')
    for name in sorted(table['idle'], key=table['idle'].get, reverse=True):
        out.append(f'profile-idle\t{name}\t{table["idle"][name]:.3f}')
    return out


def _drain() -> None:
    """Wait for everything dispatched so far: every array the process holds
    is ready (a step's outputs are, once the step has run)."""
    import jax
    jax.block_until_ready(jax.live_arrays())


class TraceWindow:
    """Start/stop ``jax.profiler`` around a window of training batches.
    It waits for the device (:func:`_drain`) before the trace starts and
    before it stops, so that the trace holds the window's steps whole and no
    other: a loop that scores nothing on the host runs many steps ahead of
    the device."""

    def __init__(self, hlo_text: Optional[Callable[[], str]] = None):
        self.hlo_text = hlo_text     # the traced program's compiled text
        self.profile_dir = ''
        self.start_batch = 10
        self.stop_batch = 20
        self._active = False
        self._done = False

    def set_param(self, name: str, val: str) -> None:
        if name == 'profile_dir':
            self.profile_dir = val
        if name == 'profile_start_batch':
            self.start_batch = int(val)
        if name == 'profile_stop_batch':
            self.stop_batch = int(val)

    def configure(self, cfg: List[Tuple[str, str]]) -> None:
        for name, val in cfg:
            self.set_param(name, val)

    @property
    def enabled(self) -> bool:
        return bool(self.profile_dir)

    def before_update(self, batch_counter: int) -> None:
        """Call before each ``trainer.update``; ``batch_counter`` counts from 0."""
        if not self.enabled or self._done:
            return
        if not self._active and batch_counter >= self.start_batch:
            # single-flight vs the on-demand /profile session: if one
            # is mid-trace, retry at the next batch instead of stacking
            # a second global trace on the jax profiler
            if not acquire_trace('profile_dir'):
                return
            import jax
            _drain()
            jax.profiler.start_trace(self.profile_dir)
            self._active = True
        elif self._active and batch_counter >= self.stop_batch:
            self.stop()

    def stop(self) -> None:
        """Finish the trace (idempotent; also call at end of training)."""
        if self._active:
            import jax
            _drain()
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            release_trace('profile_dir')
            self._write_scope_table()

    def _write_scope_table(self) -> None:
        found = sorted(glob.glob(os.path.join(
            self.profile_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
        if not found or self.hlo_text is None:
            return
        table = device_time_by_scope(found[-1], self.hlo_text)
        if table is not None:
            sys.stderr.write('\n'.join(format_scope_table(table)) + '\n')
