"""graftprof — compiler-truth observability (doc/observability.md
"Programs, memory, and MFU").

graftscope/graftwatch made the *runtime* observable; this module makes
the **compiler's** truth observable.  Every load-bearing compiled
executable in the process — the trainer's per-step / scanned-window /
grad / apply programs, PredictEngine's bucket ladder, DecodeEngine's
prefill / decode / verify / spec programs — registers into one
process-wide :class:`ProgramLedger`:

* **program ledger** — each call site claims a :class:`LedgerProgram`
  (a name plus an optional declared shape-key bound) and routes its
  ``jax.jit`` through :meth:`LedgerProgram.jit`.  Dispatch stays the
  plain jit C++ fast path — byte-for-byte the pre-ledger call, so
  every bitwise twin is untouched and the steady-state tax is one
  Python frame; a trace-time hook registers each XLA compilation
  (name, shape-key, signature, sentinel) as it happens, and the
  compiler-truth numbers — compile wall-ms, ``cost_analysis()``
  flops / bytes-accessed, ``memory_analysis()`` argument / output /
  temp / peak bytes — fill lazily via an AOT probe
  (``lower().compile()`` from a ShapeDtypeStruct skeleton) on first
  READ of an entry, never on the dispatch path.  Served raw on
  ``/programs``, summarized in ``/statusz``, exported as gauges on
  ``/metrics`` (so every one is SLO-able through the graftwatch
  engine for free; the cost/memory gauges fill once their entry has
  been read — counts and the sentinel are always live).
* **recompile sentinel** — a program whose compile count exceeds its
  declared bound bumps ``recompiles_total`` and records the typed
  ``faults.RecompileStormError`` kind; ``obs.recompile=raise`` raises
  it at the offending call site (default ``warn``).
* **device-memory gauges** — :class:`DeviceMemory` fills ``hbm.*``
  per-device bytes_in_use / peak / headroom-fraction from
  ``device.memory_stats()``, with a cpu-safe ``jax.live_arrays()``
  fallback (``hbm.supported`` says which source answered).  Registered
  as an ordinary hub StatSet, the existing history sampler and the
  fleet scraper pick it up unchanged (rank labels for free).
* **MFU** — :func:`peak_flops` is the per-platform peak-FLOPs table
  (``CXXNET_PEAK_TFLOPS`` overrides); :func:`mfu` divides ledger
  flops/step × measured steps/sec by it.  The train eval line and
  bench receipts both read it from here so the denominators can't
  drift.
* **on-demand profiler** — :class:`ProfilerSession` backs the
  ``/profile?ms=N`` endpoint: a single-flight ``jax.profiler`` trace
  into the obs dir, mutually exclusive with a config-driven
  ``profile_dir`` TraceWindow (``utils/profiler.acquire_trace``) and
  deliberately NOT demoting the scanned dispatch — an on-demand trace
  observes the program shape that is actually live.
"""

from __future__ import annotations

import collections
import os
import re
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

__all__ = ['ProgramLedger', 'LedgerProgram', 'ProgramEntry', 'get_ledger',
           'install_ledger', 'peak_bytes_for', 'DeviceMemory',
           'register_hbm', 'ProfilerSession', 'profile_session',
           'peak_flops', 'mfu', 'PEAK_BF16_TFLOPS',
           'UnknownDeviceKindError']


# --- per-platform peak FLOPs (MFU denominators) -----------------------------

#: bf16 peak TFLOP/s of one chip by TPU generation (Google Cloud TPU
#: documentation; v5e: 197), matched as a substring of the lower-cased,
#: space-stripped ``device_kind`` — the v5e reports "TPU v5 lite".  THE
#: table: bench.py and the train eval line both divide by it.
PEAK_BF16_TFLOPS: Tuple[Tuple[str, float], ...] = (
    ('v6', 918.0), ('v5p', 459.0), ('v5', 197.0), ('v4', 275.0),
)



def an_instruction_a_line(hlo_text: str) -> str:
    """``hlo_text`` with the one attribute the compiler prints over three
    lines (``kernel_metadata={``, the kernel's JSON, ``}}``) on one."""
    return re.sub(r'(kernel_metadata=\{)\n([^\n]*)\n(\}\})', r'\1\2\3',
                  hlo_text)


class UnknownDeviceKindError(LookupError):
    """An accelerator whose ``device_kind`` has no row in
    :data:`PEAK_BF16_TFLOPS`."""


def peak_flops(device=None) -> float:
    """Peak bf16 FLOP/s of one chip.  ``CXXNET_PEAK_TFLOPS`` overrides
    (how a CPU run or an untabulated part gets an honest denominator);
    0.0 on CPU with no override — MFU is then unreported, never faked.
    An accelerator that is not in the table raises: a guessed
    denominator would print a utilization nobody measured."""
    env = os.environ.get('CXXNET_PEAK_TFLOPS')
    if env:
        return float(env) * 1e12
    import jax
    if device is None:
        device = jax.devices()[0]
    if device.platform == 'cpu':
        return 0.0
    kind = device.device_kind.lower().replace(' ', '')
    for key, tflops in PEAK_BF16_TFLOPS:
        if key in kind:
            return tflops * 1e12
    raise UnknownDeviceKindError(
        f'no peak-FLOPs row for device_kind {device.device_kind!r}; add it '
        f'to PEAK_BF16_TFLOPS or set CXXNET_PEAK_TFLOPS')


def mfu(flops_per_step: float, steps_per_sec: float,
        device=None, devices: int = 1) -> Optional[float]:
    """Model FLOPs utilization, or None when the peak (or the flops)
    is unknown — the null-not-NaN receipt rule, applied to gauges.
    ``flops_per_step`` is the whole step's, over all chips (what the
    ledger reports for a partitioned program too), so the peak is that of
    the ``devices`` chips the step runs on: the trainer's mesh size."""
    peak = peak_flops(device) * max(1, int(devices))
    if peak <= 0 or flops_per_step <= 0 or steps_per_sec <= 0:
        return None
    return flops_per_step * steps_per_sec / peak


# --- the ledger -------------------------------------------------------------

class ProgramEntry:
    """One (program name, shape-key) row of the ledger.  Created at
    trace time with the cheap fields (name, key, signature, counts);
    the compiler-truth fields (flops, bytes, compile_ms) fill lazily on
    first read through :meth:`ProgramLedger.ensure_analyzed`."""

    __slots__ = ('name', 'shape_key', 'signature', 'compile_ms', 'flops',
                 'bytes_accessed', 'argument_bytes', 'output_bytes',
                 'temp_bytes', 'peak_bytes', 'compiles', 'steps', 'seq',
                 '_skel', '_wrapper', '_analyzed')

    def __init__(self, name: str, shape_key: str, signature: str,
                 steps: int, seq: int):
        self.name = name
        self.shape_key = shape_key
        self.signature = signature
        self.compile_ms = 0.0
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.argument_bytes = 0
        self.output_bytes = 0
        self.temp_bytes = 0
        self.peak_bytes = 0
        self.compiles = 0
        self.steps = max(1, int(steps))
        self.seq = seq
        self._skel = None
        self._wrapper = None
        self._analyzed = False

    def view(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__
                if not k.startswith('_')}


def _describe(skel) -> str:
    """Compact human signature for the /programs row: the first few
    array leaves of the skeleton as ``dtype[shape]``."""
    import jax
    parts = []
    for x in jax.tree.leaves(skel):
        shape = getattr(x, 'shape', None)
        dtype = getattr(x, 'dtype', None)
        if shape is None or dtype is None:
            continue
        parts.append(f'{dtype}[{",".join(str(s) for s in shape)}]')
        if len(parts) >= 6:
            parts.append('…')
            break
    return ','.join(parts)


#: bench A/B switch (bench.py obs mode): True suppresses the
#: trace-time recording hook, so the measured "ledger tax" is exactly
#: the wrapper's real per-call cost (one Python frame + this flag
#: check), not a proxy.  Never True in production.
_RAW_JIT = False


def set_raw_jit(flag: bool) -> bool:
    """Flip the bench-only raw-jit bypass; returns the previous value."""
    global _RAW_JIT
    prev, _RAW_JIT = _RAW_JIT, bool(flag)
    return prev


#: set while a lazy AOT analysis probe re-traces a wrapped fn: the
#: trace hook must not count the probe as a fresh compilation
_PROBE_TLS = threading.local()


class _WrappedJit:
    """The ledger-routed replacement for a direct ``jax.jit`` call
    site.  Dispatch IS the plain ``jax.jit`` C++ fast path —
    byte-for-byte the pre-ledger call, so the wrapper's steady-state
    cost is one Python frame (~100 ns) and every bitwise twin is
    untouched by construction.  Compiler truth is harvested OFF the
    hot path: a trace-time hook inside the jitted fn fires once per
    XLA compilation (the idiom PredictEngine's ``compile_count``
    always used), capturing a ``ShapeDtypeStruct`` skeleton of the
    args and registering the entry + recompile sentinel immediately;
    the expensive ``cost_analysis()`` / ``memory_analysis()`` numbers
    are filled lazily — an AOT ``lower().compile()`` from the
    skeleton runs only when somebody actually reads the entry
    (``/programs`` render, ``train_step_flops``, bench receipts),
    never on the dispatch path.  ``fixed=True`` documents a program
    whose signature is static by construction (the decode step over
    preallocated pools); dispatch is identical either way."""

    def __init__(self, program: 'LedgerProgram', fn, key=None, key_fn=None,
                 static_argnames=(), donate_argnums=(), steps: int = 1,
                 fixed: bool = False):
        import jax
        kw = {}
        if static_argnames:
            kw['static_argnames'] = tuple(static_argnames)
        if donate_argnums:
            kw['donate_argnums'] = tuple(donate_argnums)
        self._program = program
        self._static = tuple(static_argnames)
        self._key = key
        self._key_fn = key_fn
        self._steps = max(1, int(steps))
        self._fixed = bool(fixed)
        self._compiles = 0             # guarded-by: _lock
        self._lock = threading.Lock()

        def traced(*args, **kwargs):
            # runs at TRACE time only (once per XLA compilation, args
            # are tracers) — never inside the compiled program
            self._on_trace(args, kwargs)
            return fn(*args, **kwargs)

        # the compiled module and every op_name in it are named after the
        # program's function (jit_train_step, jit(train_step)/...), not
        # after this hook: that is what a profiler trace shows
        traced.__name__ = getattr(fn, '__name__', traced.__name__)
        self._jit = jax.jit(traced, **kw)

    @staticmethod
    def _skeleton(x):
        import jax
        if hasattr(x, 'shape') and hasattr(x, 'dtype'):
            return jax.ShapeDtypeStruct(
                tuple(x.shape), x.dtype,
                weak_type=getattr(x, 'weak_type', False))
        return x                       # static / python-scalar leaf

    def _on_trace(self, args, kwargs) -> None:
        if getattr(_PROBE_TLS, 'active', False) or _RAW_JIT:
            return
        import jax
        skel = jax.tree.map(self._skeleton, (args, kwargs))
        key = self._key
        if key is None and self._key_fn is not None:
            key = str(self._key_fn(args, kwargs))
        with self._lock:
            self._compiles += 1
        self._program._record(key, skel, self, steps=self._steps)

    def __call__(self, *args, **kwargs):
        # the C++ jit fast path, raw or not: _RAW_JIT (the bench A/B
        # twin) only suppresses the trace hook, so the measured "tax"
        # is exactly this wrapper frame
        return self._jit(*args, **kwargs)

    def _analyze(self, skel) -> tuple:
        """AOT-compile the skeleton signature and return
        ``(compile_ms, compiled)`` — the lazy analysis probe, run off
        the hot path by :meth:`ProgramLedger.ensure_analyzed`."""
        args, kwargs = skel
        t0 = time.monotonic()
        compiled = self._probe_compile(args, kwargs)
        return (time.monotonic() - t0) * 1e3, compiled

    def _probe_compile(self, args, kwargs):
        """``lower().compile()`` with the trace hook suppressed: counts
        and the sentinel never see a probe."""
        _PROBE_TLS.active = True
        try:
            return self._jit.lower(*args, **kwargs).compile()
        finally:
            _PROBE_TLS.active = False

    def compiled_text(self, *args, **kwargs) -> str:
        """The compiled program's text for these arguments (live arrays
        or ``ShapeDtypeStruct``s carrying their shardings, so a program
        partitioned over a mesh reads as the one that runs), without
        executing it and without counting as a compilation.  What
        ``utils/profiler.device_time_by_scope`` joins a trace's events
        to.  An instruction a line: a kernel's attributes that the compiler
        prints over several lines (the JSON that JAX's splash-attention
        kernels attach) are joined, so that a reader by the line finds the
        instruction's ``op_name``."""
        return an_instruction_a_line(
            self._probe_compile(args, kwargs).as_text())

    def ensure_compiled(self, *args, **kwargs) -> Optional['ProgramEntry']:
        """Register (and analyze) this signature WITHOUT executing —
        the ``train_step_flops`` probe path; returns the newest entry.
        Never runs the program: donated buffers stay live."""
        import jax
        skel = jax.tree.map(self._skeleton, (args, kwargs))
        key = self._key
        if key is None and self._key_fn is not None:
            key = str(self._key_fn(args, kwargs))
        with self._lock:
            self._compiles += 1
        entry = self._program._record(key, skel, self,
                                      steps=self._steps)
        if entry is not None:
            self._program.ledger.ensure_analyzed(entry)
        return self._program.newest_entry()

    def _cache_size(self) -> int:
        """Compilations seen by this wrapper — the same surface jax's
        jit wrapper exposes, kept so the compile-cache bound tests
        read one number either way."""
        with self._lock:
            return self._compiles


class LedgerProgram:
    """One named program family in the ledger (claimed via
    :meth:`ProgramLedger.program`).  ``bound`` is the declared shape-key
    bound the recompile sentinel enforces: more compiles than ``bound``
    (novel keys OR re-traces of a known one) is a storm."""

    def __init__(self, ledger: 'ProgramLedger', name: str,
                 bound: Optional[int] = None):
        self.ledger = ledger
        self.name = name
        self.bound = None if bound is None else int(bound)
        # compiles/_keys/_warned are mutated only inside the LEDGER's
        # record_compile (under its lock); reads are monotonic tallies
        self.compiles = 0
        self._keys: set = set()
        self._warned = False

    def jit(self, fn, *, key=None, key_fn=None, static_argnames=(),
            donate_argnums=(), steps: int = 1,
            fixed: bool = False) -> _WrappedJit:
        """Wrap ``fn`` as a ledger-routed jitted program.  ``key`` (or
        ``key_fn(args, kwargs)``) names the shape-key of each compile
        (default: auto ``v<N>``); ``steps`` is the per-entry flops
        normalization (a K-step scanned window registers steps=K)."""
        return _WrappedJit(self, fn, key=key, key_fn=key_fn,
                           static_argnames=static_argnames,
                           donate_argnums=donate_argnums, steps=steps,
                           fixed=fixed)

    def _record(self, key, skel, wrapper, steps=1):
        return self.ledger.record_trace(self, key, skel, wrapper,
                                        steps=steps)

    def retire(self) -> None:
        """Owner is shutting down: stop all future AOT probes of this
        family's entries (see :meth:`ProgramLedger.retire_program`)."""
        self.ledger.retire_program(self.name)

    def compile_headroom(self) -> Optional[int]:
        """Compiles this family can still absorb before the recompile
        sentinel calls a storm (``bound - compiles``); None = unbounded.
        The online tuner's re-plan guard reads this BEFORE compiling a
        candidate (doc/autotune.md "Recompile budget")."""
        if self.bound is None:
            return None
        with self.ledger._lock:
            return self.bound - self.compiles

    def entries(self, analyze: bool = True) -> List[ProgramEntry]:
        return self.ledger.entries_for(self.name, analyze=analyze)

    def newest_entry(self) -> Optional[ProgramEntry]:
        es = self.entries()
        return es[-1] if es else None

    def flops_per_step(self) -> float:
        """Newest flops-bearing entry's flops, normalized by its step
        count — 0.0 when nothing compiled (or the backend has no cost
        model)."""
        for e in reversed(self.entries()):
            if e.flops > 0:
                return e.flops / e.steps
        return 0.0

    def argument_bytes(self) -> int:
        """Newest entry's argument bytes (the compiled program's true
        resident working set) — what ``budget_drift`` cross-checks the
        closed-form ``resident_bytes()`` ledgers against."""
        e = self.newest_entry()
        return e.argument_bytes if e is not None else 0


class ProgramLedger:
    """Process-wide registry of compiled executables (module
    docstring).  Thread-safe; entries are bounded (oldest pruned) so a
    long test session or a model-cycling fleet cannot grow it without
    bound."""

    MAX_ENTRIES = 512

    def __init__(self):
        self._lock = threading.Lock()
        self._analyze_lock = threading.Lock()   # serializes AOT probes
        self._recompile = 'warn'       # obs.recompile: warn | raise | off
        self._names: Dict[str, int] = {}          # guarded-by: _lock
        self._entries: 'collections.OrderedDict[Tuple[str, str], ProgramEntry]' = \
            collections.OrderedDict()             # guarded-by: _lock
        self._seq = 0                  # guarded-by: _lock
        self.compiles_total = 0        # guarded-by: _lock
        self.recompiles_total = 0      # guarded-by: _lock
        self.compile_ms_total = 0.0    # guarded-by: _lock
        self._stats = None

    # -- program claims ----------------------------------------------------
    def program(self, name: str,
                bound: Optional[int] = None) -> LedgerProgram:
        """Claim a program name.  A re-claimed base name gets a ``#N``
        suffix (each engine/trainer instance owns its own sentinel
        state and its own entries; the ledger keeps both histories)."""
        with self._lock:
            n = self._names.get(name, 0)
            self._names[name] = n + 1
            full = name if n == 0 else f'{name}#{n + 1}'
        return LedgerProgram(self, full, bound=bound)

    def retire_program(self, name: str) -> None:
        """Drop the analysis hooks of every entry under ``name`` — called
        when the owning engine closes.  Rows and any compiler truth
        already probed stay in the ledger views; un-probed entries are
        marked analyzed with zeros (the failed-probe policy), so a later
        :meth:`entries` sweep never AOT-compiles a dead program — the
        owner's mesh/devices may be gone, and re-lowering a stale SPMD
        skeleton late in the process is exactly the probe that can take
        the whole XLA client down."""
        with self._analyze_lock:       # exclude an in-flight probe
            with self._lock:
                for (n, _k), e in self._entries.items():
                    if n == name:
                        e._wrapper = None
                        e._skel = None
                        e._analyzed = True

    def set_recompile(self, mode: str) -> None:
        if mode not in ('warn', 'raise', 'off'):
            raise ValueError(
                f'obs.recompile must be warn|raise|off, got {mode!r}')
        self._recompile = mode

    @property
    def recompile_mode(self) -> str:
        return self._recompile

    # -- recording ---------------------------------------------------------
    @staticmethod
    def _cost_dict(compiled) -> dict:
        try:
            ca = compiled.cost_analysis()
        # lint: allow(fault-taxonomy): backends without a cost model surface it many ways; the entry degrades to zeros, the program still runs
        except Exception:
            return {}
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return dict(ca or {})

    @staticmethod
    def _memory(compiled):
        try:
            return compiled.memory_analysis()
        # lint: allow(fault-taxonomy): memory_analysis is optional per backend; the entry degrades to zeros, the program still runs
        except Exception:
            return None

    def record_trace(self, program: LedgerProgram, key, skel, wrapper,
                     steps: int = 1) -> Optional[ProgramEntry]:
        """Register one XLA compilation of ``program`` (fired by the
        wrapper's trace-time hook — args are a ShapeDtypeStruct
        skeleton).  Cheap by design: counts, sentinel, and the human
        signature only; cost/memory analysis is deferred to
        :meth:`ensure_analyzed`.  Under ``obs.recompile=raise`` a storm
        raises ``faults.RecompileStormError`` at the offending call
        site."""
        signature = _describe(skel)
        with self._lock:
            program.compiles += 1
            if key is None:
                key = f'v{len(program._keys)}'
            program._keys.add(key)
            ek = (program.name, str(key))
            entry = self._entries.get(ek)
            if entry is None:
                self._seq += 1
                entry = ProgramEntry(program.name, str(key), signature,
                                     steps, self._seq)
                self._entries[ek] = entry
                while len(self._entries) > self.MAX_ENTRIES:
                    self._entries.popitem(last=False)
            else:
                self._entries.move_to_end(ek)
            entry.compiles += 1
            entry.signature = signature
            entry.steps = max(1, int(steps))
            entry._skel = skel
            entry._wrapper = wrapper
            entry._analyzed = False      # a fresh compile: re-probe
            self.compiles_total += 1
            storm = (program.bound is not None
                     and program.compiles > program.bound
                     and self._recompile != 'off')
            if storm:
                self.recompiles_total += 1
            warn_now = storm and not program._warned \
                and self._recompile == 'warn'
            if warn_now:
                program._warned = True
            mode = self._recompile
        from .hub import record_event
        record_event(f'compile.{program.name}', 'obs', key=str(key))
        if storm and mode != 'off':
            from ..runtime import faults
            err = faults.RecompileStormError(program.name, key,
                                             program.bound,
                                             program.compiles)
            faults.global_failure_log().record('RecompileStormError',
                                               str(err))
            if mode == 'raise':
                raise err
            if warn_now:
                import sys
                sys.stderr.write(f'obs: {err}\n')
        return entry

    def ensure_analyzed(self,
                        entry: Optional[ProgramEntry]
                        ) -> Optional[ProgramEntry]:
        """Fill the compiler-truth fields of ``entry`` (flops, bytes,
        compile wall-ms) by AOT-compiling its recorded skeleton — run
        on first READ of an entry (``/programs``, ``train_step_flops``,
        ``budget_drift``, bench receipts), never on the dispatch path.
        The probe re-traces through the wrapper with the hook
        suppressed, so counts and the sentinel never see it.
        Idempotent; a failed probe marks the entry analyzed with zeros
        (the program itself keeps running)."""
        if entry is None or entry._analyzed:
            return entry
        with self._analyze_lock:
            self._probe_and_fill(entry)
        return entry

    def _probe_and_fill(self, entry: ProgramEntry) -> None:  # requires-lock: _analyze_lock
        """One entry's AOT probe + compiler-truth fill — the body both
        :meth:`ensure_analyzed` and the batched sweep share.  A failed
        (or wrapper-less) probe marks the entry analyzed with zeros."""
        if entry._analyzed:
            return
        wrapper, skel = entry._wrapper, entry._skel
        if wrapper is None:
            entry._analyzed = True
            return
        try:
            ms, compiled = wrapper._analyze(skel)
        # lint: allow(fault-taxonomy): the analysis probe degrades to a zero-filled row; the program itself already compiled and runs
        except Exception:
            entry._analyzed = True
            return
        self._fill(entry, ms, compiled)

    def _fill(self, entry: ProgramEntry, ms: float, compiled) -> None:
        cost = self._cost_dict(compiled)
        mem = self._memory(compiled)
        arg = out = temp = peak = 0
        if mem is not None:
            arg = int(getattr(mem, 'argument_size_in_bytes', 0) or 0)
            out = int(getattr(mem, 'output_size_in_bytes', 0) or 0)
            temp = int(getattr(mem, 'temp_size_in_bytes', 0) or 0)
            peak = int(getattr(mem, 'peak_size_in_bytes', 0) or 0)
            if peak == 0:
                # XLA:CPU reports no live-range peak; argument+
                # output+temp is the honest upper bound of what the
                # program holds at once
                peak = arg + out + temp
        with self._lock:
            entry.compile_ms = float(ms)
            entry.flops = float(cost.get('flops', 0.0) or 0.0)
            entry.bytes_accessed = float(
                cost.get('bytes accessed', 0.0) or 0.0)
            entry.argument_bytes = arg
            entry.output_bytes = out
            entry.temp_bytes = temp
            entry.peak_bytes = peak
            entry._analyzed = True
            self.compile_ms_total += float(ms)

    def ensure_analyzed_batch(self, names=None, workers: int = 4) -> int:
        """Batched AOT analysis: fill every unanalyzed entry (of the
        program families in ``names``, or all of them) by fanning the
        lowerings out over a short-lived worker pool instead of
        serializing N probes on the caller thread — the autotuner's
        stage-1 sweep and the ``/programs`` first-read both need the
        whole ledger's compiler truth at once (doc/autotune.md).

        Holds ``_analyze_lock`` for the sweep, so concurrent single
        :meth:`ensure_analyzed` calls serialize against it exactly as
        before; each probe thread re-traces with the hook suppressed
        (``_PROBE_TLS`` is thread-local), so counts and the recompile
        sentinel never see the batch.  Returns how many entries this
        call analyzed (failed probes count — they are marked analyzed
        with zeros, same as the single-entry path)."""
        wanted = None if names is None else set(names)
        with self._lock:
            todo = sorted(
                (e for (n, _k), e in self._entries.items()
                 if not e._analyzed and (wanted is None or n in wanted)),
                key=lambda e: e.seq)
        if not todo:
            return 0
        with self._analyze_lock:
            todo = [e for e in todo if not e._analyzed]
            if not todo:
                return 0
            probed = []
            results = {}                 # seq -> (ms, compiled)
            res_lock = threading.Lock()

            def probe(entry):
                wrapper, skel = entry._wrapper, entry._skel
                if wrapper is None:
                    return
                try:
                    ms, compiled = wrapper._analyze(skel)
                # lint: allow(fault-taxonomy): a failed batch probe degrades that one row to zeros, like the single-entry path
                except Exception:
                    return
                with res_lock:
                    results[entry.seq] = (ms, compiled)

            n_workers = max(1, min(int(workers), len(todo)))
            if n_workers == 1:
                for e in todo:
                    probe(e)
            else:
                queue = list(todo)
                q_lock = threading.Lock()

                def drain():
                    while True:
                        with q_lock:
                            if not queue:
                                return
                            e = queue.pop(0)
                        probe(e)

                threads = [threading.Thread(
                    target=drain, name=f'cxxnet-obs-aot-{i}', daemon=True)
                    for i in range(n_workers)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            for e in todo:
                got = results.get(e.seq)
                if got is None:
                    # wrapper-less or failed probe: analyzed-with-zeros,
                    # exactly like the single-entry path
                    e._analyzed = True
                else:
                    self._fill(e, got[0], got[1])
                probed.append(e)
        return len(probed)

    # -- views -------------------------------------------------------------
    def entries_for(self, name: str,
                    analyze: bool = True) -> List[ProgramEntry]:
        """Entries of one program family.  ``analyze=False`` skips the
        lazy AOT probe — the read-only spelling for render threads
        (/statusz providers, gauge refreshes) that must never block on
        an XLA compile; unanalyzed entries then report zero flops."""
        if analyze:
            self.ensure_analyzed_batch(names=(name,))
        with self._lock:
            return sorted((e for (n, _k), e in self._entries.items()
                           if n == name), key=lambda e: e.seq)

    def entries(self) -> List[ProgramEntry]:
        # the /programs first read: one batched sweep, not N serialized
        # lowerings on the render thread
        self.ensure_analyzed_batch()
        with self._lock:
            return sorted(self._entries.values(), key=lambda e: e.seq)

    def view(self) -> dict:
        """The ``/programs`` body: every entry plus the totals."""
        entries = self.entries()
        with self._lock:
            totals = (self.compiles_total, self.recompiles_total,
                      self.compile_ms_total)
        return {
            'programs': [e.view() for e in entries],
            'compiles_total': totals[0],
            'recompiles_total': totals[1],
            'compile_ms_total': round(totals[2], 3),
            'recompile_mode': self._recompile,
        }

    def summary(self) -> dict:
        """The ``/statusz`` (and bench-receipt) one-liner: counts and
        compile cost, no per-entry detail."""
        with self._lock:
            n = len(self._entries)
            totals = (self.compiles_total, self.recompiles_total,
                      self.compile_ms_total)
        return {
            'programs': n,
            'compiles_total': totals[0],
            'recompiles_total': totals[1],
            'compile_ms_total': round(totals[2], 3),
            'recompile_mode': self._recompile,
        }

    @staticmethod
    def _base_name(name: str) -> str:
        return name.split('#', 1)[0]

    def _refresh_stats(self) -> None:
        stats = self._stats
        if stats is None:
            return
        with self._lock:
            entries = list(self._entries.values())
            stats_tuples = (len(self._entries), self.compiles_total,
                            self.recompiles_total, self.compile_ms_total)
        stats.gauge('programs', stats_tuples[0])
        stats.gauge('compiles_total', stats_tuples[1])
        stats.gauge('recompiles_total', stats_tuples[2])
        stats.gauge('compile_ms_total', round(stats_tuples[3], 3))
        # base-name aggregation keeps the /metrics label cardinality
        # bounded by the dozen-odd program families, not the entry cap.
        # Cost/memory gauges cover ANALYZED entries only (a render must
        # never trigger AOT probes from the sampler thread); counts
        # above are always live, and the detailed readers (/programs,
        # train_step_flops, budget_drift) fill the rest on first read
        agg: Dict[str, List[float]] = {}
        for e in sorted(entries, key=lambda e: e.seq):
            a = agg.setdefault(self._base_name(e.name), [0.0, 0.0, 0.0])
            a[0] = max(a[0], e.flops / e.steps)
            a[1] = max(a[1], float(e.peak_bytes))
            a[2] += e.compile_ms * e.compiles
        for base, (flops, peakb, cms) in agg.items():
            stats.gauge(f'flops[{base}]', flops)
            stats.gauge(f'peak_bytes[{base}]', peakb)
            stats.gauge(f'compile_ms[{base}]', round(cms, 3))

    def register_into(self, hub) -> None:
        """Join the telemetry hub: a ``programs`` StatSet on
        ``/metrics`` (and thereby the history sampler / SLO engine /
        fleet view) plus a ``programs`` ``/statusz`` provider."""
        if self._stats is None:
            from ..utils.metric import StatSet
            self._stats = StatSet()
        hub.register_stats('programs', self._stats,
                           refresh=self._refresh_stats)
        hub.register_status('programs', self.summary)


# --- device-memory (hbm.*) gauges -------------------------------------------

class DeviceMemory:
    """Per-device memory gauges (``hbm.*``): ``bytes_in_use[dN]`` /
    ``peak_bytes[dN]`` / ``headroom_frac[dN]`` from
    ``device.memory_stats()`` where the runtime exposes it (TPU/GPU),
    falling back to a ``jax.live_arrays()`` walk on CPU
    (``supported=0``; peak is then the in-process monotone max, and
    headroom is unreported — there is no limit to be under)."""

    def __init__(self):
        self._peak_seen: Dict[int, float] = {}

    def fill(self, stats) -> None:
        """Refresh hook: write the current per-device gauges into
        ``stats`` (called per /metrics render and per sampler tick)."""
        import jax
        fallback = None
        for i, dev in enumerate(jax.local_devices()):
            tag = f'd{i}'
            try:
                ms = dev.memory_stats()
            # lint: allow(fault-taxonomy): a backend without memory_stats must degrade to the live-array fallback, never kill the render
            except Exception:
                ms = None
            if ms and 'bytes_in_use' in ms:
                in_use = float(ms['bytes_in_use'])
                peak = float(ms.get('peak_bytes_in_use', in_use))
                stats.gauge(f'bytes_in_use[{tag}]', in_use)
                stats.gauge(f'peak_bytes[{tag}]', peak)
                limit = float(ms.get('bytes_limit', 0.0))
                if limit > 0:
                    stats.gauge(f'limit_bytes[{tag}]', limit)
                    stats.gauge(f'headroom_frac[{tag}]',
                                max(0.0, 1.0 - in_use / limit))
                stats.gauge('supported', 1)
            else:
                if fallback is None:
                    fallback = self._live_bytes()
                in_use = fallback.get(dev.id, 0.0)
                peak = max(self._peak_seen.get(dev.id, 0.0), in_use)
                self._peak_seen[dev.id] = peak
                stats.gauge(f'bytes_in_use[{tag}]', in_use)
                stats.gauge(f'peak_bytes[{tag}]', peak)
                stats.gauge('supported', 0)

    @staticmethod
    def _live_bytes() -> Dict[int, float]:
        """CPU fallback: bytes of every live ``jax.Array`` attributed
        per device from its addressable shards — a model-sharded array
        adds each device's OWN shard bytes, a replicated one its full
        bytes on EVERY device it occupies.  (An even split over the
        device set undercounts replicated arrays N-fold, which is
        exactly the error the sharded-serving budget reconciliation
        would trip over.)"""
        import jax
        out: Dict[int, float] = {}
        for arr in jax.live_arrays():
            try:
                for sh in arr.addressable_shards:
                    out[sh.device.id] = (out.get(sh.device.id, 0.0)
                                         + sh.data.nbytes)
            # lint: allow(fault-taxonomy): a deleted/donated array mid-walk must not kill the gauge fill
            except Exception:
                continue
        return out


def register_hbm(hub):
    """Register the ``hbm`` StatSet (with a :class:`DeviceMemory`
    refresh) into ``hub``; returns the StatSet.  The history sampler
    and fleet scraper consume it with zero extra wiring."""
    from ..utils.metric import StatSet
    dm = DeviceMemory()
    stats = StatSet()
    hub.register_stats('hbm', stats, refresh=lambda: dm.fill(stats))
    return stats


# --- on-demand profiler session ---------------------------------------------

class ProfilerSession:
    """Single-flight on-demand ``jax.profiler`` window — the
    ``/profile?ms=N`` endpoint's engine.  One trace at a time per
    process, mutually exclusive with a config-driven ``profile_dir``
    TraceWindow through ``utils/profiler.acquire_trace``; a second
    request while one runs answers ``busy`` instead of corrupting the
    active trace.  The stop rides a named daemon timer thread so the
    requesting scrape returns immediately."""

    MIN_MS = 50.0
    MAX_MS = 60_000.0

    def __init__(self):
        self._lock = threading.Lock()
        self._active: Optional[str] = None   # guarded-by: _lock
        self._seq = 0                        # guarded-by: _lock
        self.sessions = 0                    # guarded-by: _lock

    def start(self, out_dir: str, ms: float = 1000.0) -> dict:
        """Begin one bounded trace into ``out_dir``; returns a JSON-able
        result (``started``/``path``/``ms``, or ``busy`` naming the
        holder)."""
        from ..utils import profiler as _prof
        ms = min(self.MAX_MS, max(self.MIN_MS, float(ms)))
        with self._lock:
            if self._active is not None:
                return {'started': False, 'busy': self._active}
            if not _prof.acquire_trace('obs.profile'):
                return {'started': False,
                        'busy': _prof.trace_owner() or 'profile_dir'}
            self._seq += 1
            path = os.path.join(out_dir,
                                f'profile_{os.getpid()}_{self._seq:03d}')
            self._active = path
        try:
            import jax
            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
        except BaseException:
            # release the slot BEFORE clearing _active: a racing
            # start() keeps answering busy until both are undone, so
            # the slot can never be released out from under a session
            # that just acquired it
            _prof.release_trace('obs.profile')
            with self._lock:
                self._active = None
            raise
        t = threading.Thread(target=self._stop_after, args=(ms / 1e3,),
                             daemon=True, name='cxxnet-obs-profile')
        t.start()
        return {'started': True, 'path': path, 'ms': ms}

    def _stop_after(self, seconds: float) -> None:
        from ..utils import profiler as _prof
        time.sleep(seconds)
        try:
            import jax
            jax.profiler.stop_trace()
        # lint: allow(fault-taxonomy): a failed trace stop must still release the single-flight slot or /profile wedges forever
        except Exception:
            pass
        finally:
            # release-then-clear, in that order: until _active clears a
            # racing start() answers busy, so this thread can never
            # release the slot out from under a session that just
            # acquired it (the hazard of the reverse order)
            _prof.release_trace('obs.profile')
            with self._lock:
                self._active = None
                self.sessions += 1

    def status(self) -> dict:
        with self._lock:
            return {'active': self._active, 'sessions': self.sessions}


_PROFILE: Optional[ProfilerSession] = None
_LEDGER: Optional[ProgramLedger] = None
_MOD_LOCK = threading.Lock()


def profile_session() -> ProfilerSession:
    """The process-wide profiler session (created on first use)."""
    global _PROFILE
    p = _PROFILE
    if p is None:
        with _MOD_LOCK:
            if _PROFILE is None:
                _PROFILE = ProfilerSession()
            p = _PROFILE
    return p


def get_ledger() -> ProgramLedger:
    """The process-wide program ledger (created on first use)."""
    global _LEDGER
    led = _LEDGER
    if led is None:
        with _MOD_LOCK:
            if _LEDGER is None:
                _LEDGER = ProgramLedger()
            led = _LEDGER
    return led


def install_ledger(ledger: Optional[ProgramLedger]
                   ) -> Optional[ProgramLedger]:
    """Swap the process-wide ledger (tests); returns the previous one.
    ``None`` resets to a fresh default on next :func:`get_ledger`."""
    global _LEDGER
    with _MOD_LOCK:
        prev, _LEDGER = _LEDGER, ledger
    return prev


def peak_bytes_for(name: str, ledger: Optional[ProgramLedger] = None) -> int:
    """Compiler-truth peak HBM bytes of one program family: the max
    ``memory_analysis`` peak over every analyzed entry whose base name
    matches ``name`` (``#N`` re-claim suffixes included).  The one
    number the ``micro_batch`` bench sweep and the autotuner's memory
    gate compare across candidate splits — 0 when nothing under the
    name has compiled yet (never a guess)."""
    led = ledger if ledger is not None else get_ledger()
    led.ensure_analyzed_batch()
    peak = 0
    for e in led.entries():
        if ProgramLedger._base_name(e.name) == name:
            peak = max(peak, int(e.peak_bytes))
    return peak
