"""grafttune search space: the ``autotune=`` grammar (doc/autotune.md).

A :class:`TuneSpace` declares WHICH knobs the tuner may move and HOW FAR,
in the FaultPlan/ScenarioSpec spirit: one ``;``-separated spec string,
parse/describe round-trip, seeded determinism::

    autotune=knobs=steps_per_dispatch:1..8,nworker:1..8;budget=120;mode=train

Every knob a spec may name lives in the :data:`KNOBS` registry with HARD
bounds — a spec asking for a range outside the declared-safe envelope is
a :class:`~cxxnet_tpu.runtime.faults.TuneSpecError` at parse time, before
anything compiles or runs.  ``mem`` marks knobs whose value scales live
accelerator bytes roughly linearly; the stage-1 ledger gate
(search.py) uses that to price candidates from compiler truth alone,
and the online :class:`~cxxnet_tpu.tune.controller.TuneController`
shrinks exactly those knobs under memory pressure.

NOTE the spec string cannot go through ``utils.config.parse_kv_list``:
that helper folds ``,`` into ``;`` (segment separators), which would
tear the comma-separated knob list apart.  :meth:`TuneSpace.parse`
tokenizes the raw text itself — ``;`` separates keys, ``,`` separates
knobs inside the ``knobs=`` value.
"""

import dataclasses
from typing import Dict, Optional, Tuple

from ..runtime import faults

__all__ = ['KnobDecl', 'KNOBS', 'KnobRange', 'TuneSpace']


@dataclasses.dataclass(frozen=True)
class KnobDecl:
    """Registry row: the declared-safe envelope for one tunable knob."""
    name: str
    lo: int          # hard floor — no spec may tune below this
    hi: int          # hard ceiling — no spec may tune above this
    default: int
    mem: bool        # value scales live accelerator bytes ~linearly
    spec: bool = False   # speculative-decoding knob (grow on high accept)
    mem_inv: bool = False   # value scales live bytes ~INVERSELY (splits)


# The full declared-safe knob surface.  Adding a row here is the ONLY way
# to make a knob tunable; doc/autotune.md documents each.
KNOBS: Dict[str, KnobDecl] = {d.name: d for d in (
    KnobDecl('steps_per_dispatch', 1, 64, 1, mem=True),
    KnobDecl('nworker', 1, 16, 1, mem=False),
    KnobDecl('slots', 1, 64, 4, mem=True),
    KnobDecl('pages', 1, 4096, 64, mem=True),
    KnobDecl('page_size', 1, 128, 16, mem=True),
    KnobDecl('spec_k', 0, 8, 0, mem=False, spec=True),
    KnobDecl('max_queue', 1, 1024, 64, mem=False),
    # μ-cuDNN-style convolution microbatching (layers/conv.py): a
    # LARGER split shrinks the conv workspace, so it prices inversely
    KnobDecl('micro_batch', 1, 64, 1, mem=False, mem_inv=True),
)}


@dataclasses.dataclass(frozen=True)
class KnobRange:
    """One knob's tuning interval, already clamp-checked vs its decl."""
    name: str
    lo: int
    hi: int

    def describe(self) -> str:
        return f'{self.name}:{self.lo}..{self.hi}'


def _parse_knob(token: str) -> KnobRange:
    token = token.strip()
    name, sep, rng = token.partition(':')
    name = name.strip()
    decl = KNOBS.get(name)
    if decl is None:
        raise faults.TuneSpecError(
            f'unknown knob {name!r} — declared-safe knobs are '
            f'{sorted(KNOBS)}')
    if not sep:
        return KnobRange(name, decl.lo, decl.hi)
    lo_s, dots, hi_s = rng.partition('..')
    try:
        lo = int(lo_s)
        hi = int(hi_s) if dots else lo
    except ValueError:
        raise faults.TuneSpecError(
            f'bad range for knob {name!r}: {rng!r} (want lo..hi)')
    if lo > hi:
        raise faults.TuneSpecError(
            f'empty range for knob {name!r}: {lo}..{hi}')
    if lo < decl.lo or hi > decl.hi:
        raise faults.TuneSpecError(
            f'knob {name!r} range {lo}..{hi} escapes the declared-safe '
            f'envelope {decl.lo}..{decl.hi}')
    return KnobRange(name, lo, hi)


@dataclasses.dataclass(frozen=True)
class TuneSpace:
    """Parsed ``autotune=`` spec.  Frozen; :meth:`parse`/:meth:`describe`
    round-trip exactly (determinism tests pin this)."""

    knobs: Tuple[KnobRange, ...]
    mode: str = 'train'          # train | decode
    budget: float = 60.0         # stage-2 wall-clock budget, seconds
    seed: int = 0
    probe_steps: int = 8         # steps (or decode requests) per probe
    probe_repeats: int = 2       # timed repeats per probe, best-of
    max_probes: int = 16         # stage-2 cap, independent of budget
    headroom: float = 0.1        # required HBM headroom frac, stage 1
    mem_mb: float = 0.0          # per-device byte ceiling (0 = ledger/HBM)
    compile_budget: int = 8      # online re-plan compile budget

    # doc/autotune.md's grammar table is drift-pinned against this tuple.
    KEYS = ('knobs', 'mode', 'budget', 'seed', 'probe_steps',
            'probe_repeats', 'max_probes', 'headroom', 'mem_mb',
            'compile_budget')

    @classmethod
    def registered_keys(cls) -> Tuple[str, ...]:
        return cls.KEYS

    @classmethod
    def parse(cls, text: str) -> 'TuneSpace':
        vals: Dict[str, object] = {}
        seen = set()
        for seg in str(text).split(';'):
            seg = seg.strip()
            if not seg:
                continue
            key, sep, val = seg.partition('=')
            key = key.strip()
            if not sep or not key:
                raise faults.TuneSpecError(
                    f'malformed autotune segment {seg!r} (want key=value)')
            if key not in cls.KEYS:
                raise faults.TuneSpecError(
                    f'unknown autotune key {key!r} — known keys are '
                    f'{list(cls.KEYS)}')
            if key in seen:
                raise faults.TuneSpecError(
                    f'duplicate autotune key {key!r}')
            seen.add(key)
            val = val.strip()
            try:
                if key == 'knobs':
                    ranges = tuple(_parse_knob(t)
                                   for t in val.split(',') if t.strip())
                    if not ranges:
                        raise faults.TuneSpecError('knobs= declared empty')
                    names = [r.name for r in ranges]
                    if len(set(names)) != len(names):
                        raise faults.TuneSpecError(
                            f'knob listed twice in {val!r}')
                    vals['knobs'] = ranges
                elif key == 'mode':
                    if val not in ('train', 'decode'):
                        raise faults.TuneSpecError(
                            f"mode must be 'train' or 'decode', got {val!r}")
                    vals['mode'] = val
                elif key in ('budget', 'headroom', 'mem_mb'):
                    vals[key] = float(val)
                else:
                    vals[key] = int(val)
            except ValueError:
                raise faults.TuneSpecError(
                    f'bad value for autotune key {key!r}: {val!r}')
        if 'knobs' not in vals:
            raise faults.TuneSpecError(
                "autotune spec must declare 'knobs=' — nothing to tune")
        space = cls(**vals)
        if space.budget <= 0:
            raise faults.TuneSpecError('budget must be > 0 seconds')
        if not 0.0 <= space.headroom < 1.0:
            raise faults.TuneSpecError('headroom must be in [0, 1)')
        if space.probe_steps < 1 or space.probe_repeats < 1 \
                or space.max_probes < 1 or space.compile_budget < 1:
            raise faults.TuneSpecError(
                'probe_steps/probe_repeats/max_probes/compile_budget '
                'must be >= 1')
        return space

    def describe(self) -> str:
        """Canonical spelling; ``parse(describe())`` is the identity."""
        knobs = ','.join(r.describe() for r in self.knobs)
        return (f'knobs={knobs};mode={self.mode};budget={self.budget:g};'
                f'seed={self.seed};probe_steps={self.probe_steps};'
                f'probe_repeats={self.probe_repeats};'
                f'max_probes={self.max_probes};headroom={self.headroom:g};'
                f'mem_mb={self.mem_mb:g};'
                f'compile_budget={self.compile_budget}')

    # -- candidate helpers -------------------------------------------------
    def knob_range(self, name: str) -> Optional[KnobRange]:
        for r in self.knobs:
            if r.name == name:
                return r
        return None

    def mem_knobs(self) -> Tuple[str, ...]:
        return tuple(r.name for r in self.knobs if KNOBS[r.name].mem)

    def mem_inv_knobs(self) -> Tuple[str, ...]:
        """Knobs whose value DIVIDES live accelerator bytes (split
        counts like ``micro_batch``) — the stage-1 gate prices these
        inversely, and the online controller GROWS them under memory
        pressure instead of shrinking."""
        return tuple(r.name for r in self.knobs if KNOBS[r.name].mem_inv)

    def ladder(self, name: str) -> Tuple[int, ...]:
        """Deterministic geometric probe ladder for one knob: the range
        endpoints plus the powers of two between them.  Keeps the
        cross-product tractable without giving up the interesting
        doubling points."""
        rng = self.knob_range(name)
        if rng is None:
            raise faults.TuneSpecError(f'knob {name!r} not in this space')
        vals = {rng.lo, rng.hi}
        v = 1
        while v <= rng.hi:
            if v >= rng.lo:
                vals.add(v)
            v *= 2
        return tuple(sorted(vals))
