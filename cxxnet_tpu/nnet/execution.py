"""ExecutionPlan: ONE composable step loop for every training run.

PR 5 introduced ``steps_per_dispatch=K`` (one ``lax.scan`` dispatch per K
staged batches — zero per-step link RTT) but guarded it with a fallback
matrix that demoted to per-step whenever ``supervise``, ``update_period>1``
or ``eval_train`` metrics were on — i.e. on every production run.  This
module is the μ-cuDNN lesson (PAPERS.md) applied to the loop itself: the
fast path must COMPOSE with the real workload's constraints, not exclude
them.

* :class:`ExecutionPlan` resolves the requested K once per run into an
  effective plan.  The only remaining static demotions are profiling
  (``profile_dir`` — a trace window cannot bracket steps inside one
  dispatch) and ``test_io`` (nothing is dispatched at all); everything
  else — gradient accumulation, supervised recovery, train metrics,
  async saves — now rides the scan (``trainer.compile_multi_step``).
* :class:`WindowedStepper` is the loop body both the plain round and the
  supervised round drive: feed batches one at a time, it stages them
  (async H2D), dispatches a K-window (or per-step with the classic
  one-batch lookahead when K=1), and handles the one RUNTIME demotion —
  an ``attachtxt`` chain attaching ``extra_data`` mid-round — for the
  CURRENT round only (the next round re-probes; nothing is permanently
  mutated).
* ``scan_strict=1`` turns any demotion into a typed
  ``runtime.faults.ScanStrictError`` so production configs can assert
  they actually got the scanned path instead of discovering a silent
  10x dispatch-overhead regression in a dashboard.

``DEMOTION_REASONS`` is the programmatic registry of every way a plan can
demote; ``tests/test_execution_plan.py`` asserts it matches the documented
matrix in ``doc/trainer.md`` so the docs cannot silently rot.
"""

from __future__ import annotations

from typing import Callable, Optional

from ..runtime import faults

#: Every way the scanned K-dispatch path can demote to per-step, keyed by
#: the reason tag `scan_strict` errors and fallback notes carry.  This IS
#: the fallback matrix (doc/trainer.md keeps the prose copy; a tier-1
#: drift test pins the two together).
DEMOTION_REASONS = {
    'profile_dir': 'the trace window brackets per-step dispatches — '
                   'inside a scanned window there is nothing to '
                   'start/stop the profiler between',
    'test_io': 'test_io=1 dispatches no compute at all',
    'extra_data': 'the scan body carries data+label+mask only; an '
                  'attachtxt chain\'s extra_data demotes this round '
                  '(re-probed next round)',
}

#: Reasons resolved once at plan creation (config/run shape) vs. detected
#: mid-round from the batch stream.
STATIC_REASONS = ('profile_dir', 'test_io')
RUNTIME_REASONS = ('extra_data',)


class ExecutionPlan:
    """The resolved step-loop shape for one training run.

    Build via :meth:`resolve`; then ask for one :class:`WindowedStepper`
    per round (:meth:`round_stepper`) — per-round steppers are what make
    the ``extra_data`` demotion a round property instead of a permanent
    trainer mutation.  Compiled multi-step programs are cached on the
    plan across rounds (keyed by (K, train_eval))."""

    def __init__(self, requested_k: int, k: int, strict: bool = False,
                 silent: bool = False):
        self.requested_k = int(requested_k)
        self.k = int(k)
        self.strict = bool(strict)
        self.silent = bool(silent)
        self._noted = set()
        self._scan_fns = {}

    @classmethod
    def resolve(cls, requested_k: int, profiling: bool = False,
                test_io: bool = False, strict: bool = False,
                silent: bool = False) -> 'ExecutionPlan':
        """Resolve the effective plan for this run.  Raises
        ``faults.ScanStrictError`` when ``strict`` and a static demotion
        applies; otherwise demotions print one note per reason."""
        k = max(1, int(requested_k))
        reason = None
        if k > 1:
            if test_io:
                reason = 'test_io'
            elif profiling:
                reason = 'profile_dir'
        plan = cls(requested_k=k, k=(1 if reason else k), strict=strict,
                   silent=silent)
        if reason is not None:
            plan.demote(reason)
        # the run's plan choice is /statusz state: one provider per
        # process, latest resolve wins (one plan per run by contract)
        from ..obs import get_hub
        get_hub().register_status(
            'execution_plan',
            lambda p=plan: {'requested_k': p.requested_k, 'k': p.k,
                            'scanned': p.scanned,
                            'demotions': sorted(p._noted),
                            # compiler truth (obs/programs.py): the
                            # per-step HLO flops of whatever program
                            # this plan is actually dispatching
                            'flops_per_step': p.flops_per_step()})
        return plan

    @property
    def scanned(self) -> bool:
        return self.k > 1

    def flops_per_step(self) -> float:
        """Ledger flops/step of the trainer this plan last built a
        stepper for (0.0 before the first round or first compile).
        analyzed_only: this renders on the /statusz endpoint thread,
        which must never block on a lazy AOT analysis probe — it
        reports 0.0 until the MFU line (or /programs) fills the
        entry."""
        trainer = getattr(self, '_trainer', None)
        if trainer is None:
            return 0.0
        return trainer.train_step_flops(analyzed_only=True)

    def demote(self, reason: str) -> None:
        """Register a demotion: typed error under ``scan_strict=1``,
        otherwise a once-per-reason stdout note (a run that demotes for
        reason A must still report a later, different reason B)."""
        if self.strict:
            raise faults.ScanStrictError(reason, DEMOTION_REASONS[reason])
        self.note(reason)

    def note(self, reason: str) -> Optional[str]:
        """The fallback note for ``reason`` — printed (unless silent) and
        returned the FIRST time each reason occurs, None after."""
        if reason in self._noted:
            return None
        self._noted.add(reason)
        msg = (f'steps_per_dispatch={self.requested_k} falls back to '
               f'per-step: {DEMOTION_REASONS[reason]}')
        if not self.silent:
            print(msg, flush=True)
        return msg

    def scan_fn(self, trainer, train_eval: bool):
        key = (self.k, bool(train_eval))
        if key not in self._scan_fns:
            self._scan_fns[key] = trainer.compile_multi_step(
                self.k, train_eval=train_eval)
        return self._scan_fns[key]

    def round_stepper(self, trainer, before_dispatch=None,
                      lookahead: int = 1) -> 'WindowedStepper':
        """A fresh stepper for one round's batches.  ``lookahead`` only
        shapes the per-step (K=1 / demoted) path: 1 = the classic
        one-batch H2D lookahead of the plain loop, 0 = dispatch
        immediately (the supervised loop, whose recovery re-winds by
        DISPATCHED steps and simply discards staged-but-undispatched
        work)."""
        self._trainer = trainer        # /statusz flops_per_step source
        scan = None
        if self.scanned:
            armed = bool(trainer.eval_train and len(trainer.train_metric))
            scan = self.scan_fn(trainer, armed)
        return WindowedStepper(trainer, k=self.k, scan_fn=scan,
                               lookahead=lookahead,
                               before_dispatch=before_dispatch,
                               on_demote=self.demote)


class WindowedStepper:
    """One round's step loop at window granularity — THE loop body.

    ``feed(batch)`` stages the batch (async H2D enqueue) and dispatches
    whenever a window fills; ``finish()`` drains the tail on the per-step
    path (bitwise-identical, so epoch length need not divide K).  With
    ``k=1`` it IS the per-step loop (with ``lookahead`` staged batches
    riding ahead of the dispatch), so plain, scanned, and supervised
    rounds all drive this one implementation.

    ``feed``/``finish`` return the number of updates dispatched by that
    call, so callers (the supervisor's periodic-save cadence) can detect
    window boundaries without peeking inside."""

    def __init__(self, trainer, k: int = 1, scan_fn=None,
                 lookahead: int = 1,
                 before_dispatch: Optional[Callable[[int], None]] = None,
                 on_demote: Optional[Callable[[str], None]] = None):
        if k > 1 and scan_fn is None:
            raise ValueError('k>1 needs a compile_multi_step scan_fn')
        self.trainer = trainer
        self.k = int(k)
        self.scan_fn = scan_fn
        self.lookahead = max(0, int(lookahead))
        self.before_dispatch = before_dispatch or (lambda _u: None)
        self.on_demote = on_demote or (lambda _reason: None)
        self.window = []
        self.updates = 0
        self.demoted = False

    def _step_one(self, staged) -> None:
        self.before_dispatch(self.updates)
        self.trainer.update_staged(staged)
        self.updates += 1

    def feed(self, batch) -> int:
        """Stage one batch; dispatch whatever became due.  Returns the
        updates applied by THIS call (0 while a window is filling)."""
        staged = self.trainer.stage_batch(batch)
        u0 = self.updates
        if self.k > 1 and not self.demoted and staged[2]:
            # extra_data (attachtxt): the scan body can't carry it —
            # demote THIS round only, mid-epoch, WITHOUT re-winding the
            # iterator (strict mode raises instead)
            self.demoted = True
            self.on_demote('extra_data')
            for st in self.window:
                self._step_one(st)
            self.window = []
        if self.k == 1 or self.demoted:
            self.window.append(staged)
            while len(self.window) > self.lookahead:
                self._step_one(self.window.pop(0))
        else:
            self.window.append(staged)
            if len(self.window) == self.k:
                # no tracer hook inside a window: profile_dir demotes at
                # resolve time (a trace window can't bracket steps inside
                # one dispatch).  The trainer's train.dispatch span
                # brackets the DISPATCH (host enqueue of one scanned
                # window), never a step inside it — which is why it
                # composes where profile_dir must demote
                # (doc/observability.md)
                self.trainer.update_staged_window(self.scan_fn,
                                                  self.window)
                self.updates += self.k
                self.window = []
        return self.updates - u0

    def finish(self) -> int:
        """Drain staged-but-undispatched batches per-step (the short
        epoch tail, or the K=1 lookahead's last batch).  Returns the
        updates applied."""
        u0 = self.updates
        window, self.window = self.window, []
        for st in window:
            self._step_one(st)
        return self.updates - u0

    def discard(self) -> None:
        """Drop staged-but-undispatched batches without dispatching —
        for callers whose step budget is already met (the supervisor's
        ``n_steps`` break)."""
        self.window = []


def measured_probe(trainer, requested_k: int, batches,
                   repeats: int = 2) -> float:
    """One grafttune stage-2 measurement: steps/sec of THIS trainer
    driving the REAL plan/stepper path at ``requested_k``.

    Resolves a silent plan (a probe must not spam fallback notes or
    register itself as the run's /statusz plan choice... it does —
    latest-resolve-wins means the tuner's final resolve at the chosen K
    leaves the right plan registered), runs one untimed warm-up pass
    over ``batches`` (compiles the scan program outside the clock), then
    times ``repeats`` full passes and returns the BEST pass's
    updates/sec — min-wall over repeats, the same noise policy as
    bench.py.  The trainer's params advance (probes are measurement,
    not state management); callers that need pristine params snapshot
    and restore around the sweep."""
    import time as _time
    plan = ExecutionPlan.resolve(requested_k, strict=False, silent=True)

    def one_pass() -> int:
        stepper = plan.round_stepper(trainer, lookahead=0)
        done = 0
        for b in batches:
            done += stepper.feed(b)
        return done + stepper.finish()

    one_pass()                          # warm-up: compile outside the clock
    best = float('inf')
    updates = 0
    for _ in range(max(1, int(repeats))):
        t0 = _time.perf_counter()
        updates = one_pass()
        best = min(best, _time.perf_counter() - t0)
    if best <= 0 or updates <= 0:
        raise faults.TuneProbeError(
            f'k={requested_k}', RuntimeError('probe produced no updates'))
    return updates / best
