#!/usr/bin/env python
"""bench_guard — validate the committed BENCH_*.json receipt ledger.

Usage::

    python tools/bench_guard.py [--strict] [--tolerance F] [root]

Every committed receipt is a measurement the trajectory's claims stand
on, so the guard enforces the rules the bench modes promise
(doc/benchmarks.md):

* **strict JSON** — ``NaN``/``Infinity`` are not JSON; an unmeasured
  quantity must be ``null`` (the null-not-NaN rule every receipt
  writer follows since PR 8).  A receipt that fails to parse strictly
  fails the guard.
* **platform stamp** — a measured payload (``value`` not null) must
  say what it was measured ON (``"platform"``: ``tpu`` / ``cpu`` /
  ``host``), or a host number could pass as a per-chip one.  Receipts committed before the stamp rule are grandfathered in
  ``LEGACY_NO_PLATFORM`` — a shrink-only list: entries may be removed
  as old rounds are re-measured, never added.
* **regression flags** — within a receipt family (``BENCH_SERVE_r03``
  → family ``BENCH_SERVE``), the same metric re-measured in a later
  round is compared: a throughput (``*/sec``) drop or a latency
  (``*ms``) rise beyond ``--tolerance`` (default 30%) is flagged.
  Flags are warnings (exit 0) unless ``--strict`` — cross-round
  hardware may legitimately differ; the stamp says so.
* **scenario receipts** — a ``BENCH_SCENARIOS_*`` receipt
  (``scenario_autoscale_wins``) is an A/B claim, so its structure is
  validated: at least four scenarios, each with a static AND an
  autoscale leg whose every served stream was twin-checked in-bench,
  the win count consistent with the per-scenario verdicts and at
  least 3, and a composed chaos leg with zero twin violations and
  zero untyped sheds.  Per-leg ``p99_ms``/``loss`` are expanded into
  synthetic payloads so cross-round regression flags cover them.
* **kv-tier receipts** — a ``BENCH_KV_*`` receipt
  (``kv_tier_speedup``) claims the tiered cache beats cold prefill, so
  the guard re-checks the claim's load-bearing structure: the cached
  working set is LARGER than the HBM pool (``cache_pages`` >
  ``hbm_pages`` — otherwise the tiers were never needed), the warm leg
  actually promoted through tier 2 (``kv_promoted_pages`` and
  ``kv.disk_promote_pages`` both positive), every stream in BOTH legs
  was twin-asserted in-bench, and the speedup is at least 2x.  Per-leg
  throughput and promote latency are expanded into synthetic payloads
  for cross-round regression flags.

Exit codes: ``0`` clean (or warnings only), ``1`` validation failure
(or flagged regressions under ``--strict``), ``2`` internal error.
``pytest -m obs`` runs the guard over the repo ledger, so a bad
receipt fails tier-1 before it is ever cited.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

#: receipts committed before the platform-stamp rule (PR 5) existed —
#: shrink-only: remove entries as rounds are re-measured, NEVER add
LEGACY_NO_PLATFORM = frozenset({
    'BENCH_IO_r01.json',       # PR 5 host-only io sweep (no device leg)
})

_ROUND_RE = re.compile(r'^(.*)_r(\d+)\.json$')


def _reject_const(tok: str):
    raise ValueError(f'non-strict JSON constant {tok!r} (the '
                     'null-not-NaN rule: unmeasured must be null)')


def load_strict(path: str):
    with open(path, encoding='utf-8') as f:
        return json.load(f, parse_constant=_reject_const)


def payloads(doc) -> List[dict]:
    """Metric payloads inside a receipt file: the file may be one
    payload, a list of payloads, or a driver envelope carrying them
    under ``parsed``."""
    if isinstance(doc, list):
        return [p for p in doc if isinstance(p, dict) and 'metric' in p]
    if not isinstance(doc, dict):
        return []
    if 'metric' in doc:
        return [doc]
    parsed = doc.get('parsed')
    return payloads(parsed) if parsed is not None else []


SCENARIO_METRIC = 'scenario_autoscale_wins'

#: a scenario receipt must show the autoscaler beating the static
#: baseline on at least this many scenarios — the claim it exists for
SCENARIO_MIN_WINS = 3


def expand_scenarios(p: dict, name: str) -> Tuple[List[str], List[dict]]:
    """Validate one ``scenario_autoscale_wins`` payload and expand its
    per-scenario legs into synthetic payloads for regression flags."""
    errs: List[str] = []
    synth: List[dict] = []
    plat = p.get('platform')
    rows = p.get('scenarios')
    if not isinstance(rows, list) or len(rows) < 4:
        return [f'{name}: scenario receipt carries '
                f'{len(rows) if isinstance(rows, list) else 0} '
                'scenarios (need >= 4)'], []
    wins = 0
    for row in rows:
        rname = row.get('name', '?')
        for leg_name in ('static', 'autoscale'):
            leg = row.get(leg_name)
            if not isinstance(leg, dict):
                errs.append(f'{name}: scenario {rname!r} has no '
                            f'{leg_name!r} leg')
                continue
            if leg.get('twin_checked') != leg.get('served'):
                errs.append(
                    f'{name}: scenario {rname!r} {leg_name} leg '
                    f'twin-checked {leg.get("twin_checked")} of '
                    f'{leg.get("served")} served streams — every '
                    'served stream must be twin-asserted in-bench')
            for key, unit in (('p99_ms', 'ms'), ('loss', 'requests')):
                synth.append({
                    'metric': f'scenario_{rname}_{leg_name}_{key}',
                    'value': leg.get(key), 'unit': unit,
                    'platform': plat})
        wins += bool(row.get('win'))
    if wins != p.get('value'):
        errs.append(f'{name}: win count {p.get("value")} disagrees '
                    f'with per-scenario verdicts ({wins})')
    if wins < SCENARIO_MIN_WINS:
        errs.append(f'{name}: autoscale beat static on only {wins} '
                    f'scenarios (need >= {SCENARIO_MIN_WINS})')
    chaos = p.get('chaos')
    if not isinstance(chaos, dict):
        errs.append(f'{name}: scenario receipt has no composed chaos '
                    'leg')
    else:
        for key in ('twin_violations', 'untyped_sheds'):
            if chaos.get(key) != 0:
                errs.append(f'{name}: chaos leg {key}='
                            f'{chaos.get(key)} (must be 0)')
        if not chaos.get('slow_steps_fired'):
            errs.append(f'{name}: chaos leg fired no faults — it is '
                        'not a chaos leg')
    return errs, synth


KV_METRIC = 'kv_tier_speedup'

#: the tier thesis the receipt exists for: serving a prefix hit through
#: the host/disk tiers must beat re-prefilling it cold by at least 2x
KV_MIN_SPEEDUP = 2.0


def expand_kv_tiers(p: dict, name: str) -> Tuple[List[str], List[dict]]:
    """Validate one ``kv_tier_speedup`` payload and expand its per-leg
    numbers into synthetic payloads for regression flags."""
    errs: List[str] = []
    synth: List[dict] = []
    plat = p.get('platform')
    for leg_name in ('warm', 'cold'):
        leg = p.get(leg_name)
        if not isinstance(leg, dict):
            errs.append(f'{name}: kv receipt has no {leg_name!r} leg')
            continue
        if leg.get('twin_checked') != leg.get('streams'):
            errs.append(
                f'{name}: {leg_name} leg twin-checked '
                f'{leg.get("twin_checked")} of {leg.get("streams")} '
                'streams — every stream must be twin-asserted in-bench')
        synth.append({'metric': f'kv_{leg_name}_tokens_per_sec',
                      'value': leg.get('tokens_per_sec'),
                      'unit': 'tokens/sec', 'platform': plat})
    warm = p.get('warm') if isinstance(p.get('warm'), dict) else {}
    kv = warm.get('kv') if isinstance(warm.get('kv'), dict) else {}
    if not warm.get('kv_promoted_pages') or not kv.get(
            'disk_promote_pages'):
        errs.append(f'{name}: warm leg never promoted through the '
                    'tiers (kv_promoted_pages='
                    f'{warm.get("kv_promoted_pages")}, '
                    f'disk_promote_pages={kv.get("disk_promote_pages")})'
                    ' — the speedup is not a tier claim')
    cache_pages, hbm_pages = p.get('cache_pages'), p.get('hbm_pages')
    if not (isinstance(cache_pages, int) and isinstance(hbm_pages, int)
            and cache_pages > hbm_pages):
        errs.append(f'{name}: cached working set ({cache_pages} pages) '
                    f'does not exceed the HBM pool ({hbm_pages} pages) '
                    '— the bench proves nothing about tiering')
    value = p.get('value')
    if not (isinstance(value, (int, float))
            and value >= KV_MIN_SPEEDUP):
        errs.append(f'{name}: kv_tier_speedup {value} is below the '
                    f'{KV_MIN_SPEEDUP}x claim the receipt exists for')
    for key in ('promote_ms_p50', 'promote_ms_p99'):
        synth.append({'metric': f'kv_{key}', 'value': warm.get(key),
                      'unit': 'ms', 'platform': plat})
    return errs, synth


SHARD_METRIC = 'decode_shard_scaling'

#: the graftshard capacity thesis: at 4 devices (fixed per-device page
#: budget, slots scaling with the mesh) aggregate decode tokens/sec
#: must beat the single-device leg by at least this factor
SHARD_MIN_SCALING = 1.5


def expand_sharded(p: dict, name: str) -> Tuple[List[str], List[dict]]:
    """Validate one ``decode_shard_scaling`` payload and expand its
    per-width legs + disaggregation A/B into synthetic payloads."""
    errs: List[str] = []
    synth: List[dict] = []
    plat = p.get('platform')
    legs = p.get('legs')
    if not isinstance(legs, list) or len(legs) < 2:
        return [f'{name}: shard receipt carries '
                f'{len(legs) if isinstance(legs, list) else 0} '
                'width legs (need >= 2)'], []
    for leg in legs:
        tp = leg.get('tp', '?')
        if leg.get('twin_checked') != leg.get('streams'):
            errs.append(
                f'{name}: tp:{tp} leg twin-checked '
                f'{leg.get("twin_checked")} of {leg.get("streams")} '
                'streams — every stream must be twin-asserted in-bench')
        per = leg.get('resident_bytes_per_device')
        if not (isinstance(per, list) and len(per) == tp
                and all(isinstance(b, int) and b > 0 for b in per)):
            errs.append(f'{name}: tp:{tp} leg resident_bytes_per_device'
                        f'={per!r} does not ledger {tp} devices')
        synth.append({'metric': f'shard_tp{tp}_tokens_per_sec',
                      'value': leg.get('tokens_per_sec'),
                      'unit': 'tokens/sec', 'platform': plat})
    if p.get('twin_violations') != 0:
        errs.append(f'{name}: twin_violations='
                    f'{p.get("twin_violations")} (must be 0)')
    value = p.get('value')
    if legs[-1].get('tp') == 4 and not (
            isinstance(value, (int, float))
            and value >= SHARD_MIN_SCALING):
        errs.append(f'{name}: decode_shard_scaling {value} is below '
                    f'the {SHARD_MIN_SCALING}x claim the receipt '
                    'exists for')
    disagg = p.get('disagg')
    if not isinstance(disagg, dict):
        errs.append(f'{name}: shard receipt has no disaggregation A/B')
    else:
        for leg_name in ('off', 'on'):
            leg = disagg.get(leg_name)
            if not isinstance(leg, dict):
                errs.append(f'{name}: disagg A/B has no {leg_name!r} '
                            'leg')
                continue
            if leg.get('twin_checked') != leg.get('streams'):
                errs.append(
                    f'{name}: disagg {leg_name} leg twin-checked '
                    f'{leg.get("twin_checked")} of '
                    f'{leg.get("streams")} streams')
            synth.append({
                'metric': f'shard_disagg_{leg_name}_short_ttft_p99_ms',
                'value': leg.get('short_ttft_p99_ms'), 'unit': 'ms',
                'platform': plat})
        imp = disagg.get('short_ttft_improvement')
        if not (isinstance(imp, (int, float)) and imp > 1.0):
            errs.append(f'{name}: disaggregation did not improve '
                        f'short-stream TTFT p99 (improvement={imp}) — '
                        'admission past the head-of-line blocker is the '
                        'claim the knob exists for')
    return errs, synth


TUNE_METRIC = 'autotune_speedup'

#: the modes an autotune receipt must cover — the "beats the default on
#: >= 2 bench modes" claim (doc/autotune.md)
TUNE_MODES = ('scan', 'decode')


def expand_autotune(p: dict, name: str) -> Tuple[List[str], List[dict]]:
    """Validate one ``autotune_speedup`` payload and expand its per-mode
    throughputs into synthetic payloads for regression flags."""
    errs: List[str] = []
    synth: List[dict] = []
    plat = p.get('platform')
    modes = p.get('modes')
    if not isinstance(modes, dict):
        return [f'{name}: autotune receipt has no per-mode legs'], []
    speedups = []
    for mode in TUNE_MODES:
        leg = modes.get(mode)
        if not isinstance(leg, dict):
            errs.append(f'{name}: autotune receipt has no {mode!r} leg')
            continue
        sp = leg.get('speedup')
        if not (isinstance(sp, (int, float)) and sp >= 1.0):
            errs.append(f'{name}: {mode} leg speedup {sp} < 1.0 — the '
                        'tuned config must never lose to the default')
        else:
            speedups.append(sp)
        search = leg.get('search')
        if not isinstance(search, dict):
            errs.append(f'{name}: {mode} leg carries no search block')
        else:
            if not search.get('budget_honored') or not (
                    isinstance(search.get('wall_s'), (int, float))
                    and isinstance(search.get('budget_s'), (int, float))
                    and search['wall_s'] <= search['budget_s']):
                errs.append(f'{name}: {mode} search wall '
                            f'{search.get("wall_s")}s broke its declared '
                            f'{search.get("budget_s")}s budget')
            if not (isinstance(search.get('measured'), int)
                    and search['measured'] >= 1):
                errs.append(f'{name}: {mode} search measured no '
                            'candidates')
        for key, unit in (('default_steps_per_sec', 'steps/sec'),
                          ('tuned_steps_per_sec', 'steps/sec'),
                          ('default_tokens_per_sec', 'tokens/sec'),
                          ('tuned_tokens_per_sec', 'tokens/sec')):
            if key in leg:
                synth.append({'metric': f'autotune_{mode}_{key}',
                              'value': leg.get(key), 'unit': unit,
                              'platform': plat})
    if modes.get('scan', {}).get('bitwise_equal') is not True:
        errs.append(f'{name}: scan leg is not bitwise-asserted — the '
                    'speedup could be bought with a semantics drift')
    if modes.get('decode', {}).get('stream_twins') is not True:
        errs.append(f'{name}: decode leg streams were not twin-checked')
    search = p.get('search')
    if not isinstance(search, dict):
        errs.append(f'{name}: autotune receipt has no aggregate search '
                    'block')
    else:
        if not search.get('budget_honored'):
            errs.append(f'{name}: aggregate search broke its declared '
                        'budget')
        if not (isinstance(search.get('stage1_pruned'), int)
                and search['stage1_pruned'] >= 1):
            errs.append(f'{name}: stage 1 pruned nothing '
                        f'({search.get("stage1_pruned")}) — the ledger '
                        'gate never demonstrably gated')
    guard = p.get('storm_guard')
    if not isinstance(guard, dict):
        errs.append(f'{name}: autotune receipt has no storm-guard drill')
    else:
        if guard.get('storm_errors') != 0:
            errs.append(f'{name}: storm-guard drill recorded '
                        f'{guard.get("storm_errors")} RecompileStormError'
                        '(s) — the guard exists to make this 0')
        if not (isinstance(guard.get('compiles'), int)
                and isinstance(guard.get('compile_budget'), int)
                and guard['compiles'] <= guard['compile_budget']):
            errs.append(f'{name}: drill compiles '
                        f'{guard.get("compiles")} exceed the declared '
                        f'budget {guard.get("compile_budget")}')
        if not guard.get('vetoes'):
            errs.append(f'{name}: the drill never vetoed a re-plan — '
                        'it did not exercise the guard')
    value = p.get('value')
    if speedups and isinstance(value, (int, float)) \
            and abs(value - min(speedups)) > 1e-6:
        errs.append(f'{name}: headline {value} is not the worst-mode '
                    f'speedup ({min(speedups)})')
    return errs, synth


CNN_METRIC = 'cnn_fused_speedup'


def expand_cnn_fused(p: dict, name: str) -> Tuple[List[str], List[dict]]:
    """Validate one ``cnn_fused_speedup`` payload (BENCH_CNN — the
    graftfuse A/B, doc/kernels.md): every A/B leg must carry its
    in-bench twin assertion (a speedup over diverging math is not a
    speedup), the micro_batch sweep must be bitwise at every split with
    ledger peak bytes monotone non-increasing in the split, and the
    headline must be the inference leg's speedup.  Per-leg throughputs are
    expanded into synthetic payloads for cross-round regression
    flags."""
    errs: List[str] = []
    synth: List[dict] = []
    plat = p.get('platform')
    infer = p.get('inference')
    if not isinstance(infer, dict):
        errs.append(f'{name}: cnn_fused receipt has no inference leg')
        infer = {}
    else:
        if infer.get('twin_ok') is not True:
            errs.append(f'{name}: inference leg scores were not '
                        'twin-asserted against the unfolded engine')
        fv = infer.get('fold_view')
        if not (isinstance(fv, dict) and fv.get('pairs')):
            errs.append(f'{name}: inference leg folded no conv+BN '
                        'pairs — the A/B measured nothing')
    mb = p.get('micro_batch')
    if not (isinstance(mb, dict)
            and isinstance(mb.get('sweep'), list) and mb['sweep']):
        errs.append(f'{name}: cnn_fused receipt has no micro_batch '
                    'sweep')
    else:
        peaks = []
        for row in mb['sweep']:
            if row.get('bitwise_equal_to_unsplit') is not True:
                errs.append(
                    f'{name}: micro_batch={row.get("micro_batch")} row '
                    'is not bitwise-asserted against the unsplit step')
            if isinstance(row.get('peak_bytes'), int) \
                    and row['peak_bytes'] > 0:
                peaks.append(row['peak_bytes'])
            else:
                errs.append(
                    f'{name}: micro_batch={row.get("micro_batch")} row '
                    'carries no ledger peak_bytes — the split\'s memory '
                    'claim is unsubstantiated')
        if any(a < b for a, b in zip(peaks, peaks[1:])):
            errs.append(f'{name}: micro_batch peak_bytes {peaks} grow '
                        'with the split — splitting must bound peak '
                        'HBM, not inflate it')
    speedup = infer.get('speedup')
    value = p.get('value')
    if isinstance(speedup, (int, float)) \
            and isinstance(value, (int, float)) \
            and abs(value - speedup) > 1e-6:
        errs.append(f'{name}: headline {value} is not the inference '
                    f"leg's speedup ({speedup})")
    for key in ('folded_rows_per_sec', 'plain_rows_per_sec'):
        if key in infer:
            synth.append({'metric': f'cnn_fused_{key}',
                          'value': infer.get(key), 'unit': 'rows/sec',
                          'platform': plat})
    return errs, synth


def check_file(path: str) -> Tuple[List[str], List[dict]]:
    """(errors, payloads) for one receipt file."""
    name = os.path.basename(path)
    try:
        doc = load_strict(path)
    except ValueError as e:
        return [f'{name}: invalid strict JSON: {e}'], []
    errs = []
    loads = payloads(doc)
    extra: List[dict] = []               # synthetic, never re-scanned
    for p in loads:
        if p.get('value') is None:
            continue                     # unmeasured/error payload
        if 'platform' not in p and name not in LEGACY_NO_PLATFORM:
            errs.append(
                f'{name}: measured payload {p.get("metric")!r} carries '
                'no "platform" stamp (tpu / cpu / host)')
        if p.get('metric') == SCENARIO_METRIC:
            s_errs, synth = expand_scenarios(p, name)
            errs.extend(s_errs)
            extra.extend(synth)
        elif p.get('metric') == KV_METRIC:
            k_errs, synth = expand_kv_tiers(p, name)
            errs.extend(k_errs)
            extra.extend(synth)
        elif p.get('metric') == SHARD_METRIC:
            s_errs, synth = expand_sharded(p, name)
            errs.extend(s_errs)
            extra.extend(synth)
        elif p.get('metric') == TUNE_METRIC:
            t_errs, synth = expand_autotune(p, name)
            errs.extend(t_errs)
            extra.extend(synth)
        elif p.get('metric') == CNN_METRIC:
            c_errs, synth = expand_cnn_fused(p, name)
            errs.extend(c_errs)
            extra.extend(synth)
    return errs, loads + extra


def _direction(unit: Optional[str], metric: str) -> int:
    """+1 = higher is better (throughput), -1 = lower is better
    (latency), 0 = not comparable."""
    u = (unit or '').lower()
    if '/sec' in u:
        return 1
    if u == 'ms' or metric.endswith('_ms') or '_ms_' in metric:
        return -1
    if metric.endswith(('_loss', '_shed')):
        return -1                        # lost/shed requests: fewer wins
    return 0


def flag_regressions(rounds: Dict[str, Dict[int, List[dict]]],
                     tolerance: float) -> List[str]:
    """Compare each metric against its most recent PRIOR round within
    the same receipt family; returns human-readable flags."""
    flags = []
    for family, per_round in sorted(rounds.items()):
        seen: Dict[str, Tuple[int, float, Optional[str]]] = {}
        for rnd in sorted(per_round):
            for p in per_round[rnd]:
                metric, value = p.get('metric'), p.get('value')
                if not metric or not isinstance(value, (int, float)):
                    continue
                prior = seen.get(metric)
                if prior is not None:
                    prnd, pval, punit = prior
                    d = _direction(p.get('unit'), metric)
                    if d and punit == p.get('unit') and pval > 0:
                        change = (value - pval) / pval
                        if change * d < -tolerance:
                            flags.append(
                                f'{family}: {metric} '
                                f'{"fell" if d > 0 else "rose"} '
                                f'{abs(change):.0%} from r{prnd:02d} '
                                f'({pval:g}) to r{rnd:02d} ({value:g})')
                seen[metric] = (rnd, float(value), p.get('unit'))
    return flags


def run(root: str, tolerance: float = 0.30,
        strict: bool = False) -> int:
    files = sorted(glob.glob(os.path.join(root, 'BENCH_*.json')))
    if not files:
        print(f'bench_guard: no BENCH_*.json under {root}',
              file=sys.stderr)
        return 1
    errors: List[str] = []
    rounds: Dict[str, Dict[int, List[dict]]] = {}
    for path in files:
        errs, loads = check_file(path)
        errors.extend(errs)
        m = _ROUND_RE.match(os.path.basename(path))
        if m and loads:
            rounds.setdefault(m.group(1), {})[int(m.group(2))] = loads
    flags = flag_regressions(rounds, tolerance)
    for e in errors:
        print(f'ERROR {e}')
    for f in flags:
        print(f'FLAG  {f}')
    ok = len(files) - len({e.split(':')[0] for e in errors})
    print(f'bench_guard: {len(files)} receipts, {ok} clean, '
          f'{len(errors)} error(s), {len(flags)} regression flag(s)')
    if errors:
        return 1
    if flags and strict:
        return 1
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument('root', nargs='?',
                   default=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))))
    p.add_argument('--strict', action='store_true',
                   help='regression flags fail (exit 1), not just warn')
    p.add_argument('--tolerance', type=float, default=0.30,
                   help='relative change beyond which a re-measured '
                        'metric is flagged (default 0.30)')
    args = p.parse_args(argv)
    try:
        return run(os.path.abspath(args.root), tolerance=args.tolerance,
                   strict=args.strict)
    except Exception:
        import traceback
        traceback.print_exc()
        print('bench_guard: internal error (no verdict)', file=sys.stderr)
        return 2


if __name__ == '__main__':
    sys.exit(main())
