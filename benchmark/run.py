"""Run one cell of BENCHMARK.json in this process and print its result.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and,
traced, ``breakdown``.  Lines before it are for reading.  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.  ``--rehearse 1`` (never given by the driver) runs the cell at the
tiny size of its traffic file's ``rehearsal`` entry on whatever backend is
there, to find faults without the chip; its line is stamped with the
platform and is not a measurement.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys

from . import harness
from .harness import HERE, ROOT


def process_age_s() -> float:
    """Seconds since this process was started, interpreter start-up and
    imports included."""
    with open('/proc/self/stat') as f:
        start_ticks = int(f.read().rsplit(')', 1)[1].split()[19])
    with open('/proc/uptime') as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf('SC_CLK_TCK')


def say(text: str) -> None:
    print(f'benchmark: {text}', flush=True)


def die(why: str) -> None:
    print(f'benchmark: FAIL - {why}', file=sys.stderr, flush=True)
    raise SystemExit(2)


def meet_backend(cell, rehearsal: bool):
    """The devices of this cell, or an exit: a measurement runs on a TPU
    with the chips the cell asks for, and on nothing else."""
    import jax
    from cxxnet_tpu.utils.backend import (BackendUnavailable,
                                          enable_compile_cache, require_chip)
    cache_dir = enable_compile_cache()
    # every program goes into the cache, the small ones too: a run pays
    # dozens of sub-second compiles otherwise (parameter init, leaf by leaf)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    try:
        backend = require_chip()
    except BackendUnavailable as e:
        die(str(e))
    if backend != 'tpu' and not rehearsal:
        die(f'JAX backend is {backend!r}, not a TPU (a CPU run is a '
            f'rehearsal: --rehearse 1)')
    devices = jax.devices()
    if len(devices) < cell.chips:
        die(f'cell {cell.name} asks for {cell.chips} chips, JAX has '
            f'{len(devices)}')
    return devices, cache_dir


def peaks_of(device, rehearsal: bool):
    table = harness.load_json(HERE, 'peaks.json')['device_kinds']
    if device.device_kind in table:
        return table[device.device_kind]
    if rehearsal:
        return None
    die(f'device_kind {device.device_kind!r} has no row in '
        f'benchmark/peaks.json; a guessed peak is not a measurement')


def memory_peak(devices) -> int:
    """The fullest chip's peak: the allocator's own peak plus what the
    runtime holds reserved beside it.  On this TPU runtime a loaded
    program's scratch (its temporaries: 3.2 GB for the AlexNet step) is
    reserved outside the allocator's pool and its ``peak_bytes_in_use``
    leaves it out (PERF.md, Findings)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        reserved = stats.get('peak_bytes_reserved',
                             stats.get('bytes_reserved', 0))
        peak = max(peak, int(stats.get('peak_bytes_in_use', 0))
                   + int(reserved))
    return peak


def take_trace(cell, pump, spans, compiles_now):
    """A traced sub-window after the timed one: ``trace_steps`` steps or
    ``trace_seconds``, whichever ends first, inside one ``bench.traced``
    span; returns the sub-window and the reduced trace."""
    import jax
    from . import trace as T
    out = os.path.join(HERE, '.cache', 'trace')
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # the harness's spans are enough,
    #                                       and Python frames cost the host
    n, secs = int(cell.t('trace_steps')), float(cell.t('trace_seconds'))
    pump.drain()
    jax.profiler.start_trace(out, profiler_options=opts)
    spans.tracing = True
    try:
        with spans.span('traced'):
            sub = pump.window(compiles_now,
                              lambda steps, t: steps < n and t < secs)
    finally:
        spans.tracing = False
        jax.profiler.stop_trace()
    planes = T.load_file(T.find_xplane(out))
    keep = os.environ.get('BENCHMARK_KEEP_TRACE')
    if keep:                               # for reading a trace by hand
        os.makedirs(keep, exist_ok=True)
        with open(os.path.join(keep, cell.name + '.trace.txt'), 'w') as f:
            f.write(T.describe(planes))
        if T.device_planes(planes):
            from .selftest import fixture
            fixture.record(planes, cell.chips, os.path.join(keep, cell.name))
    shutil.rmtree(out, ignore_errors=True)
    if cell.rehearsal and not T.device_planes(planes):
        return sub, None       # a CPU has no device plane to reduce
    return sub, T.reduce(planes, cell.chips)


def learned(cell, losses) -> dict:
    """Training really updates: the mean loss of a late span of steps is
    below that of an early one by the margin the cell's own file states.
    Steps are counted from the trainer's first, warm-up included."""
    want = cell.own.get('learn_check')
    if not want or cell.rehearsal:
        return {'asked': False, 'ok': True}
    (a0, a1), (b0, b1) = want['first'], want['last']
    if len(losses) < b1:
        return {'asked': True, 'ok': False,
                'why': f'{len(losses)} steps, the check needs {b1}'}
    first = sum(losses[a0 - 1:a1]) / (a1 - a0 + 1)
    last = sum(losses[b0 - 1:b1]) / (b1 - b0 + 1)
    return {'asked': True, 'first': first, 'last': last,
            'margin': want['margin'],
            'ok': bool(first - last >= want['margin'])}


def judge(devices, rehearsal, window, traced, losses, after, learn):
    """What ``correct`` rests on, each with its name (PERF.md section 2)."""
    return {
        'on the chip': devices[0].platform == 'tpu' or rehearsal,
        'steps completed': window.steps > 0,
        'no compilation in the window': window.compiles == 0
        and (traced is None or traced.compiles == 0),
        'every loss finite': all(math.isfinite(v) for v in losses),
        'agrees with the plain reference': after['ok'],
        'training updates': learn['ok'],
    }


def tell(run, cell, used, losses, at_init, after, learn, hlo_flops):
    """Lines worth reading that are no metric."""
    window, traced, reduced, feed = run.window, run.traced, run.trace, run.feed
    iv = window.intervals_ms()
    p50 = harness.percentile(iv, 0.5) or 0.0
    say(f'window {window.wall_s:.3f} s: {window.steps} steps of '
        f'{feed.samples_per_step} samples completed '
        f'({run.samples_per_s():.1f} samples/s), step interval p50 '
        f'{p50:.2f} ms p95 {harness.percentile(iv, 0.95) or 0:.2f} ms, '
        f'rounds begun {feed.rounds}, compilations inside {window.compiles}')
    say(f'memory peak {run.memory_peak_bytes / 2**30:.3f} GiB on the fullest '
        f'of {cell.chips} chip(s); device 0 says {used[0].memory_stats()}')
    say(f'plain reference: error at init {at_init["errors"]}, after '
        f'{len(losses)} steps {after["errors"]} (tolerance '
        f'{after["tolerance"]})')
    say(f'loss: first {losses[0]:.4f}, last {losses[-1]:.4f}; learning '
        f'check {learn}')
    if traced is not None:
        say(f'traced sub-window {traced.wall_s:.3f} s, {traced.steps} steps, '
            f'step interval p50 '
            f'{harness.percentile(traced.intervals_ms(), 0.5) or 0:.2f} ms '
            f'against {p50:.2f} ms untraced')
        say(f'flops a step: analytic {run.flops_per_step:.4g}, the '
            f'compiler\'s count of its own program {hlo_flops:.4g}')
    if reduced is not None:
        say(f'device busy {reduced.busy_s:.4f} s of {reduced.window_s:.4f} s '
            f'traced; by kind, device 0: {reduced.by_category_s}')
        say(f'Mosaic custom calls {reduced.pallas_s * 1e3:.3f} ms, '
            f'collectives {reduced.collective_s * 1e3:.3f} ms '
            f'(exposed {reduced.collective_exposed_s * 1e3:.3f} ms) in '
            f'{reduced.steps} traced steps; idle gaps by host span '
            f'{reduced.idle_gaps_s}')


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog='benchmark.run')
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), required=True)
    ap.add_argument('--rehearse', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    rehearsal = bool(args.rehearse)
    cell = harness.load_cell(args.workload, rehearsal)
    sys.path.insert(0, ROOT)
    try:
        import cxxnet_tpu  # noqa: F401
    except ImportError as e:
        die(f'the program under test is not in this checkout: {e}')
    import jax
    from . import cxx

    devices, cache_dir = meet_backend(cell, rehearsal)
    used = devices[:cell.chips]
    peaks = peaks_of(devices[0], rehearsal)
    say(f'cell {cell.name}: {cell.chips} of {len(devices)} '
        f'{devices[0].device_kind!r} ({devices[0].platform}), host cores '
        f'{os.cpu_count()}, compile cache {cache_dir} '
        f'({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} '
        f'entries), jax {jax.__version__}'
        + (' - REHEARSAL, not a measurement' if rehearsal else ''))

    # --- set-up: program, inputs, every shape the window will use ----------
    clock = harness.CompileClock()
    spans = harness.Spans()
    phases = [('process start, imports, reaching the chip', process_age_s())]
    feed = harness.load_module('feeds', cell.traffic['feed']).Feed(
        cell, args.seed, spans)
    phases.append(('program and inputs', process_age_s()))
    tap = cxx.LossTap(feed.trainer)
    pump = harness.Pump(feed, tap, int(cell.t('max_inflight')))
    reference = harness.load_module('references', cell.config['reference'])
    at_init = reference.compare(feed, cell, args.seed)   # warms both programs
    phases.append(('forward and reference', process_age_s()))
    for _ in range(int(cell.t('warmup_feeds'))):
        pump.pump()
    pump.drain()
    phases.append(('warm-up feeds', process_age_s()))

    def compiles_now() -> int:
        return clock.count + cxx.ledger_compiles()

    setup_s, setup_compile_s = process_age_s(), clock.seconds
    say(f'set-up {setup_s:.1f} s, of it compile or cache retrieval '
        f'{setup_compile_s:.1f} s in {clock.count} programs; by phase: '
        + ', '.join(f'{name} {t1 - t0:.1f} s' for (name, t1), t0 in
                    zip(phases, [0.0] + [t for _, t in phases])))

    # --- the timed window, then (traced run) a traced sub-window ------------
    window = pump.window(compiles_now, lambda steps, t: t < args.seconds)
    peak_bytes = memory_peak(used)
    traced = reduced = None
    if args.trace:
        traced, reduced = take_trace(cell, pump, spans, compiles_now)

    # --- correct? -----------------------------------------------------------
    losses = [float(v) for v in jax.device_get(tap.losses)]
    in_window = losses[window.first_step:window.first_step + window.steps]
    after = reference.compare(feed, cell, args.seed)
    learn = learned(cell, losses)
    verdicts = judge(devices, rehearsal, window, traced, losses, after, learn)
    run = harness.Run(
        cell=cell, feed=feed, spans=spans, setup_s=setup_s,
        setup_compile_s=setup_compile_s, window=window, traced=traced,
        trace=reduced, memory_peak_bytes=peak_bytes,
        flops_per_step=reference.train_flops_per_step(feed), peaks=peaks)
    tell(run, cell, used, losses, at_init, after, learn,
         cxx.hlo_flops_per_step(feed.trainer) if args.trace else 0.0)
    for what, ok in verdicts.items():
        if not ok:
            say(f'NOT CORRECT: {what}')
    feed.close()

    # --- the result ---------------------------------------------------------
    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': len(devices),
              'memory_peak_bytes': peak_bytes}
    result = {'correct': all(verdicts.values()), 'attempted': window.steps,
              'failed': sum(1 for v in in_window if not math.isfinite(v))}
    if args.trace:
        result['metrics'] = harness.read_metrics('layer_metrics',
                                                 cell.per_layer, run)
        if reduced is not None:
            device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
            result['breakdown'] = {
                'device_ops': [[k, v] for k, v in reduced.by_category_s],
                'idle_gaps': [[k, v] for k, v in reduced.idle_gaps_s]}
    else:
        result['metrics'] = harness.read_metrics('e2e_metrics',
                                                 cell.end_to_end, run)
    result['device'] = device
    if rehearsal:
        result['rehearsal'] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
