"""The step record (doc/observability.md "The step record"): one
``train.dispatch`` a step with ``train.launch`` inside it and the process's
running totals in its ``attrs``, the collector's pauses on the hub, a late
step put down to a cause, the benchmark's four readers of it, and the two
rules ``utils/profiler.py`` shares with the benchmark's reduction."""

import gc
import os
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, step_records                     # noqa: E402
from cxxnet_tpu.io.data import DataBatch                        # noqa: E402
from cxxnet_tpu.nnet.execution import WindowedStepper            # noqa: E402
from cxxnet_tpu.nnet.trainer import NetTrainer                   # noqa: E402
from cxxnet_tpu.obs import TelemetryHub, install_hub             # noqa: E402
from cxxnet_tpu.obs import hub as hub_module                     # noqa: E402
from cxxnet_tpu.obs import step_record                           # noqa: E402
from cxxnet_tpu.utils import profiler                            # noqa: E402
from cxxnet_tpu.utils.config import parse_config_string          # noqa: E402

CONF = """
netconfig = start
layer[0->1] = flatten
layer[1->2] = fullc:fc
  nhidden = 4
layer[2->2] = softmax
netconfig = end
input_shape = 1,4,4
batch_size = 8
dev = cpu
eta = 0.1
eval_train = 0
"""
TOTALS = step_records.TOTALS


@pytest.fixture
def hub():
    h = TelemetryHub()
    prev = install_hub(h)
    yield h
    install_hub(prev)


@pytest.fixture(scope='module')
def trainer():
    tr = NetTrainer(parse_config_string(CONF))
    tr.init_model()
    return tr


def _batch():
    rng = np.random.RandomState(0)
    return DataBatch(rng.rand(8, 1, 4, 4).astype(np.float32),
                     rng.randint(0, 4, (8, 1)).astype(np.float32))


def _named(hub, name):
    return [e for e in hub.events() if e['name'] == name]


# --- one train.dispatch a step, wherever the step is called from -------------

def _direct(tr):
    tr.update_staged(tr.stage_batch(_batch()))


def _through_update(tr):
    tr.update(_batch())


def _through_stepper(tr):
    stepper = WindowedStepper(tr, k=1, lookahead=0)
    assert stepper.feed(_batch()) == 1


@pytest.mark.parametrize('call', [_direct, _through_update, _through_stepper])
def test_a_step_leaves_one_dispatch_with_one_launch_inside(hub, trainer, call):
    update = trainer.sample_counter
    call(trainer)
    (outer,) = _named(hub, 'train.dispatch')
    (inner,) = _named(hub, 'train.launch')
    assert outer['attrs']['k'] == inner['attrs']['k'] == 1
    assert outer['attrs']['update'] == inner['attrs']['update'] == update
    assert inner['attrs']['parent'] == 'train.dispatch'
    assert outer['t_start_ns'] <= inner['t_start_ns']
    assert inner['t_start_ns'] + inner['dur_ns'] \
        <= outer['t_start_ns'] + outer['dur_ns']
    assert all(isinstance(outer['attrs'][k], int) for k in TOTALS)


def test_a_scanned_window_leaves_one_of_each_with_k(hub, trainer):
    fn = trainer.compile_multi_step(2)
    stepper = WindowedStepper(trainer, k=2, scan_fn=fn)
    assert stepper.feed(_batch()) == 0 and stepper.feed(_batch()) == 2
    (outer,) = _named(hub, 'train.dispatch')
    (inner,) = _named(hub, 'train.launch')
    assert outer['attrs']['k'] == inner['attrs']['k'] == 2
    assert all(k in outer['attrs'] for k in TOTALS)


def test_totals_in_consecutive_records_never_fall(hub, trainer):
    staged = trainer.stage_batch(_batch())
    for _ in range(6):
        trainer.update_staged(staged)
    records = [e['attrs'] for e in _named(hub, 'train.dispatch')]
    assert len(records) == 6
    for a, b in zip(records, records[1:]):
        assert all(b[k] >= a[k] for k in TOTALS), (a, b)
    assert records[-1]['thread_cpu_ns'] > records[0]['thread_cpu_ns']


def test_a_disabled_hub_records_nothing(no_collector, hub, trainer):
    hub.enabled = False
    trainer.update_staged(trainer.stage_batch(_batch()))
    _make_garbage(20000)
    gc.collect()
    assert hub.events() == [] and (hub.gc_ns, hub.gc_n) == (0, 0)
    assert hub_module._gc_open is None       # the callback returned at once
    hub.enabled = True
    trainer.update_staged(trainer.stage_batch(_batch()))
    assert len(_named(hub, 'train.dispatch')) == 1


# --- the collector -----------------------------------------------------------

class _Node:
    pass


def _make_garbage(n):
    """``n`` cycles the reference counts cannot free."""
    for _ in range(n):
        a, b = _Node(), _Node()
        a.other, b.other = b, a


@pytest.fixture
def no_collector():
    """The collector runs when the test says so, and not before."""
    gc.collect()
    gc.disable()
    yield
    gc.enable()


def test_a_forced_collection_raises_the_totals_and_leaves_an_event(
        no_collector, hub):
    _make_garbage(200000)
    before = hub.host_totals()
    gc.collect()
    after = hub.host_totals()
    assert after['gc_n'] == before['gc_n'] + 1
    assert after['gc_ns'] - before['gc_ns'] >= hub_module.GC_EVENT_NS
    (ev,) = _named(hub, 'host.gc')
    assert ev['attrs']['generation'] == 2
    assert ev['attrs']['collected'] >= 400000
    assert ev['dur_ns'] == after['gc_ns'] - before['gc_ns']


def test_young_collections_stay_out_of_the_ring(no_collector, hub):
    hub.record_event('entry.backend', 'entry')
    n0, events0 = hub.gc_n, len(hub.events())
    for _ in range(5000):
        gc.collect(0)
    assert hub.gc_n == n0 + 5000
    assert len(hub.events()) - events0 <= 5      # one a ms or more, if any
    assert _named(hub, 'entry.backend')


def test_the_callback_is_installed_once(hub):
    TelemetryHub()
    assert gc.callbacks.count(hub_module._on_gc) == 1


# --- a late step is put down to a cause --------------------------------------

MS = 1_000_000
QUIET = {k: 0 for k in TOTALS}


def _series(n, launch_ms=1, whole_ms=2, every_ms=10):
    """A series of ``n`` dispatches ``every_ms`` apart on an injected clock;
    returns it with the clock's reading."""
    s = step_record.StepSeries()
    t = 0
    for i in range(n):
        s.observe(t, QUIET, update=i)
        s.launch_ns = launch_ms * MS
        s.end(whole_ms * MS)
        t += every_ms * MS
    return s, t - every_ms * MS


@pytest.mark.parametrize('where,launch_ms,whole_ms,gap_ms', [
    ('launch', 401, 402, 410),       # the jitted call blocked
    ('dispatch', 1, 402, 410),       # the step loop's own host code
    ('caller', 1, 2, 410),           # between two calls
])
def test_a_long_interval_writes_one_stall_that_says_where(
        hub, capsys, where, launch_ms, whole_ms, gap_ms):
    s, t = _series(9)                # 9 dispatches: 8 intervals known
    s.launch_ns = launch_ms * MS     # the ninth dispatch is the long one
    s.end(whole_ms * MS)
    now = dict(QUIET, gc_ns=300 * MS, gc_n=2, thread_cpu_ns=5 * MS,
               process_cpu_ns=350 * MS, nivcsw=3, compiles=1)
    s.observe(t + gap_ms * MS, now, update=9)
    assert not _named(hub, 'train.stall')    # the next dispatch confirms it
    s.observe(t + (gap_ms + 10) * MS, now, update=10)
    (ev,) = _named(hub, 'train.stall')
    assert ev['t_start_ns'] == t and ev['dur_ns'] == gap_ms * MS
    a = ev['attrs']
    assert a['where'] == where and a['update'] == 9
    assert a['interval_ms'] == gap_ms and a['median_ms'] == 10
    assert (a['gc_ms'], a['gc_n'], a['thread_cpu_ms'], a['process_cpu_ms']) \
        == (300, 2, 5, 350)
    assert (a['nivcsw'], a['majflt'], a['compiles']) == (3, 0, 1)
    (line,) = capsys.readouterr().err.splitlines()
    assert line.startswith('train.stall\tupdate:9\tinterval_ms:410.000\t')
    assert f'\twhere:{where}\tgc_ms:300.000\tgc_n:2\t' in line
    assert line.endswith('\tcompiles:1')
    s.observe(t + (gap_ms + 20) * MS, now, update=11)     # and only one
    assert len(_named(hub, 'train.stall')) == 1


@pytest.mark.parametrize('known,gap_ms,stalls', [
    (7, 410, 0),        # fewer than 8 intervals: no verdict yet
    (8, 410, 1),
    (8, 29, 0),         # under 3 medians
    (8, 55, 0),         # over 3 medians of 10 ms, not 50 ms over
    (8, 61, 1),
])
def test_what_counts_as_a_stall(hub, capsys, known, gap_ms, stalls):
    s, t = _series(known + 1)
    s.observe(t + gap_ms * MS, QUIET)
    s.observe(t + (gap_ms + 10) * MS, QUIET)
    assert len(_named(hub, 'train.stall')) == s.stalls == stalls


def test_a_change_of_pace_is_no_stall(hub, capsys):
    """A loop that has filled its queue of steps in flight goes from the
    host's pace to the device's and stays there: every interval is long
    against the series until the median has followed, and none is a
    stall; the first long one after that is."""
    s, t = _series(12, every_ms=2)           # the queue fills: 2 ms apart
    for _ in range(40):                      # then the device's 60 ms
        t += 60 * MS
        s.observe(t, QUIET)
    assert s.stalls == 0 and not _named(hub, 'train.stall')
    s.observe(t + 600 * MS, QUIET)
    s.observe(t + 602 * MS, QUIET)           # the host catches up
    assert s.stalls == 1


def test_start_round_resets_the_series(hub, trainer, capsys):
    staged = trainer.stage_batch(_batch())
    trainer.start_round(trainer.round)
    for _ in range(10):
        trainer.update_staged(staged)
    series = trainer._steps
    assert len(series._rows) == 9
    trainer.start_round(trainer.round)
    assert len(series._rows) == 0 and series._prev is None
    trainer.update_staged(staged)            # however late: a round began
    assert len(series._rows) == 0 and not _named(hub, 'train.stall')


def test_the_ninth_line_is_a_count(hub, capsys):
    s, t = _series(9)
    for i in range(12):
        t += 1000 * MS
        s.observe(t, QUIET, update=i)
        for _ in range(20):          # quiet steps, so the median stays
            t += 10 * MS
            s.observe(t, QUIET)
    assert s.stalls == 12 and len(_named(hub, 'train.stall')) == 12
    lines = capsys.readouterr().err.splitlines()
    assert len(lines) == 9
    assert all(l.startswith('train.stall\tupdate:') for l in lines[:8])
    assert lines[8].startswith('train.stall\tcount:9\t')


# --- the benchmark's four readers --------------------------------------------

READERS = {name: harness.load_module('layer_metrics', name) for name in (
    'step.host_self_ms_p50', 'step.interval_max_over_p50',
    'step.stall_lost_pct', 'step.gc_ms_per_step')}
STEP_MS, SELF_MS, LAUNCH_MS = 50, 1.5, 0.5


def _made_run(hub, intervals_ms, inflight=8, gc_ms_a_step=0.02,
              backend=True):
    """A ``harness.Run`` over a hub that holds one ``train.dispatch`` (with
    its ``train.launch``) a step, ``intervals_ms`` apart, inside a window
    that opens at the first and closes a step after the last."""
    t0 = 10_000 * MS
    if backend:
        hub.record_event('entry.backend', 'entry', t_start_ns=MS)
    starts = [t0]
    for v in intervals_ms:
        starts.append(starts[-1] + int(v * MS))
    for i, t in enumerate(starts):
        totals = dict(QUIET, gc_ns=int(i * gc_ms_a_step * MS), gc_n=i)
        hub.record_event('train.launch', 'train', t_start_ns=t + MS // 2,
                         dur_ns=int(LAUNCH_MS * MS), k=1, update=i)
        hub.record_event('train.dispatch', 'train', t_start_ns=t,
                         dur_ns=int((SELF_MS + LAUNCH_MS) * MS), k=1,
                         update=i, **totals)
    window = harness.Window(t0, starts[-1] + STEP_MS * MS, 0, len(starts),
                            [], 0)
    cell = types.SimpleNamespace(t=lambda key: {'max_inflight': inflight}[key])
    return harness.Run(cell=cell, feed=None, spans=None, setup_s=0.0,
                       setup_compile_s=0.0, window=window, traced=None,
                       trace=None, memory_peak_bytes=0, flops_per_step=0.0,
                       peaks=None)


def _read(run):
    return {name.split('.', 1)[1]: mod.read(run)
            for name, mod in READERS.items()}


def test_the_readers_on_a_quiet_series(hub):
    got = _read(_made_run(hub, [2] * 8 + [STEP_MS] * 40 + [53] + [STEP_MS]))
    assert got['host_self_ms_p50'] == pytest.approx(SELF_MS)
    assert got['interval_max_over_p50'] == pytest.approx(53 / STEP_MS)
    assert got['stall_lost_pct'] == 0.0
    assert got['gc_ms_per_step'] == pytest.approx(0.02)


# with 8 steps of 50 ms in flight a stall runs the queue dry after 400 ms;
# while the host catches up its intervals are 2 ms, then the steady 50 again.
# Where the window closes before the 8 intervals after the stall are out
# (rest 0), those to come count as nought: a lower bound, so what the queue
# absorbed is not read as lost
@pytest.mark.parametrize('stall_ms,then,rest,lost_ms', [
    (150, [2, 2] + [46], 30, 0.0),        # absorbed: made good at once
    (350, [2] * 6 + [38], 30, 0.0),       # absorbed, the queue nearly dry
    (600, [2] * 7 + [36], 30, 200.0),     # 0.6 s less 8 steps of 50 ms
    (3500, [2] * 7 + [36], 30, 3100.0),
    (150, [2, 2], 0, 0.0),                # absorbed; cut short it summed to 4
    (350, [2] * 5, 0, 0.0),
    (600, [2, 2], 0, 154.0),              # at least: 604 less 9 steps
    (3500, [], 0, 3050.0),
])
def test_stall_lost_pct_reads_what_the_queue_did_not_absorb(
        hub, stall_ms, then, rest, lost_ms):
    series = [STEP_MS] * 30 + [stall_ms] + then + [STEP_MS] * rest
    run = _made_run(hub, series)
    got = _read(run)
    assert got['stall_lost_pct'] == pytest.approx(
        100 * lost_ms / (run.window.wall_s * 1e3))
    assert got['interval_max_over_p50'] == pytest.approx(stall_ms / STEP_MS)


def test_two_stalls_are_two_sums_and_an_interval_is_in_one(hub):
    after = [2] * 7 + [36]
    series = [STEP_MS] * 20 + [600] + after + [600] + after + [STEP_MS] * 20
    run = _made_run(hub, series)
    assert _read(run)['stall_lost_pct'] == pytest.approx(
        100 * 400.0 / (run.window.wall_s * 1e3))


@pytest.mark.parametrize('case', ['wrapped ring', 'no step record',
                                  'no dispatch'])
def test_the_readers_return_none_without_the_record(hub, case):
    run = _made_run(hub, [STEP_MS] * 20, backend=case != 'wrapped ring')
    if case != 'wrapped ring':
        kept = [e for e in hub.events()
                if case == 'no step record' or e['name'] != 'train.dispatch']
        fresh = TelemetryHub()
        install_hub(fresh)           # the fixture puts the first one back
        for e in kept:               # a program before PR 38
            attrs = {k: v for k, v in e['attrs'].items() if k not in TOTALS}
            fresh.record_event(e['name'], e['subsystem'],
                               t_start_ns=e['t_start_ns'],
                               dur_ns=e['dur_ns'], **attrs)
    assert set(_read(run).values()) == {None}


# --- utils/profiler.py: a loop once, the grouped products under their layer ---

def _event(text, start, dur):
    return types.SimpleNamespace(name=text, start_ns=start, duration_ns=dur)


LOOP_HLO = """
%body.1 (p: f32[8]) -> f32[8] {
  ROOT %fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fc.2, metadata={op_name="jit(train_step)/jvp(l03_loss)/while/body/mul"}
}
%branch.1 (p: f32[8]) -> f32[8] {
  ROOT %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fc.3, metadata={op_name="jit(train_step)/transpose(jvp(l03_loss))/cond/branch_1_fun/mul"}
}
ENTRY %main (p: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%fc.1, metadata={op_name="jit(train_step)/jvp(l01_conv)/conv"}
  %while.4 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(train_step)/jvp(l03_loss)/while"}
  %conditional.5 = f32[8]{0} conditional(s32[] %i, f32[8]{0} %p), branch_computations={%branch.1}, metadata={op_name="jit(train_step)/transpose(jvp(l03_loss))/cond"}
  ROOT %call.6 = f32[8]{0} call(f32[8]{0} %p), to_apply=%body.1
}
"""


def test_a_loop_is_counted_once_by_its_body():
    """The event of a ``while``, a ``conditional`` or a ``call`` spans its
    body's events; the table's sum is the device's busy time."""
    def text(name):
        return next(l.strip().replace('ROOT ', '')
                    for l in LOOP_HLO.splitlines() if f'%{name} = ' in l)
    ops = [_event(text('fusion.1'), 1000, 1000),
           _event(text('while.4'), 2000, 600),          # spans two bodies
           _event(text('fusion.2'), 2000, 300),
           _event(text('fusion.2'), 2300, 300),
           _event(text('conditional.5'), 2600, 300),
           _event(text('fusion.3'), 2600, 300),
           _event(text('call.6'), 2900, 100),
           _event(text('fusion.2'), 2900, 100)]
    modules = [_event('jit_train_step(1)', 1000, 2000)]
    table = profiler.reduce_by_scope(ops, modules, LOOP_HLO)
    assert table['steps'] == 1
    assert table['scopes'] == {
        ('l01_conv', 'fwd'): pytest.approx(1000e-6),
        ('l03_loss', 'fwd'): pytest.approx(700e-6),
        ('l03_loss', 'bwd'): pytest.approx(300e-6)}
    assert sum(table['scopes'].values()) == pytest.approx(2000e-6)
    assert table['busy'] == pytest.approx(2000e-6)
    # an event whose instruction the text does not hold: its own opcode says
    bare = profiler.reduce_by_scope(ops, modules, '')
    assert bare['scopes'] == {('other', '-'): pytest.approx(2000e-6)}


def _line(name, opcode, reads, op_name=''):
    meta = f', metadata={{op_name="{op_name}"}}' if op_name else ''
    extra = ', custom_call_target="tpu_custom_call"' \
        if opcode == 'custom-call' else ''
    return (f'  %{name} = f32[8]{{0}} {opcode}('
            + ', '.join('f32[8]{0} %' + r for r in reads)
            + f'){extra}{meta}')


def test_a_grouped_product_is_filed_under_its_moe_layer():
    """XLA writes ``op_name="ragged-dot-none"`` over the layer's path: the
    call goes where most of what it reads and of what reads it is filed,
    what it reads on a tie, nowhere among neighbours without a scope; no
    other kind of instruction adopts one."""
    moe, bwd = ('jit(train_step)/jvp(l02_moe_e)/mul',
                'jit(train_step)/transpose(jvp(l02_moe_e))/mul')
    text = '\n'.join([
        'ENTRY %main (x: f32[8]) -> f32[8] {',
        _line('x', 'parameter', []),
        _line('a', 'add', ['x'], moe),
        _line('b', 'add', ['x'], bwd),
        _line('c', 'add', ['x'], bwd),
        _line('ragged-dot-none.1', 'custom-call', ['a', 'x'],
              'ragged-dot-none'),
        _line('ragged-dot-none.2', 'custom-call', ['a', 'b', 'c'],
              'ragged-dot-none'),
        _line('ragged-dot-none.3', 'custom-call', ['x'], 'ragged-dot-none'),
        _line('copy.4', 'copy', ['a']),
        _line('u', 'add', ['ragged-dot-none.1'], 'jit(train_step)/update/add'),
        '}'])
    program = profiler.hlo_program(text)
    names = profiler.hlo_op_names(text)

    def filed(name):
        assert program[name][1] == names[name]
        return profiler.scope_of(names[name])
    assert program['ragged-dot-none.1'][0] == 'custom-call'
    assert filed('ragged-dot-none.1') == ('l02_moe_e', 'fwd')   # the tie
    assert filed('ragged-dot-none.2') == ('l02_moe_e', 'bwd')   # 2 of 3
    assert filed('ragged-dot-none.3') == ('other', '-')
    assert filed('copy.4') == ('other', '-')
    ops = [_event(_line('a', 'add', ['x'], moe).strip(), 0, 100),
           _event(_line('ragged-dot-none.1', 'custom-call', ['a', 'x'],
                        'ragged-dot-none').strip(), 100, 400),
           _event(_line('ragged-dot-none.2', 'custom-call', ['a', 'b', 'c'],
                        'ragged-dot-none').strip(), 500, 300)]
    table = profiler.reduce_by_scope(ops, [_event('jit_train_step(1)', 0,
                                                  1000)], text)
    assert table['scopes'] == {('l02_moe_e', 'fwd'): pytest.approx(500e-6),
                               ('l02_moe_e', 'bwd'): pytest.approx(300e-6)}
    assert table['kernels'] == {'ragged-dot-none': pytest.approx(700e-6)}


def test_idle_gaps_go_to_the_innermost_host_span():
    busy = [(0, 100), (150, 400), (700, 900)]
    spans = [('train.dispatch', 90, 300), ('train.launch', 120, 200),
             ('host.gc', 420, 520), ('train.dispatch', 650, 950)]
    got = profiler.idle_by_host_span(busy, (0, 1000), spans)
    # [100, 150): dispatch till 120, then the launch inside it; [400, 700):
    # 20 outside, 100 in the collector, 130 outside, 50 in the next dispatch;
    # [900, 1000): 50 and 50
    assert got == {'train.dispatch': 20 + 50 + 50, 'train.launch': 30,
                   'host.gc': 100, 'outside': 20 + 130 + 50}
    table = profiler.reduce_by_scope(
        [_event('%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)', 0, 600)],
        [_event('jit_train_step(1)', 0, 500),
         _event('jit_train_step(1)', 500, 500)], '',
        [('train.launch', 600, 800)])
    assert table['idle'] == {'train.launch': pytest.approx(100e-6),
                             'outside': pytest.approx(100e-6)}
    assert 'profile-idle\ttrain.launch\t0.000' in \
        profiler.format_scope_table(table)


def test_host_spans_are_the_dispatching_threads_and_every_collection():
    def line(*spans):
        return types.SimpleNamespace(events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=e - s)
            for n, s, e in spans])
    planes = [
        types.SimpleNamespace(name='/device:TPU:0', lines=[
            line(('cxxnet.train.dispatch', 0, 9))]),
        types.SimpleNamespace(name='/host:CPU', lines=[
            line(('cxxnet.train.dispatch', 90, 300),
                 ('cxxnet.train.launch', 120, 200), ('PjitFunction', 0, 5),
                 ('cxxnet.io.next', 310, 330)),
            # a checkpoint's writer, open all along, and a collection there
            line(('cxxnet.ckpt.write', 0, 1000), ('cxxnet.host.gc', 420, 520)),
        ])]
    spans = profiler.host_spans(planes)
    assert sorted(spans) == [('host.gc', 420, 520), ('io.next', 310, 330),
                             ('train.dispatch', 90, 300),
                             ('train.launch', 120, 200)]
    got = profiler.idle_by_host_span([(0, 100), (150, 400)], (0, 600), spans)
    assert got == {'train.dispatch': 20, 'train.launch': 30, 'host.gc': 100,
                   'outside': 20 + 80}
