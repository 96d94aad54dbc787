"""One set of names from the conf to the trace (doc/observability.md "Names in
the trace"): a ``jax.named_scope`` per conf layer and per part of the step,
a ``name=`` per Pallas kernel, hub spans written into the profiler's trace,
and the function that reads the names back out of a trace."""

import ast
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.obs import get_hub, span
from cxxnet_tpu.ops import pallas_kernels as pk
from cxxnet_tpu.utils import profiler
from cxxnet_tpu.utils.config import parse_config_file, parse_config_string

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONV_CONF = """
netconfig = start
layer[0->1] = conv:c1
  kernel_size = 3
  nchannel = 8
  pad = 1
layer[1->2] = relu
layer[2->3] = max_pooling
  kernel_size = 2
  stride = 2
layer[3->4] = lrn
  local_size = 3
layer[4->5] = flatten
layer[5->6] = fullc:fc
  nhidden = 4
layer[6->6] = softmax
netconfig = end
input_shape = 1,8,8
batch_size = 8
dev = cpu
eta = 0.1
metric = error
"""
SCOPES = ['l00_conv_c1', 'l01_relu', 'l02_max_pooling', 'l03_lrn',
          'l04_flatten', 'l05_fullc_fc', 'l06_softmax']


def _trainer(extra=''):
    tr = NetTrainer(parse_config_string(CONV_CONF + extra))
    tr.init_model()
    return tr


def _batch(n=8):
    rng = np.random.RandomState(0)
    return DataBatch(rng.rand(n, 1, 8, 8).astype(np.float32),
                     rng.randint(0, 4, (n, 1)).astype(np.float32))


def _lowered_step(tr):
    """The step program ``update_staged`` dispatches, lowered and not run."""
    data, label, extra, mask = tr.stage_batch(_batch())[:4]
    rng = jax.random.fold_in(tr._rng, 1)
    return tr._train_step_fn._jit.lower(
        tr.params, tr.opt_state, tr.grad_acc, data, label, extra, mask, rng,
        tr.epoch_counter, tr.round, do_update=True, norm=())


# --- A.1: a scope per conf layer --------------------------------------------

def test_scope_names_come_from_the_conf():
    tr = _trainer()
    assert tr.net.layer_scopes == SCOPES
    assert all(re.fullmatch(r'\w+', s) for s in tr.net.layer_scopes)


@pytest.fixture(scope='module')
def step_hlo():
    return _lowered_step(_trainer()).compile().as_text()


@pytest.fixture(scope='module')
def period2_step_hlo():
    return _lowered_step(
        _trainer('update_period = 2\n')).compile().as_text()


@pytest.mark.parametrize('scope', SCOPES)
def test_layer_is_forward_and_backward_in_the_step_program(step_hlo, scope):
    assert f'/jvp({scope})/' in step_hlo
    assert f'/transpose(jvp({scope}))/' in step_hlo


def test_step_parts_are_scoped(step_hlo, period2_step_hlo):
    assert re.search(r'op_name="jit\(train_step\)/update/', step_hlo)
    # the accumulator is a part of update_period > 1 programs only
    assert 'grad_acc/' not in step_hlo
    assert re.search(r'op_name="jit\(train_step\)/update/',
                     period2_step_hlo)
    assert 'op_name="jit(train_step)/grad_acc/' in period2_step_hlo
    # the input's transpose and the gate melt into their neighbours'
    # fusions once compiled; the lowered program still says whose they are
    nan = _lowered_step(_trainer('nan_action = skip\n')).as_text(
        debug_info=True)
    assert '"jit(train_step)/jvp(input)/transpose"' in nan
    assert '"jit(train_step)/nan_gate/' in nan


@pytest.mark.parametrize('period', [1, 2])
def test_scanned_step_carries_the_same_scopes(period):
    tr = _trainer(f'update_period = {period}\n')
    fn = tr.compile_multi_step(2)
    staged = [tr.stage_batch(_batch()) for _ in range(2)]
    stack = lambda i: tr._device_stack([s[i] for s in staged])  # noqa: E731
    text = jax.jit(fn).lower(
        tr.params, tr.opt_state, tr.grad_acc, stack(0), stack(1), tr._rng,
        tr.epoch_counter, 0, stack(3), tr.round).as_text(debug_info=True)
    for scope in ('"jvp(l00_conv_c1)/', '"transpose(jvp(l05_fullc_fc))/',
                  '"update/'):
        assert scope in text, scope
    assert ('"grad_acc/' in text) == (period > 1)


def _strip_names(mlir: str) -> str:
    """A lowered program without what a name or a scope may change: the
    ``loc(...)`` annotations and their table."""
    mlir = re.sub(r'\s*loc\((?:[^()]|\([^()]*\))*\)', '', mlir)
    return '\n'.join(l for l in mlir.splitlines()
                     if not l.startswith('#loc'))


def test_scopes_change_names_only(monkeypatch):
    """The step program lowered with the scopes is the one lowered without
    them (the parent's) once names and metadata are stripped."""
    import contextlib
    with_names = _lowered_step(_trainer('nan_action = skip\n'))
    monkeypatch.setattr(jax, 'named_scope',
                        lambda name: contextlib.nullcontext())
    bare = _lowered_step(_trainer('nan_action = skip\n'))
    assert '/update/' in with_names.as_text(debug_info=True)
    assert '/update/' not in bare.as_text(debug_info=True)
    assert _strip_names(with_names.as_text(debug_info=True)) \
        == _strip_names(bare.as_text(debug_info=True))
    assert with_names.as_text() == bare.as_text()


def test_a_delta_attention_layer_is_scoped_like_every_conf_layer():
    """``kda`` layers run under ``lNN_kda_<name>``, forward, recomputation
    and backward, which is what ``net.kda_ms_per_step`` and
    ``net.kda_roofline_pct`` split the trace by (``scope_times.scope_ms(run,
    'kda')``); lowered, not compiled."""
    pairs = parse_config_file(os.path.join(REPO, 'example', 'LM',
                                           'tiny-solar.conf'))
    # from the net on: the data section names an iterator, not the net
    tr = NetTrainer(pairs[pairs.index(('netconfig', 'start')):])
    tr.init_model()
    scopes = [s for s in tr.net.layer_scopes if '_kda_' in s]
    assert scopes == ['l04_kda_kda1', 'l06_kda_kda2', 'l08_kda_kda3']
    rng = np.random.RandomState(0)
    data, label, extra, mask = tr.stage_batch(DataBatch(
        rng.randint(0, 96, (2, 1, 1, 97)).astype(np.float32),
        rng.randint(0, 96, (2, 96)).astype(np.float32)))[:4]
    text = tr._train_step_fn._jit.lower(
        tr.params, tr.opt_state, tr.grad_acc, data, label, extra, mask,
        jax.random.PRNGKey(0), 0, 0, do_update=True).as_text(debug_info=True)
    for scope in scopes:
        assert f'/jvp({scope})/' in text and \
            f'/transpose(jvp({scope}))/' in text, scope


# --- A.2: a name per Pallas kernel ------------------------------------------

def _pallas_names(fn, *args):
    """``name`` of every ``pallas_call`` equation ``fn(*args)`` traces to,
    nested jaxprs (custom_vjp, pjit) included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == 'pallas_call':
                found.append(eqn.params['name'])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, 'jaxpr', sub)
                    if hasattr(inner, 'eqns'):
                        walk(inner)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _f32(*shape):
    return jnp.ones(shape, jnp.float32)


def _grad(fn):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                    argnums=0)


KERNEL_CASES = {
    'matmul': lambda: (pk.pallas_matmul, (_f32(8, 16), _f32(16, 8))),
    'matmul_nt': lambda: (_grad(pk.pallas_matmul),
                          (_f32(8, 16), _f32(16, 8))),
    'matmul_tn': lambda: (jax.grad(lambda a, b: jnp.sum(
        pk.pallas_matmul(a, b)), argnums=1), (_f32(8, 16), _f32(16, 8))),
    'delta_rule_fwd': lambda: (_delta_rule, _delta_rule_args()),
    'delta_rule_bwd': lambda: (_grad(_delta_rule), _delta_rule_args()),
}


def _delta_rule(q, k, v, g, beta):
    from cxxnet_tpu.ops import delta_rule_kernel
    return delta_rule_kernel.chunk_gated_delta_rule(q, k, v, g, beta, 0.5, 16,
                                                    8, interpret=True)


def _delta_rule_args():
    return (_f32(1, 1, 16, 8), _f32(1, 1, 16, 8), _f32(1, 1, 16, 8),
            -_f32(1, 1, 16, 8), _f32(1, 1, 16))


def test_kernel_table_is_the_cases():
    assert sorted(KERNEL_CASES) == sorted(pk.KERNEL_NAMES)
    assert all(re.fullmatch(r'[a-z0-9_]+', n) for n in pk.KERNEL_NAMES)


@pytest.mark.parametrize('name', pk.KERNEL_NAMES)
def test_pallas_call_carries_its_name(name):
    fn, args = KERNEL_CASES[name]()
    assert name in _pallas_names(fn, *args)


def test_every_pallas_call_site_is_named_from_the_table():
    """No ``pl.pallas_call(`` under ``ops/`` without a ``name=`` that is a
    literal of the table."""
    sites = 0
    for path in glob.glob(os.path.join(REPO, 'cxxnet_tpu', 'ops', '*.py')):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == 'pallas_call':
                sites += 1
                kw = {k.arg: k.value for k in node.keywords}
                assert 'name' in kw, f'{path}:{node.lineno}'
                v = kw['name']
                assert isinstance(v, ast.Constant) \
                    and v.value in pk.KERNEL_NAMES, f'{path}:{node.lineno}'
    assert sites == 5


# --- B: hub spans on the profiler's clock -----------------------------------

def test_hub_span_is_an_event_of_the_profilers_trace(tmp_path):
    from jax.profiler import ProfileData, TraceAnnotation
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation('outer.marker'):
            with span('unit.bridge', 'test', k=1):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / 'plugins' / 'profile' / '*'
                         / '*.xplane.pb'))[0]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith('/host:'):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in ('outer.marker', 'cxxnet.unit.bridge'):
                    found[e.name] = (line.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
    assert set(found) == {'outer.marker', 'cxxnet.unit.bridge'}
    (l0, s0, e0), (l1, s1, e1) = (found['outer.marker'],
                                  found['cxxnet.unit.bridge'])
    assert l0 == l1 and s0 <= s1 and e1 <= e0
    # and the hub's own record of it is unchanged
    ev = [e for e in get_hub().events() if e['name'] == 'unit.bridge']
    assert ev and ev[-1]['attrs'] == {'k': 1}


# --- C: the spans the benchmark reads ---------------------------------------

def _events_since(t0, name):
    return [e for e in get_hub().events()
            if e['name'] == name and e['t_start_ns'] >= t0]


def _now():
    import time
    return time.monotonic_ns()


def test_entry_backend_span_is_recorded_once_a_process(monkeypatch):
    from cxxnet_tpu.utils import backend
    monkeypatch.setattr(backend, '_met', False)
    t0 = _now()
    assert backend.meet_backend() == 'cpu'
    assert backend.require_chip() == 'cpu'
    ev = _events_since(t0, 'entry.backend')
    assert len(ev) == 1
    assert ev[0]['attrs'] == {'platform': 'cpu',
                              'devices': jax.device_count()}


def test_trainer_spans_fire_once_a_call_with_their_attributes():
    t0 = _now()
    tr = _trainer()
    (ev,) = _events_since(t0, 'net.init_model')
    leaves = jax.tree.leaves(tr.params)
    assert ev['attrs'] == {'leaves': len(leaves),
                           'bytes': sum(x.nbytes for x in leaves)}
    assert ev['attrs']['leaves'] == 4          # conv and fullc: wmat, bias

    t0 = _now()
    staged = tr.stage_batch(_batch())
    (ev,) = _events_since(t0, 'train.stage')
    assert ev['attrs'] == {'rows': 8, 'bytes': 8 * 64 * 4 + 8 * 4,
                           'cast': False}

    t0 = _now()
    tr.update_staged(staged)
    tr.update_staged(staged)
    launches = _events_since(t0, 'train.launch')
    # update_staged's own span, train.dispatch, holds them since PR 38: the
    # hub stamps what it holds with its name
    inside = {'parent': 'train.dispatch'}
    assert [e['attrs'] for e in launches] == [
        {'k': 1, 'update': 0, **inside}, {'k': 1, 'update': 1, **inside}]
    # eval_train = 1 with a metric: the second step drains the first's
    (fetch,) = _events_since(t0, 'train.eval_fetch')
    (score,) = _events_since(t0, 'train.eval_score')
    assert fetch['attrs'] == score['attrs'] == {'rows': 8, **inside}
    assert fetch['t_start_ns'] + fetch['dur_ns'] <= score['t_start_ns']

    t0 = _now()
    fn = tr.compile_multi_step(2, train_eval=True)
    tr.update_staged_window(fn, [tr.stage_batch(_batch()) for _ in range(2)])
    (ev,) = _events_since(t0, 'train.launch')
    assert ev['attrs'] == {'k': 2, 'update': 2, **inside}
    tr.flush_train_metrics()
    (fetch,) = [e for e in _events_since(t0, 'train.eval_fetch')
                if e['attrs'] == {'rows': 16}]


def test_bf16_stage_says_it_cast():
    tr = _trainer('compute_type = bfloat16\n')
    t0 = _now()
    tr.stage_batch(_batch())
    (ev,) = _events_since(t0, 'train.stage')
    assert ev['attrs']['cast'] is True
    assert ev['attrs']['bytes'] == 8 * 64 * 2 + 8 * 4


def test_io_next_spans_every_wait_for_a_batch():
    from cxxnet_tpu.main import _spanned_batches
    t0 = _now()
    assert list(_spanned_batches(iter('abc'))) == ['a', 'b', 'c']
    assert len(_events_since(t0, 'io.next')) == 4     # three, and the end


# --- the public per-step loss hook ------------------------------------------

def test_loss_listener_hears_every_dispatched_step():
    tr = _trainer()
    heard = []
    tr.add_loss_listener(heard.append)
    staged = tr.stage_batch(_batch())
    tr.update_staged(staged)
    tr.update_staged(staged)
    assert len(heard) == 2 and all(isinstance(v, jax.Array) for v in heard)
    fn = tr.compile_multi_step(3, train_eval=True)
    last = tr.update_staged_window(fn, [staged] * 3)
    assert len(heard) == 5
    assert float(heard[-1]) == float(last)
    assert all(np.isfinite(float(v)) for v in heard)
    assert float(heard[0]) > float(heard[-1])          # it trains


# --- train-mfu over the mesh ------------------------------------------------

def test_mfu_divides_by_the_peak_of_the_whole_mesh(monkeypatch, capsys):
    from cxxnet_tpu.main import LearnTask
    from cxxnet_tpu.obs.programs import mfu
    monkeypatch.setenv('CXXNET_PEAK_TFLOPS', '0.000001')
    assert mfu(1e3, 100.0) == pytest.approx(0.1)
    assert mfu(1e3, 100.0, devices=2) == pytest.approx(0.05)

    def train_mfu(dev):
        task = LearnTask()
        task.net_trainer = NetTrainer(parse_config_string(
            CONV_CONF.replace('dev = cpu', f'dev = {dev}')))
        task.net_trainer.init_model()
        task.net_trainer.update(_batch())
        capsys.readouterr()
        task._write_train_speed(10, 1.0)
        line = capsys.readouterr().err
        return (float(re.search(r'train-mfu:([0-9.e+-]+)', line).group(1)),
                task.net_trainer.train_step_flops())

    one, flops_one = train_mfu('cpu')
    two, flops_two = train_mfu('cpu:0-1')
    assert flops_one == flops_two          # the whole step's, on any mesh
    assert two == pytest.approx(one / 2, rel=1e-3)


# --- A.3: the names read back out of a trace --------------------------------

HAND_MADE = """
# One device, two executions of the step program jit_train_step, [1000,
# 5000) and [6000, 10000) ns, and one of another program at [10500, 10600).
# Ops (ns):                                       op_name in the HLO text
#   fusion.1     [1000, 2000) and [6000, 7200)     none of its own; what
#                                                  it calls: jvp(l00_conv_c1)
#   matmul.1     [2000, 2500) and [7200, 7700)     jvp(l05_fullc_fc)
#   matmul_nt.1  [2500, 3500) and [7700, 8500)     transpose(jvp(
#                                                  l05_fullc_fc))
#   fusion.2     [3500, 4000) and [8500, 9000)     .../update/mul
#   copy.7       [4000, 4100) and [9000, 9100)     none
#   fusion.1     [10500, 10600)                    outside the step program
# a step: l00_conv_c1 fwd (1000 + 1200) / 2 = 1100 ns; l05_fullc_fc fwd 500,
# bwd (1000 + 800) / 2 = 900; update 500; other 100; kernels matmul 500,
# matmul_nt 900.
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 1500000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 2500000 duration_ps: 500000 }
    events { metadata_id: 5 offset_ps: 3000000 duration_ps: 100000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1200000 }
    events { metadata_id: 2 offset_ps: 6200000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 6700000 duration_ps: 800000 }
    events { metadata_id: 4 offset_ps: 7500000 duration_ps: 500000 }
    events { metadata_id: 5 offset_ps: 8000000 duration_ps: 100000 }
    events { metadata_id: 1 offset_ps: 9500000 duration_ps: 100000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 7 offset_ps: 5000000 duration_ps: 4000000 }
    events { metadata_id: 8 offset_ps: 9500000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kOutput, calls=%fused_computation.1" } }
  event_metadata { key: 2 value { id: 2 name: "%matmul.1 = (bf16[8,128]{1,0}, f32[8,128]{1,0}) custom-call(bf16[8,128]{1,0} %bitcast), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%matmul_nt.1 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %bitcast.2), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p.2), kind=kLoop, calls=%fused_computation.2" } }
  event_metadata { key: 5 value { id: 5 name: "%copy.7 = f32[8]{0} copy(f32[8]{0} %p.3)" } }
  event_metadata { key: 7 value { id: 7 name: "jit_train_step(123)" } }
  event_metadata { key: 8 value { id: 8 name: "jit_fold_in(7)" } }
}
"""
HAND_MADE_HLO = """
%fused_computation.1 (p: bf16[8]) -> bf16[8] {
  ROOT %convolution.3 = bf16[8]{0} convolution(%p), metadata={op_name="jit(train_step)/jvp(l00_conv_c1)/conv_general_dilated"}
}
ENTRY %main (p: bf16[8]) -> f32[8] {
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kOutput, calls=%fused_computation.1
  %matmul.1 = (bf16[8,128]{1,0}, f32[8,128]{1,0}) custom-call(%bitcast), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(l05_fullc_fc)/matmul/pallas_call" stack_frame_id=9}
  %matmul_nt.1 = bf16[8,128]{1,0} custom-call(%bitcast.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(l05_fullc_fc))/matmul_nt/pallas_call" stack_frame_id=2}
  %fusion.2 = f32[8]{0} fusion(%p.2), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(train_step)/update/mul"}
  ROOT %copy.7 = f32[8]{0} copy(%p.3)
}
"""


def test_scope_of():
    assert profiler.scope_of(
        'jit(train_step)/jit(main)/transpose(jvp(l03_lrn))/mul') \
        == ('l03_lrn', 'bwd')
    assert profiler.scope_of('jit(train_step)/jvp(l00_conv_c1)/conv') \
        == ('l00_conv_c1', 'fwd')
    assert profiler.scope_of('jit(train_step)/update/mul') == ('update', '-')
    assert profiler.scope_of('jit(multi_step)/while/body/update/cond/'
                             'branch_1_fun/mul') == ('update', '-')
    assert profiler.scope_of('jit(forward_step)/l03_lrn/jit(lrn)/mul') \
        == ('l03_lrn', '-')
    assert profiler.scope_of('jit(train_step)/convert_element_type') \
        == ('other', '-')
    assert profiler.scope_of('') == ('other', '-')


def test_device_time_by_scope_on_a_hand_made_trace():
    from jax.profiler import ProfileData
    text = '\n'.join(l for l in HAND_MADE.splitlines()
                     if not l.startswith('#'))
    (plane,) = ProfileData.from_text_proto(text).planes
    ops, modules = (list(l.events) for l in plane.lines)
    table = profiler.reduce_by_scope(ops, modules, HAND_MADE_HLO)
    assert table['module'] == 'jit_train_step(123)' and table['steps'] == 2
    want = {('l00_conv_c1', 'fwd'): 1100e-6,
            ('l05_fullc_fc', 'fwd'): 500e-6,
            ('l05_fullc_fc', 'bwd'): 900e-6, ('update', '-'): 500e-6,
            ('other', '-'): 100e-6}
    assert set(table['scopes']) == set(want)
    for key, ms in want.items():
        assert table['scopes'][key] == pytest.approx(ms), key
    assert table['kernels'] == {'matmul': pytest.approx(500e-6),
                                'matmul_nt': pytest.approx(900e-6)}
    lines = profiler.format_scope_table(table)
    assert lines[1].split('\t')[:2] == ['profile-scope', 'l00_conv_c1']
    assert 'profile-kernel\tmatmul_nt\t0.001' in lines
    # without the program's text every event is 'other'; kernels keep names
    bare = profiler.reduce_by_scope(ops, modules, '')
    assert bare['scopes'] == {('other', '-'): pytest.approx(3100e-6)}
    assert bare['kernels'] == table['kernels']


def test_step_program_text_is_the_dispatched_step(monkeypatch):
    from cxxnet_tpu.obs.programs import get_ledger
    tr = _trainer()
    assert tr.step_program_text() == ''
    tr.update(_batch())
    compiles = get_ledger().summary()['compiles_total']
    text = tr.step_program_text()
    assert get_ledger().summary()['compiles_total'] == compiles
    assert text.startswith('HloModule jit_train_step')
    names = profiler.hlo_op_names(text)
    assert {profiler.scope_of(v) for v in names.values()} >= {
        ('l00_conv_c1', 'fwd'), ('l00_conv_c1', 'bwd'), ('update', '-')}


def test_trace_window_has_nothing_to_print_on_the_cpu(tmp_path, capsys):
    def no_text():
        raise AssertionError('no device plane: the text is not asked for')
    win = profiler.TraceWindow(hlo_text=no_text)
    win.configure([('profile_dir', str(tmp_path)),
                   ('profile_start_batch', '0'),
                   ('profile_stop_batch', '1')])
    win.before_update(0)
    jnp.ones(4).block_until_ready()
    win.before_update(1)
    assert not win._active and win._done
    assert 'profile-scope' not in capsys.readouterr().err
