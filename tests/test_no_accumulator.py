"""``update_period = 1`` keeps no gradient accumulator (PR 32).

Only a period above 1 has anything to carry between steps.  At period 1
``NetTrainer.grad_acc`` is ``None``, an empty pytree: the per-step and the
scanned program keep their positional signature and hold three arrays a
parameter under Adam (the parameter and two moments), not four; the update
reads the gradients ``value_and_grad`` returned.  The numbers are the
parent's: ``0 + g`` is ``g``.  The exact-resume sidecar follows the
reader's period, not the writer's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.nnet import sharded_ckpt
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.obs import TelemetryHub, install_hub
from cxxnet_tpu.obs.programs import ProgramLedger, install_ledger
from cxxnet_tpu.runtime import faults
from cxxnet_tpu.runtime.async_ckpt import AsyncCheckpointer
from cxxnet_tpu.runtime.supervisor import SupervisorConfig, TrainSupervisor
from cxxnet_tpu.updater.updaters import apply_updates
from cxxnet_tpu.utils.config import parse_config_string

from test_device_normalize import assert_params_equal, snap_params
from test_net_mnist import synth_batches

# fc1 wide enough that a window's batches weigh less than one copy of the
# parameters: the ledger's argument bytes then count the copies
CONF = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 64
  init_sigma = 0.1
layer[+1:sg1] = sigmoid:se1
layer[sg1->fc2] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,16
batch_size = 32
dev = cpu
eta = 0.5
momentum = 0.9
wd  = 0.0001
metric[label] = error
eval_train = 0
"""
PARAM_BYTES = (16 * 64 + 64 + 64 * 4 + 4) * 4


@pytest.fixture
def ledger():
    led = ProgramLedger()
    prev = install_ledger(led)
    yield led
    install_ledger(prev)


@pytest.fixture
def hub():
    h = TelemetryHub(ring_events=256)
    prev = install_hub(h)
    yield h
    h.disarm()
    install_hub(prev)


def _trainer(extra=''):
    tr = NetTrainer(parse_config_string(CONF + extra))
    tr.init_model()
    return tr


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _assert_trees_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(_leaves(got), _leaves(want)):
        np.testing.assert_array_equal(g, w)


def _state(tr):
    return {'params': tr.params, 'opt_state': tr.opt_state,
            'grad_acc': tr.grad_acc,
            'counters': (tr.epoch_counter, tr.sample_counter)}


# --- what is allocated, and what the programs hold ------------------------

@pytest.mark.parametrize('period', [1, 2])
def test_accumulator_only_above_period_1(period):
    tr = _trainer(f'update_period = {period}\n')
    if period == 1:
        assert tr.grad_acc is None
    else:
        _assert_trees_equal(tr.grad_acc,
                            jax.tree.map(np.zeros_like, tr.params))
    for b in synth_batches(n_batches=2):
        tr.update(b)
    assert (tr.grad_acc is None) == (period == 1)
    assert tr.resident_state_bytes() == {
        'param_bytes': PARAM_BYTES, 'opt_state_bytes': PARAM_BYTES,
        'accumulator_bytes': PARAM_BYTES * (period > 1)}


@pytest.mark.parametrize('program', ['step', 'scan'])
@pytest.mark.parametrize('period', [1, 2])
def test_program_holds_three_arrays_a_parameter_not_four(ledger, period,
                                                         program):
    """Adam at period 1: parameter, two moments.  Period 2 adds the
    accumulator, its scope and one more copy among the arguments."""
    tr = _trainer(f'updater = adam\nupdate_period = {period}\n')
    batches = synth_batches(n_batches=2)
    staged = [tr.stage_batch(b) for b in batches]
    if program == 'step':
        data, label, extra, mask = staged[0][:4]
        lowered = tr._train_step_fn._jit.lower(
            tr.params, tr.opt_state, tr.grad_acc, data, label, extra, mask,
            tr._rng, tr.epoch_counter, tr.round, do_update=True, norm=())
        tr.update_staged(staged[0])
        entry = tr._prog_step.newest_entry()
    else:
        fn = tr.compile_multi_step(2)
        stack = lambda i: tr._device_stack(  # noqa: E731
            [s[i] for s in staged])
        lowered = jax.jit(fn).lower(
            tr.params, tr.opt_state, tr.grad_acc, stack(0), stack(1),
            tr._rng, tr.epoch_counter, 0, stack(3), tr.round)
        tr.update_staged_window(fn, staged)
        entry = tr._prog_multi.newest_entry()
    assert ('grad_acc/' in lowered.as_text(debug_info=True)) == (period > 1)
    # the batches are less than one copy, so the quotient counts copies
    assert entry.argument_bytes // PARAM_BYTES == 3 + (period > 1)
    assert (tr.grad_acc is None) == (period == 1)


# --- the numbers are the parent's -----------------------------------------

@pytest.mark.parametrize('updater', ['sgd', 'nag', 'adam'])
def test_period_1_steps_equal_updates_applied_to_zero_plus_g(updater):
    """Three steps of the program without an accumulator against the
    parent's arithmetic done by hand: the gradients added into a zeroed
    accumulator, the update applied to the sum.  Bit for bit."""
    tr = _trainer(f'updater = {updater}\n')
    ref = _trainer(f'updater = {updater}\n')
    grad_fn = ref.compile_grad_step()

    @jax.jit
    def by_hand(params, opt_state, grads, epoch):
        acc = jax.tree.map(jnp.add, jax.tree.map(jnp.zeros_like, grads),
                           grads)
        return apply_updates(updater, ref.hypers, params, acc, opt_state,
                             epoch)

    params, opt_state = ref.params, ref.opt_state
    for step, b in enumerate(synth_batches(n_batches=3)):
        tr.update(b)
        data, label, extra, mask = ref.stage_batch(b)[:4]
        rng = jax.random.fold_in(ref._rng, 1 + step * 131)
        _, grads = grad_fn(params, data, label, extra, mask, rng, 0)
        params, opt_state = by_hand(params, opt_state, grads, step)
        _assert_trees_equal(tr.params, params)
        _assert_trees_equal(tr.opt_state, opt_state)
    assert tr.grad_acc is None and tr.epoch_counter == 3


def test_scanned_window_without_accumulator_equals_per_step():
    batches = synth_batches(n_batches=4)
    per, win = _trainer(), _trainer()
    for b in batches:
        per.update(b)
    fn = win.compile_multi_step(4)
    win.update_staged_window(fn, [win.stage_batch(b) for b in batches])
    _assert_trees_equal(_state(win), _state(per))
    assert win.grad_acc is None and win.epoch_counter == 4


# --- update_period set after init_model -----------------------------------

@pytest.mark.parametrize('program', ['step', 'scan'])
def test_period_changed_after_init_allocates_then_drops(program):
    """The accumulator appears at the first step of a period above 1 and
    goes at the first step of period 1, and the run equals one built at
    each period from the same state."""
    batches = synth_batches(n_batches=6)

    def drive(tr, chunk):
        if program == 'step':
            for b in chunk:
                tr.update(b)
        else:
            fn = tr.compile_multi_step(len(chunk))
            tr.update_staged_window(fn, [tr.stage_batch(b) for b in chunk])

    tr = _trainer()
    drive(tr, batches[:2])
    tr.set_param('update_period', '2')
    assert tr.grad_acc is None           # nothing moves before a step
    drive(tr, batches[2:4])
    assert tr.grad_acc is not None and tr.epoch_counter == 3
    _assert_trees_equal(tr.grad_acc, jax.tree.map(np.zeros_like, tr.params))
    tr.set_param('update_period', '1')
    drive(tr, batches[4:])
    assert tr.grad_acc is None and tr.epoch_counter == 5

    # the parent's arithmetic: an accumulator carried through every step,
    # added into and zero-filled at period 1 too
    ref = _trainer()
    ref.grad_acc = ref._zero_accumulator()
    for i, b in enumerate(batches):
        period = 2 if i in (2, 3) else 1
        data, label, extra, mask = ref.stage_batch(b)[:4]
        do_update = (i + 1) % period == 0
        ref.params, ref.opt_state, ref.grad_acc, *_ = ref._train_step_fn(
            ref.params, ref.opt_state, ref.grad_acc, data, label, extra,
            mask, jax.random.fold_in(ref._rng, 1 + i * 131),
            ref.epoch_counter, 0, do_update=do_update, norm=())
        ref.epoch_counter += do_update
    assert ref.epoch_counter == 5
    assert_params_equal(snap_params(tr), snap_params(ref), rtol=0, atol=0)
    _assert_trees_equal(tr.opt_state, ref.opt_state)


@pytest.mark.parametrize('program', ['step', 'scan'])
def test_dropping_a_half_filled_accumulator_raises(program):
    batches = synth_batches(n_batches=3)
    tr = _trainer('update_period = 2\n')
    tr.update(batches[0])                # one step of two: gradients held
    held = _leaves(tr.grad_acc)
    assert any(np.any(g != 0) for g in held)
    tr.set_param('update_period', '1')
    with pytest.raises(RuntimeError, match='unapplied gradients'):
        if program == 'step':
            tr.update(batches[1])
        else:
            fn = tr.compile_multi_step(1)
            tr.update_staged_window(fn, [tr.stage_batch(batches[1])])
    # nothing was lost or stepped: back at period 2 the run goes on
    assert tr.sample_counter == 1
    for g, h in zip(_leaves(tr.grad_acc), held):
        np.testing.assert_array_equal(g, h)
    tr.set_param('update_period', '2')
    tr.update(batches[1])
    assert tr.epoch_counter == 1


# --- the counter -----------------------------------------------------------

def test_train_state_event_says_what_is_resident(hub):
    def states():
        return [e['attrs'] for e in hub.events()
                if e['name'] == 'train.state']

    tr = _trainer('updater = adam\n')
    assert states() == [{'param_bytes': PARAM_BYTES,
                         'opt_state_bytes': 2 * PARAM_BYTES,
                         'accumulator_bytes': 0}]
    batches = synth_batches(n_batches=2)
    tr.update(batches[0])
    assert len(states()) == 1            # placed once, not a step event
    tr.set_param('update_period', '2')
    tr.update(batches[1])
    assert states()[-1] == {'param_bytes': PARAM_BYTES,
                            'opt_state_bytes': 2 * PARAM_BYTES,
                            'accumulator_bytes': PARAM_BYTES}
    assert len(states()) == 2
    _trainer('update_period = 3\n')
    assert states()[-1] == {'param_bytes': PARAM_BYTES,
                            'opt_state_bytes': PARAM_BYTES,
                            'accumulator_bytes': PARAM_BYTES}


# --- the sidecar -----------------------------------------------------------

def _parent_format_sidecar(tr, ckpt_dir, step, fill=0.0, native=False):
    """What the parent wrote at every period: an accumulator always."""
    tree = tr._training_state()
    tree['grad_acc'] = jax.tree.map(
        lambda p: jnp.full_like(p, fill), tr.params)
    if native:
        return sharded_ckpt.save_tree_native(
            ckpt_dir, step, jax.device_get(tree), retry=faults.NO_WAIT_RETRY)
    return sharded_ckpt.save_sharded(ckpt_dir, step, tree)


@pytest.mark.parametrize('native', [False, True], ids=['orbax', 'native'])
def test_parent_sidecar_restores_into_period_1_if_its_accumulator_is_zero(
        tmp_path, native):
    batches = synth_batches(n_batches=4)
    tr = _trainer()
    for b in batches[:2]:
        tr.update(b)
    _parent_format_sidecar(tr, str(tmp_path), 2, native=native)
    assert 'grad_acc' in sharded_ckpt.saved_keys(
        sharded_ckpt.step_dir(str(tmp_path), 2))
    t2 = _trainer()
    assert t2.load_training_state(str(tmp_path), restore_params=True) == 2
    assert t2.grad_acc is None and t2.sample_counter == 2
    for b in batches[2:]:
        tr.update(b)
        t2.update(b)
    _assert_trees_equal(_state(t2), _state(tr))


@pytest.mark.parametrize('native', [False, True], ids=['orbax', 'native'])
def test_sidecar_with_gradients_does_not_restore_into_period_1(tmp_path,
                                                               native):
    tr = _trainer()
    tr.update(synth_batches(n_batches=1)[0])
    _parent_format_sidecar(tr, str(tmp_path), 1, fill=0.25, native=native)
    t2 = _trainer()
    before = _leaves(t2.opt_state)
    with pytest.raises(RuntimeError, match='unapplied gradients'):
        t2.load_training_state(str(tmp_path), restore_params=True)
    # refused whole: nothing of the sidecar was adopted
    assert t2.sample_counter == 0
    for a, b in zip(_leaves(t2.opt_state), before):
        np.testing.assert_array_equal(a, b)
    # a trainer that accumulates takes it
    t3 = _trainer('update_period = 2\n')
    t3.load_training_state(str(tmp_path), restore_params=True)
    assert all(np.all(g == 0.25) for g in _leaves(t3.grad_acc))


@pytest.mark.parametrize('native', [False, True], ids=['orbax', 'native'])
def test_period_1_sidecar_restores_into_period_3_as_zeros(tmp_path, native):
    """...and the run equals one that never stopped: one trainer whose
    period went from 1 to 3 at the step the sidecar was written."""
    batches = synth_batches(n_batches=7)
    whole = _trainer()
    for b in batches[:2]:
        whole.update(b)
    if native:
        ck = AsyncCheckpointer(workers=2)
        ck.save_sharded_async(str(tmp_path), 2,
                              whole.snapshot_training_state(),
                              retry=faults.NO_WAIT_RETRY)
        ck.wait()
        ck.close()
    else:
        whole.save_training_state(str(tmp_path), 2)
    assert 'grad_acc' not in sharded_ckpt.saved_keys(
        sharded_ckpt.step_dir(str(tmp_path), 2))
    whole.set_param('update_period', '3')

    resumed = _trainer()
    resumed.set_param('update_period', '3')
    assert resumed.load_training_state(str(tmp_path),
                                       restore_params=True) == 2
    _assert_trees_equal(resumed.grad_acc,
                        jax.tree.map(np.zeros_like, resumed.params))
    for b in batches[2:]:
        whole.update(b)
        resumed.update(b)
    # samples 2-6 at period 3 apply after 2 and after 5: one step is held
    assert resumed.epoch_counter == whole.epoch_counter == 4
    _assert_trees_equal(_state(resumed), _state(whole))
    assert any(np.any(g != 0) for g in _leaves(resumed.grad_acc))


@pytest.mark.parametrize('writer', ['sync', 'async', 'supervisor-sync',
                                    'supervisor-async'])
@pytest.mark.parametrize('period', [1, 2])
def test_snapshots_round_trip_at_both_periods(tmp_path, period, writer):
    """Three steps (at period 2 the accumulator is half-filled), a save
    by each writer, a restore into a fresh trainer of the same period, and
    both go on as one."""
    extra = f'update_period = {period}\n'
    batches = synth_batches(n_batches=5)
    tr = _trainer(extra)
    for b in batches[:3]:
        tr.update(b)
    d = str(tmp_path / 'ck')
    if writer == 'sync':
        tr.save_training_state(d, 3)
    elif writer == 'async':
        ck = AsyncCheckpointer(workers=2)
        ck.save_sharded_async(d, 3, tr.snapshot_training_state(),
                              retry=faults.NO_WAIT_RETRY)
        ck.wait()
        ck.close()
    else:
        sup = TrainSupervisor(tr, d, SupervisorConfig(
            retry=faults.NO_WAIT_RETRY, save_every=0,
            save_async=int(writer == 'supervisor-async')))
        sup.save()
        sup.wait_for_saves()
        sup.close()
    assert ('grad_acc' in sharded_ckpt.saved_keys(
        sharded_ckpt.step_dir(d, 3))) == (period > 1)
    t2 = _trainer(extra)
    assert t2.load_training_state(d, restore_params=True,
                                  fallback=True) == 3
    _assert_trees_equal(_state(t2), _state(tr))
    for b in batches[3:]:
        tr.update(b)
        t2.update(b)
    _assert_trees_equal(_state(t2), _state(tr))
