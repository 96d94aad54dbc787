"""graftfuse suite (doc/kernels.md): inference conv+BN folding and
μ-cuDNN convolution microbatching.

Two contracts, each pinned here:

* a ``fold_bn=1`` PredictEngine serves scores equal (``FOLD_RTOL``/
  ``FOLD_ATOL``) to the unfolded engine on the calibration batch, and
  keeps that equality through hot swaps (re-fold) and re-placed trees
  (the double-fold identity guard);
* a ``micro_batch=k`` training step is a **bitwise** twin of the
  unsplit step at every declared split, composes with
  ``steps_per_dispatch`` scan dispatch, and bounds the ``train.step``
  program's ledger peak bytes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cxxnet_tpu.layers.conv import (_conv_im2col_mb, _conv_native_mb,
                                    microbatched_conv)
from cxxnet_tpu.nnet.fold import FOLD_ATOL, FOLD_RTOL
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.obs.programs import get_ledger
from cxxnet_tpu.serve.engine import PredictEngine
from cxxnet_tpu.utils.config import parse_config_string

pytestmark = pytest.mark.cnn_fused


# --- the small CNN the trainer-level tests share ---------------------------

_CNN_CONF = """
netconfig = start
layer[0->1] = conv:c1
  kernel_size = 3
  pad = 1
  nchannel = 8
layer[1->1] = relu
layer[1->2] = max_pooling
  kernel_size = 2
  stride = 2
layer[2->3] = conv:c2
  kernel_size = 3
  pad = 1
  nchannel = 16
layer[3->3] = relu
layer[3->4] = flatten
layer[4->5] = fullc:fc1
  nhidden = 10
layer[5->6] = softmax
netconfig = end

input_shape = 3,12,12
batch_size = 8
eta = 0.01
momentum = 0.9
metric = error
eval_train = 0
random_type = xavier
"""


def _trainer(extra=''):
    tr = NetTrainer(parse_config_string(_CNN_CONF + extra))
    tr.init_model()
    return tr


def _batch(rng):
    data = rng.randn(8, 3, 12, 12).astype(np.float32)
    label = rng.randint(0, 10, (8, 1)).astype(np.float32)
    return data, label


def _param_maxerr(a, b):
    return max(float(np.max(np.abs(
        np.asarray(a.params[lk][f], np.float32)
        - np.asarray(b.params[lk][f], np.float32))))
        for lk in a.params for f in a.params[lk])


# --- conv+BN folding through a real PredictEngine --------------------------

_FOLD_CONF = """
netconfig = start
layer[0->1] = conv:c1
  kernel_size = 3
  pad = 1
  nchannel = 8
layer[1->2] = batch_norm:bn1
layer[2->3] = relu
layer[3->4] = conv:c2
  kernel_size = 3
  pad = 1
  stride = 2
  nchannel = 16
layer[4->5] = batch_norm:bn2
layer[5->6] = relu
layer[6->7] = flatten
layer[7->8] = fullc:fc1
  nhidden = 10
layer[8->9] = softmax
netconfig = end

input_shape = 3,12,12
batch_size = 8
random_type = xavier
"""


@pytest.fixture()
def fold_engines():
    tr = NetTrainer(parse_config_string(_FOLD_CONF))
    tr.init_model()
    calib = np.random.RandomState(3).randn(8, 3, 12, 12).astype(np.float32)
    plain = PredictEngine(tr, (8,))
    folded = PredictEngine(tr, (8,), fold_bn=1, fold_batch=calib)
    return tr, calib, plain, folded


def test_fold_engine_serves_equal_scores(fold_engines):
    """The pinned fold contract: ON the calibration batch (BN here uses
    incoming-batch statistics even at eval — the reference quirk — so
    the frozen-stats fold is exact only where its statistics came from)
    the folded engine's scores equal the unfolded engine's."""
    _, calib, plain, folded = fold_engines
    view = folded.fold_view()
    assert view['pairs'] == [('c1', 'bn1'), ('c2', 'bn2')]
    assert view['max_abs_err'] <= FOLD_ATOL + FOLD_RTOL
    s_plain = plain.predict_scores(calib)
    s_fold = folded.predict_scores(calib)
    np.testing.assert_allclose(s_fold, s_plain,
                               rtol=FOLD_RTOL, atol=FOLD_ATOL)


def test_fold_ledger_key_carries_fold_suffix(fold_engines):
    """/programs must show the FOLDED program as its own compiler-truth
    row — the '+fold' shape-key suffix keeps it from aliasing the
    unfolded forward's entry."""
    _, calib, plain, folded = fold_engines
    folded.predict_scores(calib)
    led = get_ledger()
    keys = [e.shape_key for e in led.entries_for(folded._program.name,
                                                 analyze=False)]
    assert any(k.endswith('+fold') for k in keys), keys


def test_fold_hot_swap_refolds(fold_engines):
    """A hot swap hands the engine RAW conv+BN weights: the placement
    path must re-fold them (a sharding-match shortcut would serve
    unfolded weights through the identity-BN forward)."""
    tr, calib, _, folded = fold_engines
    s0 = folded.predict_scores(calib)
    folded.swap_params(tr.params)
    s1 = folded.predict_scores(calib)
    np.testing.assert_array_equal(s0, s1)


def test_fold_double_pass_identity_guard(fold_engines):
    """Re-passing the engine's OWN placed tree must be the identity —
    folding twice would corrupt the weights (the `_last_placed` object
    identity guard, serve/engine.py)."""
    tr, calib, _, folded = fold_engines
    s0 = folded.predict_scores(calib)
    placed = folded.place_params(tr.params)
    assert folded.place_params(placed) is placed
    folded.swap_params(placed)
    s1 = folded.predict_scores(calib)
    np.testing.assert_array_equal(s0, s1)


# --- μ-cuDNN convolution microbatching -------------------------------------

@pytest.mark.parametrize('split', [2, 4, 8])
@pytest.mark.parametrize('conv_fn', [_conv_native_mb, _conv_im2col_mb],
                         ids=['native', 'im2col'])
def test_microbatched_conv_bitwise(split, conv_fn):
    """Forward, dx AND dw of the microbatched conv are bitwise-equal to
    the unsplit op at every declared split, on both lowerings."""
    kx, kw_ = jax.random.split(jax.random.PRNGKey(split), 2)
    x = jax.random.normal(kx, (8, 9, 9, 4), jnp.float32)
    w = jax.random.normal(kw_, (3, 3, 4, 8), jnp.float32)
    strides, pad = (1, 1), ((1, 1), (1, 1))

    y_mb = jax.jit(lambda x, w: microbatched_conv(
        x, w, strides, pad, 1, split, conv_fn))(x, w)
    y_ref = jax.jit(lambda x, w: conv_fn(x, w, strides, pad, 1))(x, w)
    np.testing.assert_array_equal(np.asarray(y_mb), np.asarray(y_ref))

    def loss_mb(x, w):
        return jnp.sum(jnp.sin(microbatched_conv(
            x, w, strides, pad, 1, split, conv_fn)))

    def loss_ref(x, w):
        return jnp.sum(jnp.sin(conv_fn(x, w, strides, pad, 1)))

    dx_mb, dw_mb = jax.jit(jax.grad(loss_mb, argnums=(0, 1)))(x, w)
    dx_rf, dw_rf = jax.jit(jax.grad(loss_ref, argnums=(0, 1)))(x, w)
    np.testing.assert_array_equal(np.asarray(dx_mb), np.asarray(dx_rf))
    np.testing.assert_array_equal(np.asarray(dw_mb), np.asarray(dw_rf))


@pytest.mark.parametrize('split', [2, 4, 8])
def test_micro_batch_trainer_step_bitwise(split):
    """A full optimizer step (fwd + bwd + momentum update) with
    ``micro_batch=k`` is bitwise-equal to the unsplit step."""
    rng = np.random.RandomState(1)
    data, label = _batch(rng)
    t1 = _trainer('micro_batch = 1\n')
    tk = _trainer(f'micro_batch = {split}\n')
    for t in (t1, tk):
        d = t._shard_batch(data)
        lb = t._shard_batch(label, cast=False)
        for _ in range(3):
            t.update_on_device(d, lb)
    assert _param_maxerr(t1, tk) == 0.0


def test_autotune_setter_rebuilds_the_step_and_stays_bitwise():
    """``LearnTask._set_micro_batch`` is what ``task = autotune`` calls
    between candidates: it re-splits every layer of the LIVE trainer and
    recompiles its steps, so the next step runs the new split (a new jit,
    ``micro_batch`` read at trace time) and the weights stay bitwise equal
    to a trainer that never split; the ``finally`` of ``_autotune_train``
    sets it back the same way."""
    from cxxnet_tpu.main import LearnTask
    rng = np.random.RandomState(4)
    data, label = _batch(rng)
    ref = _trainer('micro_batch = 1\n')
    task = LearnTask()
    task.net_trainer = live = _trainer('micro_batch = 1\n')

    def step(t):
        t.update_on_device(t._shard_batch(data),
                           t._shard_batch(label, cast=False))

    step(ref), step(live)
    for value in (2, 1):
        before = live._train_step_fn
        task._set_micro_batch(value)
        assert live._train_step_fn is not before
        assert {lyr.param.micro_batch for lyr in live.net.layers} == {value}
        step(ref), step(live)
        assert _param_maxerr(ref, live) == 0.0


def test_micro_batch_composes_with_steps_per_dispatch():
    """``micro_batch`` composes with the scanned K-step dispatch
    (steps_per_dispatch machinery) without touching its values: the
    scanned run at split k is bitwise-equal to the scanned run unsplit,
    exactly as the sequential runs are.  (Scan-vs-sequential itself is
    a *separate* program XLA may compile to a different-rounding HLO
    for conv nets — that cross-path envelope is not this knob's
    contract, and the split must not move it either way.)"""
    rng = np.random.RandomState(2)
    batches = [_batch(rng) for _ in range(2)]
    n_steps = 4

    def seq_run(extra):
        tr = _trainer(extra)
        for t in range(n_steps):
            data, label = batches[t % 2]
            tr.update_on_device(tr._shard_batch(data),
                                tr._shard_batch(label, cast=False))
        return tr

    def scan_run(extra):
        tr = _trainer(extra)
        dstack = tr.shard_batch_stack(np.stack([d for d, _ in batches]))
        lstack = tr.shard_batch_stack(np.stack([lb for _, lb in batches]),
                                      cast=False)
        fn = tr.compile_multi_step(n_steps)
        tr.update_n_on_device(fn, dstack, lstack, n_steps)
        return tr

    seq_1 = seq_run('micro_batch = 1\n')
    seq_k = seq_run('micro_batch = 2\n')
    scan_1 = scan_run('micro_batch = 1\n')
    scan_k = scan_run('micro_batch = 2\n')
    assert _param_maxerr(seq_1, seq_k) == 0.0
    assert _param_maxerr(scan_1, scan_k) == 0.0
    assert scan_1.epoch_counter == scan_k.epoch_counter == n_steps


def test_micro_batch_bounds_ledger_peak_bytes():
    """The knob's whole point: the split bounds the compiled step's
    ``memory_analysis`` peak bytes (compiler truth on the ProgramLedger
    — the number grafttune's mem_inv pricing scales) while the math
    stays bitwise (asserted above)."""
    rng = np.random.RandomState(4)
    data, label = _batch(rng)
    led = get_ledger()
    peaks = {}
    for split in (1, 4):
        tr = _trainer(f'micro_batch = {split}\n')
        tr.update_on_device(tr._shard_batch(data),
                            tr._shard_batch(label, cast=False))
        entries = led.entries_for(tr._prog_step.name)
        peaks[split] = max(int(e.peak_bytes) for e in entries)
    assert peaks[4] <= peaks[1], peaks
    assert peaks[4] > 0


# --- doc drift -------------------------------------------------------------

def _repo_doc(rel):
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, 'doc', rel)) as f:
        return f.read()


def test_tasks_doc_documents_the_fusion_surface():
    text = _repo_doc('tasks.md')
    assert '`fuse`' not in text
    assert '`micro_batch`' in text
    assert 'serve.fold_bn' in text


def test_kernels_doc_exists_and_is_linked():
    """tasks.md/autotune.md link kernels.md for the fusion story — the
    target must exist and cover the two graftfuse contracts."""
    text = _repo_doc('kernels.md')
    for needle in ('micro_batch', 'fold_bn', 'bitwise'):
        assert needle in text, f'doc/kernels.md missing {needle!r}'
    assert 'kernels.md' in _repo_doc('README.md')
