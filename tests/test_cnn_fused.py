"""graftfuse suite (doc/kernels.md): the fused Pallas conv+bias+act
block, inference conv+BN folding, and μ-cuDNN convolution microbatching.

Three contracts, each pinned here:

* the fused block equals the XLA reference composition within the
  tolerances pinned in ``ops/pallas_cnn`` (``_FUSED_RTOL``/``_FUSED_ATOL``
  — pinned-tolerance, never silently looser), forward AND gradients,
  on every stride/pad/group/bias/activation leg, in interpret mode;
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

from cxxnet_tpu.layers.conv import _conv_im2col_mb, _conv_native_mb
from cxxnet_tpu.nnet.fold import FOLD_ATOL, FOLD_RTOL
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.obs.programs import get_ledger
from cxxnet_tpu.ops.pallas_cnn import (_FUSED_ATOL, _FUSED_RTOL, _conv_ref,
                                       conv_use_fused, fused_conv_bias_act,
                                       microbatched_conv)
from cxxnet_tpu.serve.engine import PredictEngine
from cxxnet_tpu.utils.config import parse_config_string

pytestmark = pytest.mark.cnn_fused


def _ref_composition(x, w, b, strides, pad, groups, act):
    y = _conv_ref(x, w, strides, pad, groups)
    if b is not None:
        y = y + b
    return jnp.maximum(y, 0.0) if act == 'relu' else y


def _leg_data(key, cin, cout, groups, hw=9):
    kx, kw_, kb = jax.random.split(jax.random.PRNGKey(key), 3)
    x = jax.random.normal(kx, (4, hw, hw, cin), jnp.float32)
    w = jax.random.normal(kw_, (3, 3, cin // groups, cout), jnp.float32)
    b = jax.random.normal(kb, (cout,), jnp.float32)
    return x, w, b


# --- the fused block's twins (fwd + grad, every leg) -----------------------

@pytest.mark.parametrize(
    'stride,pad,groups,act,bias',
    [(1, 1, 1, 'relu', True),        # the paired-layer fast path
     (1, 1, 1, 'relu', False),       # no_bias conv
     (1, 1, 1, 'identity', True),    # fuse=1 solo conv (no relu reader)
     (2, 1, 1, 'relu', True),        # strided
     (1, 0, 1, 'relu', True),        # valid padding
     (2, 2, 1, 'identity', False),   # strided + wide pad, bare conv
     (1, 1, 2, 'relu', True),        # grouped
     (2, 1, 4, 'identity', True)],   # grouped + strided
    ids=['base', 'nobias', 'identity', 'stride2', 'pad0',
         's2p2bare', 'group2', 'group4s2'])
def test_fused_block_matches_reference(stride, pad, groups, act, bias):
    x, w, b = _leg_data(7 * stride + pad + groups, 4 * groups, 8, groups)
    b = b if bias else None
    strides, padding = (stride, stride), ((pad, pad), (pad, pad))

    y_fused = fused_conv_bias_act(x, w, b, strides, padding, groups, act)
    y_ref = _ref_composition(x, w, b, strides, padding, groups, act)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_ref),
                               rtol=_FUSED_RTOL, atol=_FUSED_ATOL)

    def loss_fused(x, w, b):
        return jnp.sum(jnp.cos(
            fused_conv_bias_act(x, w, b, strides, padding, groups, act)))

    def loss_ref(x, w, b):
        return jnp.sum(jnp.cos(
            _ref_composition(x, w, b, strides, padding, groups, act)))

    args = (x, w) if b is None else (x, w, b)
    nums = (0, 1) if b is None else (0, 1, 2)
    gf = jax.grad(loss_fused, argnums=nums)(*args, *(() if b is not None
                                                     else (None,)))
    gr = jax.grad(loss_ref, argnums=nums)(*args, *(() if b is not None
                                                   else (None,)))
    for a, r in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=_FUSED_RTOL, atol=_FUSED_ATOL)


def test_fused_relu_grad_matches_reference_at_exact_ties():
    """The reference relu is ``jnp.maximum(x, 0)``, whose XLA gradient
    at an EXACT z==0 tie is 0.5 — and zero-padded integer images with a
    zero-init bias tie densely at step 0, so the fused backward must
    mirror that convention bitwise, not just a.e."""
    # all-zero input + zero bias => every pre-activation is exactly 0
    x = jnp.zeros((2, 5, 5, 3), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (3, 3, 3, 4), jnp.float32)
    b = jnp.zeros((4,), jnp.float32)
    strides, padding = (1, 1), ((1, 1), (1, 1))

    def loss_fused(x, w, b):
        return jnp.sum(
            fused_conv_bias_act(x, w, b, strides, padding, 1, 'relu')
            * jnp.arange(1.0, 5.0))

    def loss_ref(x, w, b):
        return jnp.sum(
            _ref_composition(x, w, b, strides, padding, 1, 'relu')
            * jnp.arange(1.0, 5.0))

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
    for a, r in zip(gf, gr):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))
    # the tie convention is the half-gradient, not a dead unit
    assert float(jnp.abs(gf[2]).max()) > 0.0


def test_conv_use_fused_gate_tristate(monkeypatch):
    """``fuse=1`` forces the block on (the CPU validation path),
    ``fuse=0`` kills it, and auto never picks it, on any backend: Mosaic
    refuses the kernel on the chip (doc/kernels.md), so only a forced
    spelling (``fuse=1`` / ``use_pallas=1``) reaches it."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.delenv('CXXNET_PALLAS', raising=False)
    assert conv_use_fused('1') is True
    assert conv_use_fused('0') is False
    monkeypatch.setattr(pk, '_interpret', lambda: False)   # "on a TPU"
    assert conv_use_fused('auto') is False
    assert conv_use_fused(None) is False
    monkeypatch.setenv('CXXNET_PALLAS', '1')
    assert conv_use_fused('auto') is True
    assert conv_use_fused('0') is False             # explicit key wins


# --- net-level fusion pass -------------------------------------------------

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


def test_fusion_pass_pairs_inplace_relus():
    tr = _trainer('fuse = 1\n')
    assert tr.net._convact_pairs == {0: 1, 3: 4}
    assert tr.net._convact_solo == set()
    tr0 = _trainer('fuse = 0\n')
    assert tr0.net._convact_pairs == {}
    assert tr0.net._convact_solo == set()


def test_fusion_excluded_under_microbatching():
    """The fused block has its own tiling — ``micro_batch>1`` convs must
    fall out of the pairing (they take the microbatched path instead)."""
    tr = _trainer('fuse = 1\nmicro_batch = 2\n')
    assert tr.net._convact_pairs == {}
    assert tr.net._convact_solo == set()


def test_fused_training_twin():
    """fuse=1 and fuse=0 trainers fed the identical update stream stay
    within the fused block's pinned tolerance — on the f32 cpu interpret
    path they are in practice bitwise (err 0.0), and any drift past the
    pinned envelope is a bug, not a tolerance to widen."""
    rng = np.random.RandomState(0)
    data, label = _batch(rng)
    t_on, t_off = _trainer('fuse = 1\n'), _trainer('fuse = 0\n')
    for t in (t_on, t_off):
        d = t._shard_batch(data)
        lb = t._shard_batch(label, cast=False)
        for _ in range(3):
            t.update_on_device(d, lb)
    err = _param_maxerr(t_on, t_off)
    assert err <= _FUSED_ATOL, f'fused training drifted: {err}'


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
    t1 = _trainer('fuse = 0\nmicro_batch = 1\n')
    tk = _trainer(f'fuse = 0\nmicro_batch = {split}\n')
    for t in (t1, tk):
        d = t._shard_batch(data)
        lb = t._shard_batch(label, cast=False)
        for _ in range(3):
            t.update_on_device(d, lb)
    assert _param_maxerr(t1, tk) == 0.0


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

    seq_1 = seq_run('fuse = 0\nmicro_batch = 1\n')
    seq_k = seq_run('fuse = 0\nmicro_batch = 2\n')
    scan_1 = scan_run('fuse = 0\nmicro_batch = 1\n')
    scan_k = scan_run('fuse = 0\nmicro_batch = 2\n')
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
        tr = _trainer(f'fuse = 0\nmicro_batch = {split}\n')
        tr.update_on_device(tr._shard_batch(data),
                            tr._shard_batch(label, cast=False))
        entries = led.entries_for(tr._prog_step.name)
        peaks[split] = max(int(e.peak_bytes) for e in entries)
    assert peaks[4] <= peaks[1], peaks
    assert peaks[4] > 0


# --- doc drift (satellite 5) -----------------------------------------------

def _repo_doc(rel):
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, 'doc', rel)) as f:
        return f.read()


def test_tasks_doc_documents_the_fusion_surface():
    text = _repo_doc('tasks.md')
    assert '`fuse`' in text
    assert '`micro_batch`' in text
    assert 'serve.fold_bn' in text


def test_kernels_doc_exists_and_is_linked():
    """tasks.md/autotune.md link kernels.md for the fusion story — the
    target must exist and cover the three graftfuse contracts."""
    text = _repo_doc('kernels.md')
    for needle in ('fused_conv_bias_act', 'micro_batch', 'fold_bn',
                   'bitwise', 'interpret'):
        assert needle in text, f'doc/kernels.md missing {needle!r}'
    assert 'kernels.md' in _repo_doc('README.md')
