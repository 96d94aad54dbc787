"""Trainer semantics: LR schedules, multi-label graphs, extract, rec@n."""

import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.updater.updaters import UpdaterHyper
from cxxnet_tpu.utils.config import parse_config_string
from cxxnet_tpu.utils.metric import create_metric


def _hyper(**params):
    h = UpdaterHyper()
    for k, v in params.items():
        h.set_param(k, str(v))
    return h


class TestSchedules:
    """Closed-form checks of ``ScheduleEpoch`` (reference param.h:76-94)."""

    def test_expdecay(self):
        h = _hyper(eta=0.1, **{'lr:schedule': 'expdecay', 'lr:gamma': 0.5,
                               'lr:step': 100})
        lr, _ = h.schedule(200)
        assert np.isclose(float(lr), 0.1 * 0.5 ** 2.0)
        lr, _ = h.schedule(50)       # fractional exponent (continuous decay)
        assert np.isclose(float(lr), 0.1 * 0.5 ** 0.5)

    def test_polydecay(self):
        h = _hyper(eta=0.1, **{'lr:schedule': 'polydecay', 'lr:gamma': 2.0,
                               'lr:alpha': 0.5, 'lr:step': 10})
        lr, _ = h.schedule(35)       # floor(35/10)=3 -> (1+3*2)^-0.5
        assert np.isclose(float(lr), 0.1 * (1 + 3 * 2.0) ** -0.5)

    def test_factor_with_minimum(self):
        h = _hyper(eta=0.1, **{'lr:schedule': 'factor', 'lr:factor': 0.1,
                               'lr:step': 10, 'lr:minimum_lr': 5e-4})
        assert np.isclose(float(h.schedule(0)[0]), 0.1)
        assert np.isclose(float(h.schedule(25)[0]), 0.1 * 0.01)
        assert np.isclose(float(h.schedule(99)[0]), 5e-4)   # clamped

    def test_tag_scoped_override(self):
        from cxxnet_tpu.updater.updaters import create_updater_hyper
        defcfg = [('eta', '0.1'), ('wd', '0.001'), ('bias:wd', '0.0')]
        wmat = create_updater_hyper('sgd', 'wmat', defcfg, [])
        bias = create_updater_hyper('sgd', 'bias', defcfg, [])
        assert wmat.wd == pytest.approx(0.001)
        assert bias.wd == pytest.approx(0.0)


MULTILABEL_CONF = """
netconfig = start
layer[0->1] = fullc:fc1
  nhidden = 16
layer[1->2] = sigmoid
layer[2->cls_out] = fullc:cls
  nhidden = 4
layer[cls_out->cls_out] = softmax
layer[2->reg_out] = fullc:reg
  nhidden = 2
layer[reg_out->reg_out] = l2_loss
  target = extra
netconfig = end
input_shape = 1,1,8
batch_size = 16
input_flat = 1
dev = cpu
eta = 0.1
momentum = 0.9
label_vec[0,1) = label
label_vec[1,3) = extra
metric[label,cls_out] = error
metric[extra,reg_out] = rmse
"""


def _multilabel_batch(rng, n=16):
    x = rng.rand(n, 1, 1, 8).astype(np.float32)
    cls = rng.randint(0, 4, (n, 1)).astype(np.float32)
    reg = (x.reshape(n, 8)[:, :2] * 2.0).astype(np.float32)
    return DataBatch(x, np.concatenate([cls, reg], axis=1))


def test_multilabel_two_heads_train():
    """label_vec splits the label matrix into named fields consumed by
    different loss heads (softmax on 'label', l2 on 'extra'); metrics are
    per-field (``nnet_impl:271-285``, ``metric.h:175-236``)."""
    rng = np.random.RandomState(0)
    tr = NetTrainer(parse_config_string(MULTILABEL_CONF))
    tr.init_model()
    batches = [_multilabel_batch(rng) for _ in range(20)]
    first = None
    for r in range(8):
        tr.start_round(r)
        for b in batches:
            tr.update(b)
        res = tr.evaluate(iter(batches[:5]), 'v')
        rmse = float(res.split('v-rmse[extra]:')[-1])
        err = float(res.split('v-error:')[-1].split('\t')[0])
        if first is None:
            first = (err, rmse)
    assert rmse < first[1], 'regression head did not improve'
    assert err <= first[0], 'classification head did not improve'


def test_extract_topk_and_named_node():
    rng = np.random.RandomState(0)
    tr = NetTrainer(parse_config_string(MULTILABEL_CONF))
    tr.init_model()
    b = _multilabel_batch(rng)
    feat = tr.extract_feature(b, 'top[-1]')      # final node (reg head)
    assert feat.shape[-1] == 2
    named = tr.extract_feature(b, 'cls_out')      # named node
    assert named.shape[-1] == 4
    hidden = tr.extract_feature(b, '2')           # node named by index
    assert hidden.reshape(16, -1).shape == (16, 16)


TAIL_CONF = """
netconfig = start
layer[0->1] = fullc:fc1
  nhidden = 8
layer[1->2] = sigmoid
layer[2->3] = fullc:cls
  nhidden = 4
layer[3->3] = softmax
netconfig = end
input_shape = 1,1,6
batch_size = 100
input_flat = 1
dev = cpu
eta = 0.1
metric = error
"""


def _padded_batches(x, y, bs, pad_fill):
    """Split (n, ...) arrays into full batches; pad the short tail with
    ``pad_fill`` rows and set num_batch_padd — the shape the batch adapter
    emits for round_batch=0."""
    n = x.shape[0]
    out = []
    for s in range(0, n, bs):
        xb, yb = x[s:s + bs], y[s:s + bs]
        npadd = bs - xb.shape[0]
        if npadd:
            xb = np.concatenate([xb, np.full((npadd,) + x.shape[1:],
                                             pad_fill, x.dtype)])
            yb = np.concatenate([yb, np.full((npadd, y.shape[1]),
                                             pad_fill, y.dtype)])
        out.append(DataBatch(xb, yb, num_batch_padd=npadd,
                             pad_synthetic=bool(npadd)))
    return out


def test_tail_batch_trains_and_evals_all_instances():
    """A 250-instance dataset at batch 100 trains/evals all 250 — the pad
    rows of the short tail batch (num_batch_padd=50) are masked out of
    gradients and metrics (reference: iter_batch_proc-inl.hpp:101-103 emits
    the tail; nnet_impl-inl.hpp:239 excludes pads from eval)."""
    rng = np.random.RandomState(3)
    x = rng.rand(250, 1, 1, 6).astype(np.float32)
    y = rng.randint(0, 4, (250, 1)).astype(np.float32)

    # two trainers, identical seed, fed the same real rows but tail pads
    # filled with wildly different garbage: masked pads => identical params
    results = []
    for pad_fill in (0.0, 1e6):
        tr = NetTrainer(parse_config_string(TAIL_CONF))
        tr.init_model()
        tr.start_round(0)
        for b in _padded_batches(x, y, 100, pad_fill):
            tr.update(b)
        import jax
        results.append(jax.device_get(tr.params))
    for (ka, va), (kb, vb) in zip(sorted(results[0].items()),
                                  sorted(results[1].items())):
        for f in va:
            np.testing.assert_array_equal(va[f], vb[f]), (ka, f)
    assert all(np.all(np.isfinite(v[f])) for v in results[1].values()
               for f in v), 'garbage pad rows leaked into gradients'

    # eval counts exactly 250 instances, pads excluded
    tr = NetTrainer(parse_config_string(TAIL_CONF))
    tr.init_model()
    tr.evaluate(iter(_padded_batches(x, y, 100, 1e6)), 'v')
    assert tr.metric.evals[0].cnt_inst == 250


def test_train_metric_counts_tail_instances():
    """eval_train metrics over an epoch with a padded tail count every real
    instance once (250, not 300 or 200)."""
    rng = np.random.RandomState(4)
    x = rng.rand(250, 1, 1, 6).astype(np.float32)
    y = rng.randint(0, 4, (250, 1)).astype(np.float32)
    tr = NetTrainer(parse_config_string(TAIL_CONF))
    tr.init_model()
    tr.start_round(0)
    for b in _padded_batches(x, y, 100, 0.0):
        tr.update(b)
    tr.flush_train_metrics()        # the last step's deferred readback
    assert tr.train_metric.evals[0].cnt_inst == 250


def test_rec_at_n():
    m = create_metric('rec@2')
    pred = np.array([[0.1, 0.5, 0.4], [0.9, 0.05, 0.6]])
    label = np.array([[2.0], [1.0]])      # top2 = {1,2} hit; {0,2} miss
    m.add_eval(pred, label)
    assert m.get() == pytest.approx(0.5)
    with pytest.raises(ValueError):
        bad = create_metric('rec@5')
        bad.add_eval(pred, label)


@pytest.mark.parametrize('update_period', [1, 2])
def test_lookahead_staging_equals_plain_update(update_period):
    """The CLI train loop's one-batch lookahead (stage_batch for i+1
    enqueued before update_staged for i) must produce bitwise-identical
    training to plain per-batch update() — staging must not disturb rng
    streams, counters, masks, gradient accumulation (update_period>1),
    or deferred train metrics."""
    batches = [_multilabel_batch(np.random.RandomState(100 + i))
               for i in range(5)]

    def final_params(drive):
        tr = NetTrainer(parse_config_string(
            MULTILABEL_CONF + f'seed = 7\nupdate_period = {update_period}\n'))
        tr.init_model()
        drive(tr)
        tr.flush_train_metrics()
        return tr

    def plain(tr):
        for b in batches:
            tr.update(b)

    def lookahead(tr):
        pending = None
        for b in batches:
            staged = tr.stage_batch(b)
            if pending is not None:
                tr.update_staged(pending)
            pending = staged
        tr.update_staged(pending)

    t1, t2 = final_params(plain), final_params(lookahead)
    assert t1.sample_counter == t2.sample_counter
    assert t1.epoch_counter == t2.epoch_counter
    # 5 batches at update_period=2: the tail accumulation lives only in
    # grad_acc — compare it too, or a staging bug in a non-applying step
    # would be invisible.  update_period=1 keeps no accumulator.
    assert (t1.grad_acc is None) == (t2.grad_acc is None) \
        == (update_period == 1)
    for k, fields in (t1.grad_acc or {}).items():
        for f, v in fields.items():
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(t2.grad_acc[k][f]),
                                          err_msg=f'grad_acc {k}/{f}')
    for k, fields in t1.params.items():
        for f, v in fields.items():
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(t2.params[k][f]),
                                          err_msg=f'{k}/{f}')
    assert t1.train_metric.print('t') == t2.train_metric.print('t')


def test_momentum_saturation_schedule():
    """Momentum saturation (updater/param.h:76-94): with the schedule on,
    the effective momentum is min(momentum + ramp(e) + base_momentum,
    final_momentum) — the reference's quirky additive formula, preserved,
    with the unconditional final_momentum cap (param.h:88)."""
    from cxxnet_tpu.updater.updaters import UpdaterHyper
    h = UpdaterHyper(tag='wmat')
    for k, v in (('momentum', '0.0'), ('momentum_schedule', '1'),
                 ('base_momentum', '0.5'), ('final_momentum', '0.9'),
                 ('saturation_epoch', '100')):
        h.set_param(k, v)
    import numpy as _np
    for epoch, want in ((0, 0.5), (50, 0.7), (200, 0.9)):
        _lr, mom = h.schedule(epoch)
        assert _np.asarray(mom) == pytest.approx(want, abs=1e-6)
    # schedule off: static momentum
    h2 = UpdaterHyper(tag='wmat')
    h2.set_param('momentum', '0.8')
    _lr, mom = h2.schedule(123)
    assert _np.asarray(mom) == pytest.approx(0.8)


def test_clip_gradient_clips_and_zeroes_nan():
    """clip_gradient both clips to [-c, c] and zeroes NaN gradients in
    one functor (sgd_updater-inl.hpp:15-22)."""
    import jax.numpy as _jnp
    import numpy as _np
    from cxxnet_tpu.updater.updaters import UpdaterHyper, _sgd_leaf
    h = UpdaterHyper(tag='wmat')
    h.set_param('clip_gradient', '1.0')
    h.set_param('wd', '0')
    g = _jnp.asarray([10.0, _np.nan, -5.0, 0.5])
    w = _jnp.zeros(4)
    m = _jnp.zeros(4)
    w_new, _m_new = _sgd_leaf(w, g, m, lr=1.0, mom=0.0, h=h)
    _np.testing.assert_allclose(_np.asarray(w_new),
                                [-1.0, 0.0, 1.0, -0.5], atol=1e-7)
    # clip_gradient = 0 (default): NaNs pass through untouched
    h0 = UpdaterHyper(tag='wmat')
    h0.set_param('wd', '0')
    w_raw, _ = _sgd_leaf(w, g, m, lr=1.0, mom=0.0, h=h0)
    assert _np.isnan(_np.asarray(w_raw)[1])


def test_nag_updater_matches_reference_math():
    """NAG (nag_updater-inl.hpp:65-72): m' = mom*m - lr*(g + wd*w);
    w' = w + (1+mom)*m' - mom*m."""
    import jax.numpy as _jnp
    import numpy as _np
    from cxxnet_tpu.updater.updaters import UpdaterHyper, _nag_leaf
    h = UpdaterHyper(tag='wmat')
    h.set_param('wd', '0.01')
    w, g, m, lr, mom = 1.0, 0.5, 0.2, 0.1, 0.9
    w2, m2 = _nag_leaf(_jnp.float32(w), _jnp.float32(g), _jnp.float32(m),
                       lr, mom, h)
    m_ref = mom * m - lr * (g + 0.01 * w)
    w_ref = w + (1 + mom) * m_ref - mom * m
    assert _np.asarray(m2) == pytest.approx(m_ref, rel=1e-6)
    assert _np.asarray(w2) == pytest.approx(w_ref, rel=1e-6)


def test_adam_updater_matches_reference_math():
    """Adam (adam_updater-inl.hpp:73-82): decay1/decay2 are (1-beta)
    rates, lr_t = base_lr*sqrt(fix2)/fix1 with fix_i = 1-(1-decay_i)^(e+1),
    and the reference's wd sign quirk (grad -= wd*w) is kept verbatim."""
    import jax.numpy as _jnp
    import numpy as _np
    from cxxnet_tpu.updater.updaters import UpdaterHyper, _adam_leaf
    h = UpdaterHyper(tag='wmat')
    h.set_param('eta', '0.002')
    h.set_param('wd', '0.05')
    # config keys are beta1/beta2, which (reference quirk) directly SET
    # the decay rates 1-beta (adam_updater-inl.hpp:56-57) — non-default
    # values prove the keys land
    h.set_param('beta1', '0.2')
    h.set_param('beta2', '0.005')
    w, g, m1, m2v, epoch = 0.7, 0.3, 0.02, 0.004, 4
    w2, m1n, m2n = _adam_leaf(_jnp.float32(w), _jnp.float32(g),
                              _jnp.float32(m1), _jnp.float32(m2v), epoch, h)
    g_eff = g - 0.05 * w                      # the reference sign quirk
    fix1 = 1.0 - (1.0 - 0.2) ** (epoch + 1)
    fix2 = 1.0 - (1.0 - 0.005) ** (epoch + 1)
    lr_t = 0.002 * _np.sqrt(fix2) / fix1
    m1_ref = m1 + 0.2 * (g_eff - m1)
    m2_ref = m2v + 0.005 * (g_eff * g_eff - m2v)
    w_ref = w - lr_t * (m1_ref / (_np.sqrt(m2_ref) + 1e-8))
    assert _np.asarray(m1n) == pytest.approx(m1_ref, rel=1e-6)
    assert _np.asarray(m2n) == pytest.approx(m2_ref, rel=1e-6)
    assert _np.asarray(w2) == pytest.approx(w_ref, rel=1e-6)


def test_lr_constant_and_start_epoch_hold():
    """The two schedule behaviors TestSchedules doesn't pin: the constant
    schedule, and lr:start_epoch holding the base LR until the start
    epoch is reached (updater/param.h:89-92)."""
    import numpy as _np
    lr, _ = _hyper(eta=0.1).schedule(250)
    assert _np.asarray(lr) == pytest.approx(0.1)
    h = _hyper(eta=0.1, **{'lr:schedule': 'expdecay', 'lr:gamma': 0.5,
                           'lr:step': 100, 'lr:start_epoch': 500})
    lr, _ = h.schedule(250)
    assert _np.asarray(lr) == pytest.approx(0.1)    # held at base before
