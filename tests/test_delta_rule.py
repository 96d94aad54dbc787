"""The pieces of Solar-Open2-250B's layers that need no conf, at small
sizes on the CPU: the chunked delta rule (ops/delta_rule.py) against the
plain reference's token-by-token recurrence (benchmark/references/
solar_open2.py) at chunks of 16 and 64, with beta near 2 and decays past
float32's ``exp`` range, forward and backward, in XLA and on the Pallas
kernels (ops/delta_rule_kernel.py, in Pallas's interpreter) at heads of 128
and chunks of 64; which of the two a step takes; the step statistic of the
worst chunk; the head shares of a ``kda`` and a ``gqa`` layer and the expert
shares of a ``moe`` layer against the uncut layers; and the ``gqa`` layer
without positions and with a gate a channel."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.references import solar_open2 as R              # noqa: E402
from cxxnet_tpu.layers import ForwardContext, NodeSpec         # noqa: E402
from cxxnet_tpu.layers import sequence as S                    # noqa: E402
from cxxnet_tpu.ops import delta_rule                          # noqa: E402
from cxxnet_tpu.ops import delta_rule_kernel                   # noqa: E402


# --- the chunked delta rule against the recurrence ---------------------------

def _recurrence_inputs(seq, seed=0, heads=2, dk=8, dv=6, decay=0.05,
                       beta_max=2.0):
    """Unit ``q`` and ``k``, ``beta`` in ``(0, beta_max)``, log-decays in
    ``(-decay, 0]`` a channel, as ``(b, h, s, .)`` arrays."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    return (unit(jax.random.normal(ks[0], (1, heads, seq, dk))),
            unit(jax.random.normal(ks[1], (1, heads, seq, dk))),
            jax.random.normal(ks[2], (1, heads, seq, dv)),
            -decay * jax.random.uniform(ks[3], (1, heads, seq, dk)),
            beta_max * jax.random.uniform(ks[4], (1, heads, seq),
                                          minval=0.0, maxval=0.999))


def _token_by_token(q, k, v, g, beta, scale):
    """The reference's recurrence, on ``(b, h, s, .)`` arrays."""
    swap = lambda a: jnp.swapaxes(a, 1, 2)                       # noqa
    with jax.default_matmul_precision('highest'):
        o = R.delta_rule(R.MODEL, swap(q), swap(k), swap(v), swap(g),
                         jnp.swapaxes(beta, 1, 2))
    return swap(o) * scale


_chunked = jax.jit(delta_rule.chunk_gated_delta_rule, static_argnums=(5, 6))


@pytest.mark.parametrize('chunk,case', [
    (16, 'beta near 2'), (16, 'decay past -88'),
    (64, 'beta near 2'), (64, 'decay past -88')])
def test_chunked_delta_rule_equals_the_recurrence(chunk, case):
    """Lengths that are no multiple of the chunk (150 positions at chunk 16,
    130 at 64), beta all but 2 (``I - beta k k^T`` then near -1 along
    ``k``), and decays of up to -3 a position, whose sum over a chunk of 64
    reaches about -96 and over a sub-chunk of 16 some -24: the chunked form
    agrees with the token-by-token recurrence, and no ``exp`` overflows."""
    seq = 150 if chunk == 16 else 130
    q, k, v, g, beta = _recurrence_inputs(
        seq, seed=chunk, decay=3.0 if case == 'decay past -88' else 0.05)
    if case == 'beta near 2':
        beta = jnp.full_like(beta, 2.0 * 0.9999)
    got = _chunked(q, k, v, g, beta, 0.3, chunk)
    want = _token_by_token(q, k, v, g, beta, 0.3)
    assert np.isfinite(np.asarray(got)).all()
    scale = float(np.abs(want).max())
    assert float(np.abs(got - want).max()) <= 2e-5 * max(scale, 1.0)
    if case == 'decay past -88':
        assert float(delta_rule.chunk_log_decay_sums(g).min()) < -88.0


def test_chunked_delta_rule_gradients_equal_the_recurrences():
    """Every operand's gradient, at chunk 16 over 40 positions with decays
    whose chunk sums pass -88: finite, and the recurrence's."""
    q, k, v, g, beta = _recurrence_inputs(40, seed=7, decay=8.0)
    w = jax.random.normal(jax.random.PRNGKey(3), v.shape)
    chunked = jax.grad(lambda *a: jnp.sum(
        w * delta_rule.chunk_gated_delta_rule(*a, 0.3, 16)),
        argnums=range(5))(q, k, v, g, beta)
    plain = jax.grad(lambda *a: jnp.sum(w * _token_by_token(*a, 0.3)),
                     argnums=range(5))(q, k, v, g, beta)
    for a, b in zip(chunked, plain):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=5e-5 * max(
            1.0, float(np.abs(b).max())))


_kernels = functools.partial(delta_rule_kernel.chunk_gated_delta_rule,
                             chunk=64, sub=16, interpret=True)
KERNEL_CASES = ['beta near 2', 'decay past -88']


def _kernel_inputs(case):
    """2 heads of 128 over 130 positions (two chunks of 64 and a short
    one), beta all but 2 or decays of up to -3 a position (chunk sums near
    -96)."""
    q, k, v, g, beta = _recurrence_inputs(
        130, seed=11, dk=128, dv=128,
        decay=3.0 if case == 'decay past -88' else 0.05)
    if case == 'beta near 2':
        beta = jnp.full_like(beta, 2.0 * 0.9999)
    else:
        assert float(delta_rule.chunk_log_decay_sums(g).min()) < -88.0
    return q, k, v, g, beta


@pytest.mark.parametrize('case', KERNEL_CASES)
def test_the_kernels_equal_the_recurrence_and_the_xla_form(case):
    """The forward kernel's output against the token-by-token recurrence
    and against the XLA form, at the tolerance of the XLA form's test."""
    args = _kernel_inputs(case)
    got = jax.jit(lambda *a: _kernels(*a, 0.3))(*args)
    assert np.isfinite(np.asarray(got)).all()
    for want in (_token_by_token(*args, 0.3), _chunked(*args, 0.3, 64)):
        scale = float(np.abs(want).max())
        assert float(np.abs(got - want).max()) <= 2e-5 * max(scale, 1.0)


@pytest.mark.parametrize('case', KERNEL_CASES)
def test_the_kernels_gradients_equal_the_recurrences(case):
    """All five gradients through the backward kernel (the solve's, the
    sub-chunk exponentials' and the chunk decays' among them) against the
    recurrence's and the XLA form's, at the tolerance of the XLA form's
    test."""
    args = _kernel_inputs(case)
    w = jax.random.normal(jax.random.PRNGKey(3), args[2].shape)

    def grads(rule):
        return jax.jit(jax.grad(lambda *a: jnp.sum(w * rule(*a)),
                                argnums=range(5)))(*args)
    got = grads(lambda *a: _kernels(*a, 0.3))
    for wants in (grads(lambda *a: _token_by_token(*a, 0.3)),
                  grads(lambda *a: delta_rule.chunk_gated_delta_rule(
                      *a, 0.3, 64))):
        for a, b in zip(got, wants):
            assert np.isfinite(np.asarray(a)).all()
            np.testing.assert_allclose(a, b, atol=5e-5 * max(
                1.0, float(np.abs(b).max())))


@pytest.mark.parametrize('spmd,dk,dv,kernel', [
    (1, 128, 128, True), (1, 256, 128, True), (4, 128, 128, False),
    (1, 64, 128, False), (1, 128, 96, False)])
def test_the_kernels_run_on_one_chip_at_heads_of_128(monkeypatch, spmd, dk,
                                                     dv, kernel):
    """``gated_delta_rule`` takes the kernels where the step runs on one
    TPU chip and both head widths are multiples of 128, the XLA form
    elsewhere; on the CPU always the XLA form."""
    q, v = jnp.zeros((1, 1, 8, dk)), jnp.zeros((1, 1, 8, dv))
    assert not delta_rule._use_kernel(q, v, spmd)
    monkeypatch.setattr(delta_rule.jax, 'default_backend', lambda: 'tpu')
    assert delta_rule._use_kernel(q, v, spmd) == kernel


def test_the_statistic_is_the_worst_chunk_over_layers():
    """``kda.chunk_log_decay_min``: a layer's most negative chunk sum, and
    over layers the most negative of them (``trainer._over_layers``)."""
    from cxxnet_tpu.nnet.trainer import _over_layers
    g = (-jnp.ones((1, 2, 130, 3))).at[0, 1, 70, 2].set(-50.0)
    sums = delta_rule.chunk_log_decay_sums(g)
    assert sums.shape == (1, 2, 3, 3)
    assert float(sums.min()) == -113.0 and float(sums.max()) == -2.0
    folded = _over_layers({'l04_kda/kda.chunk_log_decay_min': -3.0,
                           'l06_kda/kda.chunk_log_decay_min': -7.0,
                           'l05_moe/moe.load_max_over_mean': 2.0,
                           'l07_moe/moe.load_max_over_mean': 3.0,
                           'l05_moe/moe.local_assignment_share': 0.2,
                           'l07_moe/moe.local_assignment_share': 0.4})
    assert float(folded['kda.chunk_log_decay_min']) == -7.0
    assert float(folded['moe.load_max_over_mean']) == 3.0
    assert abs(float(folded['moe.local_assignment_share']) - 0.3) < 1e-6


# --- the chip's share against the whole layer --------------------------------

def _layer(cls, keys, spec):
    layer = cls('x')
    for key, val in keys.items():
        layer.set_param(key, str(val))
    layer.infer_shapes([spec])
    return layer


HEAD_KEYS = {
    S.DeltaAttentionLayer: (dict(head_dim=8, init_sigma=0.3),
                            ('wq', 'wk', 'wv', 'conv_q', 'conv_k', 'conv_v',
                             'wa_up', 'dt_bias', 'wg_up', 'g_bias'),
                            ('a_log', 'wbeta'), ('wo',)),
    S.GroupedAttentionLayer: (dict(head_dim=8, use_rope=0,
                                   gate='elementwise', init_sigma=0.3),
                              ('wq', 'wgate'), (), ('wo',)),
}


@pytest.mark.parametrize('cls', [S.DeltaAttentionLayer,
                                 S.GroupedAttentionLayer])
def test_the_head_shares_add_up_to_the_whole_layer(cls):
    """16 heads in 4 shares of 4 (the ``gqa`` layer's 4 key/value heads in
    shares of 1): each share holds its heads' columns of the head-major
    leaves, its rows of ``W_o`` and, whole, the pre-norm and the low-rank
    down projections; the shares' changes of the residual stream add up to
    the uncut layer's, which is the reference's."""
    keys, columns, per_head, rows = HEAD_KEYS[cls]
    spec = NodeSpec(32, 1, 40)
    whole = _layer(cls, dict(keys, nhead=16, nkvhead=4), spec)
    p = jax.device_get(whole.init_params(jax.random.PRNGKey(1), [spec]))
    p['norm'] = 1.0 + 0.1 * np.arange(32, dtype=np.float32)
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (2, 1, 40, 32)))
    ctx = ForwardContext(is_train=False)
    got = np.zeros_like(h)
    shares = [_layer(cls, dict(keys, nhead=4, nkvhead=1, head_first=first,
                               nhead_published=16), spec)
              for first in (0, 4, 8, 12)]
    # a share's arithmetic is the same wherever its heads sit: one program
    run = jax.jit(lambda p, h: shares[0].forward(p, [h], ctx)[0])
    for first in (0, 4, 8, 12):
        mine = dict(p)
        for f in columns:
            width = p[f].shape[-1] // 16
            mine[f] = p[f][..., first * width:(first + 4) * width]
        for f in per_head:
            mine[f] = p[f][..., first:first + 4]
        for f in rows:
            width = p[f].shape[0] // 16
            mine[f] = p[f][first * width:(first + 4) * width]
        if cls is S.GroupedAttentionLayer:
            for f in ('wk', 'wv'):
                mine[f] = p[f][:, first // 4 * 8:(first // 4 + 1) * 8]
        got += np.asarray(run(mine, jnp.asarray(h))) - h
    kind = 'kda' if cls is S.DeltaAttentionLayer else 'gqa'
    ref = R.Layer(0, kind, '', [], [], dict(nhead='16', nkvhead='4',
                                            head_dim='8', eps='1e-5'), 0)
    with jax.default_matmul_precision('highest'):
        want = np.asarray(R.OPS[kind](ref, [jnp.asarray(h[:, 0])],
                                      {k: jnp.asarray(a)
                                       for k, a in p.items()}, R.MODEL)[0])
    # the sums of four shares round otherwise than one layer's: outputs of
    # about 10 agree to 1e-4
    np.testing.assert_allclose(h[:, 0] + got[:, 0], want, atol=1e-4)
    mine = np.asarray(jax.jit(lambda p, h: whole.forward(p, [h], ctx)[0])(
        p, jnp.asarray(h)))[:, 0]
    np.testing.assert_allclose(mine, want, atol=1e-4)


@pytest.mark.parametrize('keys', [
    dict(nhead=4, nhead_published=16, head_first=13),
    dict(nhead=8, nkvhead=2, nhead_published=16, head_first=2),
    dict(nhead=4, nhead_published=2)])
def test_a_share_of_part_of_a_head_group_is_refused(keys):
    layer = S.GroupedAttentionLayer('a')
    for key, val in dict(dict(nkvhead=1, head_dim=8), **keys).items():
        layer.set_param(key, str(val))
    with pytest.raises(ValueError, match='heads'):
        layer.infer_shapes([NodeSpec(32, 1, 8)])


CFG32 = dict(nhidden=16, experts_published=32, experts_per_token=4,
             routed_scaling_factor=1.0, eps=1e-5)


def test_the_expert_shares_add_up_to_the_whole_layer():
    """32 experts in 4 shares of 8, top-4 by a sigmoid router at
    ``routed_scaling_factor`` 1: the shares' routed parts, with the shared
    expert and the residual counted once, add up to the uncut reference's
    layer, and every assignment lands on exactly one share."""
    spec = NodeSpec(32, 1, 40)
    keys = lambda first, held: dict(CFG32, experts_held=held,  # noqa: E731
                                    expert_first=first, init_sigma=0.3)
    p = jax.device_get(_layer(S.MoELayer, keys(0, 32), spec).init_params(
        jax.random.PRNGKey(1), [spec]))
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (2, 1, 40, 32)))
    ctx = ForwardContext(is_train=False)
    routed = {k: v for k, v in p.items() if not k.startswith('s')}
    total, shares = np.zeros_like(h), []
    for first in (0, 8, 16, 24):
        out, stats = _layer(S.MoELayer, keys(first, 8), spec) \
            .forward_with_stats(dict(routed, **{
                f: p[f][first:first + 8] for f in ('wgate', 'wup', 'wdown')}),
                [jnp.asarray(h)], ctx)
        total += np.asarray(out[0]) - h
        shares.append(float(stats['moe.local_assignment_share']))
    assert abs(sum(shares) - 1.0) < 1e-6
    shared = dict(p, **{f: p[f][:1] * 0 for f in ('wgate', 'wup', 'wdown')})
    shared = np.asarray(_layer(S.MoELayer, keys(0, 1), spec).forward(
        shared, [jnp.asarray(h)], ctx)[0]) - h
    ref = R.Layer(0, 'moe', '', [], [], {k: str(v) for k, v in dict(
        keys(0, 32)).items()}, 0)
    with jax.default_matmul_precision('highest'):
        want, _ = R.moe(ref, jnp.asarray(h[:, 0]),
                        {k: jnp.asarray(v) for k, v in p.items()})
    np.testing.assert_allclose((h + total + shared)[:, 0], want, atol=2e-5)


# --- the softmax layer: no positions, a gate a channel -----------------------

def test_nope_attention_sees_no_position_and_the_gate_is_a_channels():
    """With ``use_rope = 0`` a sequence's last output depends on the set of
    earlier positions and not on their order; with ``W_g = 0`` every gate is
    a half; one channel's gate driven shut removes that channel's row of
    ``W_o`` from the result."""
    spec = NodeSpec(32, 1, 12)
    layer = _layer(S.GroupedAttentionLayer, dict(
        nhead=4, nkvhead=2, head_dim=8, use_rope=0, gate='elementwise',
        init_sigma=0.3), spec)
    p = jax.device_get(layer.init_params(jax.random.PRNGKey(1), [spec]))
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (1, 1, 12, 32)))
    ctx = ForwardContext(is_train=False)
    run = lambda p, h: np.asarray(layer.forward(  # noqa: E731
        p, [jnp.asarray(h)], ctx)[0])[0, 0]
    swapped = h.copy()
    swapped[:, :, [2, 7]] = h[:, :, [7, 2]]
    np.testing.assert_allclose(run(p, h)[-1], run(p, swapped)[-1], atol=1e-5)
    half = dict(p, wgate=np.zeros_like(p['wgate']))
    ungated = R.gqa(R.Layer(0, 'gqa', '', [], [], dict(
        nhead='4', nkvhead='2', head_dim='8'), 0), jnp.asarray(h[:, 0]),
        {k: jnp.asarray(a) for k, a in p.items()}, R.Variant(gqa_gate=False))
    np.testing.assert_allclose(run(half, h) - h[0, 0],
                               0.5 * (np.asarray(ungated)[0] - h[0, 0]),
                               atol=2e-5)
    x = h[0, 0] / np.sqrt((h[0, 0] ** 2).mean(-1, keepdims=True) + 1e-5)
    shut = dict(p, wgate=p['wgate'].copy())
    shut['wgate'][:, 13] = -100.0 * x[5] / (x[5] ** 2).sum()
    wo = shut['wo'].copy()
    wo[13] = 7.0
    np.testing.assert_allclose(run(shut, h)[5], run(dict(shut, wo=wo), h)[5],
                               atol=1e-5)
