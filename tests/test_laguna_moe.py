"""Laguna-S-2.1's layers as conf layer types (layers/sequence.py: ``gqa``,
``moe`` with a softmax router) against the plain reference
(benchmark/references/laguna_moe.py) at the tiny twin's size: the head's
probabilities and loss, the gradient of every leaf, the window's edge, both
group sizes, both rotary kinds against the formula, the gate, the router's
weights, the blocked XLA attention, the chip's share against the whole
layer, and the trainer's part."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import confnet, tokens                          # noqa: E402
from benchmark.references import laguna_moe as R               # noqa: E402
from cxxnet_tpu.io.data import DataBatch                       # noqa: E402
from cxxnet_tpu.layers import ForwardContext, NodeSpec         # noqa: E402
from cxxnet_tpu.layers import sequence as S                    # noqa: E402
from cxxnet_tpu.nnet.trainer import NetTrainer                 # noqa: E402
from cxxnet_tpu.ops import attention                           # noqa: E402
from cxxnet_tpu.parallel import moe as moe_ops                 # noqa: E402

TINY = os.path.join(ROOT, 'example', 'LM', 'tiny-laguna.conf')
BIG = os.path.join(ROOT, 'example', 'LM', 'Laguna-S-2.1.ep32.conf')
DATA = {'successors': 4, 'p_likely': 0.9}
SEQ = 64


def _pairs(path, **over):
    pairs = confnet.drop_sections(confnet.parse_conf(open(path).read()),
                                  ('data', 'eval', 'pred'))
    return pairs + [(k, str(v)) for k, v in over.items()]


def _trainer(pairs):
    tr = NetTrainer(pairs)
    tr.init_model()
    return tr


def _batch(graph, seed=3, rows=2):
    ids = tokens.token_rows(seed, rows, graph.seq + 2, graph.vocab, DATA)
    return ids, DataBatch(ids[:, None, None, :graph.seq + 1],
                          R.label_matrix(graph, ids).astype(np.float32))


def _run(pairs, rows=2):
    tr = _trainer(pairs)
    graph = R.build_graph(pairs)
    ids, batch = _batch(graph, rows=rows)
    params = jax.device_get(tr.params)
    staged = tr.stage_batch(batch)
    loss, grads = tr.compile_grad_step()(
        tr.params, staged[0], staged[1], (), staged[3],
        jax.random.PRNGKey(0), 0)
    probs = {n: tr.extract_feature(batch, n).reshape(rows, graph.seq, -1)
             for n in graph.loss_nodes()}
    want = R.forward(graph, params, batch.data)
    total, each, rgrads = R.loss_and_grads(graph, params, batch.data,
                                           batch.label)
    return dict(tr=tr, graph=graph, batch=batch, ids=ids, params=params,
                loss=float(loss), grads=jax.device_get(grads), probs=probs,
                want=want, total=total, each=each, rgrads=rgrads,
                pairs=pairs)


@pytest.fixture(scope='module')
def tiny():
    """The tiny twin in float32 at 64 positions (window 8): trainer, graph,
    a batch, the program's probabilities, loss and gradients, and the
    reference's."""
    return _run(_pairs(TINY, seed=5, silent=1))


def test_graph_and_leaves(tiny):
    tr, graph = tiny['tr'], tiny['graph']
    assert graph.loss_nodes() == ['logits']
    assert (graph.seq, graph.vocab, graph.width) == (SEQ, 96, 64)
    kinds = [(l.geti('nhead'), l.geti('window')) for l in graph.of_type('gqa')]
    assert kinds == [(4, 0), (6, 8), (6, 8), (6, 8), (4, 0)]
    (first,) = [l.index for l in graph.of_type('gqa')][:1]
    assert sorted(tr.params[str(first)]) == sorted(
        S.GroupedAttentionLayer.param_fields)
    assert tr.params[str(first)]['wq'].shape == (64, 4 * 16)
    assert tr.params[str(first)]['wk'].shape == (64, 2 * 16)
    assert tr.params[str(first)]['wgate'].shape == (64, 4)


@pytest.mark.parametrize('seq', [32, 64])
def test_probabilities_and_loss_match_reference(tiny, seq):
    """At 64 positions and at 32 (``seq_len`` a global pair of the conf)."""
    run = tiny if seq == SEQ else _run(_pairs(
        TINY, seed=5, silent=1, seq_len=seq, input_shape=f'1,1,{seq + 1}'))
    got, want = run['probs']['logits'], run['want']['logits']
    assert got.shape == want.shape == (2, seq, 96)
    np.testing.assert_allclose(np.log(got), np.log(want), atol=2e-5)
    y = run['batch'].label[:, :seq].astype(int)[..., None]
    mine = -np.mean(np.take_along_axis(np.log(got), y, -1))
    assert abs(mine - run['each']['logits']) < 1e-5 * run['each']['logits']
    assert abs(run['loss'] - run['total']) < 1e-5 * run['total']


def _leaves():
    tr = NetTrainer(_pairs(TINY) + [('dev', 'cpu')])
    tr.init_net()
    shapes = jax.eval_shape(tr.net.init_params, jax.random.PRNGKey(0))
    return [(k, f) for k in sorted(shapes, key=int) for f in sorted(shapes[k])]


@pytest.mark.parametrize('layer,field', _leaves())
def test_gradient_of_every_leaf(tiny, layer, field):
    got = np.asarray(tiny['grads'][layer][field])
    if field == 'router_bias':
        # it reaches the choice alone, and the reference does not read it
        assert not got.any()
        return
    want = np.asarray(tiny['rgrads'][int(layer)][field])
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1e-8)
    assert float(np.abs(got - want).max()) <= 2e-4 * scale, (layer, field)


def test_bfloat16_program_is_inside_a_band(tiny):
    """bf16 products on float32 masters: at nine positions in ten the
    log-probabilities are within 0.05 of the float32 reference's spread at
    this size (at the others a router's third choice flips on the rounding:
    another function, not an error of precision), and well off exact."""
    tr = _trainer(tiny['pairs'] + [('compute_type', 'bfloat16')])
    got = tr.extract_feature(tiny['batch'], 'logits').reshape(2, SEQ, -1)
    want = np.log(tiny['want']['logits'])
    err = np.abs(np.log(got) - want).max(-1) / want.std()
    assert 1e-4 < np.median(err) and np.quantile(err, 0.9) < 0.05, err
    assert np.abs(np.log(got) - want).mean() / want.std() < 0.02


# --- the attention: window, grouping, the blocked path -----------------------

def _qkv(heads, kv_heads, seq, dim=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (2, heads, seq, dim)),
            jax.random.normal(ks[1], (2, kv_heads, seq, dim)),
            jax.random.normal(ks[2], (2, kv_heads, seq, dim)))


def _by_hand(q, k, v, scale, window):
    """One query head at a time, one query at a time, over the keys the
    mask names: ``j <= i`` and ``i - j < window``."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    b, h, s, _ = q.shape
    group = h // k.shape[1]
    out = np.zeros(q.shape[:3] + (v.shape[3],))
    for n in range(b):
        for i in range(h):
            for t in range(s):
                lo = max(0, t - window + 1) if window else 0
                sc = k[n, i // group, lo:t + 1] @ q[n, i, t] * scale
                p = np.exp(sc - sc.max())
                out[n, i, t] = (p / p.sum()) @ v[n, i // group, lo:t + 1]
    return out


@pytest.mark.parametrize('heads,window,seq', [
    (4, 0, 32), (6, 8, 32), (6, 8, 6), (4, 8, 8), (6, 1, 16), (2, 5, 16)])
def test_attention_sees_the_window_and_its_group(heads, window, seq):
    """2 key/value heads under 4 and 6 (groups of 2 and 3) and under 2
    (equal heads); a window of 8 over 32 positions, over a sequence shorter
    than it and over one of its own length; a window of one key."""
    q, k, v = _qkv(heads, 2, seq)
    got = attention.causal_attention(q, k, v, 0.25, window=window)
    np.testing.assert_allclose(got, _by_hand(q, k, v, 0.25, window),
                               atol=2e-5)


def test_the_windows_edge():
    """``i - j = window - 1`` is seen and ``i - j = window`` is not: a value
    planted at one key reaches the queries up to 7 positions on and not the
    eighth, at the tiny window's 8 as at the published 512 (511 in, 512
    out)."""
    for window, seq, at in ((8, 32, 5), (512, 1024, 100)):
        q = jnp.zeros((1, 2, seq, 16))
        k = jnp.zeros((1, 2, seq, 16))
        v = jnp.zeros((1, 2, seq, 16)).at[:, :, at].set(1.0)
        got = np.asarray(attention.causal_attention(q, k, v, 1.0,
                                                    window=window))[0, 0, :, 0]
        assert got[at - 1] == 0.0                       # causal
        assert got[at + window - 1] > 0.0               # i - j = window - 1
        assert got[at + window] == 0.0                  # i - j = window
        np.testing.assert_allclose(got[at + window - 1], 1.0 / window,
                                   rtol=1e-6)


@pytest.mark.parametrize('window', [0, 8, 40])
def test_the_blocked_xla_path_equals_the_one_block_path(window):
    """Blocks of 16 queries over 64 positions against one block, output and
    the gradient of all three operands, with and without a window (one
    narrower and one wider than a block)."""
    q, k, v = _qkv(6, 2, 64, seed=1)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def run(block_q):
        def loss(q, k, v):
            o = attention.causal_attention_xla(q, k, v, 0.25, block_q,
                                               window=window)
            return jnp.sum(o * g), o
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)
    for a, b in zip(jax.tree.leaves(run(16)), jax.tree.leaves(run(64))):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_latent_attention_still_takes_the_one_door():
    """Equal heads, no window: what ``mla`` asks, as before."""
    q, k, v = _qkv(4, 4, 32, seed=2)
    got = attention.causal_attention(q, k, v, 0.25)
    np.testing.assert_allclose(got, _by_hand(q, k, v, 0.25, 0), atol=2e-5)
    with pytest.raises(ValueError, match='key/value heads'):
        attention.causal_attention(q, k[:, :3], v[:, :3], 0.25)


# --- rotary positions, the gate, the router ----------------------------------

def _yarn_by_hand(dims, theta, factor, original, beta_fast, beta_slow):
    """Hugging Face's ``_compute_yarn_parameters``, written out."""
    if factor <= 1:
        return np.array([theta ** (-2 * i / dims) for i in range(dims // 2)])
    def correction_dim(turns):
        return dims * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dims - 1)
    out = []
    for i in range(dims // 2):
        freq = theta ** (-2 * i / dims)
        ramp = min(max((i - low) / ((high - low) or 0.001), 0.0), 1.0)
        extrapolation = 1.0 - ramp
        out.append(freq / factor * (1 - extrapolation)
                   + freq * extrapolation)
    return np.array(out)


@pytest.mark.parametrize('args', [
    (64, 500000.0, 128.0, 8192, 32.0, 1.0),      # the published full layer
    (8, 500000.0, 8.0, 32, 32.0, 1.0),           # the tiny twin's
    (128, 10000.0, 1.0, 0, 32.0, 1.0)])          # plain
def test_yarn_frequencies_against_the_formula(args):
    got = S.yarn_frequencies(*args)
    np.testing.assert_allclose(got, _yarn_by_hand(*args), rtol=1e-12)
    np.testing.assert_allclose(R.yarn_frequencies(*args), got, rtol=1e-12)
    dims, theta, factor = args[:3]
    plain = theta ** (-np.arange(0, dims, 2) / dims)
    if factor > 1:
        # fast pairs unchanged, slow pairs interpolated, a ramp between
        assert got[0] == plain[0] and got[-1] == plain[-1] / factor
        assert ((got <= plain) & (got >= plain / factor)).all()
        if dims == 64:
            assert (got[:10] == plain[:10]).all()       # low = 9
            assert np.allclose(got[18:], plain[18:] / 128)    # high = 18
            assert math.isclose(0.1 * math.log(128) + 1, 1.4852030263919618)
    else:
        np.testing.assert_array_equal(got, plain)


def test_partial_rotary_turns_the_first_dims_and_passes_the_rest():
    """8 of 16 dims rotated by hand: component ``i`` pairs with ``i + 4``,
    the angle is position times frequency, ``cos`` and ``sin`` carry the
    attention factor, dims 8-15 pass."""
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (1, 2, 5, 16)))
    inv = S.yarn_frequencies(8, 500000.0, 8.0, 32, 32.0, 1.0)
    got = np.asarray(S.rotary(jnp.asarray(x), inv, 1.2))
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    for t in range(5):
        for i in range(4):
            c, s = 1.2 * math.cos(t * inv[i]), 1.2 * math.sin(t * inv[i])
            np.testing.assert_allclose(
                got[0, :, t, i], x[0, :, t, i] * c - x[0, :, t, i + 4] * s,
                atol=1e-6)
            np.testing.assert_allclose(
                got[0, :, t, i + 4], x[0, :, t, i + 4] * c + x[0, :, t, i] * s,
                atol=1e-6)
    # the whole head, plain: what ``rope`` (latent attention's) computes
    whole = S.rotary(jnp.asarray(x), S.yarn_frequencies(16, 1e4, 1, 0, 32, 1))
    np.testing.assert_allclose(whole, S.rope(jnp.asarray(x), 1e4), atol=1e-6)


def _gqa_layer(**keys):
    layer = S.GroupedAttentionLayer('a')
    for key, val in dict(dict(nhead=6, nkvhead=2, head_dim=16, window=8,
                              init_sigma=0.2), **keys).items():
        layer.set_param(key, str(val))
    layer.infer_shapes([NodeSpec(64, 1, 32)])
    return layer


def test_the_gate_scales_each_heads_output():
    """With ``W_g = 0`` every gate is a half: the layer's change of the
    residual stream is half that of the same attention ungated (computed by
    hand from the layer's own leaves); and one head's gate driven shut
    removes that head's columns of ``W_o`` from the result."""
    layer = _gqa_layer()
    p = jax.device_get(layer.init_params(jax.random.PRNGKey(1),
                                         [NodeSpec(64, 1, 32)]))
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (2, 1, 32, 64)))
    ctx = ForwardContext(is_train=False)
    ref = R.Layer(0, 'gqa', '', [], [], dict(nhead='6', nkvhead='2',
                                             head_dim='16', window='8'), 0)
    run = lambda p, v=R.MODEL: np.asarray(R.gqa(  # noqa: E731
        ref, jnp.asarray(h[:, 0]), {k: jnp.asarray(a) for k, a in p.items()},
        v))
    half = dict(p, wgate=np.zeros_like(p['wgate']))
    got = np.asarray(layer.forward(half, [jnp.asarray(h)], ctx)[0])[:, 0]
    ungated = run(p, R.Variant(gate=False))
    np.testing.assert_allclose(got - h[:, 0], 0.5 * (ungated - h[:, 0]),
                               atol=2e-5)
    # the program against the reference with the gate as initialised
    got = np.asarray(layer.forward(p, [jnp.asarray(h)], ctx)[0])[:, 0]
    np.testing.assert_allclose(got, run(p), atol=2e-5)
    assert np.abs(got - ungated).max() > 1e-2
    # head 3's gate shut: its 16 rows of W_o no longer matter
    shut = dict(p, wgate=p['wgate'].copy())
    shut['wgate'][:, 3] = 0.0
    shut['norm'] = p['norm'].copy()
    x = h[:, 0] / np.sqrt((h[:, 0] ** 2).mean(-1, keepdims=True) + 1e-5)
    shut['wgate'][:, 3] = -100.0 * x[0, 0] / (x[0, 0] ** 2).sum()
    a = np.asarray(layer.forward(shut, [jnp.asarray(h)], ctx)[0])[0, 0, 0]
    wo = shut['wo'].copy()
    wo[3 * 16:4 * 16] = 7.0
    b = np.asarray(layer.forward(dict(shut, wo=wo), [jnp.asarray(h)],
                                 ctx)[0])[0, 0, 0]
    np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize('score', ['softmax', 'sigmoid'])
def test_the_routers_weights_sum_to_the_scaling_factor(score):
    x = jax.random.normal(jax.random.PRNGKey(0), (50, 64))
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    idx, weights = moe_ops.topk_route(x, w, jnp.zeros(16), 3, 2.5, score)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
    logits = np.asarray(x @ w, np.float64)
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s = s / s.sum(-1, keepdims=True) if score == 'softmax' \
        else 1 / (1 + np.exp(-logits))
    top = np.argsort(-s, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(top, -1))
    chosen = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(weights, 2.5 * chosen
                               / chosen.sum(-1, keepdims=True), rtol=1e-5)
    with pytest.raises(ValueError, match='router_score'):
        S.MoELayer('e').set_param('router_score', 'tanh')


# --- the chip's share against the whole layer --------------------------------

CFG16 = dict(nhidden=32, experts_published=16, experts_held=16,
             expert_first=0, experts_per_token=3, routed_scaling_factor=2.5,
             router_score='softmax', eps=1e-6)


def _moe_layer(first, held):
    layer = S.MoELayer('e')
    for key, val in dict(CFG16, experts_held=held, expert_first=first,
                         init_sigma=0.2).items():
        layer.set_param(key, str(val))
    layer.infer_shapes([NodeSpec(64, 1, 32)])
    return layer


def _share(p, first, held):
    return dict(p, **{f: p[f][first:first + held]
                      for f in ('wgate', 'wup', 'wdown')})


def _ref_moe(cfg, h, p):
    l = R.Layer(0, 'moe', '', [], [], {k: str(v) for k, v in cfg.items()}, 0)
    with jax.default_matmul_precision('highest'):
        out, _ = R.moe(l, jnp.asarray(h), {k: jnp.asarray(v)
                                           for k, v in p.items()})
    return np.asarray(out)


@pytest.mark.parametrize('seq', [32, 256])
def test_the_shares_add_up_to_the_whole_layer(seq):
    """16 experts in 4 shares of 4, top-3 by a softmax router: the shares'
    routed parts, with the shared expert and the residual counted once, add
    up to the uncut reference's layer."""
    p = jax.device_get(_moe_layer(0, 16).init_params(
        jax.random.PRNGKey(1), [NodeSpec(64, 1, 32)]))
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (2, 1, seq, 64)))
    ctx = ForwardContext(is_train=False)
    no_shared = {k: v for k, v in p.items() if not k.startswith('s')}
    total, shares = np.zeros_like(h), []
    for first in (0, 4, 8, 12):
        out, stats = _moe_layer(first, 4).forward_with_stats(
            _share(no_shared, first, 4), [jnp.asarray(h)], ctx)
        total += np.asarray(out[0]) - h            # the routed part alone
        shares.append(float(stats['moe.local_assignment_share']))
    assert abs(sum(shares) - 1.0) < 1e-6           # every assignment, once
    shared_only = dict(p, **{f: p[f][:1] * 0
                             for f in ('wgate', 'wup', 'wdown')})
    shared = np.asarray(_moe_layer(0, 1).forward(
        shared_only, [jnp.asarray(h)], ctx)[0]) - h
    want = _ref_moe(CFG16, h[:, 0], p)
    np.testing.assert_allclose((h + total + shared)[:, 0], want, atol=2e-5)


# --- the step against the reference ------------------------------------------

@pytest.mark.parametrize('fault', sorted(
    k for k, v in R.PROBE.items() if v.loss_tokens != 'all'))
def test_a_fault_in_the_steps_loss_leaves_the_limits(tiny, fault):
    """The timed program's own step against the reference: its loss and the
    change of the head's weight and the final norm agree as the model is,
    and a reference whose loss drops or masks tokens comes out not
    correct."""
    graph, ids = tiny['graph'], tiny['ids']
    tr = _trainer(tiny['pairs'])
    sides = {v: R.reference_side(graph, tr.params, ids, tiny['probs'], v)
             for v in (R.MODEL, R.PROBE[fault])}
    step = R.program_step(tr, graph, ids)
    found, ok = R.judge(graph, sides[R.MODEL], step)
    assert ok and found['loss'] < 1e-6, found
    assert max(found['update'].values()) < 1e-3, found
    assert sorted(found['update']) == sorted(
        f'{k}.{f}' for k, f in R.glm.tail_leaves(graph))
    found, ok = R.judge(graph, sides[R.PROBE[fault]], step)
    assert not ok and found['loss'] > R.STEP_LOSS_TOLERANCE, found
    assert max(found['update'].values()) > R.UPDATE_TOLERANCE, found
    # a state left unchanged reads one
    found, ok = R.judge(graph, sides[R.MODEL], dict(step, after=step['w']))
    assert not ok
    np.testing.assert_allclose(list(found['update'].values()), 1.0)


@pytest.mark.parametrize('fault', sorted(
    k for k, v in R.PROBE.items() if v.loss_tokens == 'all'))
def test_a_fault_in_the_model_shows_in_the_probabilities(tiny, fault):
    """Each of the probe's faults of the model moves the reference's
    log-probabilities at the tiny size by far more than the program differs
    from the model (``selftest.laguna`` holds them against the limits)."""
    want = np.log(tiny['want']['logits'])
    wrong = np.log(R.forward(tiny['graph'], tiny['params'],
                             tiny['batch'].data,
                             variant=R.PROBE[fault])['logits'])
    exact = np.abs(np.log(tiny['probs']['logits']) - want).max()
    assert np.abs(wrong - want).max() > 100 * max(exact, 1e-6), fault


# --- the trainer's part ------------------------------------------------------

def test_training_through_the_step_loop_learns_and_counts():
    tr = _trainer(_pairs(TINY, seed=1, silent=1))
    graph = R.build_graph(_pairs(TINY))
    staged = [tr.stage_batch(_batch(graph, seed=s)[1]) for s in range(4)]
    losses = []
    tr.add_loss_listener(losses.append)
    for step in range(24):
        tr.update_staged(staged[step % 4])
    rows = tr.step_stats()
    assert len(rows) == 24 and tr.step_stats() == []
    assert rows[-1]['loss'] < rows[0]['loss'] - 0.5
    assert abs(rows[0]['loss'] - float(losses[0])) < 1e-6
    for r in rows:
        assert 0.0 <= r['moe.local_assignment_share'] <= 1.0
        assert r['moe.load_max_over_mean'] >= 1.0
        assert r['moe.full_buffer_share'] in (0.0, 0.25, 0.5, 0.75, 1.0)
    assert tr.train_step_flops() > 0
    text = tr.step_program_text()
    assert 'l02_gqa_attn0' in text and 'l05_moe_moe1' in text


def test_recomputation_keeps_the_layer_scope():
    """The checkpointed ``gqa`` layers' forward, recomputation and backward
    all carry the conf layer's scope (``lNN_gqa_<name>``), which is what
    the trace is split by."""
    tr = _trainer(_pairs(TINY, seed=1, silent=1))
    graph = R.build_graph(_pairs(TINY))
    staged = tr.stage_batch(_batch(graph)[1])
    text = tr._train_step_fn._jit.lower(
        tr.params, tr.opt_state, tr.grad_acc, staged[0], staged[1], (),
        staged[3], jax.random.PRNGKey(0), 0, 0,
        do_update=True).as_text(debug_info=True)
    for name in ('l02_gqa_attn0', 'l04_gqa_attn1', 'l10_gqa_attn4',
                 'l05_moe_moe1'):
        for scope in (f'jvp({name})', f'transpose(jvp({name}))'):
            assert scope in text, scope
    assert 'checkpoint' in text or 'remat' in text


def test_published_conf_counts_its_parameters():
    """The example conf's leaves, by shape alone: ISSUE 36's table, to the
    unit."""
    tr = NetTrainer(_pairs(BIG) + [('dev', 'cpu')])
    tr.init_net()
    shapes = jax.eval_shape(tr.net.init_params, jax.random.PRNGKey(0))
    count = lambda d: sum(int(np.prod(a.shape)) for a in d.values())  # noqa
    graph = R.build_graph(_pairs(BIG))
    by_type = {}
    for k in sorted(shapes, key=int):
        by_type.setdefault(graph.layers[int(k)].type, []).append(
            count(shapes[k]))
    d = 3072
    full, sliding = 44_187_648 + d, 63_135_744 + d        # + the pre-norm
    assert by_type['gqa'] == [full, sliding, sliding, sliding, full]
    expert = 3 * d * 1024
    assert expert == 9_437_184
    # the router and its (zero) choice bias, 8 held experts, the shared one
    assert set(by_type['moe']) == {d * 256 + 256 + 9 * expert + d}
    assert by_type['swiglu'] == [3 * d * 12288 + d]
    assert by_type['embedding'] == by_type['lm_head_loss'] == [12544 * d]
    assert by_type['rmsnorm'] == [d]
    total = sum(count(v) for v in shapes.values())
    assert total == 811_018_240
    assert 12.0 < total * 16 / 2 ** 30 < 12.1             # GiB in a step


def test_train_flops_by_hand():
    """30.0 TFLOP a step of one 8,192-token sequence, by hand; the pairs a
    window layer scores are ISSUE 36's 4,063,488."""
    graph = R.build_graph(_pairs(BIG))
    s, d, hd = 8192, 3072, 128
    assert R.attended_pairs(s, 512) == sum(min(i + 1, 512)
                                           for i in range(s)) == 4_063_488
    assert R.attended_pairs(s, 0) == s * (s + 1) // 2
    assert R.attended_pairs(6, 8) == 21

    def attn(heads, pairs):
        proj = d * heads * hd + 2 * d * 8 * hd + d * heads + heads * hd * d
        return s * proj + pairs * heads * 2 * hd
    expert = 3 * d * 1024
    moe = s * (d * 256 + expert) + (s * 10 * 8 / 256) * expert
    hand = (2 * attn(48, s * (s + 1) // 2) + 3 * attn(72, 4_063_488)
            + s * 3 * d * 12288 + 4 * moe + s * d * 12544)
    assert sum(R.forward_macs(graph).values()) == hand
    assert abs(R.train_flops_per_sequence(graph) - 30.0e12) < 0.1e12


def test_model_file_round_trip(tmp_path):
    pairs = _pairs(TINY, seed=2, silent=1)
    tr = _trainer(pairs)
    batch = _batch(R.build_graph(pairs))[1]
    before = tr.extract_feature(batch, 'logits')
    path = tmp_path / 'tiny.model'
    with open(path, 'wb') as f:
        tr.save_model(f)
    other = NetTrainer(pairs)
    with open(path, 'rb') as f:
        other.load_model(f)
    for k, d in tr.params.items():
        for name, a in d.items():
            np.testing.assert_array_equal(np.asarray(other.params[k][name]),
                                          np.asarray(a))
    np.testing.assert_array_equal(other.extract_feature(batch, 'logits'),
                                  before)


def test_cli_trains_the_tiny_twin(tmp_path, capfd):
    """``python -m cxxnet_tpu.main`` on the tiny conf: ``task = train``
    through LearnTask, the round's line carries a falling train-loss and
    the ``moe.*`` counters."""
    import re
    from cxxnet_tpu.main import LearnTask
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        LearnTask().run([TINY, 'num_round=3', 'silent=1'])
    finally:
        os.chdir(cwd)
    err = capfd.readouterr().err
    losses = [float(x) for x in re.findall(r'train-loss:([0-9.]+)', err)]
    assert len(losses) == 3 and losses[-1] < losses[0] - 0.5, err
    assert 'train-moe.local_assignment_share:' in err
    assert 'train-moe.full_buffer_share:' in err
