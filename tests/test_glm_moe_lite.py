"""GLM-4.7-Flash's layers as conf layer types (layers/sequence.py) against
the plain reference (benchmark/references/glm_moe_lite.py) at the tiny
twin's size: both heads' probabilities, both losses, the gradient of every
leaf, the chip's share against the whole layer, routing under imbalance, and
what the multi-token-prediction head shares with the main model."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import confnet, tokens                          # noqa: E402
from benchmark.references import glm_moe_lite as R             # noqa: E402
from cxxnet_tpu.io.data import DataBatch                       # noqa: E402
from cxxnet_tpu.layers import ForwardContext, NodeSpec         # noqa: E402
from cxxnet_tpu.layers.sequence import MoELayer                # noqa: E402
from cxxnet_tpu.nnet.trainer import NetTrainer                 # noqa: E402
from cxxnet_tpu.parallel import moe as moe_ops                 # noqa: E402
from cxxnet_tpu.utils.config import parse_config_file          # noqa: E402

TINY = os.path.join(ROOT, 'example', 'LM', 'tiny-glm.conf')
BIG = os.path.join(ROOT, 'example', 'LM', 'GLM-4.7-Flash.ep8.conf')
DATA = {'successors': 4, 'p_likely': 0.9}


def _pairs(path, **over):
    text = open(path).read()
    pairs = confnet.drop_sections(confnet.parse_conf(text),
                                  ('data', 'eval', 'pred'))
    return pairs + [(k, str(v)) for k, v in over.items()]


def _trainer(pairs):
    tr = NetTrainer(pairs)
    tr.init_model()
    return tr


def _batch(graph, seed=3, rows=2):
    ids = tokens.token_rows(seed, rows, graph.seq + 2, graph.vocab, DATA)
    s = graph.seq
    label = np.concatenate([ids[:, 1:s + 1], ids[:, 2:s + 2]], 1)
    return ids, DataBatch(ids[:, None, None, :s + 1],
                          label.astype(np.float32))


@pytest.fixture(scope='module')
def tiny():
    """The tiny twin in float32: trainer, graph, a batch, the program's
    probabilities, loss and gradients, and the reference's."""
    pairs = _pairs(TINY, seed=5, silent=1)
    tr = _trainer(pairs)
    graph = R.build_graph(pairs)
    ids, batch = _batch(graph)
    params = jax.device_get(tr.params)
    staged = tr.stage_batch(batch)
    loss, grads = tr.compile_grad_step()(
        tr.params, staged[0], staged[1], (), staged[3],
        jax.random.PRNGKey(0), 0)
    probs = {n: tr.extract_feature(batch, n).reshape(2, graph.seq, -1)
             for n in graph.loss_nodes()}
    want = R.forward(graph, params, batch.data)
    total, each, rgrads = R.loss_and_grads(graph, params, batch.data,
                                           batch.label)
    return dict(tr=tr, graph=graph, batch=batch, ids=ids, params=params,
                loss=float(loss), grads=jax.device_get(grads), probs=probs,
                want=want, total=total, each=each, rgrads=rgrads,
                pairs=pairs)


def test_graph_and_leaves(tiny):
    tr, graph = tiny['tr'], tiny['graph']
    assert graph.loss_nodes() == ['logits', 'mtp_logits']
    assert (graph.seq, graph.vocab, graph.width) == (32, 96, 64)
    assert tr.net.takes_token_ids
    # the shared embedding holds no leaves of its own, and both heads are
    # inputs of the one layer that holds the head's weight
    share = [i for i, l in enumerate(graph.layers) if l.primary != i]
    assert [graph.layers[i].type for i in share] == ['embedding']
    assert all(str(i) not in tr.params for i in share)
    (head,) = graph.of_type('lm_head_loss')
    assert head.ins == ['hn', 'mn'] and list(tr.params[str(head.index)]) \
        == ['wmat']


@pytest.mark.parametrize('node', ['logits', 'mtp_logits'])
def test_probabilities_match_reference(tiny, node):
    got, want = tiny['probs'][node], tiny['want'][node]
    assert got.shape == want.shape == (2, 32, 96)
    np.testing.assert_allclose(np.log(got), np.log(want), atol=2e-5)


@pytest.mark.parametrize('node', ['logits', 'mtp_logits'])
def test_each_loss_matches_reference(tiny, node):
    graph, batch = tiny['graph'], tiny['batch']
    (a,) = [h.label_first for h in graph.heads() if h.node == node]
    y = batch.label[:, a:a + graph.seq].astype(int)[..., None]
    mine = -np.mean(np.take_along_axis(np.log(tiny['probs'][node]), y, -1))
    assert abs(mine - tiny['each'][node]) < 1e-5 * abs(tiny['each'][node])


def test_step_loss_is_the_weighted_sum(tiny):
    # batch of 2, loss layers scale by grad_scale / batch_size: the step's
    # loss is the mean over the batch of main + 0.3 * mtp
    assert abs(tiny['loss'] - tiny['total']) < 1e-5 * tiny['total']
    each = tiny['each']
    assert abs(each['logits'] + 0.3 * each['mtp_logits'] - tiny['total']) \
        < 1e-5


def _leaves():
    pairs = _pairs(TINY)
    tr = NetTrainer(pairs + [('dev', 'cpu')])
    tr.init_net()
    shapes = jax.eval_shape(tr.net.init_params, jax.random.PRNGKey(0))
    return [(k, f) for k in sorted(shapes, key=int) for f in sorted(shapes[k])]


@pytest.mark.parametrize('layer,field', _leaves())
def test_gradient_of_every_leaf(tiny, layer, field):
    got = np.asarray(tiny['grads'][layer][field])
    want = np.asarray(tiny['rgrads'][int(layer)][field])
    assert got.shape == want.shape
    if field == 'router_bias':
        # it reaches the choice alone: no gradient on either side
        assert not got.any() and not want.any()
        return
    scale = max(float(np.abs(want).max()), 1e-8)
    assert float(np.abs(got - want).max()) <= 2e-4 * scale, (layer, field)


@pytest.mark.parametrize('node', ['logits', 'mtp_logits'])
def test_bfloat16_program_is_inside_a_band(tiny, node):
    """bf16 products on float32 masters: log-probabilities within 0.05 of
    the float32 reference's spread at this size, and well off exact."""
    tr = _trainer(tiny['pairs'] + [('compute_type', 'bfloat16')])
    got = tr.extract_feature(tiny['batch'], node).reshape(2, 32, -1)
    want = np.log(tiny['want'][node])
    err = np.abs(np.log(got) - want).max() / want.std()
    assert 1e-4 < err < 0.05, err


@pytest.mark.parametrize('chunk', [8, 32])
def test_chunked_loss_equals_the_loss_over_whole_logits(tiny, chunk):
    """The head-and-loss layer's loss, a chunk of tokens at a time (and in
    one chunk), against the cross-entropy of its own whole probabilities:
    value and the gradient of both inputs and the weight."""
    from cxxnet_tpu.layers.sequence import LMHeadLossLayer
    layer = LMHeadLossLayer('head')
    for key, val in dict(vocab_held=96, head_weight='1,0.3', batch_size=2,
                         chunk_tokens=chunk).items():
        layer.set_param(key, str(val))
    layer.infer_shapes([NodeSpec(64, 1, 32)] * 2)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    ins = [jax.random.normal(k, (2, 1, 32, 64)) for k in ks[:2]]
    w = 0.1 * jax.random.normal(ks[2], (64, 96))
    labels = jnp.asarray(tiny['batch'].label)
    ctx = ForwardContext(is_train=True)

    def chunked(w, ins):
        return layer.loss({'wmat': w}, ins, labels, ctx)

    def whole(w, ins):
        probs = layer.forward({'wmat': w}, ins, ctx)
        nll = [-jnp.mean(jnp.log(jnp.take_along_axis(
            p[:, 0], labels[:, k * 32:(k + 1) * 32, None].astype(int),
            axis=-1))) for k, p in enumerate(probs)]
        return nll[0] + 0.3 * nll[1]
    got = jax.value_and_grad(chunked, argnums=(0, 1))(w, ins)
    want = jax.value_and_grad(whole, argnums=(0, 1))(w, ins)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)


@pytest.mark.parametrize('fault', sorted(
    k for k, v in R.PROBE.items() if v.loss_tokens != 'all'))
def test_a_fault_in_the_steps_loss_leaves_the_limits(tiny, fault):
    """The timed program's own step against the reference: its loss and
    the change of the leaves behind the heads agree as the model is, and a
    reference whose loss drops or masks tokens (what a fault in the chunked
    loss would be, seen from the other side) comes out not correct."""
    graph, ids = tiny['graph'], tiny['ids']
    tr = _trainer(tiny['pairs'])
    sides = {v: R.reference_side(graph, tr.params, ids, tiny['probs'], v)
             for v in (R.MODEL, R.PROBE[fault])}
    step = R.program_step(tr, graph, ids)
    found, ok = R.judge(graph, sides[R.MODEL], step)
    assert ok and found['loss'] < 1e-6, found
    assert max(found['update'].values()) < 1e-3, found
    assert sorted(found['update']) == sorted(
        f'{k}.{f}' for k, f in R.tail_leaves(graph))
    found, ok = R.judge(graph, sides[R.PROBE[fault]], step)
    assert not ok and found['loss'] > R.STEP_LOSS_TOLERANCE, found
    assert max(found['update'].values()) > R.UPDATE_TOLERANCE, found


def test_a_state_left_unchanged_reads_one(tiny):
    graph, ids = tiny['graph'], tiny['ids']
    tr = _trainer(tiny['pairs'])
    side = R.reference_side(graph, tr.params, ids, tiny['probs'])
    step = R.program_step(tr, graph, ids)
    found, ok = R.judge(graph, side, dict(step, after=step['w']))
    assert not ok
    np.testing.assert_allclose(list(found['update'].values()), 1.0)


# --- the chip's share against the whole layer -------------------------------

def _moe_layer(first, held, published=8, k=2, bias=None):
    layer = MoELayer('e')
    for key, val in dict(nhidden=48, experts_published=published,
                         experts_held=held, expert_first=first,
                         experts_per_token=k, routed_scaling_factor=1.8,
                         init_sigma=0.2).items():
        layer.set_param(key, str(val))
    layer.infer_shapes([NodeSpec(64, 1, 32)])
    return layer


def _whole_moe_params(rng):
    whole = _moe_layer(0, 8)
    return whole, jax.device_get(whole.init_params(
        rng, [NodeSpec(64, 1, 32)]))


def _share(p, first, held):
    q = dict(p)
    for f in ('wgate', 'wup', 'wdown'):
        q[f] = p[f][first:first + held]
    return q


def _ref_moe(layer_cfg, h, p):
    l = R.Layer(0, 'moe', '', [], [], {k: str(v) for k, v in
                                        layer_cfg.items()}, 0)
    with jax.default_matmul_precision('highest'):
        out, _ = R.moe(l, jnp.asarray(h), {k: jnp.asarray(v)
                                           for k, v in p.items()})
    return np.asarray(out)


CFG8 = dict(nhidden=48, experts_published=8, experts_held=8, expert_first=0,
            experts_per_token=2, routed_scaling_factor=1.8)


@pytest.mark.parametrize('seq', [32, 256])
def test_the_shares_add_up_to_the_whole_layer(seq):
    """Outputs of the four shares (2 held of 8 each), the shared expert and
    the residual counted once, add up to the uncut reference's layer: at 64
    tokens, where a share's buffer has one size, and at 512, where each
    share works on half of its 1,024 sorted rows."""
    _, p = _whole_moe_params(jax.random.PRNGKey(1))
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (2, 1, seq, 64)))
    ctx = ForwardContext(is_train=False)
    no_shared = {k: v for k, v in p.items() if not k.startswith('s')}
    total = np.zeros_like(h)
    shares = []
    for first in (0, 2, 4, 6):
        out, stats = _moe_layer(first, 2).forward_with_stats(
            _share(no_shared, first, 2), [jnp.asarray(h)], ctx)
        total += np.asarray(out[0]) - h            # the routed part alone
        shares.append(float(stats['moe.local_assignment_share']))
        assert float(stats['moe.full_buffer_share']) == 0.0
    assert abs(sum(shares) - 1.0) < 1e-6           # every assignment, once
    shared_only = {**p, 'wgate': p['wgate'][:1] * 0, 'wup': p['wup'][:1] * 0,
                   'wdown': p['wdown'][:1] * 0}
    shared = np.asarray(_moe_layer(0, 1).forward(
        shared_only, [jnp.asarray(h)], ctx)[0]) - h
    want = _ref_moe(CFG8, h[:, 0], p)
    np.testing.assert_allclose((h + total + shared)[:, 0], want, atol=2e-5)


@pytest.mark.parametrize('seq', [32, 256])
@pytest.mark.parametrize('favoured', [0, 1, 5])
def test_nothing_is_dropped_when_every_token_picks_one_expert(favoured, seq):
    """A correction bias that sends every token to one expert (and its
    second choice wherever): the layer has no capacity to overflow.  At 512
    tokens the favoured held expert's 512 first choices and the other's
    second choices pass the 512 rows of the bounded buffer: the layer goes on
    through the second block of 512 and says so."""
    _, p = _whole_moe_params(jax.random.PRNGKey(3))
    p['router_bias'] = np.zeros(8, np.float32)
    p['router_bias'][favoured] = 10.0
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (2, 1, seq, 64)))
    out, stats = _moe_layer(0, 2).forward_with_stats(
        _share(p, 0, 2), [jnp.asarray(h)], ForwardContext(is_train=False))
    want = _ref_moe({**CFG8, 'experts_held': 2}, h[:, 0], _share(p, 0, 2))
    np.testing.assert_allclose(np.asarray(out[0])[:, 0], want, atol=2e-5)
    if favoured < 2:
        # all 64 tokens landed on the favoured held expert: its load is at
        # least the 64 first choices, of 128 assignments
        assert float(stats['moe.local_assignment_share']) >= 0.5
        assert float(stats['moe.load_max_over_mean']) > 1.0
    assert float(stats['moe.full_buffer_share']) == float(
        favoured < 2 and seq == 256)


# --- the bounded buffer against the whole one --------------------------------

MOE_LEAVES = ('norm', 'router', 'wgate', 'wup', 'wdown', 'sgate', 'sup',
              'sdown')
FFN_OUTPUTS = ('result', 'tokens', 'weights', 'wgate', 'wup', 'wdown')
#: (tokens, experts a token, experts held, published), and the held
#: assignments tried: under, at and just over the bound's 512 rows, far over
#: it, and every assignment held (every token choosing held experts only).
#: The first is the shape of GLM's routing (top-2 of 16 here), the second
#: Laguna's (top-10 of 128 with 4 held: ten choices a token, a bound of
#: 1/5 of the buffer)
SHAPES = {'top2of16': ((512, 2, 2, 16), (300, 512, 513, 800, 1024)),
          'top10of128': ((256, 10, 4, 128), (300, 512, 513, 1500, 2560))}
ROUTINGS = [(shape, held) for shape, (_, helds) in SHAPES.items()
            for held in helds]


def _hand_routing(shape, held):
    """(tokens, experts a token) indices of ``SHAPES[shape]``: the first
    ``held`` assignments (row-major) go to the held experts in turn,
    unevenly, the others to experts held elsewhere."""
    (t, k, held_experts, published), _ = SHAPES[shape]
    flat = held_experts + np.arange(t * k) % (published - held_experts)
    flat[:held] = (np.arange(held) % 3) % held_experts
    return jnp.asarray(flat.reshape(t, k), jnp.int32)


def _assert_equal_to_float32(got, want):
    """Equal to float32's last digits: the CPU blocks a product by its rows,
    so two buffer sizes add the same terms in another order."""
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())


def test_the_bound_comes_from_the_layers_shape():
    """Twice the balanced share and no fewer than a row a token, up to a
    multiple of ``ROW_TILE``, never past the buffer: the published cells' layers
    (GLM's twice-the-share is its token count; Laguna's is 5,120, under
    it), the tiny twin's (one size, one path), the 512-token twin of the
    tests below, a chip that holds every expert, a share that is no multiple
    of the tile."""
    assert moe_ops.bounded_rows(8192 * 4, 8, 64, 8192) == 8192
    assert moe_ops.bounded_rows(8192 * 4, 8, 64) == 8192
    assert moe_ops.bounded_rows(8192 * 10, 8, 256, 8192) == 8192
    assert moe_ops.bounded_rows(8192 * 10, 8, 256) == 5120
    assert moe_ops.bounded_rows(64 * 2, 2, 8, 64) == 64 * 2
    assert moe_ops.bounded_rows(512 * 2, 2, 8, 512) == 512
    assert moe_ops.bounded_rows(8192 * 4, 64, 64, 8192) == 8192 * 4
    assert moe_ops.bounded_rows(1000 * 4, 8, 64, 1000) == 1024   # rounded up
    assert 1024 % moe_ops.ROW_TILE == 0            # what the bound rounds to


@pytest.fixture(scope='module')
def hand_routed():
    """``held_experts_ffn`` on hand-made routings of each of ``SHAPES``: a
    bound of 512 rows of the 1,024 and 2,560 sorted ones, with as many
    assignments held as ``SHAPES`` lists, against the one path over all the
    rows (what the layer was before it had a bound): result, loads, and the
    gradients of the tokens, the routing weights and the three matrices.
    Past the bound the layer works through blocks of 512 rows: two at 513
    and 800, three at 1,500, all two or five when every assignment is
    held."""
    d, f = 64, 48
    found = {}
    for shape, ((t, k, held_experts, published), helds) in SHAPES.items():
        assert moe_ops.bounded_rows(t * k, held_experts, published, t) == 512
        keys = jax.random.split(jax.random.PRNGKey(11), 6)
        x = jax.random.normal(keys[0], (t, d))
        weights = jax.random.uniform(keys[1], (t, k), minval=0.2, maxval=1.0)
        ws = [0.2 * jax.random.normal(key, (held_experts,) + dims)
              for key, dims in zip(keys[2:5], [(d, f), (d, f), (f, d)])]
        g = jax.random.normal(keys[5], (t, d))

        def run(fn):
            def loss(x, weights, *ws):
                y, sizes, full = fn(x, weights, *ws)
                return jnp.sum(y * g), (y, sizes, full)
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                              has_aux=True))

        for held in helds:
            idx = _hand_routing(shape, held)
            got = run(lambda x, w, *ws: moe_ops.held_experts_ffn(
                x, idx, w, *ws, 0, published))(x, weights, *ws)

            def one_path(x, w, *ws):
                order, sizes = moe_ops.sort_by_held_expert(idx, 0,
                                                           held_experts)
                return moe_ops._ffn_over_rows(t * k, x, order, w, sizes,
                                              *ws), sizes, 0.0
            found[shape, held] = jax.device_get(
                (got, run(one_path)(x, weights, *ws)))
    return found


@pytest.mark.parametrize('what', FFN_OUTPUTS)
@pytest.mark.parametrize('shape,held', ROUTINGS)
def test_either_buffer_gives_the_whole_ones_result_and_gradients(
        hand_routed, shape, held, what):
    """Up to the bound's 512 held assignments the layer works on 512 rows,
    from 513 on through blocks of 512 until none holds an assignment:
    either way what the one path over all of them gives, and the counter
    says which."""
    ((_, (y, sizes, full)), grads), ((_, (want_y, want_sizes, _)),
                                     want_grads) = hand_routed[shape, held]
    assert sizes.sum() == held and (sizes == want_sizes).all()
    assert float(full) == float(held > 512)
    at = FFN_OUTPUTS.index(what) - 1
    _assert_equal_to_float32(*((y, want_y) if what == 'result'
                               else (grads[at], want_grads[at])))


@pytest.fixture(scope='module')
def both_buffers():
    """Two held of eight experts over 512 tokens x 2, so that the bound
    (512 rows) is under the buffer (1,024): output, counters and gradients
    of the layer as it is and with the bound taken away (the one path of
    the layer before it had a bound), on a routing that fits the bound and
    on one that passes it (every token's first choice a held expert)."""
    _, p = _whole_moe_params(jax.random.PRNGKey(7))
    p = _share(p, 0, 2)
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 1, 256, 64))
    g = jax.random.normal(jax.random.PRNGKey(9), h.shape)
    ctx = ForwardContext(is_train=True)
    layer = _moe_layer(0, 2)

    def run(p, h):
        out, stats = layer.forward_with_stats(p, [h], ctx)
        return jnp.sum(out[0] * g), (out[0], stats)

    def step(p):                   # a trace of its own each time it is asked
        return jax.device_get(jax.jit(jax.value_and_grad(
            run, argnums=(0, 1), has_aux=True))(p, h))

    def both(p):
        p = {k: jnp.asarray(v) for k, v in p.items()}
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(moe_ops, 'bounded_rows', lambda rows, *_: rows)
            whole = step(p)
        return step(p), whole

    crowded = dict(p, router_bias=np.where(np.arange(8) == 1, 10.0,
                                           0.0).astype(np.float32))
    return {'fits': both(p), 'passes': both(crowded)}


@pytest.mark.parametrize('routing', ['fits', 'passes'])
def test_the_bounded_buffer_gives_the_full_ones_output(both_buffers, routing):
    """The same rows in the same order, a block at a time: the output is
    what one pass over the whole buffer gives and the routing counters are
    equal; only ``moe.full_buffer_share`` says whether the layer went past
    its first block."""
    ((_, (out, stats)), _), ((_, (want, full)), _) = both_buffers[routing]
    _assert_equal_to_float32(out, want)
    for name in ('moe.local_assignment_share', 'moe.load_max_over_mean'):
        assert float(stats[name]) == float(full[name])
    held = float(stats['moe.local_assignment_share']) * 1024
    assert (held > 512) == (routing == 'passes')
    assert float(stats['moe.full_buffer_share']) == float(routing == 'passes')
    assert float(full['moe.full_buffer_share']) == 0.0   # one path: no bound


@pytest.mark.parametrize('routing', ['fits', 'passes'])
@pytest.mark.parametrize('leaf', MOE_LEAVES + ('tokens',))
def test_the_bounded_buffer_gives_the_full_ones_gradient(both_buffers, leaf,
                                                         routing):
    """Every leaf's gradient and the tokens', through the layer's own
    derivative rule (a conditional a pass), against autodiff through the
    one full path."""
    (_, (dp, dh)), (_, (want_p, want_h)) = both_buffers[routing]
    _assert_equal_to_float32(*((dh, want_h) if leaf == 'tokens'
                               else (dp[leaf], want_p[leaf])))


# --- the grouped products' tiling (PR 37) -------------------------------------

@pytest.mark.parametrize('rows,groups,k,n,want', [
    # the bounded buffer of the two LM cells, 8,192 rows over 8 held experts:
    # gate and up, then down (its transposes ride each product's tiling)
    pytest.param(8192, 8, 2048, 1536, (256, 512, 1536), id='glm-gate-up'),
    pytest.param(8192, 8, 1536, 2048, (256, 1536, 512), id='glm-down'),
    pytest.param(8192, 8, 3072, 1024, (256, 512, 1024), id='laguna-gate-up'),
    pytest.param(8192, 8, 1024, 3072, (256, 1024, 512), id='laguna-down'),
    pytest.param(8192, 8, 1024, 1024, (256, 512, 1024), id='square'),
    pytest.param(2048, 2, 2048, 1536, (256, 512, 1536), id='two-tiles-a-group'),
    # a chip that holds every expert: ``rows == total``, 4,096 rows a group
    pytest.param(32768, 8, 2048, 1536, None, id='every-expert-held'),
    pytest.param(4096, 1, 2048, 1536, None, id='one-long-group'),
    # the tiny twins: fewer rows than a tile
    pytest.param(128, 2, 64, 48, None, id='tiny-layer'),
    pytest.param(8000, 8, 2048, 1536, None, id='rows-no-multiple-of-the-tile'),
    pytest.param(8192, 8, 2000, 1500, None, id='widths-nothing-divides'),
    pytest.param(8192, 8, 2304, 1536, None, id='wide-no-multiple-of-512'),
    pytest.param(8192, 8, 4096, 2048, None, id='narrow-past-vmem'),
])
def test_the_products_tiling_comes_from_their_shape(rows, groups, k, n, want):
    """256 rows, the narrower width whole and the wider in 512s where the
    buffer gives a group at most two tiles of 512 rows; else, and wherever
    the compiler would refuse the tiling (rows it does not divide) or it was
    never measured (widths it does not divide), none: the compiler's own."""
    assert moe_ops.grouped_tiling(rows, groups, k, n) == want
    if want is not None:
        assert rows % want[0] == 0 and rows <= 2 * moe_ops.ROW_TILE * groups


def test_the_bound_and_the_tile_are_two_constants():
    """``ROW_TILE`` is what ``bounded_rows`` rounds to (and the compiler's
    own row tile), ``GROUP_ROW_TILE`` what the products run on: the bounded
    buffer of both cells keeps its 8,192 rows, which both divide."""
    assert (moe_ops.ROW_TILE, moe_ops.GROUP_ROW_TILE) == (512, 256)
    for shape in ((8192 * 4, 8, 64, 8192), (8192 * 10, 8, 256, 8192)):
        assert moe_ops.bounded_rows(*shape) % moe_ops.ROW_TILE == 0
        assert moe_ops.bounded_rows(*shape) % moe_ops.GROUP_ROW_TILE == 0


#: the tiling a test hands every product in place of the rule's (``None`` at
#: every size a CPU test can afford), so that the context is in place
HANDED = (256, 512, 1024)
TILINGS = [pytest.param(None, id='the-rules-own'),
           pytest.param(HANDED, id='a-tiling-handed')]


@pytest.fixture
def tiling(request, monkeypatch):
    if request.param is not None:
        monkeypatch.setattr(moe_ops, 'grouped_tiling',
                            lambda *shape: request.param)
    return request.param


def _dense_expert(x, wg, wu, wd):
    with jax.default_matmul_precision('highest'):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _dense_swiglu(xs, wg, wu, wd, sizes):
    """Every expert's SwiGLU over its rows by plain products, no grouped
    one: the rows past the groups come back zero."""
    ends = np.cumsum(sizes)
    out = jnp.zeros(xs.shape, jnp.float32)
    for e, (lo, hi) in enumerate(zip(ends - sizes, ends)):
        out = out.at[lo:hi].set(_dense_expert(xs[lo:hi], wg[e], wu[e], wd[e]))
    return out


SWIGLU_OUTPUTS = ('result', 'rows', 'wgate', 'wup', 'wdown')


@pytest.mark.parametrize('what', SWIGLU_OUTPUTS)
@pytest.mark.parametrize('sizes', [(10, 20, 5, 7), (0, 64, 0, 0),
                                   (16, 16, 16, 16)], ids=str)
@pytest.mark.parametrize('tiling', TILINGS, indirect=True)
def test_grouped_swiglu_equals_the_experts_one_by_one(tiling, sizes, what):
    """Value and the four gradients of the three grouped products under
    the tiling's context (which no CPU reads) against each expert's plain
    products over its own rows: uneven groups, one group, full groups."""
    d, f, rows = 16, 8, 64
    keys = jax.random.split(jax.random.PRNGKey(21), 5)
    xs = jax.random.normal(keys[0], (rows, d))
    ws = [0.3 * jax.random.normal(key, (len(sizes),) + dims)
          for key, dims in zip(keys[1:4], [(d, f), (d, f), (f, d)])]
    g = jax.random.normal(keys[4], (rows, d))
    held = sum(sizes)
    sz = np.asarray(sizes, np.int32)

    def run(fn):
        def loss(xs, *ws):
            y = fn(xs, *ws)[:held]
            return jnp.sum(y * g[:held]), y
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                          has_aux=True))(xs, *ws)

    (_, y), grads = run(lambda xs, *ws: moe_ops.grouped_swiglu(
        xs, *ws, jnp.asarray(sz)))
    (_, want_y), want = run(lambda xs, *ws: _dense_swiglu(xs, *ws, sz))
    at = SWIGLU_OUTPUTS.index(what) - 1
    got, want = (y, want_y) if what == 'result' else (grads[at], want[at])
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * np.abs(want).max())


def _dense_held_ffn(x, idx, weights, wg, wu, wd, first):
    """``held_experts_ffn``'s result with no sort, buffer or grouped
    product: every held expert over every token, times the weight of the
    choices that picked it."""
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(wg.shape[0]):
        picked = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=1)
        y = y + _dense_expert(x, wg[e], wu[e], wd[e]) * picked[:, None]
    return y


@pytest.fixture(scope='module')
def against_dense():
    """``held_experts_ffn`` on hand-made routings of ``SHAPES`` that stay in
    the bounded branch (300 held assignments) and that take the blocks (800
    and 1,500), with the rule's own tiling and with one handed to every
    product, against ``_dense_held_ffn``: result and five gradients."""
    d, f = 64, 48
    found = {}
    for shape, held in (('top2of16', 300), ('top2of16', 800),
                        ('top10of128', 300), ('top10of128', 1500)):
        (t, k, held_experts, published), _ = SHAPES[shape]
        keys = jax.random.split(jax.random.PRNGKey(13), 6)
        x = jax.random.normal(keys[0], (t, d))
        weights = jax.random.uniform(keys[1], (t, k), minval=0.2, maxval=1.0)
        ws = [0.2 * jax.random.normal(key, (held_experts,) + dims)
              for key, dims in zip(keys[2:5], [(d, f), (d, f), (f, d)])]
        g = jax.random.normal(keys[5], (t, d))
        idx = _hand_routing(shape, held)

        def run(fn):
            def loss(x, weights, *ws):
                y = fn(x, weights, *ws)
                return jnp.sum(y * g), y
            return jax.device_get(jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(x, weights,
                                                              *ws))

        def layer(x, w, *ws):
            return moe_ops.held_experts_ffn(x, idx, w, *ws, 0, published)[0]

        found[shape, held, None] = run(layer)
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(moe_ops, 'grouped_tiling', lambda *shape: HANDED)
            # the backward rule is jitted: a trace of its own for the handed
            # tiling, and the rule's own again afterwards
            moe_ops._fitting_bwd.clear_cache()
            found[shape, held, HANDED] = run(layer)
        moe_ops._fitting_bwd.clear_cache()
        found[shape, held, 'dense'] = run(lambda x, w, *ws: _dense_held_ffn(
            x, idx, w, *ws, 0))
    return found


@pytest.mark.parametrize('what', FFN_OUTPUTS)
@pytest.mark.parametrize('shape,held', [
    ('top2of16', 300), ('top2of16', 800), ('top10of128', 300),
    ('top10of128', 1500)])
@pytest.mark.parametrize('handed', [None, HANDED],
                         ids=['the-rules-own', 'a-tiling-handed'])
def test_the_layer_equals_every_expert_over_every_token(against_dense, shape,
                                                        held, handed, what):
    """The bounded branch and the blocks, value and gradients, through the
    layer's own derivative rule with the products under a tiling's context:
    what plain per-expert products give."""
    (_, y), grads = against_dense[shape, held, handed]
    (_, want_y), want = against_dense[shape, held, 'dense']
    at = FFN_OUTPUTS.index(what) - 1
    got, want = (y, want_y) if what == 'result' else (grads[at], want[at])
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * np.abs(want).max())


def test_a_tiling_rides_every_product_and_its_transposes(monkeypatch):
    """The frontend attribute is on every product of the lowered layer,
    forward and backward, in the bounded branch and in the blocks (off the
    TPU a grouped product lowers to a masked ``dot_general``: 12 a branch),
    and on none where the rule gives no tiling."""
    (t, k, held_experts, published), _ = SHAPES['top2of16']
    x = jnp.ones((t, 64))
    ws = [jnp.ones((held_experts,) + dims)
          for dims in [(64, 48), (64, 48), (48, 64)]]
    idx = _hand_routing('top2of16', 300)

    def lowered():
        moe_ops._fitting_bwd.clear_cache()
        return jax.jit(jax.value_and_grad(lambda x, w, *ws: jnp.sum(
            moe_ops.held_experts_ffn(x, idx, w, *ws, 0, published)[0]),
            argnums=(0, 1, 2, 3, 4))).lower(
                x, jnp.ones((t, k)), *ws).as_text()

    products = [l for l in lowered().splitlines() if 'dot_general' in l]
    assert len(products) == 24 and not any(
        'ragged_dot_tiling' in l for l in products)
    monkeypatch.setattr(
        moe_ops, 'grouped_tiling',
        lambda rows, groups, k, n: (256, 512, n) if n <= k else (256, k, 512))
    products = [l for l in lowered().splitlines() if 'dot_general' in l]
    moe_ops._fitting_bwd.clear_cache()
    carried = [l for l in products if 'ragged_dot_tiling = "256,' in l]
    assert len(products) == len(carried) == 24
    assert sum('"256,512,48"' in l for l in carried) == 16   # gate, up: d x f
    assert sum('"256,48,512"' in l for l in carried) == 8    # down: f x d


def test_mtp_head_shares_embedding_and_head_with_the_main_model(tiny):
    """One leaf each, two gradients summed: the leaf's gradient is the main
    head's plus 0.3 times the multi-token-prediction head's."""
    graph, params, batch = tiny['graph'], tiny['params'], tiny['batch']

    def only(node, scale):
        g = R.build_graph(tiny['pairs'])
        for l in g.of_type('lm_head_loss'):
            l.cfg = dict(l.cfg, head_weight=','.join(
                str(scale if out == node else 0.0) for out in l.outs))
        return R.loss_and_grads(g, params, batch.data, batch.label)[2]
    main, mtp = only('logits', 1.0), only('mtp_logits', 1.0)
    emb = graph.of_type('embedding')[0].index
    head = graph.of_type('lm_head_loss')[0].index
    for layer in (emb, head):
        both = main[layer]['wmat'] + 0.3 * mtp[layer]['wmat']
        assert np.abs(mtp[layer]['wmat']).max() > 0
        got = tiny['grads'][str(layer)]['wmat']
        assert np.abs(got - both).max() <= 2e-4 * np.abs(both).max()


# --- the trainer's part ------------------------------------------------------

def test_token_ids_reach_the_embedding_as_integers():
    """bf16 compute, ids above 256: a cast to the wire type would fold
    them together."""
    pairs = [(k, v) for k, v in _pairs(TINY, compute_type='bfloat16')]
    pairs = [(k, '1000' if k == 'vocab_held' else v) for k, v in pairs]
    tr = _trainer(pairs)
    ids = np.arange(700, 700 + 2 * 33, dtype=np.int32).reshape(2, 33)
    batch = DataBatch(ids[:, None, None, :], np.zeros((2, 64), np.float32))
    staged = tr.stage_batch(batch)
    assert staged[0].dtype == jnp.int32
    got = tr.extract_feature(batch, 'ids')
    np.testing.assert_array_equal(got, ids[:, :32])
    table = np.asarray(tr.params['2']['wmat'])
    emb = tr.extract_feature(batch, 'e_next').reshape(2, 32, 64)
    np.testing.assert_allclose(
        emb, table[ids[:, 1:]].astype(jnp.bfloat16).astype(np.float32))


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
    line = tr.evaluate(None, 'train')
    assert line == ''                      # drained above
    assert tr.train_step_flops() > 0
    text = tr.step_program_text()
    assert 'l06_moe_moe1' in text and 'l03_mla_attn0' in text


def test_recomputation_keeps_the_layer_scope():
    """The checkpointed layers' forward, recomputation and backward all
    carry the conf layer's scope, which is what the trace is split by."""
    tr = _trainer(_pairs(TINY, seed=1, silent=1))
    graph = R.build_graph(_pairs(TINY))
    staged = tr.stage_batch(_batch(graph)[1])
    text = tr._train_step_fn._jit.lower(
        tr.params, tr.opt_state, tr.grad_acc, staged[0], staged[1], (),
        staged[3], jax.random.PRNGKey(0), 0, 0,
        do_update=True).as_text(debug_info=True)
    for scope in ('jvp(l05_mla_attn1)', 'transpose(jvp(l05_mla_attn1))',
                  'jvp(l06_moe_moe1)', 'transpose(jvp(l06_moe_moe1))',
                  'transpose(jvp(l13_lm_head_loss_head))'):
        assert scope in text, scope
    assert 'checkpoint' in text or 'remat' in text


def test_published_conf_counts_its_parameters():
    """The example conf's leaves, by shape alone: 706,518,848 parameters,
    the counts of ISSUE 29's table."""
    tr = NetTrainer(_pairs(BIG) + [('dev', 'cpu')])
    tr.init_net()
    shapes = jax.eval_shape(tr.net.init_params, jax.random.PRNGKey(0))
    count = lambda d: sum(int(np.prod(a.shape)) for a in d.values())  # noqa
    graph = R.build_graph(_pairs(BIG))
    by_type = {}
    for k, d in shapes.items():
        by_type.setdefault(graph.layers[int(k)].type, []).append(count(d))
    assert set(by_type['mla']) == {21_759_232 + 2048}      # + its pre-norm
    assert set(by_type['moe']) == {8 * 9_437_184 + 9_437_184 + 131_136
                                   + 2048}
    assert by_type['swiglu'] == [3 * 2048 * 10240 + 2048]
    assert by_type['embedding'] == by_type['lm_head_loss'] == [39_649_280]
    assert sum(count(d) for d in shapes.values()) == 706_518_848


def test_train_flops_by_hand():
    """29.7 TFLOP a step of one 8,192-token sequence, by hand."""
    graph = R.build_graph(_pairs(BIG))
    s, d = 8192, 2048
    attn_proj = (d * 768 + 768 * 20 * 256 + d * 576 + 512 * 20 * 448
                 + 20 * 256 * d)
    attn_core = (s * (s + 1) // 2) * 20 * (256 + 256)
    expert = 3 * d * 1536
    moe = s * (d * 64 + expert) + (s * 4 * 8 / 64) * expert
    hand = (6 * (s * attn_proj + attn_core) + s * 3 * d * 10240 + 5 * moe
            + s * 2 * d * d + 2 * s * d * 19360)
    assert sum(R.forward_macs(graph).values()) == hand
    assert abs(R.train_flops_per_sequence(graph) - 29.7e12) < 0.05e12


# --- the iterator, the model file ---------------------------------------------

def test_synth_tokens_iterator_feeds_the_conf():
    from cxxnet_tpu.io.data import create_iterator
    it = create_iterator([('iter', 'synth_tokens'), ('seq_len', '32'),
                          ('vocab', '96'), ('num_batches', '3'),
                          ('batch_size', '2'), ('seed_data', '9'),
                          ('silent', '1'), ('iter', 'end')])
    it.init()
    batches = list(it)
    assert len(batches) == 3 and len(list(it)) == 3        # every round
    b = batches[0]
    assert b.data.shape == (2, 1, 1, 33) and b.data.dtype == np.int32
    assert b.label.shape == (2, 64) and b.label.dtype == np.float32
    ids = b.data[:, 0, 0]
    np.testing.assert_array_equal(b.label[:, :32], ids[:, 1:33])
    np.testing.assert_array_equal(b.label[:, 32:63], ids[:, 2:33])
    assert 0 <= ids.min() and ids.max() < 96
    # a chain, not noise: most tokens are one of their predecessor's four
    # likely successors, so far fewer distinct pairs than positions
    again = create_iterator([('iter', 'synth_tokens'), ('seq_len', '32'),
                             ('vocab', '96'), ('num_batches', '3'),
                             ('batch_size', '2'), ('seed_data', '9'),
                             ('silent', '1'), ('iter', 'end')])
    again.init()
    np.testing.assert_array_equal(next(iter(again)).data, b.data)


def test_model_file_round_trip(tmp_path):
    """save_model writes every leaf of the sequence layers and load_model
    reads them back: the same probabilities after the trip."""
    pairs = _pairs(TINY, seed=2, silent=1)
    tr = _trainer(pairs)
    graph = R.build_graph(pairs)
    batch = _batch(graph)[1]
    before = tr.extract_feature(batch, 'mtp_logits')
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
    np.testing.assert_array_equal(other.extract_feature(batch, 'mtp_logits'),
                                  before)


def test_cli_trains_the_tiny_twin(tmp_path, capfd):
    """``python -m cxxnet_tpu.main`` on the tiny conf: ``task = train``
    through LearnTask, the round's line carries a falling train-loss."""
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
