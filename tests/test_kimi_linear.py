"""Kimi-Linear-48B-A3B's layers as conf layer types (layers/sequence.py:
``mla`` with a full-rank query and no positions, ``kda`` with ``beta`` up to
1, ``moe`` over 256 experts at ``routed_scaling_factor`` 2.446, a dense
``swiglu`` under a delta layer) against the plain reference
(benchmark/references/kimi_linear.py) at the tiny twin's size: the head's
probabilities and loss, the gradient of every leaf, the timed step and the
probe's faults, the chip's share of the experts against the whole layer,
and the trainer's part.  Which attention kernel the shapes choose:
tests/test_attention.py; the compiled step for the chip:
tests/test_v5e_compile.py."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import confnet, tokens                          # noqa: E402
from benchmark.references import glm_moe_lite as G             # noqa: E402
from benchmark.references import kimi_linear as R              # noqa: E402
from cxxnet_tpu.io.data import DataBatch                       # noqa: E402
from cxxnet_tpu.layers import ForwardContext, NodeSpec         # noqa: E402
from cxxnet_tpu.layers import sequence as S                    # noqa: E402
from cxxnet_tpu.nnet.trainer import NetTrainer                 # noqa: E402

TINY = os.path.join(ROOT, 'example', 'LM', 'tiny-kimi.conf')
BIG = os.path.join(ROOT, 'example', 'LM', 'Kimi-Linear-48B-A3B.ep32.conf')
DATA = {'successors': 4, 'p_likely': 0.9}
SEQ = 96


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


@pytest.fixture(scope='module')
def tiny():
    """The tiny twin in float32 at 96 positions (a chunk of the delta rule
    and a half): trainer, graph, a batch, the program's probabilities, loss
    and gradients, and the reference's."""
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
    total, each, rgrads = R.loss_and_grads(graph, params, batch.data,
                                           batch.label)
    return dict(tr=tr, graph=graph, batch=batch, ids=ids, params=params,
                loss=float(loss), grads=jax.device_get(grads), probs=probs,
                want=R.forward(graph, params, batch.data), total=total,
                each=each, rgrads=rgrads, pairs=pairs)


def test_graph_and_leaves(tiny):
    """A delta layer over the dense SwiGLU, then three delta layers and a
    latent one over expert layers; the latent layer holds the published
    leaves of a full-rank query and no ``W_qa`` or ``q_norm``."""
    params, graph = tiny['params'], tiny['graph']
    assert graph.loss_nodes() == ['logits']
    assert (graph.seq, graph.vocab, graph.width) == (SEQ, 96, 64)
    assert [l.type for l in graph.layers[2:12]] == [
        'kda', 'swiglu', 'kda', 'moe', 'kda', 'moe', 'mla', 'moe', 'kda',
        'moe']
    (attn,) = graph.of_type('mla')
    leaves = params[str(attn.index)]
    assert tuple(sorted(leaves)) == tuple(sorted(
        S.LatentAttentionLayer.FULL_RANK_FIELDS))
    assert {f: tuple(a.shape) for f, a in leaves.items()} == {
        'norm': (64,), 'wq': (64, 4 * (16 + 8)), 'wkv_a': (64, 16 + 8),
        'kv_norm': (16,), 'wkv_b': (16, 4 * (16 + 16)), 'wo': (4 * 16, 64)}
    layer = tiny['tr'].net.layers[attn.index]
    assert layer.param_fields == S.LatentAttentionLayer.FULL_RANK_FIELDS
    assert 'wq_a' in S.LatentAttentionLayer.param_fields  # GLM's, as before
    for l in graph.of_type('kda'):
        assert tiny['tr'].net.layers[l.index].beta_max == 1.0


def test_probabilities_and_loss_match_reference(tiny):
    got, want = tiny['probs']['logits'], tiny['want']['logits']
    assert got.shape == want.shape == (2, SEQ, 96)
    np.testing.assert_allclose(np.log(got), np.log(want), atol=3e-5)
    y = tiny['batch'].label[:, :SEQ].astype(int)[..., None]
    mine = -np.mean(np.take_along_axis(np.log(got), y, -1))
    assert abs(mine - tiny['each']['logits']) < 1e-5 * tiny['each']['logits']
    assert abs(tiny['loss'] - tiny['total']) < 1e-5 * tiny['total']


def _leaves():
    tr = NetTrainer(_pairs(TINY) + [('dev', 'cpu')])
    tr.init_net()
    shapes = jax.eval_shape(tr.net.init_params, jax.random.PRNGKey(0))
    return [(k, f) for k in sorted(shapes, key=int) for f in sorted(shapes[k])]


@pytest.mark.parametrize('layer,field', _leaves())
def test_gradient_of_every_leaf(tiny, layer, field):
    got = np.asarray(tiny['grads'][layer][field])
    want = np.asarray(tiny['rgrads'][int(layer)][field])
    if field == 'router_bias':
        # it reaches the choice alone: no gradient in either
        assert not got.any() and not want.any()
        return
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1e-8)
    assert float(np.abs(got - want).max()) <= 2e-4 * scale, (layer, field)


# --- the step against the reference ------------------------------------------

@pytest.fixture(scope='module')
def step(tiny):
    """The timed program's own step on the tiny batch (``update_staged``, as
    the window times it) and the reference's side of it as the model is."""
    graph, ids, tr = tiny['graph'], tiny['ids'], tiny['tr']
    params = jax.device_get(tr.params)      # the step donates the trainer's
    side = R.reference_side(graph, params, ids, tiny['probs'])
    return params, side, R.program_step(tr, graph, ids)


def test_the_steps_loss_and_update_agree_with_the_reference(tiny, step):
    """Its loss and the change of the head's weight, the final norm, every
    leaf of the last delta layer and every leaf of the latent layer agree
    as the model is; a state left unchanged reads one."""
    graph = tiny['graph']
    _, side, found = step
    numbers, ok = R.judge(graph, side, found)
    assert ok and numbers['loss'] < 1e-5, numbers
    for part, leaves in (('update', 2), ('kda_update', 17),
                         ('mla_update', 6)):
        assert len(numbers[part]) == leaves, numbers[part]
        assert max(numbers[part].values()) < 1e-3, numbers[part]
    numbers, ok = R.judge(graph, side, dict(found, after=found['w']))
    assert not ok
    np.testing.assert_allclose(
        [u for part in ('update', 'kda_update', 'mla_update')
         for u in numbers[part].values()], 1.0)


def test_the_chain_gives_the_reference_gradient(tiny, step):
    """The step's reference gradients, taken back a block of positions or a
    group of heads at a time through the head, the final norm, the last
    expert layer, the last delta layer and the expert layer before it to the
    latent layer, are the whole graph's."""
    side, want = step[1], tiny['rgrads']
    chain, _ = R.tail(tiny['graph'])
    assert [l.type for l in chain] == ['mla', 'moe', 'kda', 'moe',
                                       'rmsnorm']
    assert {k for k, _ in side['grads']} == {chain[0].primary,
                                             chain[2].primary,
                                             chain[-1].primary,
                                             tiny['graph'].heads()[0]
                                             .layer.primary}
    for (layer, field), got in side['grads'].items():
        w = np.asarray(want[layer][field])
        np.testing.assert_allclose(got, w, rtol=2e-4,
                                   atol=2e-5 * float(np.abs(w).max()),
                                   err_msg=f'{layer}.{field}')


def test_a_frozen_latent_layer_leaves_the_limits(tiny, step):
    """The step with every leaf of the latent layer left as it was: each
    reads one, the others' stay the model's."""
    graph = tiny['graph']
    _, side, found = step
    mla = R.tail(graph)[0][0].primary
    frozen = {k: (found['w'][k] if k[0] == mla else a)
              for k, a in found['after'].items()}
    numbers, ok = R.judge(graph, side, dict(found, after=frozen))
    assert not ok and max(numbers['kda_update'].values()) < 1e-3, numbers
    np.testing.assert_allclose(list(numbers['mla_update'].values()), 1.0)


@pytest.mark.parametrize('fault', [
    'rotary on the latent layer\'s 64 dims',
    'no gradient through the attention kernel',
    'beta up to 2 (a negative eigenvalue)'])
def test_a_fault_of_the_new_mechanisms_leaves_the_limits(tiny, step, fault):
    """Rotary on the 64 dims the model leaves unrotated, an attention that
    passes no gradient back, or ``beta`` up to 2 where the model stops at 1:
    the latent layer's leaves move otherwise than the reference says."""
    graph, ids = tiny['graph'], tiny['ids']
    params, _, found = step
    side = R.reference_side(graph, params, ids, tiny['probs'],
                            R.PROBE[fault])
    numbers, ok = R.judge(graph, side, found)
    assert not ok
    assert max(numbers['mla_update'].values()) > R.MLA_UPDATE_TOLERANCE, \
        numbers['mla_update']


# --- the layers alone ----------------------------------------------------------

def _layer(cls, keys, spec):
    layer = cls('x')
    for key, val in keys.items():
        layer.set_param(key, str(val))
    layer.infer_shapes([spec])
    return layer


MLA = dict(nhead=4, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=12, rope_theta=10000, init_sigma=0.3, eps=1e-5)


@pytest.mark.parametrize('q_lora_rank,use_rope', [(0, 0), (0, 1), (24, 0),
                                                  (24, 1)])
def test_latent_attention_matches_the_reference(q_lora_rank, use_rope):
    """The latent layer with a full-rank or a low-rank query, rotary on its
    shared dims or none, output and every leaf's gradient: a full-rank
    query against this family's reference, a low-rank one (GLM's form)
    against GLM's."""
    spec = NodeSpec(32, 1, 40)
    keys = dict(MLA, q_lora_rank=q_lora_rank, use_rope=use_rope)
    layer = _layer(S.LatentAttentionLayer, keys, spec)
    p = jax.device_get(layer.init_params(jax.random.PRNGKey(1), [spec]))
    assert ('wq' in p) == (not q_lora_rank) and ('wq_a' in p) \
        == bool(q_lora_rank)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 1, 40, 32))
    w = jnp.cos(jnp.arange(2 * 40 * 32, dtype=jnp.float32)).reshape(
        2, 40, 32)
    ctx = ForwardContext(is_train=False)
    got = jax.jit(jax.value_and_grad(lambda p: jnp.sum(
        w * layer.forward(p, [h], ctx)[0][:, 0])))(p)
    ref = R.Layer(0, 'mla', '', [], [], {k: str(v) for k, v in keys.items()},
                  0)
    rule = (lambda l, h, p: G.mla(l, h, p, G.Variant(rotary=bool(use_rope)))
            ) if q_lora_rank else R.mla
    with jax.default_matmul_precision('highest'):
        want = jax.jit(jax.value_and_grad(lambda p: jnp.sum(
            w * rule(ref, h[:, 0], p))))(p)
    assert abs(float(got[0]) - float(want[0])) <= 1e-5 * abs(float(want[0]))
    for f in p:
        scale = float(jnp.abs(want[1][f]).max())
        np.testing.assert_allclose(got[1][f], want[1][f], atol=2e-5 * scale,
                                   err_msg=f)


def test_latent_attention_without_positions_sees_a_set():
    """With ``use_rope = 0`` the last position's output depends on the set of
    the positions before it and not on their order; with rotary it does."""
    spec = NodeSpec(32, 1, 12)
    ctx = ForwardContext(is_train=False)
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (1, 1, 12, 32)))
    swapped = h.copy()
    swapped[:, :, [2, 7]] = h[:, :, [7, 2]]
    for use_rope in (0, 1):
        layer = _layer(S.LatentAttentionLayer,
                       dict(MLA, q_lora_rank=0, use_rope=use_rope), spec)
        p = jax.device_get(layer.init_params(jax.random.PRNGKey(1), [spec]))
        last = [np.asarray(layer.forward(p, [jnp.asarray(a)], ctx)[0])[
            0, 0, -1] for a in (h, swapped)]
        if use_rope:
            assert np.abs(last[0] - last[1]).max() > 1e-3
        else:
            np.testing.assert_allclose(last[0], last[1], atol=1e-5)


@pytest.mark.parametrize('allow', [0, 1])
def test_the_delta_layer_against_the_token_by_token_rule(allow):
    """The delta layer at ``allow_neg_eigval`` 0 (``beta`` in (0, 1)) and 1
    (in (0, 2)), output and every leaf's gradient, against the reference's
    layer over the token-by-token rule at the same key; the two differ."""
    spec = NodeSpec(32, 1, 80)
    keys = dict(nhead=2, head_dim=16, init_sigma=0.3, eps=1e-5)
    layer = _layer(S.DeltaAttentionLayer, dict(keys, allow_neg_eigval=allow),
                   spec)
    assert layer.beta_max == (2.0 if allow else 1.0)
    p = jax.device_get(layer.init_params(jax.random.PRNGKey(1), [spec]))
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 80, 32))
    w = jnp.sin(jnp.arange(80 * 32, dtype=jnp.float32)).reshape(1, 80, 32)
    ctx = ForwardContext(is_train=False)
    got = jax.jit(jax.value_and_grad(lambda p: jnp.sum(
        w * layer.forward(p, [h], ctx)[0][:, 0])))(p)
    sums = {}
    for key in (allow, 1 - allow):
        ref = R.Layer(0, 'kda', '', [], [], {
            k: str(v) for k, v in dict(keys, allow_neg_eigval=key).items()},
            0)
        with jax.default_matmul_precision('highest'):
            sums[key] = jax.jit(jax.value_and_grad(lambda p: jnp.sum(
                w * R.kda(ref, h[:, 0], p))))(p)
    want = sums[allow]
    assert abs(float(got[0]) - float(want[0])) <= 1e-4 * abs(float(want[0]))
    for f in p:
        scale = float(jnp.abs(want[1][f]).max())
        np.testing.assert_allclose(got[1][f], want[1][f], atol=1e-4 * scale,
                                   err_msg=f)
    assert abs(float(sums[0][0]) - float(sums[1][0])) \
        > 1e-3 * abs(float(want[0]))


def test_the_expert_shares_add_up_to_the_whole_layer():
    """32 experts in 4 shares of 8, top-8 by a sigmoid router renormalised
    and scaled by 2.446: the shares' routed parts, with the shared expert
    and the residual counted once, add up to the uncut reference's layer,
    and every assignment lands on exactly one share."""
    spec = NodeSpec(32, 1, 40)
    keys = lambda first, held: dict(  # noqa: E731
        nhidden=16, experts_published=32, experts_per_token=8,
        routed_scaling_factor=2.446, eps=1e-5, experts_held=held,
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
    ref = R.Layer(0, 'moe', '', [], [], {k: str(v) for k, v in
                                         keys(0, 32).items()}, 0)
    with jax.default_matmul_precision('highest'):
        want = R.OPS['moe'](ref, [jnp.asarray(h[:, 0])],
                            {k: jnp.asarray(v) for k, v in p.items()},
                            R.MODEL)[0]
    np.testing.assert_allclose((h + total + shared)[:, 0], want, atol=5e-5)


# --- the trainer's part ------------------------------------------------------

def test_model_file_round_trip(tiny, tmp_path):
    """save_model writes the latent layer's own leaves (a full-rank query,
    no low-rank slots) and load_model reads them back: the same
    probabilities after the trip."""
    tr, batch = tiny['tr'], tiny['batch']
    before = tr.extract_feature(batch, 'logits')
    path = tmp_path / 'tiny.model'
    with open(path, 'wb') as f:
        tr.save_model(f)
    other = NetTrainer(tiny['pairs'])
    with open(path, 'rb') as f:
        other.load_model(f)
    for k, d in tr.params.items():
        assert sorted(other.params[k]) == sorted(d)
        for name, a in d.items():
            np.testing.assert_array_equal(np.asarray(other.params[k][name]),
                                          np.asarray(a))
    np.testing.assert_array_equal(other.extract_feature(batch, 'logits'),
                                  before)


def test_cli_trains_the_tiny_twin(tmp_path, capfd):
    """``python -m cxxnet_tpu.main`` on the tiny conf: ``task = train``
    through LearnTask and ``update_staged``, the round's line carries a
    falling train-loss and the delta layers' counter."""
    import re
    from cxxnet_tpu.main import LearnTask
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        LearnTask().run([TINY, 'num_round=2', 'silent=1'])
    finally:
        os.chdir(cwd)
    err = capfd.readouterr().err
    losses = [float(x) for x in re.findall(r'train-loss:([0-9.]+)', err)]
    assert len(losses) == 2 and losses[-1] < losses[0] - 0.3, err
    assert 'train-kda.chunk_log_decay_min:' in err


def test_published_conf_counts_its_parameters():
    """The example conf's leaves, by shape alone, to the unit: four delta
    layers of 39.5 M, the latent layer's 29.1 M with its full-rank query,
    the dense layer's 63.7 M, four expert layers of 64.3 M with 8 of 256
    experts, an eighth of the vocabulary twice."""
    tr = NetTrainer(_pairs(BIG) + [('dev', 'cpu')])
    tr.init_net()
    shapes = jax.eval_shape(tr.net.init_params, jax.random.PRNGKey(0))
    count = lambda d: sum(int(np.prod(a.shape)) for a in d.values())  # noqa
    graph = R.build_graph(_pairs(BIG))
    by_type = {}
    for k in sorted(shapes, key=int):
        by_type.setdefault(graph.layers[int(k)].type, []).append(
            count(shapes[k]))
    d, w = 2304, 32 * 128
    assert by_type['kda'] == [39_520_672] * 4
    assert by_type['mla'] == [d + d * 32 * 192 + d * 576 + 512 + 512 * 32
                              * 256 + w * d] == [29_117_184]
    assert by_type['swiglu'] == [d + 3 * d * 9216] == [63_703_296]
    assert by_type['moe'] == [d + d * 256 + 256 + 9 * 3 * d * 1024] * 4 \
        == [64_293_376] * 4
    assert by_type['embedding'] == by_type['lm_head_loss'] == [20480 * d]
    total = sum(count(v) for v in shapes.values())
    assert total == 602_450_816
    assert 8.9 < total * 16 / 2 ** 30 < 9.0                # GiB in a step
