"""Solar-Open2-250B's layers as conf layer types (layers/sequence.py: ``kda``,
``gqa`` without positions and with a gate a channel, ``moe`` over 320
experts with a sigmoid router) against the plain reference
(benchmark/references/solar_open2.py) at the tiny twin's size: the head's
probabilities and loss, the gradient of every leaf, the timed step and the
probe's faults, and the trainer's part.  The chunked delta rule and the
shares against the uncut layers: tests/test_delta_rule.py."""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import confnet, kda_costs, tokens                # noqa: E402
from benchmark.references import solar_open2 as R              # noqa: E402
from cxxnet_tpu.io.data import DataBatch                       # noqa: E402
from cxxnet_tpu.layers import sequence as S                    # noqa: E402
from cxxnet_tpu.nnet.trainer import NetTrainer                 # noqa: E402

TINY = os.path.join(ROOT, 'example', 'LM', 'tiny-solar.conf')
BIG = os.path.join(ROOT, 'example', 'LM', 'Solar-Open2-250B.ep40tp4.conf')
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
    """The tiny twin in float32 at 96 positions (a chunk and a half):
    trainer, graph, a batch, the program's probabilities, loss and
    gradients, and the reference's."""
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
    params, graph = tiny['params'], tiny['graph']
    assert graph.loss_nodes() == ['logits']
    assert (graph.seq, graph.vocab, graph.width) == (SEQ, 96, 64)
    assert [l.type for l in graph.layers[2:10]] == ['gqa', 'moe'] + [
        'kda', 'moe'] * 3
    (attn,) = graph.of_type('gqa')
    assert params[str(attn.index)]['wgate'].shape == (64, 4 * 16)
    kda = graph.of_type('kda')[0]
    leaves = params[str(kda.index)]
    assert sorted(leaves) == sorted(S.DeltaAttentionLayer.param_fields)
    shapes = {f: tuple(a.shape) for f, a in leaves.items()}
    assert shapes == {
        'norm': (64,), 'wq': (64, 64), 'wk': (64, 64), 'wv': (64, 64),
        'conv_q': (4, 64), 'conv_k': (4, 64), 'conv_v': (4, 64),
        'wa_down': (64, 16), 'wa_up': (16, 64), 'dt_bias': (64,),
        'a_log': (4,), 'wbeta': (64, 4), 'wg_down': (64, 16),
        'wg_up': (16, 64), 'g_bias': (64,), 'o_norm': (16,),
        'wo': (64, 64)}
    # Mamba's convention: A in [1, 16], softplus(dt_bias) in [1e-3, 0.1]
    a = np.exp(np.asarray(leaves['a_log']))
    step = np.log1p(np.exp(np.asarray(leaves['dt_bias'])))
    assert (a >= 1).all() and (a <= 16).all()
    assert (step >= 1e-3 * 0.999).all() and (step <= 0.1 * 1.001).all()
    assert not np.asarray(leaves['g_bias']).any()


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


def test_bfloat16_program_is_inside_a_band(tiny):
    """bf16 products on float32 masters (the delta rule stays float32): the
    log-probabilities within a band of the float32 reference's spread, and
    well off exact."""
    tr = _trainer(tiny['pairs'] + [('compute_type', 'bfloat16')])
    got = tr.extract_feature(tiny['batch'], 'logits').reshape(2, SEQ, -1)
    want = np.log(tiny['want']['logits'])
    err = np.abs(np.log(got) - want).max(-1) / want.std()
    # read 0.055 and 0.079: the delta rule carries the rounding on
    assert 1e-3 < np.median(err) < 0.1 and np.quantile(err, 0.9) < 0.15, err


# --- the step against the reference ------------------------------------------

@pytest.fixture(scope='module')
def step(tiny):
    """The timed program's own step on the tiny batch (``update_staged``, as
    the window times it), the reference's side of it as the model is, and
    what twelve more steps of the same trainer counted."""
    graph, ids = tiny['graph'], tiny['ids']
    tr = tiny['tr']
    params = jax.device_get(tr.params)      # the step donates the trainer's
    side = R.reference_side(graph, params, ids, tiny['probs'])
    found = R.program_step(tr, graph, ids)
    staged = [tr.stage_batch(_batch(graph, seed=s)[1]) for s in range(4)]
    tr.step_stats()
    for i in range(12):
        tr.update_staged(staged[i % 4])
    return params, side, found, tr.step_stats(), tr.step_program_text()


def test_the_steps_loss_and_update_agree_with_the_reference(tiny, step):
    """Its loss and the change of the head's weight, the final norm and
    every leaf of the last delta layer agree as the model is; a state left
    unchanged reads one."""
    graph = tiny['graph']
    _, side, found = step[:3]
    found, ok = R.judge(graph, side, found)
    assert ok and found['loss'] < 1e-5, found
    assert max(found['update'].values()) < 1e-3, found
    assert max(found['kda_update'].values()) < 1e-3, found
    found, ok = R.judge(graph, side, dict(step[2], after=step[2]['w']))
    assert not ok
    np.testing.assert_allclose(list(found['update'].values())
                               + list(found['kda_update'].values()), 1.0)


def test_the_chain_gives_the_reference_gradient(tiny, step):
    """The step's reference gradients, taken back a block of positions and
    a group of heads at a time through the head, the final norm, the last
    expert layer and the last delta layer, are the whole graph's."""
    side, want = step[1], tiny['rgrads']
    last = R.kda_tail(tiny['graph'])[0]
    assert {k for k, _ in side['grads']} >= {last.primary}
    assert len([k for k in side['grads'] if k[0] == last.primary]) == 17
    for (layer, field), got in side['grads'].items():
        w = np.asarray(want[layer][field])
        np.testing.assert_allclose(got, w, rtol=2e-4,
                                   atol=2e-5 * float(np.abs(w).max()),
                                   err_msg=f'{layer}.{field}')


def test_a_frozen_delta_layer_leaves_the_limits(tiny, step):
    """The step with every leaf of the last delta layer left as it was: each
    reads one, the tail's stay the model's."""
    graph = tiny['graph']
    _, side, found = step[:3]
    frozen = {k: (found['w'][k] if k in found['w'] and k[0] ==
                  R.kda_tail(graph)[0].primary else a)
              for k, a in found['after'].items()}
    numbers, ok = R.judge(graph, side, dict(found, after=frozen))
    assert not ok and max(numbers['update'].values()) < 1e-3, numbers
    np.testing.assert_allclose(list(numbers['kda_update'].values()), 1.0)


@pytest.mark.parametrize('fault', ['the recurrence in bfloat16',
                                   'the recurrence fed bfloat16'])
def test_a_delta_rule_in_bfloat16_leaves_the_limits(tiny, step, fault):
    """The program's delta rule against the reference's on the same inputs:
    float32 far inside the limit, either bfloat16 form of the rule outside
    it."""
    graph, ids = tiny['graph'], tiny['ids']
    params, side = step[:2]
    assert len(side['recurrence']) == 3
    assert max(side['recurrence'].values()) < 1e-5, side['recurrence']
    wrong = R.reference_side(graph, params, ids, tiny['probs'],
                             R.PROBE[fault])
    assert min(wrong['recurrence'].values()) > R.RECURRENCE_TOLERANCE, \
        wrong['recurrence']
    assert not R.judge(graph, wrong, step[2])[1]


def test_no_gradient_through_the_delta_rule_leaves_the_limits(tiny, step):
    """A reference whose delta rule passes no gradient back: the delta
    layer's leaves before the rule move otherwise than it says."""
    graph, ids = tiny['graph'], tiny['ids']
    params, _, found = step[:3]
    side = R.reference_side(graph, params, ids, tiny['probs'],
                            R.PROBE['no gradient through the delta rule'])
    numbers, ok = R.judge(graph, side, found)
    assert not ok and numbers['loss'] < 1e-5, numbers
    last = R.kda_tail(graph)[0].primary
    for field in ('wq', 'wk', 'wv', 'conv_k', 'wa_up', 'a_log', 'wbeta'):
        assert numbers['kda_update'][f'{last}.{field}'] \
            > R.KDA_UPDATE_TOLERANCE, (field, numbers['kda_update'])
    assert numbers['kda_update'][f'{last}.wo'] < 1e-3


@pytest.mark.parametrize('fault', sorted(
    k for k, v in R.PROBE.items() if v.loss_tokens != 'all'))
def test_a_fault_in_the_steps_loss_leaves_the_limits(tiny, step, fault):
    """A reference whose loss drops or masks tokens comes out not correct
    against the same step."""
    graph, ids = tiny['graph'], tiny['ids']
    params, _, found = step[:3]
    side = R.reference_side(graph, params, ids, tiny['probs'],
                            R.PROBE[fault])
    found, ok = R.judge(graph, side, found)
    assert not ok and found['loss'] > R.STEP_LOSS_TOLERANCE, found
    assert max(found['update'].values()) > R.UPDATE_TOLERANCE, found


def test_training_through_the_step_loop_learns_and_counts(step):
    """Twelve more steps of ``update_staged`` on a ring of four: the loss
    falls, every step counts the expert layers' shares and the delta
    layers' worst chunk, and the compiled step carries each layer's scope
    in both passes."""
    rows, text = step[3], step[4]
    assert len(rows) == 12
    assert rows[-1]['loss'] < rows[0]['loss'] - 0.3, rows
    for r in rows:
        assert 0.0 <= r['moe.local_assignment_share'] <= 1.0
        assert r['kda.chunk_log_decay_min'] < 0.0
    for name in ('l02_gqa_attn0', 'l04_kda_kda1', 'l08_kda_kda3'):
        assert f'jvp({name})' in text and f'transpose(jvp({name}))' in text


@pytest.mark.parametrize('fault', sorted(
    k for k, v in R.PROBE.items()
    if v.loss_tokens == 'all' and v.recurrence_gradient))
def test_a_fault_in_the_model_shows_in_the_probabilities(tiny, fault):
    """Each of the probe's faults of the model moves the reference's
    log-probabilities at the tiny size by more than the program differs
    from the model (``selftest.solar`` holds them against the limits)."""
    want = np.log(tiny['want']['logits'])
    wrong = np.log(R.forward(tiny['graph'], tiny['params'],
                             tiny['batch'].data,
                             variant=R.PROBE[fault])['logits'])
    exact = np.abs(np.log(tiny['probs']['logits']) - want).max()
    assert np.abs(wrong - want).max() > 10 * max(exact, 1e-6), fault


# --- the trainer's part ------------------------------------------------------

def _step_text(pairs):
    tr = NetTrainer(pairs)
    tr.init_model()
    rng = np.random.RandomState(0)
    data, label, extra, mask = tr.stage_batch(DataBatch(
        rng.randint(0, 96, (2, 1, 1, SEQ + 1)).astype(np.float32),
        rng.randint(0, 96, (2, SEQ)).astype(np.float32)))[:4]
    return tr._train_step_fn._jit.lower(
        tr.params, tr.opt_state, tr.grad_acc, data, label, extra, mask,
        jax.random.fold_in(tr._rng, 1), tr.epoch_counter, tr.round,
        do_update=True, norm=()).as_text()


def test_the_head_share_keys_are_metadata():
    """The tiny twin's step is the same program without ``nhead_published``
    and ``head_first``, and with another share of the same size."""
    pairs = _pairs(TINY, seed=5, silent=1)
    bare = [(k, v) for k, v in pairs
            if k not in ('nhead_published', 'head_first')]
    assert len(bare) == len(pairs) - 2
    text = _step_text(pairs)
    assert _step_text(bare) == text
    assert _step_text(pairs + [('head_first', '8')]) == text


def test_published_conf_counts_its_parameters():
    """The example conf's leaves, by shape alone, to the unit: three delta
    layers of 35.2 M, the softmax layer's 27.3 M, four expert layers of
    142.9 M, an eighth of the vocabulary twice."""
    tr = NetTrainer(_pairs(BIG) + [('dev', 'cpu')])
    tr.init_net()
    shapes = jax.eval_shape(tr.net.init_params, jax.random.PRNGKey(0))
    count = lambda d: sum(int(np.prod(a.shape)) for a in d.values())  # noqa
    graph = R.build_graph(_pairs(BIG))
    by_type = {}
    for k in sorted(shapes, key=int):
        by_type.setdefault(graph.layers[int(k)].type, []).append(
            count(shapes[k]))
    d, w = 4096, 16 * 128
    assert by_type['kda'] == [35_225_744] * 3
    assert by_type['kda'][0] == kda_costs.parameters(graph,
                                                     graph.of_type('kda')[0])
    assert by_type['gqa'] == [d + 3 * d * w + 2 * d * 2 * 128] \
        == [27_267_072]
    expert = 3 * d * 1280
    assert by_type['moe'] == [d + d * 320 + 320 + 9 * expert] * 4 \
        == [142_872_896] * 4
    assert by_type['embedding'] == by_type['lm_head_loss'] == [24576 * d]
    total = sum(count(v) for v in shapes.values())
    assert total == 905_766_576
    assert 13.4 < total * 16 / 2 ** 30 < 13.5               # GiB in a step


def test_train_flops_by_hand():
    """16.48 TFLOP a step of one 8,192-token sequence, by hand: the delta
    rule's chunkwise products at chunks of 64 are 10.7 G a layer, its
    products 288 G."""
    graph = R.build_graph(_pairs(BIG))
    s, d, hd = 8192, 4096, 128
    w = 16 * hd
    c = 64
    rec = (s // c) * 16 * (2 * c * c * hd + 2 * c * c * hd + 3 * c * hd * hd
                           + c * c * hd)
    kda = s * (3 * d * w + 2 * (d * hd + hd * w) + d * 16 + w * d) + rec
    gqa = s * (3 * d * w + 2 * d * 2 * hd) + s * (s + 1) // 2 * 16 * 2 * hd
    moe = s * (d * 320 + 3 * d * 1280) + s * 8 * 8 / 320 * 3 * d * 1280
    hand = 3 * kda + gqa + 4 * moe + s * d * 24576
    assert sum(R.forward_macs(graph).values()) == hand
    assert abs(R.train_flops_per_sequence(graph) - 16.48e12) < 0.01e12
