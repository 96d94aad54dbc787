"""The lowered text of the step programs, ``loc(...)`` stripped, hashes to
what it is known to be, for a small conv net, AlexNet at batch 8 and
GoogLeNet at batch 4.

At ``update_period = 1`` the hashes were taken anew at PR 32, whose step
program is the parent's (02213d2, itself equal to 128de63's, from before the
sequence layers came) less the accumulator: its parameters, one add a leaf
and one zero-fill a leaf, and nothing else once the SSA values are
renumbered.  At ``update_period = 2`` the accumulator stays, and the
per-step programs (applying and not) and the scanned window hash to what
the parent's tree gave: PR 32 changed nothing there.  All were taken under
this JAX; another JAX lowers to other text, and then there is nothing to
compare with.

The language-model step programs of ``example/LM/tiny-glm.conf`` and
``tiny-laguna.conf`` at batch 2 hash to what the tree before the ``kda``
layer gave (8ec148d): the ``gqa`` keys added with it (``use_rope``,
``gate``, the head share) leave both as they were.  ``tiny-solar.conf``'s
hashes to what the tree before the ``mla`` keys ``q_lora_rank = 0`` and
``use_rope``, ``kda``'s ``allow_neg_eigval`` and the block-sparse kernel at
unequal head sizes gave (a68a39e): those leave GLM's, Laguna's and Solar's
programs as they were."""

import hashlib
import os
import re

import jax
import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import parse_config_file, parse_config_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TAKEN_WITH_JAX = '0.9.0'

SMALL = '''
netconfig=start
layer[0->1] = conv:c1
  kernel_size = 3
  nchannel = 8
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
netconfig=end
input_shape = 1,8,8
batch_size = 8
dev = cpu
eta = 0.1
metric = error
'''


def _example(name):
    return parse_config_file(os.path.join(ROOT, 'example', 'ImageNet', name))


CASES = {
    'small': (lambda: parse_config_string(SMALL), (1, 8, 8), 8, 4,
              'fe207c0d460a3c7d813d744bb3329b380e4b527bdec67668c5c139cab0029117'),
    'alexnet-b8': (lambda: _example('ImageNet.conf'), (3, 227, 227), 8, 1000,
                   '81cab4a0d575d44b03c2d2d44850e22cbec29c692b08af39e5708f8889528b8e'),
    'googlenet-b4': (lambda: _example('GoogLeNet.conf'), (3, 224, 224), 4,
                     1000,
                     'ed033090a450ac946ea2313a3155f75bd7489611e13459347814d587e4f0ad04'),
}


def _without_iterators(pairs):
    out, skipping = [], False
    for k, v in pairs:
        if k in ('data', 'eval', 'pred'):
            skipping = True
        if not skipping:
            out.append((k, v))
        if skipping and (k, v) == ('iter', 'end'):
            skipping = False
    return out


def _strip_names(mlir: str) -> str:
    mlir = re.sub(r'\s*loc\((?:[^()]|\([^()]*\))*\)', '', mlir)
    return '\n'.join(l for l in mlir.splitlines()
                     if not l.startswith('#loc'))


def _trainer(pairs, batch, extra=()):
    tr = NetTrainer(_without_iterators(pairs) + [
        ('batch_size', str(batch)), ('dev', 'cpu'), ('seed', '1'),
        *extra])
    tr.init_model()
    return tr


def _staged(tr, batch, shape, classes):
    rng = np.random.RandomState(0)
    return tr.stage_batch(DataBatch(
        rng.rand(batch, *shape).astype(np.float32),
        rng.randint(0, classes, (batch, 1)).astype(np.float32)))


def _step_hash(tr, staged, do_update=True):
    data, label, extra, mask = staged[:4]
    lowered = tr._train_step_fn._jit.lower(
        tr.params, tr.opt_state, tr.grad_acc, data, label, extra, mask,
        jax.random.fold_in(tr._rng, 1), tr.epoch_counter, tr.round,
        do_update=do_update, norm=())
    return hashlib.sha256(_strip_names(
        lowered.as_text(debug_info=True)).encode()).hexdigest()


@pytest.mark.parametrize('case', sorted(CASES))
def test_cnn_step_program_is_the_parents(case):
    if jax.__version__ != TAKEN_WITH_JAX:
        pytest.skip(f'hashes taken with jax {TAKEN_WITH_JAX}')
    pairs, shape, batch, classes, want = CASES[case]
    tr = _trainer(pairs(), batch)
    assert not tr.net.takes_token_ids and tr.grad_acc is None
    assert _step_hash(tr, _staged(tr, batch, shape, classes)) == want


# the parent's (02213d2) programs of the small net at update_period = 2
PERIOD_2 = {
    'step-applies':
        '3643ff485938f2a6b9f91ddf7c83cf40f6ea0a4b9490ead4e04626fff5a5a4fd',
    'step-accumulates':
        '6ab502bfc8c7c9970d561792ef9c06371dc4b4a0c55a6cf5a07b955db99f29c4',
    'scan-of-3':
        '0ed15ea08a02450b9dc7e410b7302f55fea33fbdef442ff3bccd63f90172a98c',
}


@pytest.mark.parametrize('program', sorted(PERIOD_2))
def test_period_2_programs_are_the_parents(program):
    if jax.__version__ != TAKEN_WITH_JAX:
        pytest.skip(f'hashes taken with jax {TAKEN_WITH_JAX}')
    tr = _trainer(parse_config_string(SMALL), 8,
                  [('update_period', '2')])
    if program == 'scan-of-3':
        fn = tr.compile_multi_step(3)
        staged = [_staged(tr, 8, (1, 8, 8), 4) for _ in range(2)]
        stack = lambda i: tr._device_stack(  # noqa: E731
            [s[i] for s in staged])
        got = hashlib.sha256(_strip_names(jax.jit(fn).lower(
            tr.params, tr.opt_state, tr.grad_acc, stack(0), stack(1),
            tr._rng, tr.epoch_counter, 0, stack(3),
            tr.round).as_text(debug_info=True)).encode()).hexdigest()
    else:
        got = _step_hash(tr, _staged(tr, 8, (1, 8, 8), 4),
                         do_update=program == 'step-applies')
    assert got == PERIOD_2[program]


# 8ec148d's tree (a68a39e's for the Solar twin): the sequence confs' step
# programs at batch 2, one token id a position and the next token's label
# (two heads for the GLM twin's multi-token-prediction module), over each
# conf's positions
LM_CASES = {
    'tiny-glm': (2, 64, '18ae84738b5228948b47da0ccf8892f55a52074e8dee9af4f416d5f8b31c5438'),
    'tiny-laguna': (1, 64, '296d12655f89cc62831dcae83eea45c333e13a37bd4850aab3e82e582aecdae6'),
    'tiny-solar': (1, 96, '76bcb57c3bde6984f5acbb9fe6d4db1ade241b33e4c5068471b95c774c412ad7'),
}


@pytest.mark.parametrize('case', sorted(LM_CASES))
def test_lm_step_program_is_the_parents(case):
    if jax.__version__ != TAKEN_WITH_JAX:
        pytest.skip(f'hashes taken with jax {TAKEN_WITH_JAX}')
    heads, seq, want = LM_CASES[case]
    tr = _trainer(parse_config_file(os.path.join(ROOT, 'example', 'LM',
                                                 case + '.conf')), 2)
    assert tr.net.takes_token_ids
    rng = np.random.RandomState(0)
    staged = tr.stage_batch(DataBatch(
        rng.randint(0, 96, (2, 1, 1, seq + 1)).astype(np.float32),
        rng.randint(0, 96, (2, seq * heads)).astype(np.float32)))
    assert _step_hash(tr, staged) == want

