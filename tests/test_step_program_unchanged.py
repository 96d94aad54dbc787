"""A conf without sequence layers lowers to the step program it lowered to
before the sequence layers came (PR 29): the lowered text of
``update_staged``'s program, ``loc(...)`` stripped, hashes to what commit
128de63 gave for a small conv net, AlexNet at batch 8 and GoogLeNet at batch
4.  The hashes were taken with that commit's tree under this JAX; another JAX
lowers to other text, and then there is nothing to compare with."""

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
              '890326187b729c0f37bb278440091421094133857db97fe66a0c329d103f648a'),
    'alexnet-b8': (lambda: _example('ImageNet.conf'), (3, 227, 227), 8, 1000,
                   'f9ecdfe391eafed0b9f370798321faf0b3270699f7b4f161c079e4090e64d522'),
    'googlenet-b4': (lambda: _example('GoogLeNet.conf'), (3, 224, 224), 4,
                     1000,
                     '629a72bb07b0ad7e1e07ab46b57f662601c1662c05a822a1e701cd3bef012504'),
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


@pytest.mark.parametrize('case', sorted(CASES))
def test_cnn_step_program_is_the_parents(case):
    if jax.__version__ != TAKEN_WITH_JAX:
        pytest.skip(f'hashes taken with jax {TAKEN_WITH_JAX}')
    pairs, shape, batch, classes, want = CASES[case]
    tr = NetTrainer(_without_iterators(pairs()) + [
        ('batch_size', str(batch)), ('dev', 'cpu'), ('seed', '1')])
    tr.init_model()
    assert not tr.net.takes_token_ids
    rng = np.random.RandomState(0)
    data, label, extra, mask = tr.stage_batch(DataBatch(
        rng.rand(batch, *shape).astype(np.float32),
        rng.randint(0, classes, (batch, 1)).astype(np.float32)))[:4]
    lowered = tr._train_step_fn._jit.lower(
        tr.params, tr.opt_state, tr.grad_acc, data, label, extra, mask,
        jax.random.fold_in(tr._rng, 1), tr.epoch_counter, tr.round,
        do_update=True, norm=())
    got = hashlib.sha256(_strip_names(
        lowered.as_text(debug_info=True)).encode()).hexdigest()
    assert got == want
