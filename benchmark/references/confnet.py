"""Plain reference for a cxxnet ``netconfig``: the evaluation-mode forward
pass, layer by layer, in straightforward ``jax.numpy``/``lax``.

Float32 throughout under ``jax.default_matmul_precision("highest")``, the
reference's own NCHW layout, no kernels, no fusion, no batching tricks, and
nothing imported from ``cxxnet_tpu``: the layer equations are the cxxnet
reference's (``src/layer/*-inl.hpp``), written down again here.  The program
hands over its parameters (``{layer index: {'wmat', 'bias'}}``, conv weights
HWIO, fullc weights ``(nin, nhidden)``) and nothing else.

A later configuration with a layer type that is missing here adds a module
beside this one that imports ``OPS``, adds its rule and re-exports
``forward``; this file is not edited.
"""

from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import confnet
from ..confnet import Graph, Layer, pool_out

Op = Callable[[Layer, List[jax.Array], Dict[str, jax.Array]], List[jax.Array]]


def _conv(l, ins, p):
    x = ins[0]
    (py, px), st = l.pad(), l.geti('stride', 1)
    w = jnp.transpose(p['wmat'], (3, 2, 0, 1))         # HWIO -> OIHW
    y = lax.conv_general_dilated(
        x, w, (st, st), ((py, py), (px, px)),
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'),
        feature_group_count=l.geti('ngroup', 1))
    if 'bias' in p:
        y = y + p['bias'][None, :, None, None]
    return [y]


def _pool(mode):
    def op(l, ins, p):
        x = ins[0]
        (kh, kw), (py, px), st = l.kernel(), l.pad(), l.geti('stride', 1)
        fill = -jnp.inf if mode == 'max' else 0.0
        h, w = x.shape[2] + 2 * py, x.shape[3] + 2 * px
        oy, ox = pool_out(h, kh, st), pool_out(w, kw, st)
        # the last window may hang over the edge: it sees only what is there
        hang_y = max((oy - 1) * st + kh - h, 0)
        hang_x = max((ox - 1) * st + kw - w, 0)
        x = jnp.pad(x, ((0, 0), (0, 0), (py, py + hang_y), (px, px + hang_x)),
                    constant_values=fill)
        y = lax.reduce_window(x, fill, lax.max if mode == 'max' else lax.add,
                              (1, 1, kh, kw), (1, 1, st, st), 'VALID')
        # cxxnet's average divides by the whole window, clipped or not
        return [y / (kh * kw) if mode == 'avg' else y]
    return op


def _lrn(l, ins, p):
    x = ins[0]
    n = l.geti('local_size', 3)
    alpha, beta = l.getf('alpha', 0.001), l.getf('beta', 0.75)
    lo = (n - 1) // 2
    sq = jnp.pad(x * x, ((0, 0), (lo, n - 1 - lo), (0, 0), (0, 0)))
    c = x.shape[1]
    window = sum(sq[:, k:k + c] for k in range(n))
    return [x * (l.getf('knorm', 1.0) + alpha / n * window) ** -beta]


def _fullc(l, ins, p):
    y = ins[0].reshape(ins[0].shape[0], -1) @ p['wmat']
    return [y + p['bias'] if 'bias' in p else y]


def _batch_norm(l, ins, p):
    # cxxnet keeps no running averages: evaluation, too, normalises with
    # the statistics of the batch in hand
    x = ins[0]
    axes = (0,) if x.ndim == 2 else (0, 2, 3)
    shape = (1, -1) if x.ndim == 2 else (1, -1, 1, 1)
    mean = x.mean(axes, keepdims=True)
    var = ((x - mean) ** 2).mean(axes, keepdims=True)
    xhat = (x - mean) / jnp.sqrt(var + l.getf('eps', 1e-10))
    return [xhat * p['wmat'].reshape(shape) + p['bias'].reshape(shape)]


def _softmax(l, ins, p):
    return [jax.nn.softmax(ins[0].reshape(ins[0].shape[0], -1), axis=-1)]


OPS: Dict[str, Op] = {
    'conv': _conv,
    'max_pooling': _pool('max'),
    'avg_pooling': _pool('avg'),
    'sum_pooling': _pool('sum'),
    'lrn': _lrn,
    'fullc': _fullc,
    'batch_norm': _batch_norm,
    'softmax': _softmax,
    'relu': lambda l, ins, p: [jnp.maximum(ins[0], 0.0)],
    'sigmoid': lambda l, ins, p: [jax.nn.sigmoid(ins[0])],
    'tanh': lambda l, ins, p: [jnp.tanh(ins[0])],
    'flatten': lambda l, ins, p: [ins[0].reshape(ins[0].shape[0], -1)],
    'dropout': lambda l, ins, p: [ins[0]],           # off in evaluation
    'split': lambda l, ins, p: [ins[0]] * len(l.outs),
    'ch_concat': lambda l, ins, p: [jnp.concatenate(ins, axis=1)],
    'concat': lambda l, ins, p: [jnp.concatenate(ins, axis=-1)],
}


def forward(graph: Graph, params, data, ops: Dict[str, Op] = OPS,
            skip=()) -> Dict[str, np.ndarray]:
    """Every loss node's value for ``data`` (NCHW float32), on the host.

    ``skip`` names layer types to leave out (identity) — only the self-test
    and the sensitivity probe use it, to show that the tolerance of the
    comparison notices a dropped layer."""
    params = {int(k): {f: jnp.asarray(v, jnp.float32) for f, v in d.items()}
              for k, d in params.items()}

    def run(params, x):
        values = {'0': x}
        for l in graph.layers:
            ins = [values[n] for n in l.ins]
            if l.type in skip:
                outs = [ins[0]] * len(l.outs)
            elif l.type not in ops:
                raise NotImplementedError(
                    f'plain reference has no rule for layer {l.type!r}')
            else:
                outs = ops[l.type](l, ins, params.get(l.index, {}))
            values.update(zip(l.outs, outs))
        return {n: values[n] for n in graph.loss_nodes()}

    with jax.default_matmul_precision('highest'):
        out = jax.jit(run)(params, jnp.asarray(data, jnp.float32))
    return {n: np.asarray(v) for n, v in out.items()}


def train_flops_per_step(feed) -> float:
    """The MFU numerator: what one step's forward and backward passes
    require, counted from the conf's shapes (``confnet``), never from the
    compiled program."""
    return float(confnet.train_flops_per_sample(feed.graph)
                 * feed.samples_per_step)


# --- the comparison that decides ``correct`` --------------------------------

#: The program computes in bfloat16 (8 bits of mantissa: about 0.4% an
#: operation, a few percent of a logit by the last layer) and the reference
#: in float32.  Log-probabilities are compared, not classes: with near-random
#: weights the largest logit changes on rounding.  The error is the largest
#: difference over the batch and the classes, divided by the spread (standard
#: deviation) of the reference's log-probabilities.  On the chip the cells
#: read 0.017-0.033 after a hundred steps and up to 0.09 after a dozen, when
#: the output is still nearly flat and its spread small; the reference with
#: every LRN layer left out reads 8.3, with the biases zeroed 12.9 (PERF.md,
#: Findings).  0.25 keeps the two sides well apart.
TOLERANCE = 0.25
_LOG_FLOOR = -60.0      # below float32 softmax's reach on either side


def log_prob_error(program: np.ndarray, reference: np.ndarray) -> float:
    zp = np.maximum(np.log(np.maximum(program, 1e-38)), _LOG_FLOOR)
    zr = np.maximum(np.log(np.maximum(reference, 1e-38)), _LOG_FLOOR)
    return float(np.max(np.abs(zp - zr)) / max(float(np.std(zr)), 1e-6))


def check_batch(feed, cell, seed: int) -> np.ndarray:
    """A seeded float32 batch of the staged traffic's kind of picture, none
    of which a ring holds."""
    from .. import synth
    (data, _), = synth.learnable_batches(
        seed + 7919, 1, int(cell.t('check_batch')), feed.graph.input_shape,
        feed.graph.num_classes, jnp.float32, cell.t('data'),
        jax.devices()[:1])
    return data


def compare(feed, cell, seed: int) -> dict:
    """The program's evaluation-mode output of every loss node (its own
    forward step, the parameters as they stand) against this reference."""
    from .. import cxx
    graph: Graph = feed.graph
    nodes = graph.loss_nodes()
    data = check_batch(feed, cell, seed)
    got = cxx.eval_outputs(feed.trainer, data, nodes)
    want = forward(graph, cxx.host_params(feed.trainer), data)
    errors = {n: log_prob_error(got[n], want[n]) for n in nodes}
    return {'errors': errors, 'tolerance': TOLERANCE,
            'ok': all(np.isfinite(e) and e <= TOLERANCE
                      for e in errors.values())}
