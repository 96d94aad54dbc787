"""The cxxnet ``.conf`` grammar, read by the benchmark itself: the pairs of a
file, the layer graph of its ``netconfig`` section, the shape of every node,
and the operations one training step of that graph requires.

Independent of ``cxxnet_tpu`` on purpose: the plain reference
(``references/confnet.py``) and the MFU numerator both stand on this file,
and neither may move when the program does.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

Pairs = List[Tuple[str, str]]


def parse_conf(text: str) -> Pairs:
    """``name = value`` pairs in file order; ``#`` comments; ``"..."`` and
    ``'...'`` quote a value."""
    toks = re.findall(r'''"((?:\\.|[^"\\])*)"|'((?:\\.|[^'\\])*)'|(=)|'''
                      r'''#[^\n]*|([^\s=#"']+)''', text)
    flat = []
    for dq, sq, eq, bare in toks:
        if eq:
            flat.append('=')
        elif bare:
            flat.append(bare)
        elif dq or sq:
            flat.append(re.sub(r'\\(.)', r'\1', dq or sq))
    # a comment matches the pattern with every group empty and is dropped
    if len(flat) % 3:
        raise ValueError(f'conf: dangling token {flat[-1]!r}')
    out: Pairs = []
    for i in range(0, len(flat), 3):
        name, eq, val = flat[i:i + 3]
        if name == '=' or eq != '=' or val == '=':
            raise ValueError(f'conf: expected name = value near {name!r}')
        out.append((name, val))
    return out


def drop_sections(pairs: Pairs, kinds: Tuple[str, ...]) -> Pairs:
    """The pairs without the iterator sections (``data = ...`` /
    ``eval = ...`` / ``pred = ...`` up to ``iter = end``) named in
    ``kinds``."""
    out, skipping = [], False
    for name, val in pairs:
        if name in kinds:
            skipping = True
        if not skipping:
            out.append((name, val))
        if skipping and (name, val) == ('iter', 'end'):
            skipping = False
    return out


@dataclasses.dataclass
class Layer:
    index: int            # position in the conf = key of its parameters
    type: str
    name: str
    ins: List[str]
    outs: List[str]
    cfg: Dict[str, str]   # global defaults overlaid with the layer's pairs

    def geti(self, key: str, default: int = 0) -> int:
        return int(self.cfg.get(key, default))

    def getf(self, key: str, default: float) -> float:
        return float(self.cfg.get(key, default))

    def kernel(self) -> Tuple[int, int]:
        k = self.geti('kernel_size')
        return (self.geti('kernel_height', k), self.geti('kernel_width', k))

    def pad(self) -> Tuple[int, int]:
        p = self.geti('pad')
        return (self.geti('pad_y', p), self.geti('pad_x', p))


@dataclasses.dataclass
class Graph:
    layers: List[Layer]
    input_shape: Tuple[int, int, int]          # c, y, x
    shapes: Dict[str, Tuple[int, ...]]         # node -> (c, y, x) or (n,)

    def loss_nodes(self) -> List[str]:
        """Output node of every loss layer, in conf order."""
        return [l.outs[0] for l in self.layers if l.type in LOSS_TYPES]

    @property
    def num_classes(self) -> int:
        """Width of the last loss node."""
        return self.shapes[self.loss_nodes()[-1]][0]


LOSS_TYPES = ('softmax', 'l2_loss', 'multi_logistic')
_SAME_SHAPE = ('relu', 'sigmoid', 'tanh', 'softplus', 'dropout', 'lrn',
               'batch_norm', 'xelu', 'prelu', 'insanity', 'bias') + LOSS_TYPES


def pool_out(n: int, k: int, stride: int) -> int:
    """cxxnet's pooling size: the last window may hang over the edge."""
    return min(n - k + stride - 1, n - 1) // stride + 1


def build_graph(pairs: Pairs) -> Graph:
    """The layer graph of a conf, with every node's shape inferred."""
    glob: Dict[str, str] = {}
    layers: List[Layer] = []
    mode, top = 0, '0'          # mode 2: pairs belong to the last layer
    ids = {'0': 0}              # node -> index, in order of first output
    input_shape = None
    own: List[Dict[str, str]] = []
    for name, val in pairs:
        if name == 'input_shape':
            input_shape = tuple(int(t) for t in val.split(','))
        if name == 'netconfig':
            mode = 1 if val == 'start' else 0
            continue
        if name.startswith('layer['):
            spec = name[len('layer['):-1]
            ltype, _, lname = val.partition(':')
            if '->' in spec:
                a, b = spec.split('->')
                ins = ['0' if t == 'in' else t for t in a.split(',')]
                outs = b.split(',')
            elif spec.startswith('+'):
                inc, _, tag = spec[1:].partition(':')
                ins = [top]
                # the unnamed node's name is the program's own, so that a
                # loss node can be asked for by it
                outs = [top if int(inc) == 0 else
                        (tag or f'!node-after-{ids[top]}')]
            else:
                raise ValueError(f'conf: bad layer spec {name!r}')
            if ltype.startswith('share'):
                raise NotImplementedError('conf: shared layers')
            for node in outs:
                ids.setdefault(node, len(ids))
            layers.append(Layer(len(layers), ltype, lname, ins, outs, {}))
            own.append({})
            top = outs[0] if len(outs) == 1 else top
            mode = 2
            continue
        if mode == 2:
            own[-1][name] = val
        else:
            glob[name] = val
    if input_shape is None:
        raise ValueError('conf: no input_shape')
    for layer, mine in zip(layers, own):
        layer.cfg = {**glob, **mine}
    shapes: Dict[str, Tuple[int, ...]] = {'0': input_shape}
    for l in layers:
        ins = [shapes[n] for n in l.ins]
        for node, shape in zip(l.outs, _out_shapes(l, ins)):
            shapes[node] = shape
    return Graph(layers, input_shape, shapes)


def _flat(shape: Tuple[int, ...]) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def _out_shapes(l: Layer, ins) -> List[Tuple[int, ...]]:
    s = ins[0]
    if l.type == 'conv':
        (kh, kw), (py, px), st = l.kernel(), l.pad(), l.geti('stride', 1)
        return [(l.geti('nchannel'), (s[1] + 2 * py - kh) // st + 1,
                 (s[2] + 2 * px - kw) // st + 1)]
    if l.type in ('max_pooling', 'avg_pooling', 'sum_pooling'):
        (kh, kw), (py, px), st = l.kernel(), l.pad(), l.geti('stride', 1)
        return [(s[0], pool_out(s[1] + 2 * py, kh, st),
                 pool_out(s[2] + 2 * px, kw, st))]
    if l.type == 'fullc':
        return [(l.geti('nhidden'),)]
    if l.type == 'flatten':
        return [(_flat(s),)]
    if l.type == 'ch_concat':
        return [(sum(i[0] for i in ins),) + tuple(s[1:])]
    if l.type == 'concat':
        return [tuple(s[:-1]) + (sum(i[-1] for i in ins),)]
    if l.type == 'split':
        return [s] * len(l.outs)
    if l.type in _SAME_SHAPE:
        return [s]
    raise NotImplementedError(f'conf: no shape rule for layer {l.type!r}')


def forward_macs(graph: Graph) -> Dict[int, int]:
    """Multiply-accumulates of one sample's forward pass, per conv and
    fullc layer (keyed by layer index).  Everything else in these nets is
    under 1% of the arithmetic and is left out, as MFU conventions do."""
    out = {}
    for l in graph.layers:
        if l.type == 'conv':
            kh, kw = l.kernel()
            cin = graph.shapes[l.ins[0]][0] // l.geti('ngroup', 1)
            c, y, x = graph.shapes[l.outs[0]]
            out[l.index] = c * y * x * cin * kh * kw
        elif l.type == 'fullc':
            out[l.index] = _flat(graph.shapes[l.ins[0]]) * l.geti('nhidden')
    return out


def train_flops_per_sample(graph: Graph) -> int:
    """Floating-point operations one training step needs per sample:
    forward, gradient to the weights and gradient to the input, two
    operations a multiply-accumulate.  A layer fed by the input node has no
    input gradient to compute.  Recomputation is not counted."""
    total = 0
    macs = forward_macs(graph)
    for l in graph.layers:
        if l.index in macs:
            passes = 2 if l.ins == ['0'] else 3
            total += 2 * passes * macs[l.index]
    return total
