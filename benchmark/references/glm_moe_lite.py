"""Plain reference for a ``glm4_moe_lite`` conf (GLM-4.7-Flash and its tiny
twins): multi-head latent attention, a dense SwiGLU layer, expert layers with
a sigmoid top-k router over experts of which the chip holds a share, a shared
expert, a multi-token-prediction module, and a cross-entropy a token on two
heads that share embedding and output head, and the update Adam makes of
the step's gradient.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no recomputation, no
grouping (the held experts are a loop, each over every token, masked by the
routing), attention as a full masked softmax, in blocks of queries only where
the sequence is long.  Nothing is imported from ``cxxnet_tpu``: the layer
equations are the published ones (``config.json`` of the model, the
DeepSeek-V3 report's 2.2 for the multi-token-prediction module), written down
again here; the program hands over its parameters and nothing else.  It is
given ``experts_held`` / ``expert_first`` / the vocabulary slice by the conf,
as the program is, so the chip's share and the whole layer are one code.

Like ``references/confnet.py`` it exports ``forward``, ``compare`` and
``train_flops_per_step``, and it adds its layer rules to that file's ``OPS``
table.  ``confnet.build_graph`` knows neither a sequence node nor
``share[tag]``, so the graph of such a conf is built here (``build_graph``).

Departures from the published model, each also under ``assumed`` in the
configuration's file: the router's correction bias is whatever the program's
leaf holds (zero); rotary pairs are half-split (``x[i]`` with ``x[i +
dim/2]``); the multi-token-prediction joint takes ``[embedding ; hidden]`` in
that order.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import confnet
from .confnet import OPS as CONFNET_OPS

F32 = jnp.float32


# --- the graph of a sequence conf ------------------------------------------

class Layer:
    """One conf layer: its pairs are the global ones overlaid by its own;
    ``primary`` is the index of the layer whose parameters it uses (itself,
    or the layer a ``share[tag]`` names).  (Plain classes, not dataclasses:
    ``harness.load_module`` runs this file without entering it in
    ``sys.modules``, where a dataclass looks its annotations up.)"""

    def __init__(self, index: int, type: str, name: str, ins: List[str],
                 outs: List[str], cfg: Dict[str, str], primary: int):
        self.index, self.type, self.name = index, type, name
        self.ins, self.outs, self.cfg, self.primary = ins, outs, cfg, primary

    def geti(self, key: str, default: int = 0) -> int:
        return int(self.cfg.get(key, default))

    def getf(self, key: str, default: float) -> float:
        return float(self.cfg.get(key, default))


class Head(NamedTuple):
    layer: Layer
    k: int                  # which of the layer's inputs
    hidden: str             # the node it reads
    node: str               # the node its probabilities go to
    weight: float           # in the step's loss
    label_first: int        # its first column of the label matrix


class Graph:
    def __init__(self, layers: List[Layer], input_width: int,
                 labels: Dict[str, Tuple[int, int]]):
        self.layers = layers
        self.input_width = input_width          # ids a staged row
        self.labels = labels                    # label field -> its columns

    def heads(self) -> List['Head']:
        """Every head of every ``lm_head_loss`` layer: input ``k`` of the
        layer is scored against columns ``[k * seq, (k + 1) * seq)`` of its
        ``target`` label field and weighs ``head_weight[k]`` (times the
        layer's ``grad_scale``) in the step's loss."""
        out = []
        for l in self.of_type('lm_head_loss'):
            weights = [float(t) for t in l.cfg.get(
                'head_weight', ','.join(['1'] * len(l.ins))).split(',')]
            first = self.labels[l.cfg.get('target', 'label')][0]
            out += [Head(l, k, l.ins[k], l.outs[k],
                         l.getf('grad_scale', 1.0) * weights[k],
                         first + k * self.seq)
                    for k in range(len(l.ins))]
        return out

    def loss_nodes(self) -> List[str]:
        return [h.node for h in self.heads()]

    def of_type(self, kind: str) -> List[Layer]:
        return [l for l in self.layers if l.type == kind]

    @property
    def seq(self) -> int:
        return self.of_type('seq_slice')[0].geti('seq_len')

    @property
    def vocab(self) -> int:
        return self.of_type('embedding')[0].geti('vocab_held')

    @property
    def width(self) -> int:
        return self.of_type('embedding')[0].geti('nhidden')


def build_graph(pairs: confnet.Pairs) -> Graph:
    """The layer graph of a conf: layers in conf order, each with the
    global pairs overlaid by its own, ``share[tag]`` resolved to the layer
    it names (whose pairs and parameters it takes)."""
    glob: Dict[str, str] = {}
    rows, own, by_name = [], [], {}
    labels: Dict[str, Tuple[int, int]] = {}
    input_shape, inside = None, False
    for name, val in pairs:
        if name == 'input_shape':
            input_shape = tuple(int(t) for t in val.split(','))
        if name.startswith('label_vec['):
            a, b = name[len('label_vec['):-1].split(',')
            labels[val] = (int(a), int(b))
        if name == 'netconfig':
            inside = False
            continue
        if name.startswith('layer['):
            spec = name[len('layer['):-1]
            if '->' not in spec:
                raise ValueError(f'conf: {name!r}: a sequence conf names '
                                 f'its nodes (layer[a->b])')
            a, b = spec.split('->')
            ltype, _, lname = val.partition(':')
            primary = len(rows)
            if ltype.startswith('share['):
                primary = by_name[ltype[len('share['):-1]]
                ltype = rows[primary][0]
            elif lname:
                by_name[lname] = len(rows)
            rows.append((ltype, lname, ['0' if t == 'in' else t
                                        for t in a.split(',')],
                         b.split(','), primary))
            own.append({})
            inside = True
            continue
        if inside:
            own[-1][name] = val
        else:
            glob[name] = val
    if input_shape is None:
        raise ValueError('conf: no input_shape')
    layers = [Layer(i, t, n, ins, outs, {**glob, **own[p]}, p)
              for i, (t, n, ins, outs, p) in enumerate(rows)]
    return Graph(layers, input_shape[0] * input_shape[1] * input_shape[2],
                 labels)


# --- the layer equations ----------------------------------------------------

class Variant(NamedTuple):
    """Switches that make the reference *wrong* on purpose: the sensitivity
    probe shows that each leaves the comparison's tolerance (PERF.md).  The
    default is the model."""
    shared_expert: bool = True
    rotary: bool = True
    scaling: Optional[float] = None     # None: the conf's
    top_k: Optional[int] = None         # None: the conf's
    matmul_dtype: Optional[str] = None  # round every product's operands
    loss_tokens: str = 'all'            # or 'all but the last eighth' (their
    #                                     share of the mean stays theirs),
    #                                     'every other' (the mean over them)

    def token_weights(self, s: int) -> np.ndarray:
        """What each of a sequence's ``s`` tokens weighs in its mean loss."""
        w = np.full(s, 1.0 / s, np.float32)
        if self.loss_tokens == 'all but the last eighth':
            w[s - s // 8:] = 0.0
        elif self.loss_tokens == 'every other':
            w[1::2] = 0.0
            w *= 2.0
        elif self.loss_tokens != 'all':
            raise ValueError(self.loss_tokens)
        return w

    def lower(self, x):
        if self.matmul_dtype is None:
            return x
        return x.astype(jnp.dtype(self.matmul_dtype)).astype(F32)


MODEL = Variant()
#: the probe: each of these must leave the comparison's limits (PERF.md,
#: Findings PR 29; ``selftest/glm.py`` at the tiny size, ``selftest/
#: glm_sensitivity.py`` on the chip)
PROBE = {
    'shared expert left out': Variant(shared_expert=False),
    'routed_scaling_factor 1': Variant(scaling=1.0),
    'rotary left out': Variant(rotary=False),
    'top-3 routing': Variant(top_k=3),
    'products in float8_e4m3': Variant(matmul_dtype='float8_e4m3fn'),
    # faults of the step alone (the log-probabilities stay the model's):
    # what a chunk dropped from the chunked loss, or a mask over half the
    # tokens, would make of the step's loss and of the head's gradient
    'an eighth of the tokens dropped from the loss':
        Variant(loss_tokens='all but the last eighth'),
    'the loss over every other token': Variant(loss_tokens='every other'),
}


def mm(v: Variant, a, b):
    return jnp.matmul(v.lower(a), v.lower(b))


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma


def rotate(x, theta):
    """``x``: (b, s, heads, dim); position = index along ``s``; the pair of
    component ``i`` is ``i + dim/2``."""
    s, dim = x.shape[1], x.shape[3]
    inv = theta ** (-np.arange(dim // 2, dtype=np.float64) * 2.0 / dim)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[None, :, None, :]
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


ATTENTION_BLOCK = 256        # queries a block, where the sequence is longer


def causal_attention(v: Variant, q, k, val, scale):
    """``q, k``: (b, s, h, dk), ``val``: (b, s, h, dv) -> (b, s, h, dv)."""
    s = q.shape[1]

    def block(qb, first):
        scores = jnp.einsum('bqhd,bkhd->bhqk', v.lower(qb), v.lower(k)) * scale
        rows = first + jnp.arange(qb.shape[1])[:, None]
        keep = jnp.arange(s)[None, :] <= rows
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return jnp.einsum('bhqk,bkhd->bqhd', v.lower(probs), v.lower(val))

    if s <= ATTENTION_BLOCK or s % ATTENTION_BLOCK:
        return block(q, 0)
    n = s // ATTENTION_BLOCK
    qs = jnp.moveaxis(q.reshape(q.shape[0], n, ATTENTION_BLOCK,
                                *q.shape[2:]), 1, 0)
    out = jax.lax.map(lambda a: block(a[0], a[1]),
                      (qs, jnp.arange(n) * ATTENTION_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape[0], s, q.shape[2],
                                           val.shape[3])


def mla(l: Layer, h, p, v: Variant = MODEL):
    b, s, _ = h.shape
    nh, eps = l.geti('nhead'), l.getf('eps', 1e-5)
    nope, rd = l.geti('qk_nope_head_dim'), l.geti('qk_rope_head_dim')
    vd, kvr = l.geti('v_head_dim'), l.geti('kv_lora_rank')
    x = rms_norm(h, p['norm'], eps)
    c_q = rms_norm(mm(v, x, p['wq_a']), p['q_norm'], eps)
    q = mm(v, c_q, p['wq_b']).reshape(b, s, nh, nope + rd)
    ckv = mm(v, x, p['wkv_a'])
    c_kv, k_r = ckv[..., :kvr], ckv[..., kvr:]
    kv = mm(v, rms_norm(c_kv, p['kv_norm'], eps),
            p['wkv_b']).reshape(b, s, nh, nope + vd)
    q_rope, k_rope = q[..., nope:], k_r[:, :, None, :]
    if v.rotary:
        theta = l.getf('rope_theta', 10000.0)
        q_rope, k_rope = rotate(q_rope, theta), rotate(k_rope, theta)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (b, s, nh, rd))], axis=-1)
    o = causal_attention(v, q, k, kv[..., nope:], 1.0 / math.sqrt(nope + rd))
    return h + mm(v, o.reshape(b, s, nh * vd), p['wo'])


def gated(v: Variant, x, w_gate, w_up, w_down):
    return mm(v, jax.nn.silu(mm(v, x, w_gate)) * mm(v, x, w_up), w_down)


def swiglu(l: Layer, h, p, v: Variant = MODEL):
    x = rms_norm(h, p['norm'], l.getf('eps', 1e-5))
    return h + gated(v, x, p['wgate'], p['wup'], p['wdown'])


def route(l: Layer, x, p, v: Variant = MODEL):
    """Scores, the chosen experts, their weights, and the gap between the
    last score chosen and the first left out (a choice that rounding can
    flip where it is small)."""
    k = v.top_k or l.geti('experts_per_token')
    scaling = l.getf('routed_scaling_factor', 1.0) \
        if v.scaling is None else v.scaling
    s = jax.nn.sigmoid(jnp.matmul(x, p['router']))   # never lowered: float32
    ranked = jnp.sort(s + p['router_bias'], axis=-1)[..., ::-1]
    _, idx = jax.lax.top_k(s + p['router_bias'], k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    weights = scaling * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx, weights, ranked[..., k - 1] - ranked[..., k]


def moe(l: Layer, h, p, v: Variant = MODEL):
    """-> (output, gap): the held experts' part of the routed sum, a loop
    over them, plus the shared expert; what the experts held elsewhere would
    add is left out."""
    x = rms_norm(h, p['norm'], l.getf('eps', 1e-5))
    idx, weights, gap = route(l, x, p, v)
    first = l.geti('expert_first')
    y = jnp.zeros_like(x)
    for e in range(p['wgate'].shape[0]):
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1)
        y = y + w_e[..., None] * gated(v, x, p['wgate'][e], p['wup'][e],
                                       p['wdown'][e])
    if v.shared_expert and 'sgate' in p:
        y = y + gated(v, x, p['sgate'], p['sup'], p['sdown'])
    return h + y, gap


def mtp_join(l: Layer, emb, h, p, v: Variant = MODEL):
    eps = l.getf('eps', 1e-5)
    return mm(v, jnp.concatenate([rms_norm(emb, p['enorm'], eps),
                                  rms_norm(h, p['hnorm'], eps)], axis=-1),
              p['wmat'])


def log_probs(v: Variant, h, w_head):
    return jax.nn.log_softmax(mm(v, h, w_head), axis=-1)


#: ``references/confnet.py``'s table with this family's rules: values are
#: (batch, seq, d) float32, ids (batch, n) integers; a rule returns its
#: outputs, and ``moe`` its gap besides (``run_graph`` takes it off)
OPS = dict(CONFNET_OPS)
OPS.update({
    'seq_slice': lambda l, ins, p, v: [
        ins[0][:, l.geti('offset'):l.geti('offset') + l.geti('seq_len')]],
    'embedding': lambda l, ins, p, v: [jnp.take(p['wmat'], ins[0], axis=0)],
    'rmsnorm': lambda l, ins, p, v: [rms_norm(ins[0], p['gamma'],
                                              l.getf('eps', 1e-5))],
    'mla': lambda l, ins, p, v: [mla(l, ins[0], p, v)],
    'swiglu': lambda l, ins, p, v: [swiglu(l, ins[0], p, v)],
    'moe': lambda l, ins, p, v: list(moe(l, ins[0], p, v)),
    'mtp_join': lambda l, ins, p, v: [mtp_join(l, ins[0], ins[1], p, v)],
    # log-probabilities, a head
    'lm_head_loss': lambda l, ins, p, v: [log_probs(v, x, p['wmat'])
                                          for x in ins],
})


def run_graph(graph: Graph, params, ids, v: Variant = MODEL, ops=OPS):
    """-> ({loss node: log-probabilities (b, s, vocab)}, {moe layer index:
    gap (b, s)}); ``params``: {layer index: {field: array}}, float32."""
    values = {'0': ids}
    gaps = {}
    for l in graph.layers:
        outs = ops[l.type](l, [values[n] for n in l.ins],
                           params.get(l.primary, {}), v)
        if l.type == 'moe':
            outs, gaps[l.index] = outs[:1], outs[1]
        values.update(zip(l.outs, outs))
    return {n: values[n] for n in graph.loss_nodes()}, gaps


def _f32(params):
    return {int(k): {f: jnp.asarray(a, F32) for f, a in d.items()}
            for k, d in params.items()}


def forward(graph: Graph, params, data, ops=OPS, skip=(),
            variant: Variant = MODEL) -> Dict[str, np.ndarray]:
    """Every loss node's probabilities for ``data`` (ids, any shape with
    the batch first), on the host: the whole graph in one program, for the
    sizes of the tests.  ``skip`` is ``references/confnet.py``'s; this
    family's probe uses ``variant``."""
    if skip:
        raise NotImplementedError('glm_moe_lite: use variant, not skip')
    ids = jnp.asarray(np.asarray(data).reshape(len(data), -1), jnp.int32)
    with jax.default_matmul_precision('highest'):
        out, _ = jax.jit(lambda p, i: run_graph(graph, p, i, variant, ops))(
            _f32(params), ids)
    return {n: np.exp(np.asarray(v)) for n, v in out.items()}


def losses(graph: Graph, params, ids, labels, v: Variant = MODEL):
    """{loss node: mean cross-entropy a token}, and their sum weighted by
    each head's weight over the batch: what the program's step minimises.
    ``labels``: the label matrix (batch, columns)."""
    logp, _ = run_graph(graph, params, ids, v)
    wt = jnp.asarray(v.token_weights(graph.seq))
    each, total = {}, 0.0
    for h in graph.heads():
        y = labels[:, h.label_first:h.label_first + graph.seq]
        nll = -jnp.take_along_axis(logp[h.node],
                                   y.astype(jnp.int32)[..., None],
                                   axis=-1)[..., 0]
        each[h.node] = jnp.mean(jnp.sum(nll * wt, axis=-1))
        total = total + h.weight * each[h.node]
    return total, each


def loss_and_grads(graph: Graph, params, data, labels,
                   variant: Variant = MODEL):
    """(total, {loss node: loss}, {layer: {field: gradient of total}}), float32,
    by ``jax.grad`` of the straightforward forward."""
    ids = jnp.asarray(np.asarray(data).reshape(len(data), -1), jnp.int32)
    labels = jnp.asarray(labels, F32)
    with jax.default_matmul_precision('highest'):
        (total, each), grads = jax.jit(jax.value_and_grad(
            lambda p: losses(graph, p, ids, labels, variant),
            has_aux=True))(_f32(params))
    return (float(total), {n: float(x) for n, x in each.items()},
            jax.device_get(grads))


# --- operations a step requires (the MFU numerator) -------------------------

def forward_macs(graph: Graph) -> Dict[int, float]:
    """Multiply-accumulates of one sequence's forward pass, by layer index:
    every matrix product, the causal half of the attention scores and of
    their product with the values, and of the routed experts only the
    assignments a balanced router sends to the experts held here
    (``tokens * experts_per_token * experts_held / experts_published``).
    Norms, rotary, softmax and the router's top-k are left out, as MFU
    conventions do."""
    s, d = graph.seq, graph.width
    out: Dict[int, float] = {}
    for l in graph.layers:
        if l.type == 'mla':
            nh = l.geti('nhead')
            nope, rd = l.geti('qk_nope_head_dim'), l.geti('qk_rope_head_dim')
            vd, qr = l.geti('v_head_dim'), l.geti('q_lora_rank')
            kvr = l.geti('kv_lora_rank')
            proj = (d * qr + qr * nh * (nope + rd) + d * (kvr + rd)
                    + kvr * nh * (nope + vd) + nh * vd * d)
            pairs = s * (s + 1) // 2             # a query and a key up to it
            out[l.index] = s * proj + pairs * nh * (nope + rd + vd)
        elif l.type == 'swiglu':
            out[l.index] = s * 3 * d * l.geti('nhidden')
        elif l.type == 'moe':
            f, pub = l.geti('nhidden'), l.geti('experts_published')
            here = (s * l.geti('experts_per_token') * l.geti('experts_held')
                    / pub)
            out[l.index] = (s * d * pub + here * 3 * d * f
                            + s * 3 * d * f * l.geti('shared_experts', 1))
        elif l.type == 'mtp_join':
            out[l.index] = s * 2 * d * d
        elif l.type == 'lm_head_loss':
            out[l.index] = len(l.ins) * s * d * l.geti('vocab_held')
    return out


def train_flops_per_sequence(graph: Graph) -> float:
    """Forward, weight gradient and input gradient: three times the forward
    pass, two operations a multiply-accumulate (the attention scores have no
    weights and two inputs, which comes to the same).  Nothing recomputed
    counts."""
    return 2.0 * 3.0 * sum(forward_macs(graph).values())


def train_flops_per_step(feed) -> float:
    return train_flops_per_sequence(feed.graph) * feed.samples_per_step


# --- the comparison that decides ``correct`` --------------------------------

#: The program computes in bfloat16 on float32 masters, the reference in
#: float32.  Two things are compared on one seeded sequence of the cell's
#: length that no ring holds.
#:
#: *What the program's evaluation-mode forward makes of it*: the
#: log-probabilities of both heads at every position.  A difference is
#: taken over the spread (standard deviation) of the reference's
#: log-probabilities, as ``references/confnet.py`` does.  A router's choice
#: of its k-th expert flips on rounding where the k-th and (k+1)-th scores
#: nearly tie, and a flipped expert is a different function, not an error of
#: precision.  So positions whose gap in the reference is under
#: ``TIE_EPSILON`` in any expert layer are counted and printed; their share
#: may not pass ``TIE_SHARE_MAX``, and the limits are on the mean, the median
#: position, and the share of positions off by more than ``TOLERANCE``, with
#: and without a near tie to explain it.
#:
#: *What the timed program itself makes of it* (``program_step``): the
#: sequence goes through one real ``update_staged``, the step the window
#: times, with its chunked loss, its recomputation and its Adam.  The loss
#: that step reports is held to the reference's (``STEP_LOSS_TOLERANCE``,
#: relative), and the change the step made to the leaves behind the heads -
#: the head's weight, which both heads share, and the two final norms - to
#: the change plain Adam makes of the reference's gradient from the
#: trainer's own moments (``UPDATE_TOLERANCE`` on the norm of the
#: difference over the norm of the reference's change: a state left
#: unchanged reads 1).  The layers in between have their gradients compared
#: on the CPU at the tiny size (``tests/``); on the chip the learning check
#: stands for them.  The numbers behind the limits are in PERF.md (Findings,
#: PR 29).
TOLERANCE = 0.25
MEAN_TOLERANCE = 0.03
MEDIAN_TOLERANCE = 0.12
OFF_SHARE_MAX = 0.15
OFF_UNTIED_SHARE_MAX = 0.01
LOSS_TOLERANCE = 3e-4
TIE_EPSILON = 4e-3
TIE_SHARE_MAX = 0.8
STEP_LOSS_TOLERANCE = 1.5e-4
UPDATE_TOLERANCE = 0.1
HEAD_CHUNK = 1024          # positions a block of the head's product


def check_ids(graph: Graph, cell, seed: int) -> np.ndarray:
    """One seeded row of ``seq + 2`` ids of the traffic's kind that no ring
    holds: the sequence, and the two tokens its last labels need."""
    from .. import tokens
    return tokens.token_rows(seed + 7919, int(cell.t('check_batch')),
                             graph.seq + 2, graph.vocab, cell.t('data'))


def label_matrix(graph: Graph, ids: np.ndarray) -> np.ndarray:
    """The traffic's labels for rows of ``seq + 2`` ids: the next token of
    every position, then the one after (``feeds/staged_tokens.py``)."""
    s = graph.seq
    return np.concatenate([ids[:, 1:s + 1], ids[:, 2:s + 2]], axis=1)


@functools.lru_cache(maxsize=None)
def _layer_program(kind: str, cfg: tuple, v: Variant):
    """One compiled program a kind of layer and its pairs: six attention
    layers of one conf are one program."""
    l = Layer(-1, kind, '', [], [], dict(cfg), -1)
    return jax.jit(lambda ins, p: OPS[kind](l, ins, p, v))


@functools.lru_cache(maxsize=None)
def _head_program(v: Variant):
    return jax.jit(lambda x, w: log_probs(v, x, w))


@functools.lru_cache(maxsize=None)
def _tail_program(v: Variant, eps: float):
    """Gradient, with respect to a final norm's gain and the head's weight,
    of a block of positions' weighted cross-entropy."""
    def nll(gamma, w, x, y, wt):
        logp = log_probs(v, rms_norm(x, gamma, eps), w)
        return -jnp.sum(jnp.take_along_axis(logp, y[..., None],
                                            axis=-1)[..., 0] * wt)
    return jax.jit(jax.grad(nll, argnums=(0, 1)))


def _of(params, layer: Layer):
    return params.get(str(layer.primary), params.get(layer.primary, {}))


def blockwise_log_probs(graph: Graph, params, ids, v: Variant = MODEL):
    """``run_graph`` at the cell's size beside a trainer's state: one layer
    a program, on the device, with that layer's parameters as they lie
    there (float32 masters, no copy); the heads in blocks of positions whose
    log-probabilities go to the host one at a time.  -> ({loss node: (b, s,
    vocab) on the host}, share of positions with a near tie, {loss node:
    (the ``rmsnorm`` layer that made the head's input, that layer's own
    input, on the device)})."""
    values = {'0': jnp.asarray(ids, jnp.int32)}
    made_by = {}
    tie = None
    out: Dict[str, np.ndarray] = {}
    before_norm = {}
    with jax.default_matmul_precision('highest'):
        for l in graph.layers:
            p = _of(params, l)
            ins = [values[n] for n in l.ins]
            if l.type == 'lm_head_loss':
                head = _head_program(v)
                for name, node, h in zip(l.ins, l.outs, ins):
                    out[node] = np.concatenate(
                        [np.asarray(head(h[:, a:a + HEAD_CHUNK], p['wmat']))
                         for a in range(0, h.shape[1], HEAD_CHUNK)], axis=1)
                    before_norm[node] = made_by[name]
                continue
            outs = _layer_program(l.type, tuple(sorted(l.cfg.items())),
                                  v)(ins, p)
            if l.type == 'moe':
                near = np.asarray(outs[1]) < TIE_EPSILON
                tie = near if tie is None else tie | near
                outs = outs[:1]
            values.update(zip(l.outs, outs))
            made_by.update((n, (l, ins[0])) for n in l.outs)
    return out, (np.zeros(ids.shape[:1] + (graph.seq,), bool)
                 if tie is None else tie), before_norm


def tail_gradients(graph: Graph, params, before_norm, ids,
                   v: Variant = MODEL) -> Dict[tuple, np.ndarray]:
    """{(layer, field): gradient of the step's loss} for the leaves behind
    the heads - the head's weight, once for every head that uses it, and
    the norm that feeds each head - on the host, float32 ``highest``, a
    block of positions at a time."""
    labels = label_matrix(graph, ids)
    wt = v.token_weights(graph.seq) / len(ids)
    grads: Dict[tuple, np.ndarray] = {}
    with jax.default_matmul_precision('highest'):
        for h in graph.heads():
            norm, x = before_norm[h.node]
            if norm.type != 'rmsnorm':
                raise ValueError(f'{h.node}: the head reads a {norm.type}')
            grad = _tail_program(v, norm.getf('eps', 1e-5))
            gamma, w = _of(params, norm)['gamma'], _of(params, h.layer)['wmat']
            y = labels[:, h.label_first:h.label_first + graph.seq]
            for a in range(0, graph.seq, HEAD_CHUNK):
                b = a + HEAD_CHUNK
                found = grad(gamma, w, x[:, a:b], jnp.asarray(y[:, a:b]),
                             jnp.asarray(h.weight * wt[a:b]))
                # summed on the host, one block at a time: the device holds
                # one block's gradient and never a queue of them
                for key, g in zip(((norm.primary, 'gamma'),
                                   (h.layer.primary, 'wmat')), found):
                    grads[key] = grads.get(key, 0.0) + np.asarray(g)
    return grads


def measure(graph: Graph, got: Dict[str, np.ndarray],
            want: Dict[str, np.ndarray], tie: np.ndarray, ids: np.ndarray,
            v: Variant = MODEL):
    """The comparison's numbers, a head: differences of log-probabilities
    over the spread (standard deviation) of the reference's.  ``mean`` over
    every position and token; a position's error is its largest difference
    over the vocabulary, of which ``median_position``, ``p99_position``,
    ``largest`` and ``largest_untied`` (over the positions without a near
    tie); ``off_share`` the share of positions whose error passes
    ``TOLERANCE`` and ``off_untied_share`` those of them without a near tie,
    as a share of all positions; ``loss`` the relative difference of the
    head's loss on the sequence, with both losses beside it.  -> (those, the
    reference's loss of the whole step: the heads' by their weights)."""
    floor = -60.0
    labels = label_matrix(graph, ids)
    wt = v.token_weights(graph.seq)
    out, total = {}, 0.0
    for h in graph.heads():
        node = h.node
        zp = np.maximum(np.log(np.maximum(got[node], 1e-38)), floor)
        zr = np.maximum(want[node], floor)
        spread = max(float(np.std(zr)), 1e-6)
        y = labels[:, h.label_first:h.label_first + graph.seq, None]
        lp = -float(np.mean(np.take_along_axis(zp, y, axis=-1)))
        nll = -np.take_along_axis(zr, y, axis=-1)[..., 0].astype(np.float64)
        lr = float(np.mean(nll))
        total += h.weight * float(np.mean(np.sum(nll * wt, axis=-1)))
        np.subtract(zp, zr, out=zp)
        np.abs(zp, out=zp)
        position = zp.max(axis=-1) / spread
        off = position > TOLERANCE
        out[node] = {
            'mean': float(zp.mean(dtype=np.float64)) / spread,
            'median_position': float(np.median(position)),
            'p99_position': float(np.quantile(position, 0.99)),
            'largest': float(position.max()),
            'largest_untied': float(position[~tie].max())
            if (~tie).any() else float('inf'),
            'off_share': float(off.mean()),
            'off_untied_share': float((off & ~tie).mean()),
            'loss': abs(lp - lr) / max(abs(lr), 1e-6),
            'losses_program_reference': (lp, lr), 'spread': spread}
    return out, total


# --- the timed program's own step ---------------------------------------------

def tail_leaves(graph: Graph) -> List[tuple]:
    """(layer, field) of the leaves whose change a step is held to."""
    made_by = {n: l for l in graph.layers for n in l.outs
               if l.type != 'lm_head_loss'}
    leaves = []
    for h in graph.heads():
        for key in ((h.layer.primary, 'wmat'),
                    (made_by[h.hidden].primary, 'gamma')):
            if key not in leaves:
                leaves.append(key)
    return leaves


def program_step(trainer, graph: Graph, ids: np.ndarray) -> dict:
    """One real step of the program under test on ``ids``: staged by its
    own ``stage_batch`` like a ring row, run by the ``update_staged`` the
    window times.  -> the loss that step reported, and for every tail leaf
    its value and Adam's two moments before the step and its value after,
    on the host, with the number of updates made before."""
    import jax
    from cxxnet_tpu.io.data import DataBatch
    leaves = tail_leaves(graph)

    def fetch(tree):
        return {(k, f): np.asarray(jax.device_get(tree[str(k)][f]))
                for k, f in leaves}

    before = {'w': fetch(trainer.params), 'm1': fetch(trainer.opt_state['m1']),
              'm2': fetch(trainer.opt_state['m2']),
              'updates': int(trainer.epoch_counter)}
    staged = trainer.stage_batch(DataBatch(
        np.ascontiguousarray(ids[:, None, None, :graph.seq + 1]),
        label_matrix(graph, ids).astype(np.float32)))
    seen: list = []
    trainer.add_loss_listener(seen.append)
    try:
        trainer.update_staged(staged)
    finally:
        trainer.remove_loss_listener(seen.append)
    return dict(before, loss=float(seen[-1]), after=fetch(trainer.params))


def adam_change(graph: Graph, g, m1, m2, updates: int):
    """What one Adam step (Kingma & Ba, 2015, in the form at the end of its
    section 2: the two corrections folded into the step size, epsilon beside
    the root of the raw second moment) makes of gradient ``g`` from moments
    ``m1``, ``m2`` after ``updates`` earlier steps, in the float32 the
    moments are kept in.  The conf gives ``eta`` and ``beta1`` / ``beta2``
    as one minus the paper's."""
    conf = graph.layers[0]
    d1, d2 = conf.getf('beta1', 0.1), conf.getf('beta2', 0.001)
    t = updates + 1
    step = conf.getf('eta', 0.01) * math.sqrt(1.0 - (1.0 - d2) ** t) \
        / (1.0 - (1.0 - d1) ** t)
    m1 = m1 + np.float32(d1) * (g - m1)
    m2 = m2 + np.float32(d2) * (g * g - m2)
    return np.float32(-step) * m1 / (np.sqrt(m2) + np.float32(1e-8))


def _norm(x) -> float:
    return math.sqrt(float(np.sum(np.square(x), dtype=np.float64)))


def step_numbers(graph: Graph, step: dict, loss_reference: float,
                 grads: Dict[tuple, np.ndarray]) -> dict:
    """``loss``: the relative difference between the loss the program's
    step reported and the reference's; ``update``: a leaf, the norm of
    (the change the step made - the change plain Adam makes of the
    reference's gradient) over the norm of the latter."""
    update = {}
    for key, g in grads.items():
        want = adam_change(graph, g, step['m1'][key], step['m2'][key],
                           step['updates'])
        got = step['after'][key] - step['w'][key]
        update[f'{key[0]}.{key[1]}'] = _norm(got - want) \
            / max(_norm(want), 1e-30)
    return {'loss': abs(step['loss'] - loss_reference)
            / max(abs(loss_reference), 1e-6),
            'losses_program_reference': (step['loss'], loss_reference),
            'updates_before': step['updates'], 'update': update}


def within_limits(numbers: dict, tie_share: float, step: dict) -> bool:
    return (tie_share <= TIE_SHARE_MAX and all(
        np.isfinite(n['largest']) and n['mean'] <= MEAN_TOLERANCE
        and n['median_position'] <= MEDIAN_TOLERANCE
        and n['off_share'] <= OFF_SHARE_MAX
        and n['off_untied_share'] <= OFF_UNTIED_SHARE_MAX
        and n['loss'] <= LOSS_TOLERANCE for n in numbers.values())
        and step['loss'] <= STEP_LOSS_TOLERANCE
        and all(u <= UPDATE_TOLERANCE for u in step['update'].values()))


def reference_side(graph: Graph, params, ids, got,
                   v: Variant = MODEL) -> dict:
    """All the reference has to say about ``ids`` under ``params``, on the
    host: the numbers of the program's probabilities ``got`` against its
    own, the share of near ties, its loss of the step, its gradients of the
    tail leaves.  Taken before the program's step moves ``params``."""
    want, tie, before_norm = blockwise_log_probs(graph, params, ids, v)
    grads = tail_gradients(graph, params, before_norm, ids, v)
    numbers, step_loss = measure(graph, got, want, tie, ids, v)
    return {'numbers': numbers, 'tie_share': float(np.mean(tie)),
            'step_loss': step_loss, 'grads': grads}


def judge(graph: Graph, side: dict, step: dict):
    """-> (the step's numbers against ``side``, inside every limit?)"""
    found = step_numbers(graph, step, side['step_loss'], side['grads'])
    return found, within_limits(side['numbers'], side['tie_share'], found)


def compare(feed, cell, seed: int, variant: Variant = MODEL) -> dict:
    """One seeded sequence through the program's evaluation-mode forward
    (its own forward step, the parameters as they stand) and then through
    one real training step, each against this reference on the same
    parameters.  The step leaves the trainer one update further on."""
    from .. import cxx
    graph: Graph = feed.graph
    ids = check_ids(graph, cell, seed)
    data = ids[:, None, None, :graph.seq + 1]
    got = {n: g.reshape(len(ids), graph.seq, -1) for n, g in
           cxx.eval_outputs(feed.trainer, data, graph.loss_nodes()).items()}
    side = reference_side(graph, feed.trainer.params, ids, got, variant)
    step, ok = judge(graph, side, program_step(feed.trainer, graph, ids))
    return {'errors': dict(side['numbers'], near_tie_share=side['tie_share'],
                           step=step),
            'tolerance': {'position': TOLERANCE, 'mean': MEAN_TOLERANCE,
                          'median_position': MEDIAN_TOLERANCE,
                          'off_share': OFF_SHARE_MAX,
                          'off_untied_share': OFF_UNTIED_SHARE_MAX,
                          'loss': LOSS_TOLERANCE,
                          'tie_epsilon': TIE_EPSILON,
                          'tie_share': TIE_SHARE_MAX,
                          'step_loss': STEP_LOSS_TOLERANCE,
                          'update': UPDATE_TOLERANCE},
            'ok': ok}
