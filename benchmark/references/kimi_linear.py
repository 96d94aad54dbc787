"""Plain reference for a ``kimi_linear`` conf (Kimi-Linear-48B-A3B and its
tiny twin): in every period of four layers three layers of Kimi Delta
Attention (a short convolution, a gated delta rule with one decay a channel,
``beta`` up to 1 without ``kda_allow_neg_eigval``, a low-rank sigmoid gate
over the normalised output) and one layer of latent attention without
positions (``mla_use_nope``: a full-rank query, ``q_lora_rank: null``; query
and key heads of 128 + 64, value heads of 128; the 64 dims of the query and
of the shared key left unrotated), a leading dense SwiGLU under a delta
layer, expert layers with a sigmoid top-k router over experts of which the
chip holds a share and a shared expert; one head with a cross-entropy a
token, and the update Adam makes of the step's gradient.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no recomputation
but the checkpoints that let a gradient at 8,192 positions fit beside a
trainer, no grouping.  The delta rule is ``references/solar_open2.py``'s
token-by-token recurrence ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t)
S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t / sqrt(d_k)``, the same
equations (Kimi Linear, arXiv 2510.26692, section 3); attention is a full
masked softmax, in checkpointed blocks of queries where the sequence is
long.  Its own are the full-rank query, the unrotated 64 dims, the split of
192 query/key dims against 128 value dims, ``beta`` in (0, 1) from the
conf's ``allow_neg_eigval``, and the comparison's last part: the change the
step makes to every leaf of the latent layer.  From ``glm_moe_lite`` it
takes the graph of a sequence conf, the rules of the layer types every
family shares (``seq_slice``, ``embedding``, ``rmsnorm``, ``swiglu``,
``lm_head_loss``) and the comparison's arithmetic; from ``solar_open2`` the
router, the expert layer and the head groups of a delta layer.  The model
imports nothing from ``cxxnet_tpu``; the comparison calls the program under
test as it is (its forward, its step, its delta rule).

Like the other references it exports ``build_graph``, ``label_matrix``,
``forward``, ``compare`` and ``train_flops_per_step``.  It is given the
chip's share of the experts and the vocabulary by the conf, as the program
is.  Departures from the published model, each also under ``assumed`` in
the configuration's file (``config.json`` has no key for them): the
router's correction bias is whatever the program's ``router_bias`` leaf
holds (zero); the delta layer's output gate has a bias ``b_g``; ``q`` is
scaled by ``1 / sqrt(d_k)``; ``L2Norm`` is ``x / sqrt(sum x^2 + 1e-6)``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import kda_costs
from . import glm_moe_lite as glm
from . import laguna_moe
from . import solar_open2 as solar
from .glm_moe_lite import (F32, Graph, Layer, build_graph,  # noqa: F401
                           rms_norm)

# --- the layer equations ----------------------------------------------------


class Variant(NamedTuple):
    """Switches that make the reference *wrong* on purpose: the sensitivity
    probe shows that each leaves the comparison's limits (PERF.md 6).  The
    default is the model."""
    beta_max: Optional[float] = None    # None: the conf's allow_neg_eigval
    conv: bool = True                   # the short convolution
    erase: bool = True                  # the delta rule's - beta k k^T S
    kda_gate: bool = True
    rope: bool = False                  # rotary on the latent layer's 64
    router_score: str = 'sigmoid'
    matmul_dtype: Optional[str] = None  # round every product's operands
    attention_gradient: bool = True     # False: no gradient through it
    # the delta rule as a kernel in this type would run it, and the rule
    # fed rounded q, k and v (``solar_open2.Variant``'s)
    recurrence_dtype: Optional[str] = None
    recurrence_inputs_dtype: Optional[str] = None
    recurrence_gradient: bool = True    # False: no gradient through the rule
    loss_tokens: str = 'all'            # ``glm_moe_lite.Variant``'s

    token_weights = glm.Variant.token_weights
    lower = glm.Variant.lower
    lower_recurrence = solar.Variant.lower_recurrence
    lower_recurrence_inputs = solar.Variant.lower_recurrence_inputs


MODEL = Variant()
#: the probe: each of these must leave the comparison's limits
#: (``selftest/kimi.py`` at the tiny size, ``selftest/kimi_sensitivity.py``
#: on the chip)
PROBE = {
    'beta up to 2 (a negative eigenvalue)': Variant(beta_max=2.0),
    'short convolution left out': Variant(conv=False),
    'the delta rule without its erase': Variant(erase=False),
    'the delta layer\'s gate left out': Variant(kda_gate=False),
    'rotary on the latent layer\'s 64 dims': Variant(rope=True),
    'softmax router': Variant(router_score='softmax'),
    'the recurrence in bfloat16': Variant(recurrence_dtype='bfloat16'),
    'the recurrence fed bfloat16':
        Variant(recurrence_inputs_dtype='bfloat16'),
    'products in float8_e4m3': Variant(matmul_dtype='float8_e4m3fn'),
    # faults of the step alone (the log-probabilities stay the model's)
    'an eighth of the tokens dropped from the loss':
        Variant(loss_tokens='all but the last eighth'),
    'the loss over every other token': Variant(loss_tokens='every other'),
    'no gradient through the delta rule':
        Variant(recurrence_gradient=False),
    'no gradient through the attention kernel':
        Variant(attention_gradient=False),
}


def mm(v: Variant, a, b):
    return jnp.matmul(v.lower(a), v.lower(b))


ATTENTION_BLOCK = 256        # queries a block, where the sequence is longer


def attention(v: Variant, q, k, val, scale):
    """``q``, ``k``: (b, s, h, dk), ``val``: (b, s, h, dv) -> (b, s, h, dv),
    causal.  Over blocks of queries, each checkpointed, so that a gradient
    holds one block's scores at a time."""
    s = q.shape[1]

    def block(qb, first):
        scores = jnp.einsum('bqhd,bkhd->bhqk', v.lower(qb),
                            v.lower(k)) * scale
        rows = first + jnp.arange(qb.shape[1])[:, None]
        keep = jnp.arange(s)[None, :] <= rows
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return jnp.einsum('bhqk,bkhd->bqhd', v.lower(probs), v.lower(val))

    if s <= ATTENTION_BLOCK or s % ATTENTION_BLOCK:
        return block(q, 0)
    n = s // ATTENTION_BLOCK
    qs = jnp.moveaxis(q.reshape(q.shape[0], n, ATTENTION_BLOCK,
                                *q.shape[2:]), 1, 0)
    body = jax.checkpoint(block)
    out = jax.lax.map(lambda a: body(a[0], a[1]),
                      (qs, jnp.arange(n) * ATTENTION_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape[:3] + val.shape[3:])


def mla(l: Layer, h, p, v: Variant = MODEL):
    """Latent attention with a full-rank query (``q_lora_rank = 0``: GLM's
    low-rank one is ``glm_moe_lite.mla``'s); the 64 shared dims rotated
    only where the conf's ``use_rope`` (or the probe) asks."""
    b, s, _ = h.shape
    nh, eps = l.geti('nhead'), l.getf('eps', 1e-5)
    nope, rd = l.geti('qk_nope_head_dim'), l.geti('qk_rope_head_dim')
    vd, kvr = l.geti('v_head_dim'), l.geti('kv_lora_rank')
    x = rms_norm(h, p['norm'], eps)
    q = mm(v, x, p['wq']).reshape(b, s, nh, nope + rd)
    ckv = mm(v, x, p['wkv_a'])
    c_kv, k_r = ckv[..., :kvr], ckv[..., kvr:]
    kv = mm(v, rms_norm(c_kv, p['kv_norm'], eps),
            p['wkv_b']).reshape(b, s, nh, nope + vd)
    q_rope, k_rope = q[..., nope:], k_r[:, :, None, :]
    if l.geti('use_rope', 1) or v.rope:
        theta = l.getf('rope_theta', 10000.0)
        q_rope, k_rope = glm.rotate(q_rope, theta), glm.rotate(k_rope, theta)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (b, s, nh, rd))], axis=-1)
    o = attention(v, q, k, kv[..., nope:], 1.0 / math.sqrt(nope + rd))
    if not v.attention_gradient:
        o = jax.lax.stop_gradient(o)
    return h + mm(v, o.reshape(b, s, nh * vd), p['wo'])


def kda_inputs(l: Layer, h, p, v: Variant = MODEL):
    """The delta layer up to its recurrence, as ``solar_open2.kda_inputs``
    but ``beta = beta_max sigmoid(x W_beta)`` with ``beta_max`` 2 where the
    conf allows a negative eigenvalue (``allow_neg_eigval = 1``, the
    default) and 1 where it does not: -> (``x``, ``q``, ``k``, ``val``,
    ``log_alpha``, ``beta``)."""
    beta_max = v.beta_max if v.beta_max is not None \
        else (2.0 if l.geti('allow_neg_eigval', 1) else 1.0)
    x, q, k, val, log_alpha, beta = solar.kda_inputs(
        l, h, p, solar.Variant(beta_max=1.0, conv=v.conv,
                               matmul_dtype=v.matmul_dtype))
    return x, q, k, val, log_alpha, beta_max * beta


def kda(l: Layer, h, p, v: Variant = MODEL):
    b, s, _ = h.shape
    nh, hd, eps = l.geti('nhead'), l.geti('head_dim'), l.getf('eps', 1e-5)
    x, q, k, val, log_alpha, beta = kda_inputs(l, h, p, v)
    o = rms_norm(solar.recurrence(v, q, k, val, log_alpha, beta),
                 p['o_norm'], eps)
    if v.kda_gate:
        o = o * jax.nn.sigmoid((mm(v, mm(v, x, p['wg_down']), p['wg_up'])
                                + p['g_bias']).reshape(b, s, nh, hd))
    return h + mm(v, o.reshape(b, s, nh * hd), p['wo'])


#: ``glm_moe_lite``'s table (``seq_slice``, ``embedding``, ``rmsnorm``,
#: ``swiglu``, ``lm_head_loss`` as there) with this family's three rules
OPS = dict(glm.OPS)
OPS.update({
    'mla': lambda l, ins, p, v: [mla(l, ins[0], p, v)],
    'kda': lambda l, ins, p, v: [kda(l, ins[0], p, v)],
    'moe': lambda l, ins, p, v: list(solar.moe(l, ins[0], p, v)),
})


def run_graph(graph: Graph, params, ids, v: Variant = MODEL):
    return glm.run_graph(graph, params, ids, v, OPS)


def forward(graph: Graph, params, data, ops=OPS, skip=(),
            variant: Variant = MODEL) -> Dict[str, np.ndarray]:
    """Every loss node's probabilities for ``data`` (ids, the batch first),
    on the host: the whole graph in one program, for the sizes of the
    tests."""
    return glm.forward(graph, params, data, ops, skip, variant)


check_ids = glm.check_ids            # one seeded row of ``seq + 2`` ids
label_matrix = laguna_moe.label_matrix   # the next token of every position


def losses(graph: Graph, params, ids, labels, v: Variant = MODEL):
    """{loss node: mean cross-entropy a token}, and their sum by each head's
    weight over the batch: what the program's step minimises."""
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
    """(total, {loss node: loss}, {layer: {field: gradient of total}}),
    float32, by ``jax.grad`` of the straightforward forward."""
    ids = jnp.asarray(np.asarray(data).reshape(len(data), -1), jnp.int32)
    labels = jnp.asarray(labels, F32)
    with jax.default_matmul_precision('highest'):
        (total, each), grads = jax.jit(jax.value_and_grad(
            lambda p: losses(graph, p, ids, labels, variant),
            has_aux=True))(glm._f32(params))
    return (float(total), {n: float(x) for n, x in each.items()},
            jax.device_get(grads))


# --- operations a step requires (the MFU numerator) -------------------------

def mla_macs(seq: int, d: int, l: Layer) -> float:
    """A latent layer's products - the query (full rank at ``q_lora_rank =
    0``, else its two low-rank factors), ``W_kva``, ``W_kvb`` and ``W_o`` -
    and its scores (``nope + rope`` a pair) and their product with the
    values (``v_head_dim`` a pair) over every causal pair."""
    nh = l.geti('nhead')
    nope, rd = l.geti('qk_nope_head_dim'), l.geti('qk_rope_head_dim')
    vd, qr = l.geti('v_head_dim'), l.geti('q_lora_rank')
    kvr = l.geti('kv_lora_rank')
    query = d * nh * (nope + rd) if not qr \
        else d * qr + qr * nh * (nope + rd)
    proj = query + d * (kvr + rd) + kvr * nh * (nope + vd) + nh * vd * d
    return seq * proj + seq * (seq + 1) // 2 * nh * (nope + rd + vd)


def forward_macs(graph: Graph) -> Dict[int, float]:
    """Multiply-accumulates of one sequence's forward pass, by layer index:
    ``glm_moe_lite.forward_macs`` for the dense, expert and head layers (of
    the routed experts the assignments a balanced router sends here), a
    latent layer's as ``mla_macs`` counts them, and a ``kda`` layer's
    products and its recurrence in the chunkwise form at chunks of 64
    (``benchmark/kda_costs.py``).  The convolution, norms, gates' sigmoids
    and the decay's exponentials are left out, as MFU conventions leave
    elementwise work out."""
    s, d = graph.seq, graph.width
    out = glm.forward_macs(graph)
    for l in graph.of_type('mla'):
        out[l.index] = mla_macs(s, d, l)
    for l in graph.of_type('kda'):
        nh, hd = l.geti('nhead'), l.geti('head_dim')
        out[l.index] = s * kda_costs.projection_macs(d, nh, hd) \
            + kda_costs.recurrence_macs(s, nh, hd, hd)
    return out


def train_flops_per_sequence(graph: Graph) -> float:
    """Forward, weight gradient and input gradient: three times the forward
    pass, two operations a multiply-accumulate.  Nothing recomputed
    counts."""
    return 2.0 * 3.0 * sum(forward_macs(graph).values())


def train_flops_per_step(feed) -> float:
    return train_flops_per_sequence(feed.graph) * feed.samples_per_step


# --- the comparison that decides ``correct`` --------------------------------

#: As ``solar_open2``: the program computes its products in bfloat16 on
#: float32 masters and its delta rule in float32, the reference all in
#: float32.  On one seeded sequence of the cell's length that no ring holds
#: are compared:
#:
#: - the evaluation-mode log-probabilities at every position (differences
#:   over the spread of the reference's; near ties of a router's eighth
#:   choice counted, the limits on shares);
#: - the program's own delta rule (``DeltaAttentionLayer.recurrence``) on
#:   each delta layer's inputs as this reference makes them, against the
#:   token-by-token rule on the same inputs;
#: - the timed program's own step: its loss, the change it makes to the
#:   head's weight and the final norm, **to every leaf of the last delta
#:   layer** and **to every leaf of the latent layer**, each against plain
#:   Adam on the reference's gradient from the trainer's own moments (a
#:   state left unchanged reads 1).  The latent layer's is the one guard on
#:   the attention kernel's dq and dkv at 192/128: its gradient comes back
#:   through the head, the final norm, the expert layer, the last delta
#:   layer and the expert layer after the latent layer, a block of positions
#:   or a group of heads at a time.
#:
#: Each limit lies between the largest reading of the program over seeds on
#: the chip (bf16 products, after a run's 24-26 steps; PERF.md 6) and the
#: smallest of the probe's faults it is there to catch, with the more room
#: above the program's reading.  Near ties: with eight of 256 sigmoid scores
#: chosen in four layers, 91% of positions have a gap under ``TIE_EPSILON``
#: somewhere.  Rotary on the 64 shared dims moves the log-probabilities no
#: more than bf16 does (mean 0.016 against 0.012): the latent layer's leaves
#: catch it.
LIMITS = {
    # name: (limit, the readings it lies between)
    'mean': (0.03, 'bf16 0.0121-0.0126; beta up to 2 0.39, float8 0.097'),
    'median_position': (0.13, 'bf16 0.054; beta up to 2 2.04, float8 '
                        '0.49'),
    'off_share': (0.1, 'positions off by more than TOLERANCE: bf16 '
                  '0.026-0.033; beta up to 2 and float8 1.0'),
    'off_untied_share': (0.01, 'those of them with no near tie to explain '
                         'it: bf16 0-0.00024; beta up to 2 0.088, float8 '
                         '0.089'),
}
TOLERANCE = glm.TOLERANCE
TIE_EPSILON = solar.TIE_EPSILON
TIE_SHARE_MAX = 0.99        # bf16 0.910-0.915; a softmax router's tie
#                             everywhere (solar_open2's reading 1.0)
STEP_LOSS_TOLERANCE = 1e-3  # bf16 1.5e-5 to 2.0e-5; an eighth of the tokens
#                             dropped 0.14 at the tiny size
UPDATE_TOLERANCE = 0.05     # bf16 0.0009-0.0022 (the norm), 0.0138-0.0162
#                             (the head); beta up to 2 0.27, float8 0.10, a
#                             state left unchanged 1
RECURRENCE_TOLERANCE = 1e-3     # the program 4.1e-5 to 8.9e-5; the rule fed
#                                 bf16 q, k, v 2.9e-3, float8 products 4.7e-3
KDA_UPDATE_TOLERANCE = 0.06     # a leaf: bf16 0.0011-0.0130; beta up to 2
#                                 0.037-0.45, float8 0.024-0.36, the layer
#                                 frozen 1
MLA_UPDATE_TOLERANCE = 0.15     # a leaf: bf16 0.0019-0.0111 on the chip,
#                                 up to 0.096 at the tiny size; rotary on the
#                                 64 dims 0.042-0.22 (wq 0.22), no gradient
#                                 through the kernel 0.12-0.35 (but wo
#                                 0.008), beta up to 2 0.06-0.23, float8
#                                 0.12-0.35, the layer frozen 1
HEAD_CHUNK = glm.HEAD_CHUNK


def _cfg(l: Layer) -> tuple:
    return tuple(sorted(l.cfg.items()))


@functools.lru_cache(maxsize=None)
def _layer_program(kind: str, cfg: tuple, v: Variant):
    """One compiled program a kind of layer and its pairs: the delta layers
    of one conf are one program."""
    l = Layer(-1, kind, '', [], [], dict(cfg), -1)
    return jax.jit(lambda ins, p: OPS[kind](l, ins, p, v))


@functools.lru_cache(maxsize=None)
def _recurrence_program(cfg: tuple, v: Variant):
    """For a group of a delta layer's heads: the program's own delta rule
    and this reference's on the inputs this reference makes.  -> (the sum of
    the squared differences, the sum of the reference's squares)."""
    from cxxnet_tpu.layers.sequence import DeltaAttentionLayer
    l = Layer(-1, 'kda', '', [], [], dict(cfg), -1)

    def run(h, p):
        _, q, k, val, log_alpha, beta = kda_inputs(l, h, p, v)
        want = solar.recurrence(v, q, k, val, log_alpha, beta)
        major = [jnp.moveaxis(a, 2, 1) for a in (q, k, val, log_alpha, beta)]
        got = jnp.moveaxis(DeltaAttentionLayer.recurrence(*major), 1, 2)
        return jnp.sum(jnp.square(got - want)), jnp.sum(jnp.square(want))
    return jax.jit(run)


def recurrence_error(l: Layer, h, p, v: Variant = MODEL) -> float:
    """The program's delta rule against this reference's on delta layer
    ``l``'s input ``h``: the norm of the difference over the norm of the
    reference's output, over every position and head."""
    group, parts = solar.head_groups(l, p)
    program = _recurrence_program(_cfg(group), v)
    sums = [program(h, part) for part in parts]
    return math.sqrt(sum(float(d) for d, _ in sums)
                     / max(sum(float(w) for _, w in sums), 1e-30))


def tail(graph: Graph):
    """The chain whose backward the step's comparison takes: the last
    latent layer, every layer after it up to the final norm that feeds the
    one head (expert layers and delta layers), and the head.  -> ([chain,
    the latent layer first, the norm last], head)."""
    (head,) = graph.heads()
    first = graph.of_type('mla')[-1]
    chain = [l for l in graph.layers if l.index >= first.index
             and l.type != 'lm_head_loss']
    norm = chain[-1]
    if norm.type != 'rmsnorm' or norm.outs != [head.hidden] \
            or any(l.type not in ('moe', 'kda') for l in chain[1:-1]) \
            or any(a.outs != b.ins for a, b in zip(chain, chain[1:])) \
            or not any(l.type == 'kda' for l in chain):
        raise ValueError('kimi_linear: the last mla layer reaches the head '
                         'through more than expert and delta layers and a '
                         'norm, or no delta layer follows it')
    return chain, head


def held_leaves(graph: Graph):
    """{layer: why} of the layers every leaf of which the step is held to:
    the last delta layer and the latent layer before it."""
    chain, _ = tail(graph)
    return {[l for l in chain if l.type == 'kda'][-1].primary: 'kda_update',
            chain[0].primary: 'mla_update'}


def blockwise_log_probs(graph: Graph, params, ids, v: Variant = MODEL):
    """``run_graph`` at the cell's size beside a trainer's state, as
    ``solar_open2.blockwise_log_probs``: one layer a program, on the device,
    with that layer's parameters as they lie there; the head in blocks of
    positions whose log-probabilities go to the host one at a time.  -> a
    dict: ``want`` {loss node: (b, s, vocab)}, ``tie`` positions with a near
    tie, ``recurrence`` {delta layer: ``recurrence_error``}, ``kept`` {layer
    index: its input} for the layers of ``tail``'s chain, all on the
    host."""
    chain, _ = tail(graph)
    keep = {l.index for l in chain}
    values = {'0': jnp.asarray(ids, jnp.int32)}
    kept, errors = {}, {}
    tie = np.zeros(ids.shape[:1] + (graph.seq,), bool)
    out: Dict[str, np.ndarray] = {}
    with jax.default_matmul_precision('highest'):
        for l in graph.layers:
            p = glm._of(params, l)
            ins = [values[n] for n in l.ins]
            if l.type == 'lm_head_loss':
                head = glm._head_program(v)
                for node, h in zip(l.outs, ins):
                    out[node] = np.concatenate(
                        [np.asarray(head(h[:, a:a + HEAD_CHUNK], p['wmat']))
                         for a in range(0, h.shape[1], HEAD_CHUNK)], axis=1)
                continue
            if l.index in keep:
                kept[l.index] = np.asarray(ins[0])
            if l.type == 'kda':
                errors[l.name] = recurrence_error(l, ins[0], p, v)
            outs = _layer_program(l.type, _cfg(l), v)(ins, p)
            if l.type == 'moe':
                tie = tie | (np.asarray(outs[1]) < TIE_EPSILON)
                outs = outs[:1]
            values.update(zip(l.outs, outs))
    return {'want': out, 'tie': tie, 'recurrence': errors, 'kept': kept}


@functools.lru_cache(maxsize=None)
def _vjp_program(kind: str, cfg: tuple, v: Variant):
    """A layer's input's and leaves' cotangents from its output's."""
    l = Layer(-1, kind, '', [], [], dict(cfg), -1)
    return jax.jit(lambda h, p, cot: jax.vjp(
        lambda x, q: OPS[kind](l, [x], q, v)[0], h, p)[1](cot))


def _delta_layer_vjp(l: Layer, h, p, cot, v: Variant):
    """A delta layer's vjp a group of heads at a time: the layer's output
    less its input is the sum of its groups', so the input's cotangent is
    the groups' summed less the ``groups - 1`` extra copies of ``cot`` their
    residuals add; a leaf of a head's part is its group's, a leaf every
    head reads is the groups' sum.  -> (input's cotangent, {field: leaf's
    gradient}), on the host."""
    group, parts = solar.head_groups(l, p)
    program = _vjp_program(l.type, _cfg(group), v)
    found = [jax.device_get(program(h, part, cot)) for part in parts]
    dh = sum(np.asarray(g[0]) for g in found) \
        - (len(found) - 1) * np.asarray(cot)
    leaves = {f: np.asarray(
        np.concatenate([g[1][f] for g in found],
                       axis=solar.HEAD_PARTS[f][0])
        if f in solar.HEAD_PARTS else sum(g[1][f] for g in found))
        for f in found[0][1]}
    return dh, leaves


def step_gradients(graph: Graph, params, kept, ids,
                   v: Variant = MODEL) -> Dict[tuple, np.ndarray]:
    """{(layer, field): gradient of the step's loss} on the host, float32
    ``highest``, from ``blockwise_log_probs``' ``kept`` inputs: the head's
    weight and the final norm's gain, a block of positions at a time
    (``solar_open2._tail_program``); back through the chain of ``tail``:
    an expert layer a block of positions at a time (each position is its
    own there), a delta layer a group of heads at a time, keeping every leaf
    of the last one; and every leaf of the latent layer, its heads whole."""
    chain, head = tail(graph)
    norm, held = chain[-1], held_leaves(graph)
    y = label_matrix(graph, ids)[:, head.label_first:
                                 head.label_first + graph.seq]
    wt = head.weight * v.token_weights(graph.seq) / len(ids)
    blocks = [slice(a, a + HEAD_CHUNK) for a in range(0, graph.seq, HEAD_CHUNK)]
    grads: Dict[tuple, np.ndarray] = {}
    cots = []
    with jax.default_matmul_precision('highest'):
        grad = solar._tail_program(v, norm.getf('eps', 1e-5))
        gamma = glm._of(params, norm)['gamma']
        w = glm._of(params, head.layer)['wmat']
        for at in blocks:
            found = grad(gamma, w, jnp.asarray(kept[norm.index][:, at]),
                         jnp.asarray(y[:, at]), jnp.asarray(wt[at]))
            for key, g in zip(((norm.primary, 'gamma'),
                               (head.layer.primary, 'wmat')), found):
                grads[key] = grads.get(key, 0.0) + np.asarray(g)
            cots.append(np.asarray(found[2]))
            del found               # the head's gradient, before the next
        cot = np.concatenate(cots, axis=1)
        for l in reversed(chain[:-1]):
            p, h = glm._of(params, l), kept[l.index]
            if l.type == 'moe':
                program = _vjp_program(l.type, _cfg(l), v)
                cot = np.concatenate(
                    [np.asarray(program(jnp.asarray(h[:, at]), p,
                                        jnp.asarray(cot[:, at]))[0])
                     for at in blocks], axis=1)
                continue
            if l.type == 'kda':
                cot, leaves = _delta_layer_vjp(l, jnp.asarray(h), p,
                                               jnp.asarray(cot), v)
            else:                                    # the latent layer
                _, leaves = jax.device_get(_vjp_program(
                    l.type, _cfg(l), v)(jnp.asarray(h), p, jnp.asarray(cot)))
            if l.primary in held:
                grads.update(((l.primary, f), np.asarray(g))
                             for f, g in leaves.items())
    return grads


def program_step(trainer, graph: Graph, ids: np.ndarray) -> dict:
    """One real step of the program under test on ``ids``, as
    ``laguna_moe.program_step`` (one head: staged by its own
    ``stage_batch`` like a ring row, run by the ``update_staged`` the window
    times), with every leaf of the held layers beside the tail's."""
    held = held_leaves(graph)

    def fetch(tree):
        return {(k, f): np.asarray(jax.device_get(a))
                for k in held for f, a in tree[str(k)].items()}

    before = {'w': fetch(trainer.params), 'm1': fetch(trainer.opt_state['m1']),
              'm2': fetch(trainer.opt_state['m2'])}
    step = laguna_moe.program_step(trainer, graph, ids)
    for part, leaves in before.items():
        step[part].update(leaves)
    step['after'].update(fetch(trainer.params))
    return step


def step_numbers(graph: Graph, step: dict, side: dict) -> dict:
    """``glm_moe_lite.step_numbers``, with the held layers' leaves under
    ``kda_update`` and ``mla_update`` (a leaf, the norm of the difference
    of the changes over the norm of the reference's)."""
    found = glm.step_numbers(graph, step, side['step_loss'], side['grads'])
    for layer, part in held_leaves(graph).items():
        found[part] = {f'{k}.{f}': found['update'].pop(f'{k}.{f}')
                       for k, f in side['grads'] if k == layer}
    return found


def compared(side: dict, step: dict) -> dict:
    """``{name: [number, limit]}``: every number the verdict holds."""
    out = {'near_tie_share': [side['tie_share'], TIE_SHARE_MAX]}
    for node, n in side['numbers'].items():
        for key, (limit, _) in LIMITS.items():
            out[f'{node}.{key}'] = [n[key], limit]
    for layer, e in side['recurrence'].items():
        out[f'recurrence.{layer}'] = [e, RECURRENCE_TOLERANCE]
    out['step.loss'] = [step['loss'], STEP_LOSS_TOLERANCE]
    for part, limit in (('update', UPDATE_TOLERANCE),
                        ('kda_update', KDA_UPDATE_TOLERANCE),
                        ('mla_update', MLA_UPDATE_TOLERANCE)):
        for leaf, u in step[part].items():
            out[f'step.update.{leaf}'] = [u, limit]
    return out


def within_limits(side: dict, step: dict) -> bool:
    return all(np.isfinite(value) and value <= limit for value, limit in
               compared(side, step).values())


def attention_paths() -> Dict[str, str]:
    """{conf layer: kernel} of the ``attention.path`` events the program
    recorded as it traced its steps: which kernel each attention layer
    runs, for the run's log."""
    from cxxnet_tpu.obs import get_hub
    return {str(e['attrs'].get('layer')): e['attrs'].get('kernel')
            for e in get_hub().events()
            if e['name'] == 'attention.path'}


def reference_side(graph: Graph, params, ids, got,
                   v: Variant = MODEL) -> dict:
    """All the reference has to say about ``ids`` under ``params``, on the
    host: the numbers of the program's probabilities ``got`` against its
    own, the share of near ties, the program's delta rule against its own,
    its loss of the step, its gradients of the leaves the step is held to.
    Taken before the program's step moves ``params``."""
    run = blockwise_log_probs(graph, params, ids, v)
    grads = step_gradients(graph, params, run['kept'], ids, v)
    numbers, step_loss = glm.measure(graph, got, run['want'], run['tie'],
                                     ids, v)
    return {'numbers': numbers, 'tie_share': float(np.mean(run['tie'])),
            'recurrence': run['recurrence'], 'step_loss': step_loss,
            'grads': grads}


def judge(graph: Graph, side: dict, step: dict):
    """-> (the step's numbers against ``side``, inside every limit?)"""
    found = step_numbers(graph, step, side)
    return found, within_limits(side, found)


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
                           recurrence=side['recurrence'], step=step,
                           attention_paths=attention_paths()),
            'tolerance': dict({k: limit for k, (limit, _) in LIMITS.items()},
                              position=TOLERANCE, tie_epsilon=TIE_EPSILON,
                              tie_share=TIE_SHARE_MAX,
                              recurrence=RECURRENCE_TOLERANCE,
                              step_loss=STEP_LOSS_TOLERANCE,
                              update=UPDATE_TOLERANCE,
                              kda_update=KDA_UPDATE_TOLERANCE,
                              mla_update=MLA_UPDATE_TOLERANCE),
            'compared': compared(side, step), 'ok': ok}
