"""Plain reference for a ``solar_open2`` conf (Solar-Open2-250B and its tiny
twin): in every period of four layers one gated softmax-attention layer
without positions (grouped heads, a sigmoid gate a channel) and three layers
of Kimi Delta Attention (a short convolution, a gated delta rule with one
decay a channel, ``beta`` up to 2, a low-rank sigmoid gate over the
normalised output), each over expert layers with a sigmoid top-k router over
experts of which the chip holds a share and a shared expert; one head with
a cross-entropy a token, and the update Adam makes of the step's gradient.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no recomputation,
no grouping.  **The delta rule is the token-by-token recurrence** ``S_t = (I
- beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t =
S_t^T q_t / sqrt(d_k)``, a ``lax.scan`` over positions (in blocks of
``SCAN_BLOCK`` positions, each block checkpointed, so that a gradient holds
one state a block): no chunks, no triangular solve.  Attention is a full
masked softmax, in blocks of queries where the sequence is long.  The
equations are the published ones (Kimi Linear, arXiv 2510.26692, section 3,
for the keys ``linear_attn_config`` and ``kda_*``; arXiv 2505.06708 for the
elementwise gate), written down again here; the model imports nothing from
``cxxnet_tpu``, and the comparison calls the program under test as it is
(its forward, its step, its delta rule).  From
``references/glm_moe_lite.py`` it takes what is no model's own:
the graph of a sequence conf, the rules of the layer types every family
shares, the comparison's arithmetic; from ``references/laguna_moe.py`` the
blocked masked softmax.  Its rules for ``gqa``, ``kda`` and ``moe``, its
probe and its limits are its own.

Like the other references it exports ``build_graph``, ``label_matrix``,
``forward``, ``compare`` and ``train_flops_per_step``.  It is given the
chip's share of heads, experts and vocabulary by the conf, as the program
is.

Departures from the published model, each also under ``assumed`` in the
configuration's file (``config.json`` has no key for them): the router's
score is a sigmoid with ``noaux_tc``'s correction bias at zero (the
program's ``router_bias`` leaf, read here); the softmax layer's gate is
``sigmoid(x W_g)`` a channel of each head, applied before ``W_o``; the delta
layer's output gate has a bias ``b_g``; ``q`` is scaled by ``1 / sqrt(d_k)``;
``L2Norm`` is ``x / sqrt(sum x^2 + 1e-6)``.
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
from .glm_moe_lite import (F32, Graph, Layer, build_graph,  # noqa: F401
                           gated, log_probs, rms_norm)

# --- the layer equations ----------------------------------------------------


class Variant(NamedTuple):
    """Switches that make the reference *wrong* on purpose: the sensitivity
    probe shows that each leaves the comparison's limits (PERF.md 6).
    The default is the model."""
    beta_max: float = 2.0               # kda_allow_neg_eigval's 2
    conv: bool = True                   # the short convolution
    erase: bool = True                  # the delta rule's - beta k k^T S
    kda_gate: bool = True
    rope: bool = False                  # rotary in the softmax layer
    gqa_gate: bool = True
    router_score: str = 'sigmoid'
    matmul_dtype: Optional[str] = None  # round every product's operands
    # the delta rule as a kernel in this type would run it: its products'
    # operands, and the state it carries from chunk to chunk, rounded
    recurrence_dtype: Optional[str] = None
    # the delta rule's q, k and v rounded before it, the rule float32
    recurrence_inputs_dtype: Optional[str] = None
    recurrence_gradient: bool = True    # False: no gradient through the rule
    loss_tokens: str = 'all'            # ``glm_moe_lite.Variant``'s

    token_weights = glm.Variant.token_weights
    lower = glm.Variant.lower

    def lower_recurrence(self, x):
        if self.recurrence_dtype is None:
            return self.lower(x)
        return _rounded(x, self.recurrence_dtype)

    def lower_recurrence_inputs(self, x):
        if self.recurrence_inputs_dtype is None:
            return x
        return _rounded(x, self.recurrence_inputs_dtype)


def _rounded(x, dtype: str):
    """``x`` rounded to ``dtype``'s precision, kept float32.  A round trip
    of ``astype`` is no rounding on the TPU: its compiler drops the pair of
    converts where no product reads the narrow type (on the chip the delta
    rule fed so read the model's own number)."""
    f = jnp.finfo(jnp.dtype(dtype))
    return jax.lax.reduce_precision(x, exponent_bits=f.nexp,
                                    mantissa_bits=f.nmant)


MODEL = Variant()
#: the probe: each of these must leave the comparison's limits
#: (``selftest/solar.py`` at the tiny size, ``selftest/solar_sensitivity.py``
#: on the chip)
PROBE = {
    'beta up to 1 (no negative eigenvalue)': Variant(beta_max=1.0),
    'short convolution left out': Variant(conv=False),
    'the delta rule without its erase': Variant(erase=False),
    'the delta layer\'s gate left out': Variant(kda_gate=False),
    'rotary in the softmax layer': Variant(rope=True),
    'the softmax layer\'s gate left out': Variant(gqa_gate=False),
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
}


def mm(v: Variant, a, b):
    return jnp.matmul(v.lower(a), v.lower(b))


def gqa(l: Layer, h, p, v: Variant = MODEL):
    """Grouped heads, causal over every position; no positions unless the
    probe asks; ``o * sigmoid(x W_g)`` a channel of each head."""
    b, s, _ = h.shape
    nh, nkv, hd = l.geti('nhead'), l.geti('nkvhead'), l.geti('head_dim')
    x = rms_norm(h, p['norm'], l.getf('eps', 1e-5))
    q = mm(v, x, p['wq']).reshape(b, s, nh, hd)
    k = mm(v, x, p['wk']).reshape(b, s, nkv, hd)
    val = mm(v, x, p['wv']).reshape(b, s, nkv, hd)
    if v.rope:
        inv = laguna_moe.yarn_frequencies(hd, 10000.0, 1.0, 0, 32.0, 1.0)
        q, k = laguna_moe.rotate(q, inv, 1.0), laguna_moe.rotate(k, inv, 1.0)
    k, val = (jnp.repeat(a, nh // nkv, axis=2) for a in (k, val))
    o = laguna_moe.attention(v, q, k, val, 1.0 / math.sqrt(hd), 0)
    if v.gqa_gate:
        o = o * jax.nn.sigmoid(mm(v, x, p['wgate'])).reshape(b, s, nh, hd)
    return h + mm(v, o.reshape(b, s, nh * hd), p['wo'])


SCAN_BLOCK = 256             # positions a checkpointed block of the scan
CARRY = 64                   # positions between the states a chunked kernel
#                              carries (``recurrence_dtype``)


def delta_rule(v: Variant, q, k, val, log_alpha, beta):
    """The recurrence, position by position: ``q``, ``k``, ``log_alpha``
    (b, s, h, dk), ``val`` (b, s, h, dv), ``beta`` (b, s, h) -> ``S_t^T
    q_t`` (b, s, h, dv)."""
    b, s, nh, dk = k.shape
    r = v.lower_recurrence

    def one(state, xs):
        qt, kt, vt, gt, bt, t = xs             # (b, h, dk) ..., (b, h), (1,)
        state = jnp.exp(gt)[..., None] * state
        if v.erase:
            seen = jnp.einsum('bhk,bhkv->bhv', r(kt), r(state))
            state = state - bt[..., None, None] * kt[..., :, None] \
                * seen[..., None, :]
        state = state + bt[..., None, None] * r(kt)[..., :, None] \
            * r(vt)[..., None, :]
        if v.recurrence_dtype is not None:
            state = jnp.where(t % CARRY == CARRY - 1, r(state), state)
        return state, jnp.einsum('bhkv,bhk->bhv', r(state), r(qt))

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(one, state, xs)

    pad = -s % SCAN_BLOCK if s > SCAN_BLOCK else 0
    xs = [jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
          for a in (q, k, val, log_alpha, beta)]
    xs.append(jnp.arange(s + pad)[None])        # the position, (1, s)
    n = (s + pad) // min(s + pad, SCAN_BLOCK)
    # (blocks, positions a block, b, ...): positions lead inside a block
    xs = [jnp.moveaxis(a, 1, 0).reshape((n, -1) + a.shape[:1] + a.shape[2:])
          for a in xs]
    _, out = jax.lax.scan(block, jnp.zeros((b, nh, dk, val.shape[-1]), F32),
                          xs)
    return jnp.moveaxis(out.reshape((-1,) + out.shape[2:]), 0, 1)[:, :s]


def kda_inputs(l: Layer, h, p, v: Variant = MODEL):
    """The delta layer up to its recurrence: -> (``x``, ``q``, ``k``,
    ``val``, ``log_alpha``, ``beta``) as ``delta_rule`` takes them."""
    b, s, _ = h.shape
    nh, hd, eps = l.geti('nhead'), l.geti('head_dim'), l.getf('eps', 1e-5)
    x = rms_norm(h, p['norm'], eps)

    def conv(a, w):
        """Causal depthwise convolution over positions, ``w`` (taps, c)."""
        if not v.conv:
            return a
        taps = w.shape[0]
        a = jnp.pad(a, ((0, 0), (taps - 1, 0), (0, 0)))
        return sum(a[:, j:j + s] * w[j] for j in range(taps))

    def heads(a):
        return a.reshape(b, s, nh, hd)

    def l2(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    q = l2(heads(jax.nn.silu(conv(mm(v, x, p['wq']), p['conv_q']))))
    k = l2(heads(jax.nn.silu(conv(mm(v, x, p['wk']), p['conv_k']))))
    val = heads(jax.nn.silu(conv(mm(v, x, p['wv']), p['conv_v'])))
    log_alpha = -jnp.exp(p['a_log'])[:, None] * jax.nn.softplus(heads(
        mm(v, mm(v, x, p['wa_down']), p['wa_up']) + p['dt_bias']))
    beta = v.beta_max * jax.nn.sigmoid(mm(v, x, p['wbeta']))
    return x, q, k, val, log_alpha, beta


def recurrence(v: Variant, q, k, val, log_alpha, beta):
    """``S_t^T q_t / sqrt(d_k)`` from ``kda_inputs``' inputs."""
    r = v.lower_recurrence_inputs
    o = delta_rule(v, r(q), r(k), r(val), log_alpha, beta) \
        / math.sqrt(q.shape[-1])
    return o if v.recurrence_gradient else jax.lax.stop_gradient(o)


def kda(l: Layer, h, p, v: Variant = MODEL):
    b, s, _ = h.shape
    nh, hd, eps = l.geti('nhead'), l.geti('head_dim'), l.getf('eps', 1e-5)
    x, q, k, val, log_alpha, beta = kda_inputs(l, h, p, v)
    o = rms_norm(recurrence(v, q, k, val, log_alpha, beta), p['o_norm'], eps)
    if v.kda_gate:
        o = o * jax.nn.sigmoid((mm(v, mm(v, x, p['wg_down']), p['wg_up'])
                                + p['g_bias']).reshape(b, s, nh, hd))
    return h + mm(v, o.reshape(b, s, nh * hd), p['wo'])


def route(l: Layer, x, p, v: Variant = MODEL):
    """The chosen experts, their weights ``scaling * s_e / sum_chosen s``
    and the gap between the last choice's ``s + bias`` and the first left
    out (a choice that rounding can flip where it is small)."""
    k = l.geti('experts_per_token')
    logits = jnp.matmul(x, p['router'])              # never lowered: float32
    s = jax.nn.sigmoid(logits) if v.router_score == 'sigmoid' \
        else jax.nn.softmax(logits, axis=-1)
    ranked = jnp.sort(s + p['router_bias'], axis=-1)[..., ::-1]
    _, idx = jax.lax.top_k(s + p['router_bias'], k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    weights = l.getf('routed_scaling_factor', 1.0) * chosen \
        / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx, weights, ranked[..., k - 1] - ranked[..., k]


def moe(l: Layer, h, p, v: Variant = MODEL):
    """-> (output, gap): the held experts' part of the routed sum, a loop
    over them, plus the shared expert."""
    x = rms_norm(h, p['norm'], l.getf('eps', 1e-5))
    idx, weights, gap = route(l, x, p, v)
    first = l.geti('expert_first')
    y = jnp.zeros_like(x)
    for e in range(p['wgate'].shape[0]):
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1)
        y = y + w_e[..., None] * gated(v, x, p['wgate'][e], p['wup'][e],
                                       p['wdown'][e])
    if 'sgate' in p:
        y = y + gated(v, x, p['sgate'], p['sup'], p['sdown'])
    return h + y, gap


#: ``glm_moe_lite``'s table (``seq_slice``, ``embedding``, ``rmsnorm``,
#: ``lm_head_loss`` as there) with this family's three rules
OPS = dict(glm.OPS)
OPS.update({
    'gqa': lambda l, ins, p, v: [gqa(l, ins[0], p, v)],
    'kda': lambda l, ins, p, v: [kda(l, ins[0], p, v)],
    'moe': lambda l, ins, p, v: list(moe(l, ins[0], p, v)),
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

def forward_macs(graph: Graph) -> Dict[int, float]:
    """Multiply-accumulates of one sequence's forward pass, by layer index:
    ``glm_moe_lite.forward_macs`` for the layer types it knows (of the
    routed experts the assignments a balanced router sends here), a ``gqa``
    layer's five products (the elementwise gate's ``d x heads * dim`` among
    them) and its scores and their product with the values over every
    causal pair, and a ``kda`` layer's products and its recurrence in the
    chunkwise form at chunks of 64 (``benchmark/kda_costs.py``).  The
    convolution, norms, gates' sigmoids and the decay's exponentials are
    left out, as MFU conventions leave elementwise work out."""
    s, d = graph.seq, graph.width
    out = glm.forward_macs(graph)
    for l in graph.of_type('gqa'):
        nh, nkv, hd = l.geti('nhead'), l.geti('nkvhead'), l.geti('head_dim')
        proj = 3 * d * nh * hd + 2 * d * nkv * hd
        out[l.index] = s * proj + s * (s + 1) // 2 * nh * 2 * hd
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

#: As ``glm_moe_lite`` and ``laguna_moe``: the program computes its products
#: in bfloat16 on float32 masters and its delta rule in float32, the
#: reference all in float32.  On one seeded sequence of the cell's length
#: that no ring holds are compared:
#:
#: - the evaluation-mode log-probabilities at every position (differences
#:   over the spread of the reference's).  A router's eighth choice over 320
#:   flips on rounding where the eighth and ninth scores nearly tie
#:   (``TIE_EPSILON``, in sigmoid scores plus the zero bias); such positions
#:   are counted, and the limits are on shares;
#: - **the program's own delta rule** (``DeltaAttentionLayer.recurrence``,
#:   what the timed step runs) on each delta layer's inputs as this
#:   reference makes them, against the token-by-token rule on the same
#:   inputs: the norm of the difference over the norm of the reference's
#:   output, every position and head.  The bf16 products ahead of the rule
#:   move the log-probabilities far more than a rule rounded to bfloat16
#:   would, so the rule is held here, on its own inputs;
#: - the timed program's own step: its loss, the change it makes to the
#:   head's weight and the final norm, and **the change it makes to every
#:   leaf of the last delta layer**, each against plain Adam on the
#:   reference's gradient from the trainer's own moments (a state left
#:   unchanged reads 1).  The last delta layer's gradient comes back through
#:   the head, the final norm and the expert layer after it, a block of
#:   positions at a time, then through the delta layer, its rule and its
#:   short convolutions, a group of heads at a time.
#:
#: Each limit lies between the largest reading of the program over seeds on
#: the chip (bf16 products, after a run's 24-30 steps; PERF.md 6) and the
#: smallest of the probe's faults it is there to catch, with the more room
#: above the program's reading.  Near ties: with eight of 320 sigmoid scores
#: chosen in four layers, 96-97% of positions have a gap under
#: ``TIE_EPSILON`` somewhere; a softmax router's scores, a few thousandths
#: each, tie everywhere (1.0).
LIMITS = {
    # name: (limit, the readings it lies between)
    'mean': (0.03, 'bf16 0.0192-0.0206; a softmax router 0.040, beta up to '
             '1 0.12, float8 0.50'),
    'median_position': (0.13, 'bf16 0.092-0.096; a softmax router 0.144, '
                        'beta up to 1 0.64, float8 2.7'),
    'off_share': (0.1, 'positions off by more than TOLERANCE: bf16 '
                  '0.024-0.031; a softmax router 0.21, every other fault of '
                  'the model 0.999-1.0'),
    'off_untied_share': (0.01, 'those of them with no near tie to explain '
                         'it: bf16 0-0.00024; every fault of the model but '
                         'the router 0.035-0.039'),
}
TOLERANCE = glm.TOLERANCE
TIE_EPSILON = 4e-3
TIE_SHARE_MAX = 0.99        # bf16 0.961-0.968; a softmax router 1.0
STEP_LOSS_TOLERANCE = 1e-3  # bf16 2.8e-7 to 4.7e-4 (after 24-30 steps);
#                             float8 1.9e-3, an eighth of the tokens 0.14
UPDATE_TOLERANCE = 0.05     # bf16 0.0011-0.0026 (the norm), 0.016-0.024
#                             (the head); float8 0.32, an eighth of the
#                             tokens dropped 0.17, a state left unchanged 1
RECURRENCE_TOLERANCE = 1e-3     # the program 3.4e-5 to 7.8e-5; the rule
#                                 as a bf16 kernel 3.6e-3, fed bf16 q, k, v
#                                 3.0e-3, float8 products 0.010, no erase
#                                 0.49
KDA_UPDATE_TOLERANCE = 0.06     # a leaf: bf16 0.0011-0.022 (after 24-30
#                                 steps); no gradient through the rule 0.15-
#                                 0.37 (but A_log 0.011), float8 0.12-0.37,
#                                 no erase 0.14-0.30, the layer frozen 1
HEAD_CHUNK = glm.HEAD_CHUNK
#: heads a program of the delta rule's check and of the delta layer's
#: gradient: the reference runs beside a trainer that fills 93% of the chip
KDA_GROUP = 4


def _cfg(l: Layer) -> tuple:
    return tuple(sorted(l.cfg.items()))


@functools.lru_cache(maxsize=None)
def _layer_program(kind: str, cfg: tuple, v: Variant):
    """One compiled program a kind of layer and its pairs: the three delta
    layers of one conf are one program."""
    l = Layer(-1, kind, '', [], [], dict(cfg), -1)
    return jax.jit(lambda ins, p: OPS[kind](l, ins, p, v))


#: the leaves of a delta layer that hold a part a head: (the axis of the
#: heads, whether a head has ``head_dim`` entries there or one); the other
#: leaves serve every head whole
HEAD_PARTS = {'wq': (1, True), 'wk': (1, True), 'wv': (1, True),
              'conv_q': (1, True), 'conv_k': (1, True), 'conv_v': (1, True),
              'wa_up': (1, True), 'dt_bias': (0, True), 'wg_up': (1, True),
              'g_bias': (0, True), 'wo': (0, True), 'a_log': (0, False),
              'wbeta': (1, False)}


def head_groups(l: Layer, p):
    """Delta layer ``l`` with leaves ``p`` as groups of ``KDA_GROUP`` heads:
    -> (the layer of one group, [each group's leaves]).  The layer's output
    less its input is the sum of its groups' outputs less theirs."""
    nh, hd = l.geti('nhead'), l.geti('head_dim')
    g = math.gcd(nh, KDA_GROUP)
    group = Layer(l.index, l.type, l.name, l.ins, l.outs,
                  dict(l.cfg, nhead=str(g)), l.primary)
    parts = []
    for a in range(0, nh, g):
        part = dict(p)
        for f, (axis, wide) in HEAD_PARTS.items():
            unit = hd if wide else 1
            part[f] = p[f][(slice(None),) * axis
                           + (slice(a * unit, (a + g) * unit),)]
        parts.append(part)
    return group, parts


@functools.lru_cache(maxsize=None)
def _recurrence_program(cfg: tuple, v: Variant):
    """For a group of a delta layer's heads: the program's own delta rule
    and this reference's on the inputs this reference makes.  -> (the sum of
    the squared differences, the sum of the reference's squares)."""
    from cxxnet_tpu.layers.sequence import DeltaAttentionLayer
    l = Layer(-1, 'kda', '', [], [], dict(cfg), -1)

    def run(h, p):
        _, q, k, val, log_alpha, beta = kda_inputs(l, h, p, v)
        want = recurrence(v, q, k, val, log_alpha, beta)
        major = [jnp.moveaxis(a, 2, 1) for a in (q, k, val, log_alpha, beta)]
        got = jnp.moveaxis(DeltaAttentionLayer.recurrence(*major), 1, 2)
        return jnp.sum(jnp.square(got - want)), jnp.sum(jnp.square(want))
    return jax.jit(run)


def recurrence_error(l: Layer, h, p, v: Variant = MODEL) -> float:
    """The program's delta rule against this reference's on delta layer
    ``l``'s input ``h``: the norm of the difference over the norm of the
    reference's output, over every position and head."""
    group, parts = head_groups(l, p)
    program = _recurrence_program(_cfg(group), v)
    sums = [program(h, part) for part in parts]
    return math.sqrt(sum(float(d) for d, _ in sums)
                     / max(sum(float(w) for _, w in sums), 1e-30))


def kda_tail(graph: Graph):
    """The chain whose backward the step's comparison takes: the last delta
    layer, the expert layers after it and the final norm that feeds the one
    head.  -> (delta layer, [expert layers], norm, head)."""
    (head,) = graph.heads()
    last = graph.of_type('kda')[-1]
    *moes, norm = [l for l in graph.layers if l.index > last.index
                   and l.type != 'lm_head_loss']
    chain = [last, *moes, norm]
    if norm.type != 'rmsnorm' or norm.outs != [head.hidden] \
            or any(l.type != 'moe' for l in moes) \
            or any(a.outs != b.ins for a, b in zip(chain, chain[1:])):
        raise ValueError('solar_open2: the last kda layer reaches the head '
                         'through more than expert layers and a norm')
    return last, moes, norm, head


def blockwise_log_probs(graph: Graph, params, ids, v: Variant = MODEL):
    """``run_graph`` at the cell's size beside a trainer's state, as
    ``laguna_moe.blockwise_log_probs``: one layer a program, on the device,
    with that layer's parameters as they lie there; the head in blocks of
    positions whose log-probabilities go to the host one at a time.  -> a
    dict: ``want`` {loss node: (b, s, vocab)}, ``tie`` positions with a near
    tie, ``recurrence`` {delta layer: ``recurrence_error``}, ``kept`` {layer
    index: its input} for the layers of ``kda_tail`` but the head, all on
    the host: what the reference keeps stays off a chip that the trainer
    fills."""
    last, moes, norm, _ = kda_tail(graph)
    keep = {last.index, norm.index, *(l.index for l in moes)}
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
def _tail_program(v: Variant, eps: float):
    """Gradient, with respect to the final norm's gain, the head's weight
    and the norm's input, of a block of positions' weighted
    cross-entropy."""
    def nll(gamma, w, x, y, wt):
        logp = log_probs(v, rms_norm(x, gamma, eps), w)
        return -jnp.sum(jnp.take_along_axis(logp, y[..., None],
                                            axis=-1)[..., 0] * wt)
    return jax.jit(jax.grad(nll, argnums=(0, 1, 2)))


@functools.lru_cache(maxsize=None)
def _input_vjp_program(kind: str, cfg: tuple, v: Variant):
    """A layer's input's cotangent from its output's."""
    l = Layer(-1, kind, '', [], [], dict(cfg), -1)
    return jax.jit(lambda h, p, cot: jax.vjp(
        lambda x: OPS[kind](l, [x], p, v)[0], h)[1](cot)[0])


@functools.lru_cache(maxsize=None)
def _leaf_vjp_program(kind: str, cfg: tuple, v: Variant):
    """A layer's leaves' gradient from its output's cotangent."""
    l = Layer(-1, kind, '', [], [], dict(cfg), -1)
    return jax.jit(lambda h, p, cot: jax.vjp(
        lambda q: OPS[kind](l, [h], q, v)[0], p)[1](cot)[0])


def step_gradients(graph: Graph, params, kept, ids,
                   v: Variant = MODEL) -> Dict[tuple, np.ndarray]:
    """{(layer, field): gradient of the step's loss} on the host, float32
    ``highest``, from ``blockwise_log_probs``' ``kept`` inputs: the head's
    weight and the final norm's gain, as ``glm_moe_lite.tail_gradients``
    has them, and every leaf of the last delta layer (``kda_tail``).  The
    head and the norm are taken back a block of positions at a time, then
    the expert layers after the delta layer (each position is its own
    there), then the delta layer a group of heads at a time; what passes
    from one to the next waits on the host, and the device holds one
    block's or one group's work at a time."""
    last, moes, norm, head = kda_tail(graph)
    y = label_matrix(graph, ids)[:, head.label_first:
                                 head.label_first + graph.seq]
    wt = head.weight * v.token_weights(graph.seq) / len(ids)
    blocks = [slice(a, a + HEAD_CHUNK) for a in range(0, graph.seq, HEAD_CHUNK)]
    grads: Dict[tuple, np.ndarray] = {}
    cots = []
    with jax.default_matmul_precision('highest'):
        grad = _tail_program(v, norm.getf('eps', 1e-5))
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
        for l in reversed(moes):
            program = _input_vjp_program(l.type, _cfg(l), v)
            cots = [np.asarray(program(jnp.asarray(kept[l.index][:, at]),
                                       glm._of(params, l), jnp.asarray(c)))
                    for at, c in zip(blocks, cots)]
        cot = jnp.asarray(np.concatenate(cots, axis=1))
        h = jnp.asarray(kept[last.index])
        group, parts = head_groups(last, glm._of(params, last))
        program = _leaf_vjp_program(last.type, _cfg(group), v)
        found = [jax.device_get(program(h, part, cot)) for part in parts]
    for f in found[0]:
        grads[(last.primary, f)] = np.asarray(
            np.concatenate([g[f] for g in found], axis=HEAD_PARTS[f][0])
            if f in HEAD_PARTS else sum(g[f] for g in found))
    return grads


def program_step(trainer, graph: Graph, ids: np.ndarray) -> dict:
    """One real step of the program under test on ``ids``, as
    ``laguna_moe.program_step`` (one head: staged by its own
    ``stage_batch`` like a ring row, run by the ``update_staged`` the window
    times), with every leaf of the last delta layer beside the tail's."""
    last = kda_tail(graph)[0]

    def fetch(tree):
        return {(last.primary, f): np.asarray(jax.device_get(a))
                for f, a in tree[str(last.primary)].items()}

    before = {'w': fetch(trainer.params), 'm1': fetch(trainer.opt_state['m1']),
              'm2': fetch(trainer.opt_state['m2'])}
    step = laguna_moe.program_step(trainer, graph, ids)
    for part, leaves in before.items():
        step[part].update(leaves)
    step['after'].update(fetch(trainer.params))
    return step


def step_numbers(graph: Graph, step: dict, side: dict) -> dict:
    """``glm_moe_lite.step_numbers``, with the last delta layer's leaves
    under ``kda_update`` (a leaf, the norm of the difference of the changes
    over the norm of the reference's)."""
    found = glm.step_numbers(graph, step, side['step_loss'], side['grads'])
    last = kda_tail(graph)[0]
    found['kda_update'] = {f'{k}.{f}': found['update'].pop(f'{k}.{f}')
                           for k, f in side['grads'] if k == last.primary}
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
    for leaf, u in step['update'].items():
        out[f'step.update.{leaf}'] = [u, UPDATE_TOLERANCE]
    for leaf, u in step['kda_update'].items():
        out[f'step.update.{leaf}'] = [u, KDA_UPDATE_TOLERANCE]
    return out


def within_limits(side: dict, step: dict) -> bool:
    return all(np.isfinite(value) and value <= limit for value, limit in
               compared(side, step).values())


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
                           recurrence=side['recurrence'], step=step),
            'tolerance': dict({k: limit for k, (limit, _) in LIMITS.items()},
                              position=TOLERANCE, tie_epsilon=TIE_EPSILON,
                              tie_share=TIE_SHARE_MAX,
                              recurrence=RECURRENCE_TOLERANCE,
                              step_loss=STEP_LOSS_TOLERANCE,
                              update=UPDATE_TOLERANCE,
                              kda_update=KDA_UPDATE_TOLERANCE),
            'compared': compared(side, step), 'ok': ok}
