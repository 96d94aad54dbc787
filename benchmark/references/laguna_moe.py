"""Plain reference for a ``laguna`` conf (Laguna-S-2.1 and its tiny twins):
grouped-query attention in which window and full layers alternate, the
number of query heads differs by layer, rotary positions are of two kinds
(plain over the whole head, or YaRN-scaled over the first half of it), a
sigmoid gate a head scales attention's output, a dense SwiGLU layer, expert
layers with a softmax top-k router over experts of which the chip holds a
share, a shared expert, one head with a cross-entropy a token, and the
update Adam makes of the step's gradient.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no recomputation, no
grouping (key/value heads are repeated by ``jnp.repeat``, the held experts
are a loop, each over every token, masked by the routing), attention as a
full masked softmax, in blocks of queries only where the sequence is long.
Nothing is imported from ``cxxnet_tpu``: the layer equations are the
published ones (``config.json`` of the model; Hugging Face's
``_compute_yarn_parameters`` for ``rope_type: yarn``; arXiv 2505.06708 for
the head-wise gate), written down again here.  From
``references/glm_moe_lite.py`` it takes what is no model's own: the graph of
a sequence conf, the rules of the layer types both families share, the
comparison's arithmetic (``measure``, ``tail_gradients``, ``adam_change``,
``step_numbers``).  Its rules for ``gqa`` and ``moe``, its probe and its
limits are its own.

Like the other references it exports ``build_graph``, ``label_matrix``,
``forward``, ``compare`` and ``train_flops_per_step``.

Departures from the published model, each also under ``assumed`` in the
configuration's file (``config.json`` has no key for them): the gate is
``sigmoid(x W_g)`` from the layer's normalised input, one number a head and
position, applied to the head's output before ``W_o``; the router's score is
a softmax over all experts with no correction bias (the program's
``router_bias`` leaf is zero and is not read here); no norm on ``q`` and
``k``; no gate on the shared expert; the window is ``i - j < sliding_window``;
rotary pairs are half-split inside the rotated part (``x[i]`` with ``x[i +
rotary_dims / 2]``).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import attention_costs
from . import glm_moe_lite as glm
from .glm_moe_lite import (F32, Graph, Layer, build_graph,  # noqa: F401
                           gated, log_probs, rms_norm)

# --- the layer equations ----------------------------------------------------


class Variant(NamedTuple):
    """Switches that make the reference *wrong* on purpose: the sensitivity
    probe shows that each leaves the comparison's limits (PERF.md 6, PR 36).
    The default is the model."""
    window_scale: float = 1.0           # 0: no window; 2: a block too wide
    grouping: str = 'blocks'            # 'strided': head h reads h % kv_heads
    gate: bool = True
    rotary_all_dims: bool = False       # a full layer rotates its whole head
    yarn: bool = True
    router_score: str = 'softmax'
    shared_expert: bool = True
    matmul_dtype: Optional[str] = None  # round every product's operands
    loss_tokens: str = 'all'            # ``glm_moe_lite.Variant``'s

    token_weights = glm.Variant.token_weights
    lower = glm.Variant.lower


MODEL = Variant()
#: the probe: each of these must leave the comparison's limits
#: (``selftest/laguna.py`` at the tiny size, ``selftest/laguna_sensitivity.py``
#: on the chip)
PROBE = {
    'window left out': Variant(window_scale=0.0),
    'window a block too wide': Variant(window_scale=2.0),
    'key/value heads strided (h % 8)': Variant(grouping='strided'),
    'gate left out': Variant(gate=False),
    'rotary on the whole head in a full layer': Variant(rotary_all_dims=True),
    'YaRN left out': Variant(yarn=False),
    'sigmoid router': Variant(router_score='sigmoid'),
    'products in float8_e4m3': Variant(matmul_dtype='float8_e4m3fn'),
    # faults of the step alone (the log-probabilities stay the model's)
    'an eighth of the tokens dropped from the loss':
        Variant(loss_tokens='all but the last eighth'),
    'the loss over every other token': Variant(loss_tokens='every other'),
}


def mm(v: Variant, a, b):
    return jnp.matmul(v.lower(a), v.lower(b))


def yarn_frequencies(dims: int, theta: float, factor: float,
                     original_positions: int, beta_fast: float,
                     beta_slow: float) -> np.ndarray:
    """``rope_type: yarn`` as ``_compute_yarn_parameters`` computes it: of
    the ``dims / 2`` pairs, those that turn more than ``beta_fast`` times
    over the original context keep ``theta ** (-2i / dims)``, those that
    turn fewer than ``beta_slow`` times take it over ``factor``, and between
    the two pairs where those counts fall (rounded outward) a linear ramp
    mixes the two."""
    i = np.arange(dims // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / dims)
    if factor <= 1.0:
        return plain

    def pair(turns):
        return dims * math.log(original_positions / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), dims - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return plain / factor * (1.0 - keep) + plain * keep


def rotate(x, inv_freq: np.ndarray, attention_factor: float):
    """``x``: (b, s, heads, dim); the first ``2 * len(inv_freq)`` components
    rotated by position (index along ``s``), pair ``i`` with ``i +
    len(inv_freq)``, ``cos`` and ``sin`` times ``attention_factor``; the
    rest passed through."""
    half = len(inv_freq)
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(ang) * attention_factor, F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * attention_factor, F32)[None, :, None, :]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


ATTENTION_BLOCK = 256        # queries a block, where the sequence is longer


def attention(v: Variant, q, k, val, scale, window: int):
    """``q``: (b, s, h, d), ``k``, ``val``: (b, s, h, d) with the key/value
    heads already repeated -> (b, s, h, d).  Position ``i`` sees ``j <= i``
    and, with a ``window``, only ``i - j < window``."""
    s = q.shape[1]

    def block(qb, first):
        scores = jnp.einsum('bqhd,bkhd->bhqk', v.lower(qb), v.lower(k)) * scale
        rows = first + jnp.arange(qb.shape[1])[:, None]
        cols = jnp.arange(s)[None, :]
        keep = cols <= rows
        if window:
            keep = keep & (rows - cols < window)
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return jnp.einsum('bhqk,bkhd->bqhd', v.lower(probs), v.lower(val))

    if s <= ATTENTION_BLOCK or s % ATTENTION_BLOCK:
        return block(q, 0)
    n = s // ATTENTION_BLOCK
    qs = jnp.moveaxis(q.reshape(q.shape[0], n, ATTENTION_BLOCK,
                                *q.shape[2:]), 1, 0)
    out = jax.lax.map(lambda a: block(a[0], a[1]),
                      (qs, jnp.arange(n) * ATTENTION_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def gqa(l: Layer, h, p, v: Variant = MODEL):
    b, s, _ = h.shape
    nh, nkv, hd = l.geti('nhead'), l.geti('nkvhead'), l.geti('head_dim')
    x = rms_norm(h, p['norm'], l.getf('eps', 1e-5))
    q = mm(v, x, p['wq']).reshape(b, s, nh, hd)
    k = mm(v, x, p['wk']).reshape(b, s, nkv, hd)
    val = mm(v, x, p['wv']).reshape(b, s, nkv, hd)
    window = int(l.geti('window') * v.window_scale)
    dims = l.geti('rotary_dims') or hd
    if v.rotary_all_dims and not l.geti('window'):
        dims = hd
    factor = l.getf('rope_factor', 1.0) if v.yarn else 1.0
    inv_freq = yarn_frequencies(
        dims, l.getf('rope_theta', 10000.0), factor,
        l.geti('rope_original_positions'), l.getf('rope_beta_fast', 32.0),
        l.getf('rope_beta_slow', 1.0))
    scale = l.getf('rope_attention_factor', 1.0) if v.yarn else 1.0
    q, k = rotate(q, inv_freq, scale), rotate(k, inv_freq, scale)
    if v.grouping == 'blocks':           # query head i reads head i // group
        k, val = (jnp.repeat(a, nh // nkv, axis=2) for a in (k, val))
    else:                                # the fault: i reads head i % nkv
        k, val = (jnp.tile(a, (1, 1, nh // nkv, 1)) for a in (k, val))
    o = attention(v, q, k, val, 1.0 / math.sqrt(hd), window)
    if v.gate:
        o = o * jax.nn.sigmoid(mm(v, x, p['wgate']))[..., None]
    return h + mm(v, o.reshape(b, s, nh * hd), p['wo'])


def route(l: Layer, x, p, v: Variant = MODEL):
    """The chosen experts, their weights ``scaling * s_e / sum_chosen s``
    with ``s`` the softmax over all experts, and the gap between the last
    logit chosen and the first left out (a choice that rounding can flip
    where it is small; a softmax keeps the logits' order)."""
    k = l.geti('experts_per_token')
    logits = jnp.matmul(x, p['router'])              # never lowered: float32
    s = jax.nn.softmax(logits, axis=-1) if v.router_score == 'softmax' \
        else jax.nn.sigmoid(logits)
    ranked = jnp.sort(logits, axis=-1)[..., ::-1]
    _, idx = jax.lax.top_k(s, k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    weights = l.getf('routed_scaling_factor', 1.0) * chosen \
        / jnp.sum(chosen, axis=-1, keepdims=True)
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


#: ``glm_moe_lite``'s table (``seq_slice``, ``embedding``, ``rmsnorm``,
#: ``swiglu``, ``lm_head_loss`` as there) with this family's two rules
OPS = dict(glm.OPS)
OPS.update({
    'gqa': lambda l, ins, p, v: [gqa(l, ins[0], p, v)],
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


check_ids = glm.check_ids          # one seeded row of ``seq + 2`` ids


def label_matrix(graph: Graph, ids: np.ndarray) -> np.ndarray:
    """The traffic's labels for rows of ``seq + 2`` ids: the next token of
    every position (one head)."""
    return ids[:, 1:graph.seq + 1]


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

#: (query, key) pairs a causal layer scores: every key up to the query, or
#: the last ``window`` of them (the count the kernels' costs use)
attended_pairs = attention_costs.pairs


def forward_macs(graph: Graph) -> Dict[int, float]:
    """Multiply-accumulates of one sequence's forward pass, by layer index:
    ``glm_moe_lite.forward_macs`` for the layer types it knows (products,
    and of the routed experts the assignments a balanced router sends to
    the experts held here), and for a ``gqa`` layer its five products (the
    gate's among them) and the scores and their product with the values
    over the pairs its mask keeps."""
    s, d = graph.seq, graph.width
    out = glm.forward_macs(graph)
    for l in graph.of_type('gqa'):
        nh, nkv, hd = l.geti('nhead'), l.geti('nkvhead'), l.geti('head_dim')
        proj = d * nh * hd + 2 * d * nkv * hd + d * nh + nh * hd * d
        out[l.index] = s * proj \
            + attended_pairs(s, l.geti('window')) * nh * 2 * hd
    return out


def train_flops_per_sequence(graph: Graph) -> float:
    """Forward, weight gradient and input gradient: three times the forward
    pass, two operations a multiply-accumulate.  Nothing recomputed
    counts."""
    return 2.0 * 3.0 * sum(forward_macs(graph).values())


def train_flops_per_step(feed) -> float:
    return train_flops_per_sequence(feed.graph) * feed.samples_per_step


# --- the comparison that decides ``correct`` --------------------------------

#: As ``glm_moe_lite``: the program computes in bfloat16 on float32 masters,
#: the reference in float32, and two things are compared on one seeded
#: sequence of the cell's length that no ring holds: the evaluation-mode
#: log-probabilities at every position (differences over the spread of the
#: reference's), and the timed program's own step (its loss, and the change
#: it makes to the head's weight and the final norm against plain Adam on
#: the reference's gradient).  A router's tenth choice flips on rounding
#: where the tenth and eleventh logits nearly tie (``TIE_EPSILON``, in
#: logits: a softmax keeps their order and their differences); such
#: positions are counted, and the limits are on shares, as there.
#:
#: Each limit lies between the largest reading of the program over the
#: builder's 30 seeds (bf16, after a run's 28-34 steps; PERF.md 6, PR 36) and
#: the smallest of the probe's faults it is there to catch, float8 products
#: among them, with the more room above the program's reading.  The head's
#: loss has no limit: precision hardly moves it (bf16 reads 1e-5 to 3.3e-4
#: relative, float8 2.7e-4).  The step's loss keeps one, three times over
#: bf16's largest reading, for what it alone can see: a loss that drops or
#: masks tokens (2.2e-3 and 0.14).
LIMITS = {
    # name: (limit, the readings it lies between)
    'mean': (0.03, 'bf16 0.0171-0.0181; a sigmoid router '
             '0.053, the window a block too wide 0.077, float8 0.27'),
    'median_position': (0.13, 'bf16 0.078-0.081; a sigmoid router 0.21, '
                        'the window a block too wide 0.41, float8 1.36'),
    'off_share': (0.1, 'positions off by more than TOLERANCE: bf16 '
                  '0.008-0.014; a sigmoid router 0.40, every other fault of '
                  'the model 0.93-1.0'),
    'off_untied_share': (0.01, 'those of them with no near tie to explain '
                         'it: bf16 0.0004-0.0021; a sigmoid router 0.11, '
                         'float8 0.29'),
}
TOLERANCE = 0.25
TIE_EPSILON = 0.016         # bf16 reads near ties at 0.706-0.716 of positions
TIE_SHARE_MAX = 0.9
STEP_LOSS_TOLERANCE = 1e-3  # bf16 5.7e-6 to 3.3e-4; every other token 2.2e-3
UPDATE_TOLERANCE = 0.05     # bf16 0.0107-0.0125 (the head), 0.0009-0.0015
#                             (the norm); float8 0.164, an eighth of the
#                             tokens dropped 0.163, a state left unchanged 1
HEAD_CHUNK = glm.HEAD_CHUNK


@functools.lru_cache(maxsize=None)
def _layer_program(kind: str, cfg: tuple, v: Variant):
    """One compiled program a kind of layer and its pairs: the three window
    layers of one conf are one program."""
    l = Layer(-1, kind, '', [], [], dict(cfg), -1)
    return jax.jit(lambda ins, p: OPS[kind](l, ins, p, v))


def blockwise_log_probs(graph: Graph, params, ids, v: Variant = MODEL):
    """``run_graph`` at the cell's size beside a trainer's state, as
    ``glm_moe_lite.blockwise_log_probs``: one layer a program, on the
    device, with that layer's parameters as they lie there; the head in
    blocks of positions whose log-probabilities go to the host one at a
    time.  -> ({loss node: (b, s, vocab) on the host}, positions with a near
    tie, {loss node: (the ``rmsnorm`` layer that made the head's input, that
    layer's own input, on the device)})."""
    values = {'0': jnp.asarray(ids, jnp.int32)}
    made_by, before_norm = {}, {}
    tie = np.zeros(ids.shape[:1] + (graph.seq,), bool)
    out: Dict[str, np.ndarray] = {}
    with jax.default_matmul_precision('highest'):
        for l in graph.layers:
            p = glm._of(params, l)
            ins = [values[n] for n in l.ins]
            if l.type == 'lm_head_loss':
                head = glm._head_program(v)
                for name, node, h in zip(l.ins, l.outs, ins):
                    out[node] = np.concatenate(
                        [np.asarray(head(h[:, a:a + HEAD_CHUNK], p['wmat']))
                         for a in range(0, h.shape[1], HEAD_CHUNK)], axis=1)
                    before_norm[node] = made_by[name]
                continue
            outs = _layer_program(l.type, tuple(sorted(l.cfg.items())),
                                  v)(ins, p)
            if l.type == 'moe':
                tie = tie | (np.asarray(outs[1]) < TIE_EPSILON)
                outs = outs[:1]
            values.update(zip(l.outs, outs))
            made_by.update((n, (l, ins[0])) for n in l.outs)
    return out, tie, before_norm


def program_step(trainer, graph: Graph, ids: np.ndarray) -> dict:
    """One real step of the program under test on ``ids``, as
    ``glm_moe_lite.program_step`` with this family's one-head labels: staged
    by its own ``stage_batch`` like a ring row, run by the ``update_staged``
    the window times.  -> the loss that step reported, and for every tail
    leaf its value and Adam's two moments before the step and its value
    after, on the host, with the number of updates made before."""
    from cxxnet_tpu.io.data import DataBatch
    leaves = glm.tail_leaves(graph)

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


def compared(numbers: dict, tie_share: float, step: dict) -> dict:
    """``{name: [number, limit]}``: every number the verdict holds."""
    out = {'near_tie_share': [tie_share, TIE_SHARE_MAX]}
    for node, n in numbers.items():
        for key, (limit, _) in LIMITS.items():
            out[f'{node}.{key}'] = [n[key], limit]
    out['step.loss'] = [step['loss'], STEP_LOSS_TOLERANCE]
    for leaf, u in step['update'].items():
        out[f'step.update.{leaf}'] = [u, UPDATE_TOLERANCE]
    return out


def within_limits(numbers: dict, tie_share: float, step: dict) -> bool:
    return all(np.isfinite(value) and value <= limit for value, limit in
               compared(numbers, tie_share, step).values())


def reference_side(graph: Graph, params, ids, got,
                   v: Variant = MODEL) -> dict:
    """All the reference has to say about ``ids`` under ``params``, on the
    host: the numbers of the program's probabilities ``got`` against its
    own, the share of near ties, its loss of the step, its gradients of the
    tail leaves.  Taken before the program's step moves ``params``."""
    want, tie, before_norm = blockwise_log_probs(graph, params, ids, v)
    grads = glm.tail_gradients(graph, params, before_norm, ids, v)
    numbers, step_loss = glm.measure(graph, got, want, tie, ids, v)
    return {'numbers': numbers, 'tie_share': float(np.mean(tie)),
            'step_loss': step_loss, 'grads': grads}


def judge(graph: Graph, side: dict, step: dict):
    """-> (the step's numbers against ``side``, inside every limit?)"""
    found = glm.step_numbers(graph, step, side['step_loss'], side['grads'])
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
    held = compared(side['numbers'], side['tie_share'], step)
    return {'errors': dict(side['numbers'], near_tie_share=side['tie_share'],
                           step=step),
            'tolerance': dict({k: limit for k, (limit, _) in LIMITS.items()},
                              position=TOLERANCE, tie_epsilon=TIE_EPSILON,
                              tie_share=TIE_SHARE_MAX,
                              step_loss=STEP_LOSS_TOLERANCE,
                              update=UPDATE_TOLERANCE),
            'compared': held, 'ok': ok}
