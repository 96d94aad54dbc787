"""Expert parallelism: top-1 (switch) mixture-of-experts with all_to_all
dispatch.

No counterpart in the reference (SURVEY.md §2.5 lists expert parallelism
as absent); built TPU-first: experts are sharded over a mesh axis, tokens
are routed with two ``lax.all_to_all`` collectives (dispatch + combine)
that ride ICI, and every shape is static (capacity-bounded routing with
token dropping, the standard Switch-Transformer discipline) so the whole
thing jits.

Layout convention inside shard_map over ``axis_name`` (n devices):
* tokens: local ``(T, D)`` (batch/sequence sharded outside),
* expert weights: local ``(E/n, D, F)`` / ``(E/n, F, D)`` — each device
  owns ``E/n`` experts,
* gate: ``(D, E)`` replicated.

Dispatch: every device builds a per-expert capacity buffer ``(E, C, D)``
from its own tokens, all_to_all ships expert-group ``e`` to the device
owning it → ``(E/n, n*C, D)``; the expert FFN runs batched over its
``n*C`` slots; the reverse all_to_all brings results home and the combine
einsum scatters them back to token order scaled by the gate probability.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def switch_gate(x, gate_w, capacity: int):
    """Top-1 gating with capacity.  x:(T,D), gate_w:(D,E) ->
    dispatch:(T,E,C) 0/1, combine:(T,E,C) = dispatch * gate_prob,
    aux: {'balance_loss', 'drop_frac'}.

    ``balance_loss`` is the Switch auxiliary load-balancing loss
    ``E * sum_e f_e * P_e`` (f_e = routed token fraction, P_e = mean router
    probability; minimum 1.0 at uniform routing) — differentiable through
    P_e, so training pressure spreads the experts.  ``drop_frac`` is the
    fraction of tokens lost to the capacity bound (metric only,
    stop-gradient)."""
    logits = x @ gate_w.astype(x.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                      # (T,)
    sel = jax.nn.one_hot(expert, gate_w.shape[1], dtype=jnp.float32)
    pos = jnp.cumsum(sel, axis=0) * sel                      # 1-based slot
    keep = (pos > 0) & (pos <= capacity)
    slot = jnp.where(keep, pos - 1, 0).astype(jnp.int32)
    dispatch = (jax.nn.one_hot(slot.max(axis=-1), capacity,
                               dtype=jnp.float32)
                [:, None, :] * (sel * keep)[:, :, None])     # (T,E,C)
    gate_prob = (probs * sel).sum(-1, keepdims=True)         # (T,1)
    combine = dispatch * gate_prob[:, :, None]
    num_experts = gate_w.shape[1]
    f = sel.mean(axis=0)                                     # (E,)
    p = probs.mean(axis=0)                                   # (E,)
    aux = {
        'balance_loss': num_experts * jnp.sum(f * p),
        'drop_frac': lax.stop_gradient(
            1.0 - dispatch.sum() / jnp.float32(x.shape[0])),
    }
    return dispatch, combine, aux


def moe_ffn_local(x, gate_w, w1, w2, *, axis_name=None,
                  capacity_factor: float = 2.0):
    """Switch FFN.  Call INSIDE shard_map when ``axis_name`` is given
    (w1/w2 then hold the local expert shard); standalone single-device
    otherwise (w1/w2 hold all experts).

    x: (T, D) local tokens; w1: (E_local, D, F); w2: (E_local, F, D);
    gate_w: (D, E_global).  Returns (out (T, D), aux dict); aux values
    are means over the ``axis_name`` group when given.
    """
    n = 1 if axis_name is None else lax.psum(1, axis_name)
    e_local = w1.shape[0]
    e_global = e_local * n
    t = x.shape[0]
    capacity = max(1, int(capacity_factor * t / e_global))
    dispatch, combine, aux = switch_gate(x, gate_w, capacity)
    if axis_name is not None:
        aux = {k: lax.pmean(v, axis_name) for k, v in aux.items()}
    xf = x.astype(jnp.float32)
    buf = jnp.einsum('td,tec->ecd', xf, dispatch)            # (E, C, D)
    if axis_name is not None:
        # ship expert-group e to its owner; receive our experts' tokens
        # from every peer: (E, C, D) -> (E_local, n*C, D)
        buf = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=1,
                             tiled=True)
    h = jax.nn.relu(jnp.einsum('ecd,edf->ecf', buf,
                               w1.astype(jnp.float32)))
    y = jnp.einsum('ecf,efd->ecd', h, w2.astype(jnp.float32))
    if axis_name is not None:
        # (E_local, n*C, D) -> (E, C, D): results back to the sender
        y = lax.all_to_all(y, axis_name, split_axis=1, concat_axis=0,
                           tiled=True)
    out = jnp.einsum('ecd,tec->td', y, combine)
    return out.astype(x.dtype), aux


def moe_ffn_reference(x, gate_w, w1, w2, capacity_factor: float = 2.0):
    """Single-device oracle: same routing/capacity semantics, dense loop
    over all experts.  w1: (E, D, F), w2: (E, F, D).
    Returns (out, aux) like moe_ffn_local."""
    return moe_ffn_local(x, gate_w, w1, w2, axis_name=None,
                         capacity_factor=capacity_factor)


# --- top-k routing over experts of which this chip holds a share -------------
# What an expert-parallel group asks of each chip, without the exchange: the
# router keeps its published width, every token picks k of all the experts,
# and the chip computes its own experts' part of the result for the tokens
# routed to them.  No capacity, so no token is dropped at any imbalance: the
# assignments are sorted by held expert into one (tokens * k)-row buffer
# (those of experts held elsewhere go last) and the products run grouped over
# it (``lax.ragged_dot``, which skips the rows past the groups: on the v5e
# its time follows the sum of the group sizes, not the rows; PERF.md PR 29).
# Everything around the products costs by the row, so the layer works on the
# first rows of the buffer alone where they hold every held assignment
# (``bounded_rows``; PERF.md PR 34).

def sigmoid_topk_route(x, router_w, router_bias, k: int, scaling: float):
    """``noaux_tc`` routing with sigmoid scores: ``x``: (T, D),
    ``router_w``: (D, E), ``router_bias``: (E,), the correction bias that
    enters the choice and not the weights.  Returns ``idx`` (T, k) int32
    and ``weights`` (T, k) float32 = ``scaling * s_e / sum_chosen s``.
    Scores in float32 at the highest precision: a choice between two
    nearly equal scores should not hang on a bf16 pass."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               router_w.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(s + router_bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(s, idx, axis=1)
    weights = scaling * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), weights


def sort_by_held_expert(idx, first: int, held: int):
    """Order of the ``T * k`` assignments (row-major over ``idx``) by the
    held expert they go to, those of experts not held here last; and the
    number of assignments of each held expert.  Stable, so equal keys keep
    token order."""
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    return order, sizes


def grouped_swiglu(xs, w_gate, w_up, w_down, sizes):
    """SwiGLU of each held expert over its rows of ``xs`` (sorted by
    expert, ``sizes`` rows each): ``w_gate, w_up``: (E, D, F), ``w_down``:
    (E, F, D).  Rows past the groups come back unspecified."""
    dt = xs.dtype
    g = lax.ragged_dot(xs, w_gate.astype(dt), sizes,
                       preferred_element_type=jnp.float32)
    u = lax.ragged_dot(xs, w_up.astype(dt), sizes,
                       preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(dt)
    return lax.ragged_dot(h, w_down.astype(dt), sizes,
                          preferred_element_type=jnp.float32).astype(dt)


def combine_sorted(ys, order, weights, valid):
    """Scatter the sorted rows' results back to their tokens, each scaled
    by its routing weight: (T, D) float32.  Rows that are not ``valid``
    (the assignments of experts held elsewhere) count as zero."""
    t, k = weights.shape
    w = jnp.where(valid, weights.reshape(-1)[order], 0.0)
    contrib = jnp.where(valid[:, None], ys.astype(jnp.float32), 0.0) \
        * w[:, None]
    return jnp.zeros((t, ys.shape[1]), jnp.float32).at[order // k].add(
        contrib)


#: the row tile of the grouped products the v5e's compiler makes of
#: ``lax.ragged_dot`` (``ragged_dot_tiling="512,512,512"`` on the Mosaic
#: calls of the compiled step, held by tests/test_v5e_compile.py)
ROW_TILE = 512


def bounded_rows(assignments: int, held: int, published: int) -> int:
    """Rows of the sorted buffer that the layer works on when the held
    assignments fit them: twice the share a balanced router would send here
    (``assignments * held / published``), up to a multiple of ``ROW_TILE``,
    at most all ``assignments``.  Derived from the layer's shape, no
    setting: 8,192 of 32,768 for 8 of 64 experts held over 8,192 tokens x 4,
    and all of them where the chip holds every expert.  (Further sizes
    between the two cost a set-up that grows with every compiled branch:
    PERF.md 6, PR 34.)"""
    rows = -(-2 * assignments * held // published)
    return min(-(-rows // ROW_TILE) * ROW_TILE, assignments)


def _ffn_over_rows(rows: int, x, order, weights, sizes, w_gate, w_up,
                   w_down):
    """``held_experts_ffn``'s result from the first ``rows`` rows of the
    sorted order: every held assignment, if they number at most ``rows``.
    The rows past the groups are masked on the way in and on the way out,
    so that what the grouped products leave there reaches neither the
    result nor, in the backward pass, the tokens' gradient."""
    k = weights.shape[1]
    order = order[:rows]
    valid = jnp.arange(rows) < jnp.sum(sizes)
    xs = jnp.where(valid[:, None], x[order // k], jnp.zeros((), x.dtype))
    ys = grouped_swiglu(xs, w_gate, w_up, w_down, sizes)
    return combine_sorted(ys, order, weights, valid)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ffn_over_fitting_rows(rows: int, x, order, weights, sizes, *w):
    """``_ffn_over_rows`` over ``rows`` rows where the held assignments fit
    them and over all of them where they do not, and the same choice again
    in the backward pass.  Its own derivative rule because autodiff through
    ``lax.cond`` hands every branch's residuals from the forward conditional
    to the backward one, the untaken branch's zero-filled (+2.08 GB of the
    step's temporaries at the published widths): here each pass's
    conditional keeps what it computes to itself."""
    return lax.cond(jnp.sum(sizes) <= rows,
                    functools.partial(_ffn_over_rows, rows),
                    functools.partial(_ffn_over_rows, order.shape[0]),
                    x, order, weights, sizes, *w)


def _fitting_fwd(rows, *operands):
    return _ffn_over_fitting_rows(rows, *operands), operands


# Jitted: every expert layer of a net calls it with the same shapes, so the
# step's trace holds the two pullbacks once and not once a layer (tracing and
# lowering are counted in every run's set-up).  The forward rule is not: the
# compiler then files every layer's forward branches under one layer's scope.
@functools.partial(jax.jit, static_argnums=0)
def _fitting_bwd(rows, operands, dy):
    x, order, weights, sizes, *w = operands

    def pull(n):
        def back(dy, x, weights, *w):
            return jax.vjp(lambda x, weights, *w: _ffn_over_rows(
                n, x, order, weights, sizes, *w), x, weights, *w)[1](dy)
        return back

    dx, dweights, *dw = lax.cond(jnp.sum(sizes) <= rows, pull(rows),
                                 pull(order.shape[0]), dy, x, weights, *w)
    return (dx, None, dweights, None, *dw)


_ffn_over_fitting_rows.defvjp(_fitting_fwd, _fitting_bwd)


def held_experts_ffn(x, idx, weights, w_gate, w_up, w_down, first: int,
                     published: int):
    """The held experts' part of a top-k MoE layer's result for tokens
    ``x`` (T, D), of ``published`` experts in all: (T, D) float32, the held
    experts' loads, and whether the whole buffer was worked on (0 or 1).

    The sorted order puts every held assignment first, so the gather, the
    masks, the products' buffers, the combine and their transposes run over
    the first ``bounded_rows`` rows whenever the held assignments fit them:
    the same rows in the same order as over all ``T * k``.  When they do
    not fit, the layer works on the whole buffer: nothing is dropped at any
    imbalance, it only costs what the worst case costs."""
    held = w_gate.shape[0]
    order, sizes = sort_by_held_expert(idx, first, held)
    total = order.shape[0]
    rows = bounded_rows(total, held, published)
    operands = (x, order, weights, sizes, w_gate, w_up, w_down)
    if rows == total:
        return _ffn_over_rows(total, *operands), sizes, jnp.float32(0.0)
    full = (jnp.sum(sizes) > rows).astype(jnp.float32)
    return _ffn_over_fitting_rows(rows, *operands), sizes, full
