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
from jax.experimental.xla_metadata import set_xla_metadata


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
# its time follows the row tiles the groups touch, not the rows; PERF.md PR
# 29, 37).  The products' tile is chosen from each product's shape and handed
# to the compiler (``grouped_tiling``, ``GROUP_ROW_TILE``: 256 rows for groups
# of a few hundred); the buffer is rounded to another constant, ``ROW_TILE``.
# Everything around the products costs by the row, so the layer works on the
# first rows of the buffer alone where they hold every held assignment
# (``bounded_rows``; PERF.md PR 34), and through as many blocks of that size
# as hold one where they do not (PR 36: at 81,920 assignments of 3,072 wide
# rows the whole buffer's temporaries, about 5 GB, fit no chip's plan).

#: the router's score functions (``router_score`` of a ``moe`` layer)
ROUTER_SCORES = {'sigmoid': jax.nn.sigmoid,
                 'softmax': functools.partial(jax.nn.softmax, axis=-1)}


def topk_route(x, router_w, router_bias, k: int, scaling: float,
               score: str = 'sigmoid'):
    """Top-``k`` routing: ``x``: (T, D), ``router_w``: (D, E), scores ``s``
    = ``score`` (``sigmoid``: ``noaux_tc``'s; ``softmax``: over all E) of
    the logits, ``router_bias``: (E,), the correction bias that enters the
    choice and not the weights.  Returns ``idx`` (T, k) int32 and
    ``weights`` (T, k) float32 = ``scaling * s_e / sum_chosen s``.
    Scores in float32 at the highest precision: a choice between two
    nearly equal scores should not hang on a bf16 pass."""
    s = ROUTER_SCORES[score](jnp.dot(x.astype(jnp.float32),
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


#: the row tile the v5e's compiler gives a grouped product that names none
#: (``ragged_dot_tiling="512,512,512"``), and what ``bounded_rows`` rounds the
#: bounded buffer to, so that every array of the step keeps its shape whatever
#: tile the products run on
ROW_TILE = 512

#: rows of the tile a grouped product runs on where its groups are short, and
#: the tile of the wider of its two widths (the narrower is taken whole, up to
#: ``WHOLE_WIDTH``): ``grouped_tiling``, measured on the v5e (PERF.md 6, PR 37)
GROUP_ROW_TILE = 256
WIDTH_TILE = 512
WHOLE_WIDTH = 1536


def grouped_tiling(rows: int, groups: int, k: int, n: int):
    """The tiling (rows, contraction ``k``, columns ``n``) of one grouped
    product of ``rows`` rows over ``groups`` groups, from its shape alone, or
    ``None``: the compiler's own (on the v5e 512, 512, 512 at these sizes).

    The kernel visits one (row tile, group) pair at a time and pays a whole
    tile for each, so groups of a few hundred rows that start anywhere cost
    ``rows / tile + groups - 1`` visits: at 4,750 held rows over 8 groups, 16.3
    tiles of 512 (8,350 rows of work) or 25.6 of 256 (6,550).  A visit re-reads
    its group's matrix, which a tile of 256 rows about balances against the
    MXU and one of 128 does not; and the fewer tiles a visit walks across the
    widths, the fewer grid steps: the narrower width whole and the wider in
    512s is the largest the weight-gradient product's float32 tile leaves room
    for in VMEM (512 x 2,048 is refused).  A product's two transposes in the
    backward pass ride its tiling.  Measured on the v5e, the twelve products
    of a layer and step against the compiler's tiling on the same routing:
    -21 to -23% at 2,048 x 1,536 (groups of 90 to 1,780 rows), -22 to -27% at
    3,072 x 1,024 (200 to 900), -2% and -6% at 1,024 rows in every group
    (PERF.md 6, PR 37).

    ``None`` where the buffer gives a group more than two of the compiler's
    row tiles (``rows == total`` on a chip that holds every expert: groups of
    thousands of rows span many tiles and little of a visit is wasted; no
    cell runs it, PERF.md 7 has what 4,096 rows a group read), where ``rows``
    is no multiple of the tile (the compiler refuses such a tiling and picks
    a smaller tile itself) and at widths these tiles do not divide."""
    narrow, wide = min(k, n), max(k, n)
    if (rows % GROUP_ROW_TILE or rows > 2 * ROW_TILE * groups
            or narrow % 128 or narrow > WHOLE_WIDTH or wide % WIDTH_TILE):
        return None
    return ((GROUP_ROW_TILE, WIDTH_TILE, n) if n <= k
            else (GROUP_ROW_TILE, k, WIDTH_TILE))


def _grouped_dot(xs, w, sizes):
    """``lax.ragged_dot`` in float32 out, on ``grouped_tiling``'s tiling:
    the frontend attribute ``ragged_dot_tiling`` rides the product and, in a
    backward pass, its two transposes to the compiler (the TPU's alone reads
    it)."""
    tiling = grouped_tiling(xs.shape[0], sizes.shape[0], *w.shape[1:])
    named = {} if tiling is None else {
        'ragged_dot_tiling': ','.join(map(str, tiling))}
    with set_xla_metadata(**named):
        return lax.ragged_dot(xs, w, sizes,
                              preferred_element_type=jnp.float32)


def grouped_swiglu(xs, w_gate, w_up, w_down, sizes):
    """SwiGLU of each held expert over its rows of ``xs`` (sorted by
    expert, ``sizes`` rows each): ``w_gate, w_up``: (E, D, F), ``w_down``:
    (E, F, D).  Rows past the groups come back unspecified."""
    dt = xs.dtype
    g = _grouped_dot(xs, w_gate.astype(dt), sizes)
    u = _grouped_dot(xs, w_up.astype(dt), sizes)
    h = (jax.nn.silu(g) * u).astype(dt)
    return _grouped_dot(h, w_down.astype(dt), sizes).astype(dt)


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


def bounded_rows(assignments: int, held: int, published: int,
                 tokens: int = 0) -> int:
    """Rows of the sorted buffer that the layer works on when the held
    assignments fit them: twice the share a balanced router would send here
    (``assignments * held / published``) and no fewer than one a token, up
    to a multiple of ``ROW_TILE`` (the rounding alone: the tile the products
    run on is ``grouped_tiling``'s, which divides it), at most all
    ``assignments``.  Derived
    from the layer's shape, no setting: 8,192 of 32,768 for 8 of 64 experts
    held over 8,192 tokens x 4, 8,192 of 81,920 for 8 of 256 over 8,192 x 10
    (twice the balanced share is 5,120 there), and all of them where the
    chip holds every expert.  The row a token is there because a buffer
    shorter than the layer's other passes over its tokens saves little (the
    routed part of a layer at 3,072 x 1,024 takes 8.0 ms at 2,560 held
    assignments and 9.1 at 5,000, forward and backward) and because what
    lies past the bound costs a block's whole time (14.6 ms at 5,200):
    PERF.md 6, PR 36.  (Further sizes between the two cost a set-up that
    grows with every compiled branch: PERF.md 6, PR 34.)"""
    rows = max(-(-2 * assignments * held // published), tokens)
    return min(-(-rows // ROW_TILE) * ROW_TILE, assignments)


def _ffn_over_rows(rows: int, x, order, weights, sizes, w_gate, w_up,
                   w_down, first_row=0):
    """``held_experts_ffn``'s result from the ``rows`` rows of the sorted
    order from ``first_row`` on: all of it if they hold every held
    assignment, else that block's share (``_ffn_in_blocks`` sums the
    blocks).  Each held expert's group is clipped to the block, and the rows
    past the groups are masked on the way in and on the way out, so that
    what the grouped products leave there reaches neither the result nor, in
    the backward pass, the tokens' gradient."""
    k = weights.shape[1]
    order = lax.dynamic_slice(order, (first_row,), (rows,))
    valid = first_row + jnp.arange(rows) < jnp.sum(sizes)
    ends = jnp.cumsum(sizes)
    inside = lambda at: jnp.clip(at, first_row, first_row + rows)  # noqa: E731
    sizes = inside(ends) - inside(ends - sizes)
    xs = jnp.where(valid[:, None], x[order // k], jnp.zeros((), x.dtype))
    ys = grouped_swiglu(xs, w_gate, w_up, w_down, sizes)
    return combine_sorted(ys, order, weights, valid)


def _blocks(rows: int, order, sizes):
    """The sorted order padded to whole blocks of ``rows`` rows, and the
    number of blocks that hold a held assignment."""
    return (jnp.pad(order, (0, -order.shape[0] % rows)),
            lax.div(jnp.sum(sizes) + (rows - 1), rows))


def _ffn_in_blocks(rows: int, x, order, weights, sizes, *w):
    """``held_experts_ffn``'s result at any imbalance in the memory of one
    block: the sorted rows ``rows`` at a time, as many blocks as hold a held
    assignment (all ``T * k`` rows at the worst), summed.  Nothing is
    dropped, and no array grows with ``T * k`` but the sorted order."""
    order, blocks = _blocks(rows, order, sizes)
    return lax.fori_loop(
        0, blocks,
        lambda i, y: y + _ffn_over_rows(rows, x, order, weights, sizes, *w,
                                        first_row=i * rows),
        jnp.zeros(x.shape, jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ffn_over_fitting_rows(rows: int, x, order, weights, sizes, *w):
    """``_ffn_over_rows`` over the first ``rows`` rows where the held
    assignments fit them and ``_ffn_in_blocks`` where they do not, and the
    same choice again in the backward pass.  Its own derivative rule because
    autodiff through ``lax.cond`` hands every branch's residuals from the
    forward conditional to the backward one, the untaken branch's
    zero-filled (+2.08 GB of the step's temporaries at GLM's widths), and
    because a loop whose length the routing decides has no transpose: here
    each pass's conditional keeps what it computes to itself, and the
    backward pass's adds the blocks' pullbacks in a loop of the same
    length.  (Two other forms were measured on the chip, PERF.md 6, PR 36.
    The loop alone, the first block in line and no further block while they
    fit, is one path and no conditional: 0.65% slower in the GLM cell, whose
    every layer then carries its gradients through a loop that does not
    turn.  Blocks of a quarter of the bound cost more at every load: a
    block's time is mostly its fixed part, the experts' matrices read and
    their gradients written and summed once a block.)"""
    return lax.cond(jnp.sum(sizes) <= rows,
                    functools.partial(_ffn_over_rows, rows),
                    functools.partial(_ffn_in_blocks, rows),
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

    def pull(order, first_row):
        return jax.vjp(lambda x, weights, *w: _ffn_over_rows(
            rows, x, order, weights, sizes, *w, first_row=first_row),
            x, weights, *w)[1](dy)

    def in_blocks():
        padded, blocks = _blocks(rows, order, sizes)
        return lax.fori_loop(
            0, blocks,
            lambda i, sums: jax.tree.map(jnp.add, sums,
                                         pull(padded, i * rows)),
            jax.tree.map(jnp.zeros_like, (x, weights, *w)))

    dx, dweights, *dw = lax.cond(jnp.sum(sizes) <= rows,
                                 lambda: pull(order, 0), in_blocks)
    return (dx, None, dweights, None, *dw)


_ffn_over_fitting_rows.defvjp(_fitting_fwd, _fitting_bwd)


def held_experts_ffn(x, idx, weights, w_gate, w_up, w_down, first: int,
                     published: int):
    """The held experts' part of a top-k MoE layer's result for tokens
    ``x`` (T, D), of ``published`` experts in all: (T, D) float32, the held
    experts' loads, and whether the layer went past its bounded buffer (0 or
    1).

    The sorted order puts every held assignment first, so the gather, the
    masks, the products' buffers, the combine and their transposes run over
    the first ``bounded_rows`` rows whenever the held assignments fit them:
    the same rows in the same order as over all ``T * k``.  When they do
    not fit, the layer works through the sorted rows a block of
    ``bounded_rows`` at a time (``_ffn_in_blocks``): nothing is dropped at
    any imbalance, it costs a block's time for every block that holds an
    assignment and one block's memory."""
    held = w_gate.shape[0]
    order, sizes = sort_by_held_expert(idx, first, held)
    total = order.shape[0]
    rows = bounded_rows(total, held, published, x.shape[0])
    operands = (x, order, weights, sizes, w_gate, w_up, w_down)
    if rows == total:
        return _ffn_over_rows(total, *operands), sizes, jnp.float32(0.0)
    full = (jnp.sum(sizes) > rows).astype(jnp.float32)
    return _ffn_over_fitting_rows(rows, *operands), sizes, full
