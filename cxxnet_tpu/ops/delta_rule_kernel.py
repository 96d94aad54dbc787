"""The chunkwise gated delta rule of ``ops/delta_rule.py`` as two Pallas TPU
kernels under one ``custom_vjp``: the same arithmetic, chunk by chunk, with
the recurrent state held in VMEM across the chunks.

``delta_rule_fwd`` runs over a grid of (batch x heads, chunks), the chunks
in order.  Its ``dk x dv`` float32 state lives in a VMEM scratch, zeroed at
a head's first chunk; a step reads one chunk of ``q``, ``k``, ``v``, the
log-decays and ``beta``, writes the chunk's output, and, where it is
differentiated, for the backward pass the state it found and the chunk's
``pairs(k, k)``, ``M`` and ``T`` (together 64 KiB a chunk and head in HBM's
tiles, as much as the state), and leaves the next state behind.  Where it
is not (evaluation, the benchmark's check of the rule) it writes the output
alone.  Inside a step (the notation of
``ops/delta_rule``):

* ``G``, the running sum of the log-decays in the chunk, in log2(chunk)
  shifted adds;
* ``A = beta . pairs(k, k)`` and ``M = scale pairs(q, k)``, the pairs in
  sub-chunks of ``sub`` tokens: a pair in two sub-chunks through the last
  position before the later one (both factors at most 1), by products; a
  pair in one sub-chunk elementwise, ``exp(min(G_r - G_i, 0))``, a
  distance ``r - i`` at a time.  The sub-chunks are not optional: a chunk's
  decay passes -88 from the first step of training, and ``exp(-G_i)`` from
  the chunk's start would overflow float32;
* ``T = (I + A)^-1``: the diagonal blocks of ``sub`` by forward
  substitution, a pivot at a time in all of them at once, then the blocks
  below merged by products, ``T21 = -T22 A21 T11`` for blocks of ``sub``
  and again for blocks of ``2 sub`` (no power series: ``beta`` runs to 2);
* ``U = T (beta V)``, ``W = T (beta K . exp(G))``, ``Delta = U - W S``,
  ``O = scale (Q . exp(G)) S + M Delta`` and ``S <- exp(G_C) S + (K .
  exp(G_C - G))^T Delta``.

``delta_rule_bwd`` walks the chunks in reverse with the state's gradient
``dS`` in VMEM: from a chunk's inputs, its saved state and its saved
matrices it computes the rest again (``G``, ``U``, ``W``, ``Delta``), then
the five gradients, the solve's (``dA = -T^T dT T^T`` below the diagonal)
and the sub-chunk exponentials' among them, and the gradient of the state
before the chunk.  Keeping the matrices takes 22% of the backward's
schedule off for as many bytes again as the states (PERF.md 6).

Everything is float32, every product at ``Precision.HIGHEST``.  The kernels
take heads of ``dk``, ``dv`` multiples of 128 (``ops/delta_rule`` chooses);
``interpret=True`` runs them through Pallas's interpreter, on any backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def _dot(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())), precision=_HIGHEST,
                           preferred_element_type=_F32)


def _mm(a, b):                              # a b
    return _dot(a, b, ((1,), (0,)))


def _mm_nt(a, b):                           # a b^T
    return _dot(a, b, ((1,), (1,)))


def _mm_tn(a, b):                           # a^T b
    return _dot(a, b, ((0,), (0,)))


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _rows_back(x, d):
    """``x`` moved ``d`` rows down: row ``r`` holds ``x[r - d]`` (rows that
    wrap are masked by the caller)."""
    return pltpu.roll(x, d, 0) if d else x


def _within(sub, d, rows, cols):
    """The pairs ``(r, r - d)`` of one sub-chunk, on a ``(C, C)`` grid."""
    return (cols == rows - d) & ((rows & (sub - 1)) >= d)


def _cross_blocks(g, sub):
    """For each sub-chunk after the first, from ``lo`` on: ``lo``, the rows'
    factor ``exp(G_r - ref)`` ``(sub, dk)`` and the keys' ``exp(min(ref -
    G_i, 0))`` ``(C, dk)``, ``ref`` the running sum at ``lo - 1``: at most
    1 for every pair that counts (``i < lo <= r``)."""
    chunk = g.shape[0]
    for lo in range(sub, chunk, sub):
        ref = g[lo - 1:lo]
        yield (lo, jnp.exp(g[lo:lo + sub] - ref),
               jnp.exp(jnp.minimum(ref - g, 0.0)))


def _pairs(q, k, g, sub):
    """``(pairs(k, k), pairs(q, k))``, ``pairs(x, y)[r, i] = sum_c x_r[c]
    y_i[c] exp(G_r[c] - G_i[c])``: ``k``'s strictly below the diagonal,
    ``q``'s on it too."""
    chunk = g.shape[0]
    rows, cols = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    pkk = jnp.zeros((chunk, chunk), _F32)
    pqk = pkk
    for d in range(sub):
        e = _rows_back(k, d) * jnp.exp(jnp.minimum(g - _rows_back(g, d), 0.0))
        on = _within(sub, d, rows, cols)
        pqk += jnp.where(on, jnp.sum(q * e, axis=-1, keepdims=True), 0.0)
        if d:
            pkk += jnp.where(on, jnp.sum(k * e, axis=-1, keepdims=True), 0.0)
    blocks_kk, blocks_qk = [jnp.zeros((sub, chunk), _F32)], \
        [jnp.zeros((sub, chunk), _F32)]
    cols = _iota((2 * sub, chunk), 1)
    for lo, f, fy in _cross_blocks(g, sub):
        x = jnp.concatenate([k[lo:lo + sub] * f, q[lo:lo + sub] * f], axis=0)
        p = jnp.where(cols < lo, _mm_nt(x, k * fy), 0.0)
        blocks_kk.append(p[:sub])
        blocks_qk.append(p[sub:])
    return (pkk + jnp.concatenate(blocks_kk, axis=0),
            pqk + jnp.concatenate(blocks_qk, axis=0))


def _pairs_grad(q, k, g, dpkk, dpqk, sub):
    """Through ``_pairs``: the gradients to ``k`` as the rows of ``pairs(k,
    k)``, to ``q`` as the rows of ``pairs(q, k)`` and to ``k`` as the keys
    of both, from theirs.  (``G``'s follows from these: ``dG += x . dx - y .
    dy``.)"""
    chunk = g.shape[0]
    rows, cols = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    dxk = jnp.zeros(k.shape, _F32)
    dxq, dy = dxk, dxk
    for d in range(sub):
        e = jnp.exp(jnp.minimum(g - _rows_back(g, d), 0.0))
        on = _within(sub, d, rows, cols)
        cq = jnp.sum(jnp.where(on, dpqk, 0.0), axis=-1, keepdims=True)
        ke = _rows_back(k, d) * e
        dxq += cq * ke
        z = cq * q
        if d:
            ck = jnp.sum(jnp.where(on, dpkk, 0.0), axis=-1, keepdims=True)
            dxk += ck * ke
            z += ck * k
        # row r's pair is with key r - d: back up d rows
        dy += pltpu.roll(z * e, chunk - d, 0) if d else z * e
    cross_k, cross_q = [jnp.zeros((sub,) + k.shape[1:], _F32)], \
        [jnp.zeros((sub,) + k.shape[1:], _F32)]
    cols = _iota((2 * sub, chunk), 1)
    for lo, f, fy in _cross_blocks(g, sub):
        c = jnp.where(cols < lo, jnp.concatenate(
            [dpkk[lo:lo + sub], dpqk[lo:lo + sub]], axis=0), 0.0)
        dx = _mm(c, k * fy)
        cross_k.append(dx[:sub] * f)
        cross_q.append(dx[sub:] * f)
        x = jnp.concatenate([k[lo:lo + sub] * f, q[lo:lo + sub] * f], axis=0)
        dy += _mm_tn(c, x) * fy
    return (dxk + jnp.concatenate(cross_k, axis=0),
            dxq + jnp.concatenate(cross_q, axis=0), dy)


def _unit_lower_inverse(a, sub):
    """``(I + a)^-1`` of a strictly lower ``a`` ``(C, C)``."""
    chunk = a.shape[0]
    rows, cols = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    start = rows & ~(sub - 1)                   # a row's sub-chunk's first
    t = (rows == cols).astype(_F32)
    # forward substitution in every diagonal block at once: once pivot p's
    # row is final, every later row of its block takes off a[r, p] times it
    for p in range(sub - 1):
        col = jnp.sum(jnp.where(cols == start + p, a, 0.0), axis=-1,
                      keepdims=True)
        pivot = jnp.concatenate(
            [jnp.broadcast_to(t[lo + p:lo + p + 1], (sub, chunk))
             for lo in range(0, chunk, sub)], axis=0)
        t = t - col * pivot
    # then the blocks below the diagonal, pairs of blocks of a size at a
    # time: [[X, 0], [Y, Z]]^-1 = [[X^-1, 0], [-Z^-1 Y X^-1, Z^-1]]
    blocks = [t[lo:lo + sub, lo:lo + sub] for lo in range(0, chunk, sub)]
    size = sub
    while len(blocks) > 1:
        merged = []
        for i in range(0, len(blocks), 2):
            x, z, lo = blocks[i], blocks[i + 1], i * size
            y = -_mm(z, _mm(a[lo + size:lo + 2 * size, lo:lo + size], x))
            merged.append(jnp.concatenate(
                [jnp.concatenate([x, jnp.zeros(x.shape, _F32)], axis=1),
                 jnp.concatenate([y, z], axis=1)], axis=0))
        blocks, size = merged, 2 * size
    return blocks[0]


def _sums_down(x, reverse=False):
    """Running sums of ``x`` ``(C, w)`` along its rows, from the first row
    (or with ``reverse`` from the last), in log2(C) steps of a shifted add."""
    chunk = x.shape[0]
    rows = _iota(x.shape, 0)
    shift = 1
    while shift < chunk:
        if reverse:
            x = x + jnp.where(rows < chunk - shift,
                              pltpu.roll(x, chunk - shift, 0), 0.0)
        else:
            x = x + jnp.where(rows >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


def _running_sums(g):
    """``G``: the log-decays summed from the chunk's start, and ``G_C``."""
    run = _sums_down(g)
    return run, run[g.shape[0] - 1:]


def _by_key_row(row, shape):
    """A ``(1, dk)`` row as a ``(dk, dv)`` matrix constant along ``dv``."""
    return jnp.transpose(jnp.broadcast_to(row, shape[::-1]))


def _chunk(q, k, v, g, beta, scale, sub, mats=None):
    """What a chunk's output and its backward pass need from its inputs
    (``mats``: its ``pkk``, ``M`` and ``T`` where they were kept)."""
    run, last = _running_sums(g)
    if mats is None:
        pkk, pqk = _pairs(q, k, run, sub)
        pqk = scale * pqk
        t = _unit_lower_inverse(beta * pkk, sub)
    else:
        pkk, pqk, t = mats
    grow = jnp.exp(run)
    kg = k * grow
    bv, bk = beta * v, beta * kg
    return dict(run=run, last=last, pkk=pkk, m=pqk, t=t, grow=grow,
                kg=kg, qg=q * grow, k_end=k * jnp.exp(last - run),
                bv=bv, bk=bk, u=_mm(t, bv), w=_mm(t, bk))


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, *refs, scale, sub,
                    residuals):
    if residuals:
        o_ref, before_ref, mats_ref, s_ref = refs
    else:
        o_ref, s_ref = refs

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, _F32)

    c = _chunk(q_ref[...], k_ref[...], v_ref[...], g_ref[...], b_ref[...],
               scale, sub)
    s = s_ref[...]
    if residuals:
        before_ref[...] = s
        mats_ref[...] = jnp.concatenate([c['pkk'], c['m'], c['t']], axis=1)
    delta = c['u'] - _mm(c['w'], s)
    o_ref[...] = scale * _mm(c['qg'], s) + _mm(c['m'], delta)
    s_ref[...] = _by_key_row(jnp.exp(c['last']), s.shape) * s \
        + _mm_tn(c['k_end'], delta)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, mats_ref,
                     do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref,
                     *, scale, sub):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = jnp.zeros(ds_ref.shape, _F32)

    q, k, v, beta = q_ref[...], k_ref[...], v_ref[...], b_ref[...]
    chunk = q.shape[0]
    mats = mats_ref[...]
    c = _chunk(q, k, v, g_ref[...], beta, scale, sub, (
        mats[:, :chunk], mats[:, chunk:2 * chunk], mats[:, 2 * chunk:]))
    s, do, ds_after = s_ref[...], do_ref[...], ds_ref[...]
    rows, cols = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    delta = c['u'] - _mm(c['w'], s)
    d_delta = _mm_tn(c['m'], do) + _mm(c['k_end'], ds_after)
    dm = jnp.where(cols <= rows, _mm_nt(do, delta), 0.0)
    dqg = scale * _mm_nt(do, s)
    dk_end = _mm_nt(delta, ds_after)
    dw = -_mm_nt(d_delta, s)
    t = c['t']
    dt = _mm_nt(d_delta, c['bv']) + _mm_nt(dw, c['bk'])
    da = jnp.where(cols < rows, -_mm_tn(t, _mm_nt(dt, t)), 0.0)
    dbv, dbk = _mm_tn(t, d_delta), _mm_tn(t, dw)
    decay = jnp.exp(c['last'])
    ds_ref[...] = _by_key_row(decay, s.shape) * ds_after + _mm_tn(
        jnp.concatenate([scale * c['qg'], -c['w']], axis=0),
        jnp.concatenate([do, d_delta], axis=0))

    dxk, dxq, dy = _pairs_grad(q, k, c['run'], beta * da, scale * dm, sub)
    dkg = beta * dbk
    dv_ref[...] = beta * dbv
    db_ref[...] = sum(jnp.sum(a * b, axis=-1, keepdims=True) for a, b in (
        (dbv, v), (dbk, c['kg']), (da, c['pkk'])))
    dq_ref[...] = dqg * c['grow'] + dxq
    k_end = c['k_end']
    dk_ref[...] = dkg * c['grow'] + dk_end * jnp.exp(c['last'] - c['run']) \
        + dxk + dy
    d_run = dkg * c['kg'] + dqg * c['qg'] - dk_end * k_end \
        + k * dxk + q * dxq - k * dy
    # G_C feeds the state's decay and K . exp(G_C - G)
    by_key = _mm_nt(jnp.ones((8, s.shape[1]), _F32), s * ds_after)[:1]
    d_last = decay * by_key + jnp.sum(dk_end * k_end, axis=0, keepdims=True)
    d_run += jnp.where(_iota(d_run.shape, 0) == chunk - 1, d_last, 0.0)
    # G is a running sum: its gradient sums from the chunk's end
    dg_ref[...] = _sums_down(d_run, reverse=True)


def _compiler_params(interpret):
    if interpret:
        return {}
    return {'compiler_params': pltpu.CompilerParams(
        dimension_semantics=('parallel', 'arbitrary'))}


def _forward(q, k, v, g, beta, scale, chunk, sub, interpret, residuals):
    """``(o,)``, and with ``residuals`` the states and matrices the backward
    kernel reads (a pallas_call's outputs are written whether read or not)."""
    bh, s, dk = k.shape
    dv, n = v.shape[-1], s // chunk
    rows = lambda w: pl.BlockSpec((None, chunk, w),               # noqa
                                  lambda i, j: (i, j, 0))
    a_chunk = lambda *w: pl.BlockSpec((None, None) + w,           # noqa
                                      lambda i, j: (i, j, 0, 0))
    out_shape, out_specs = [jax.ShapeDtypeStruct((bh, s, dv), _F32)], \
        [rows(dv)]
    if residuals:
        out_shape += [jax.ShapeDtypeStruct((bh, n, dk, dv), _F32),
                      jax.ShapeDtypeStruct((bh, n, chunk, 3 * chunk), _F32)]
        out_specs += [a_chunk(dk, dv), a_chunk(chunk, 3 * chunk)]
    return pl.pallas_call(
        functools.partial(_forward_kernel, scale=scale, sub=sub,
                          residuals=residuals),
        out_shape=tuple(out_shape),
        grid=(bh, n),
        in_specs=[rows(dk), rows(dk), rows(dv), rows(dk), rows(1)],
        out_specs=tuple(out_specs),
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        interpret=interpret,
        name='delta_rule_fwd',
        **_compiler_params(interpret),
    )(q, k, v, g, beta)


def _backward(q, k, v, g, beta, before, mats, do, scale, chunk, sub,
              interpret):
    bh, s, dk = k.shape
    dv, n = v.shape[-1], s // chunk
    rows = lambda w: pl.BlockSpec((None, chunk, w),               # noqa
                                  lambda i, j: (i, n - 1 - j, 0))
    shapes = [(dk, q), (dk, k), (dv, v), (dk, g), (1, beta)]
    return pl.pallas_call(
        functools.partial(_backward_kernel, scale=scale, sub=sub),
        out_shape=tuple(jax.ShapeDtypeStruct(a.shape, _F32)
                        for _, a in shapes),
        grid=(bh, n),
        in_specs=[rows(w) for w, _ in shapes]
        + [pl.BlockSpec((None, None, dk, dv),
                        lambda i, j: (i, n - 1 - j, 0, 0)),
           pl.BlockSpec((None, None, chunk, 3 * chunk),
                        lambda i, j: (i, n - 1 - j, 0, 0)), rows(dv)],
        out_specs=tuple(rows(w) for w, _ in shapes),
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        interpret=interpret,
        name='delta_rule_bwd',
        **_compiler_params(interpret),
    )(q, k, v, g, beta, before, mats, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _rule(q, k, v, g, beta, scale, chunk, sub, interpret):
    return _forward(q, k, v, g, beta, scale, chunk, sub, interpret, False)[0]


def _rule_fwd(q, k, v, g, beta, scale, chunk, sub, interpret):
    o, before, mats = _forward(q, k, v, g, beta, scale, chunk, sub,
                               interpret, True)
    return o, (q, k, v, g, beta, before, mats)


def _rule_bwd(scale, chunk, sub, interpret, res, do):
    return _backward(*res, do, scale, chunk, sub, interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def chunk_gated_delta_rule(q, k, v, log_decay, beta, scale: float,
                           chunk: int, sub: int, interpret: bool = False):
    """``ops/delta_rule.chunk_gated_delta_rule``'s contract, on the kernels:
    ``q``, ``k``, ``log_decay`` ``(batch, heads, seq, dk)``, ``v`` ``(batch,
    heads, seq, dv)``, ``beta`` ``(batch, heads, seq)`` -> ``o`` ``(batch,
    heads, seq, dv)`` float32; ``sub`` a power of two that divides
    ``chunk``."""
    if chunk % sub or sub & (sub - 1):
        raise ValueError(f'delta rule kernel: sub-chunk {sub} is no power '
                         f'of two dividing the chunk {chunk}')
    q, k, v, g, beta = (a.astype(_F32) for a in (q, k, v, log_decay, beta))
    b, h, s, dk = k.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, 0), (0, pad)))
    flat = lambda a: a.reshape((b * h, s + pad) + a.shape[3:])   # noqa
    o = _rule(flat(q), flat(k), flat(v), flat(g),
              beta.reshape(b * h, s + pad, 1), scale, chunk, sub, interpret)
    return o.reshape(b, h, s + pad, dv)[:, :, :s]
