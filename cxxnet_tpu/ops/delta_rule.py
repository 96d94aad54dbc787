"""The gated delta rule of Kimi Delta Attention over a training sequence, in
the chunkwise-parallel form (Kimi Linear, arXiv 2510.26692, section 3).

For each head, with a state ``S`` of ``(dk, dv)`` that starts at zero,

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = scale * S_t^T q_t

with one decay ``alpha_t = exp(log_decay_t)`` a channel of ``k``.  Token by
token that is a scan of ``seq`` steps over a ``dk x dv`` state; here the
sequence is cut into chunks of ``chunk`` tokens and a scan runs over chunks.

Inside a chunk, with ``G_r`` the running sum of ``log_decay`` from the
chunk's start to ``r`` and ``S_0`` the state before it, the state's changes
are ``delta_r = beta_r (v_r - (Diag(alpha_r) S_{r-1})^T k_r)`` and

    (I + A) Delta = Diag(beta) (V - (K . exp(G)) S_0),
    A[r, i] = beta_r sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])     (i < r)

so ``Delta = U - W S_0`` with ``U = T Diag(beta) V``, ``W = T Diag(beta)
(K . exp(G))`` and ``T = (I + A)^-1`` (the WY / UT representation: one unit
lower-triangular solve a chunk, in float32).  Then

    O = scale (Q . exp(G)) S_0 + M Delta,
    M[r, i] = scale sum_c q_r[c] k_i[c] exp(G_r[c] - G_i[c])         (i <= r)
    S_C = Diag(exp(G_C)) S_0 + (K . exp(G_C - G))^T Delta.

Two forms compute this, one algorithm on two backends; ``gated_delta_rule``
chooses from what it can observe, as ``ops/attention`` chooses a kernel:

* on one TPU chip, with ``dk`` and ``dv`` multiples of 128: two Pallas
  kernels under one ``custom_vjp`` (``ops/delta_rule_kernel``), a forward
  that walks the chunks in order with the state in VMEM and a backward that
  walks them in reverse with the state's gradient there;
* everywhere else (the CPU, a mesh of several devices, other head sizes):
  ``chunk_gated_delta_rule`` in XLA.  Every ``U``, ``W``, ``A`` and ``M``
  is computed for all chunks at once by products and one batched
  triangular solve; a scan over chunks carries ``S`` alone and does two
  products a step; the outputs are one batched product after it; autodiff
  makes the backward pass.

Either keeps one state a chunk for the backward pass, never one a token.

A decay summed over a chunk passes -88 readily (64 tokens of -1.4), and
``exp(-G_i)`` then overflows float32.  So ``exp(G_r - G_i)`` is never taken
as ``exp(G_r) exp(-G_i)`` from the chunk's start: the chunk is cut into
sub-chunks of ``sub`` tokens, a pair in two different sub-chunks is taken
relative to the last position before the later one's start (each factor
then at most 1), and a pair inside one sub-chunk elementwise, ``exp(G_r -
G_i)`` itself (as Kimi Linear's kernels do).  Everything here is float32,
its products at the highest precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import delta_rule_kernel

#: tokens a chunk, and a sub-chunk of the pairs taken elementwise
CHUNK = 64
SUB = 16

_F32 = jnp.float32


def _mm(spec, *operands):
    return jnp.einsum(spec, *operands, precision=lax.Precision.HIGHEST,
                      preferred_element_type=_F32)


@jax.checkpoint
def _pairs_within(rows, keys, g):
    """``_pairs`` inside one sub-chunk, elementwise: ``(..., sub, sub)``.
    Above the diagonal the exponent is positive and is clipped before it
    can overflow.  Checkpointed: the backward pass recomputes the ``(sub,
    sub, dk)`` exponentials inside its own reductions rather than keep
    them (four times ``k``'s size a chunk)."""
    step = jnp.minimum(g[..., :, None, :] - g[..., None, :, :], 0.0)
    return jnp.sum(rows[..., :, None, :] * keys[..., None, :, :]
                   * jnp.exp(step), axis=-1)


def _pairs(rows, keys, gc, sub: int):
    """``P[r, i] = sum_c rows_r[c] keys_i[c] exp(gc_r[c] - gc_i[c])`` for
    ``i <= r`` inside each chunk (zero above the diagonal); ``rows``,
    ``keys``, ``gc``: ``(..., chunk, dk)``, ``gc`` non-increasing along the
    chunk."""
    chunk = gc.shape[-2]
    blocks = []
    for lo in range(0, chunk, sub):
        hi = lo + sub
        g, x = gc[..., lo:hi, :], rows[..., lo:hi, :]
        parts = [_pairs_within(x, keys[..., lo:hi, :], g)]
        if lo:
            # the earlier sub-chunks, through the last position before this
            # one: both factors are at most 1
            ref = gc[..., lo - 1:lo, :]
            parts.insert(0, _mm('...rc,...ic->...ri', x * jnp.exp(g - ref),
                                keys[..., :lo, :]
                                * jnp.exp(ref - gc[..., :lo, :])))
        if hi < chunk:
            parts.append(jnp.zeros(x.shape[:-1] + (chunk - hi,), _F32))
        blocks.append(jnp.concatenate(parts, axis=-1))
    return jnp.tril(jnp.concatenate(blocks, axis=-2))


def chunk_gated_delta_rule(q, k, v, log_decay, beta, scale: float,
                           chunk: int = CHUNK, sub: int = SUB):
    """``o`` ``(batch, heads, seq, dv)`` float32 of the gated delta rule
    (module docstring): ``q``, ``k``, ``log_decay`` ``(batch, heads, seq,
    dk)``, ``v`` ``(batch, heads, seq, dv)``, ``beta`` ``(batch, heads,
    seq)``; ``log_decay <= 0``.  A sequence that is no multiple of
    ``chunk`` is padded with tokens that change nothing (``k = 0``, ``beta
    = 0``, no decay)."""
    sub = min(sub, chunk)
    if chunk % sub:
        raise ValueError(f'delta rule: chunk {chunk} is no multiple of {sub}')
    q, k, v, g, beta = (a.astype(_F32) for a in (q, k, v, log_decay, beta))
    b, h, s, dk = k.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, 0), (0, pad)))
    n = (s + pad) // chunk
    q, k, v, g = (a.reshape(b, h, n, chunk, a.shape[-1])
                  for a in (q, k, v, g))
    beta = beta.reshape(b, h, n, chunk, 1)
    gc = jnp.cumsum(g, axis=-2)                     # G, (b, h, n, chunk, dk)
    last = gc[..., -1:, :]                          # the chunk's whole decay

    a = beta * _pairs(k, k, gc, sub)                # the diagonal is ignored
    rhs = jnp.concatenate([beta * v, beta * k * jnp.exp(gc)], axis=-1)
    uw = lax.linalg.triangular_solve(a, rhs, left_side=True, lower=True,
                                     unit_diagonal=True)
    u, w = uw[..., :dv], uw[..., dv:]
    k_end = k * jnp.exp(last - gc)                  # K . exp(G_C - G)

    def step(state, xs):
        u, w, k_end, decay = xs
        delta = u - _mm('...ck,...kv->...cv', w, state)
        state_next = decay[..., 0, :, None] * state \
            + _mm('...ck,...cv->...kv', k_end, delta)
        return state_next, (delta, state)

    # the chunks' axis leads in the scan
    lead = lambda x: jnp.moveaxis(x, 2, 0)          # noqa: E731
    _, (delta, before) = lax.scan(
        step, jnp.zeros((b, h, dk, dv), _F32),
        (lead(u), lead(w), lead(k_end), lead(jnp.exp(last))))
    delta, before = jnp.moveaxis(delta, 0, 2), jnp.moveaxis(before, 0, 2)
    o = scale * (_mm('...ck,...kv->...cv', q * jnp.exp(gc), before)
                 + _mm('...ri,...iv->...rv', _pairs(q, k, gc, sub), delta))
    return o.reshape(b, h, n * chunk, dv)[:, :, :s]


def _use_kernel(q, v, spmd_devices: int) -> bool:
    # the backend, the mesh and the heads decide: no option does (the
    # kernels' interpret mode is for tests, never worth a step)
    return (spmd_devices == 1 and jax.default_backend() == 'tpu'
            and q.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0)


def gated_delta_rule(q, k, v, log_decay, beta, scale: float,
                     spmd_devices: int = 1):
    """``chunk_gated_delta_rule``'s contract at chunks of ``CHUNK``: on the
    Pallas kernels where a step runs on one TPU chip and the heads are
    multiples of 128 wide, in XLA elsewhere (module docstring)."""
    if _use_kernel(q, v, spmd_devices):
        return delta_rule_kernel.chunk_gated_delta_rule(
            q, k, v, log_decay, beta, scale, CHUNK, SUB)
    return chunk_gated_delta_rule(q, k, v, log_decay, beta, scale)


def chunk_log_decay_sums(log_decay, chunk: int = CHUNK):
    """The summed ``log_decay`` of every chunk of ``chunk`` tokens,
    ``(..., chunks, dk)`` over ``(..., seq, dk)`` (the last chunk may be
    short): how far below float32's ``exp`` range a chunk's decay runs."""
    s, dk = log_decay.shape[-2:]
    pad = -s % chunk
    g = jnp.pad(log_decay.astype(_F32),
                [(0, 0)] * (log_decay.ndim - 2) + [(0, pad), (0, 0)])
    return jnp.sum(g.reshape(g.shape[:-2] + ((s + pad) // chunk, chunk, dk)),
                   axis=-2)
