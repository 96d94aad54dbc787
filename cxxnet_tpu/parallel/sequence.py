"""Sequence / context parallelism: ring attention and all-to-all (Ulysses).

The reference is a fixed-shape CNN trainer with no sequence axis
(SURVEY.md §5: long-context is absent there), but this framework treats
long-context scale as first-class: attention over sequences longer than one
chip's memory runs sequence-sharded across the mesh.

Two interchangeable strategies, both pure ``shard_map`` programs whose
collectives ride ICI:

* ``ring_attention`` — K/V blocks rotate around the ring
  (``lax.ppermute``) while each device holds its Q shard; softmax is
  accumulated online flash-style (running max + denominator), so the full
  ``(seq, seq)`` score matrix never materializes.  Communication overlaps
  with the per-block matmuls under XLA's async collectives.
* ``ulysses_attention`` — ``lax.all_to_all`` re-shards from
  sequence-parallel to head-parallel, runs dense local attention per head
  group, and re-shards back.  Cheaper for moderate sequence lengths when
  heads >= devices.

Both compute exact attention: outputs match single-device attention to
numerical tolerance (tests/test_sequence_parallel.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _local_attention(q, k, v, scale, mask=None):
    """Dense attention on local blocks.  q:(b,sq,h,d) k,v:(b,sk,h,d)."""
    scores = jnp.einsum('bqhd,bkhd->bhqk', q, k) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v)


def attention_reference(q, k, v, causal: bool = False):
    """Single-device reference attention (the correctness oracle)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    mask = None
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))[None, None]
    return _local_attention(q, k, v, scale, mask)


def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
    """Per-shard body: accumulate attention over all K/V blocks as they
    rotate around the ring."""
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(q.shape[-1])
    b, sq, h, d = q.shape
    sk = k.shape[1]

    def body(step, carry):
        k_blk, v_blk, acc, m, l = carry
        # global block index the K/V currently held came from
        src = (my_idx + step) % n
        scores = jnp.einsum('bqhd,bkhd->bhqk', q, k_blk) * scale
        if causal:
            q_pos = my_idx * sq + jnp.arange(sq)
            k_pos = src * sk + jnp.arange(sk)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        m_blk = jnp.max(scores, axis=-1)                     # (b,h,q)
        m_new = jnp.maximum(m, m_blk)
        # guard fully-masked rows (exp(-inf - -inf)) with a finite max
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(scores - m_safe[..., None])
        p = jnp.where(jnp.isneginf(scores), 0.0, p)
        corr = jnp.exp(jnp.where(jnp.isneginf(m), m_safe, m) - m_safe)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = (acc * corr[..., None]
                   + jnp.einsum('bhqk,bkhd->bhqd', p, v_blk))
        # rotate K/V to the next device in the ring
        perm = [(i, (i - 1) % n) for i in range(n)]
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, acc_new, m_new, l_new)

    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    # constants start "unvarying" under shard_map's varying-manual-axes
    # tracking; mark them varying over the ring axis for the scan carry
    acc0, m0, l0 = (lax.pcast(x, (axis_name,), to='varying')
                    for x in (acc0, m0, l0))
    _, _, acc, m, l = lax.fori_loop(0, n, body, (k, v, acc0, m0, l0))
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc / l[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)   # (b,sq,h,d)


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = 'data',
                   causal: bool = False):
    """Exact attention over sequence-sharded q/k/v.

    Arrays are global ``(batch, seq, heads, head_dim)``; the sequence axis
    is sharded over ``axis_name`` of ``mesh``.
    """
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def _ulysses_local(q, k, v, axis_name: str, causal: bool):
    """seq-sharded -> all_to_all -> head-sharded dense attention -> back."""
    # (b, s/n, h, d) -> (b, s, h/n, d): gather sequence, scatter heads
    q = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    k = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    v = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    scale = 1.0 / math.sqrt(q.shape[-1])
    mask = None
    if causal:
        s = q.shape[1]
        mask = jnp.tril(jnp.ones((s, s), bool))[None, None]
    out = _local_attention(q, k, v, scale, mask)
    # (b, s, h/n, d) -> (b, s/n, h, d)
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention(q, k, v, mesh: Mesh, axis_name: str = 'data',
                      causal: bool = False):
    """All-to-all (Ulysses) sequence parallelism; heads must divide the
    axis size."""
    if q.shape[2] % mesh.shape[axis_name]:
        raise ValueError('ulysses: heads must divide the mesh axis')
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(_ulysses_local, axis_name=axis_name,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
