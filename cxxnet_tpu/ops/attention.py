"""Causal attention over a whole training sequence, blocked in both passes.

``causal_attention(q, k, v, scale)`` takes ``(batch, heads, seq, dim)``
operands and never holds the ``(heads, seq, seq)`` score matrix in HBM:

* on one TPU chip it is the Pallas flash-attention kernel that ships with
  JAX (``jax.experimental.pallas.ops.tpu.flash_attention``: one forward
  kernel, a dq and a dkv kernel behind a ``custom_vjp``), with the block
  sizes measured on the v5e for 20 heads of 256 over 8,192 positions
  (PERF.md, PR 29: 5.4 ms forward and 23.3 ms forward and backward at
  1024/512 against 25.6 and 101.6 ms at the kernel's default of 128);
* everywhere else (the CPU, a mesh of several devices, ``use_pallas = 0``,
  a head size the kernel does not take) it is plain XLA over blocks of
  queries, each block recomputed in the backward pass, so that what is live
  is one ``(heads, block, seq)`` slab.

Both compute the scores and the softmax in float32.  ``_use_flash`` chooses
between them from what it can observe - the backend, the mesh's size, the
operands' shapes - and ``use_pallas``; nothing else in the tree selects an
attention kernel, and this is the only flash attention a step can reach.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_kernels import pallas_mode

#: queries a block of the XLA path; a sequence no longer than this is one
#: full masked softmax
XLA_BLOCK_Q = 512
#: the Pallas kernel's tiles, measured on the v5e (module docstring)
FLASH_BLOCK_MAJOR, FLASH_BLOCK_MINOR = 1024, 512


def _use_flash(q, k, v, spmd_devices: int) -> bool:
    if pallas_mode() == 'off' or spmd_devices > 1:
        return False
    if jax.default_backend() != 'tpu':
        return False                    # the kernel has no interpret mode
    seq, dim = q.shape[2], q.shape[3]
    return (k.shape == q.shape and v.shape == q.shape and dim % 128 == 0
            and seq % FLASH_BLOCK_MINOR == 0)


def _flash(q, k, v, scale: float):
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention)
    seq = q.shape[2]
    major = FLASH_BLOCK_MAJOR if seq % FLASH_BLOCK_MAJOR == 0 \
        else FLASH_BLOCK_MINOR
    minor = FLASH_BLOCK_MINOR
    blocks = BlockSizes(
        block_q=major, block_k_major=major, block_k=minor, block_b=1,
        block_q_major_dkv=major, block_k_major_dkv=major,
        block_k_dkv=minor, block_q_dkv=minor,
        block_k_major_dq=major, block_k_dq=minor, block_q_dq=major)
    return flash_attention(q, k, v, causal=True, sm_scale=scale,
                           block_sizes=blocks)


def _masked_softmax_block(qb, k, v, scale: float, first_row):
    """One block of queries against every key: ``first_row`` is the
    position of the block's first query."""
    scores = jnp.einsum('bhqd,bhkd->bhqk', qb, k,
                        preferred_element_type=jnp.float32) * scale
    rows = first_row + jnp.arange(qb.shape[2])[:, None]
    cols = jnp.arange(k.shape[2])[None, :]
    scores = jnp.where(cols <= rows, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum('bhqk,bhkd->bhqd', probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def causal_attention_xla(q, k, v, scale: float, block_q: int = XLA_BLOCK_Q):
    seq = q.shape[2]
    if seq <= block_q or seq % block_q:
        return _masked_softmax_block(q, k, v, scale, 0)
    nblk = seq // block_q
    qs = jnp.moveaxis(q.reshape(q.shape[:2] + (nblk, block_q, q.shape[3])),
                      2, 0)
    body = jax.checkpoint(functools.partial(_masked_softmax_block,
                                            scale=scale))
    out = jax.lax.map(
        lambda a: body(a[0], k, v, first_row=a[1]),
        (qs, jnp.arange(nblk) * block_q))
    return jnp.moveaxis(out, 0, 2).reshape(q.shape[:3] + (v.shape[3],))


def causal_attention(q, k, v, scale: float, spmd_devices: int = 1):
    """softmax(q k^T * scale, causal) v; ``q, k``: ``(b, h, s, dk)``,
    ``v``: ``(b, h, s, dv)``."""
    if _use_flash(q, k, v, spmd_devices):
        return _flash(q, k, v, scale)
    return causal_attention_xla(q, k, v, scale)
