"""Causal attention over a whole training sequence, blocked in both passes.

``causal_attention(q, k, v, scale, window=0)`` takes head-major operands,
``q`` ``(batch, heads, seq, dim)`` and ``k``, ``v`` ``(batch, kv_heads, seq,
dim)``, and never holds the ``(heads, seq, seq)`` score matrix in HBM.
Query head ``h`` reads key/value head ``h // (heads / kv_heads)`` (grouped
heads: a key/value head is read once for its group, never written out a
query head); position ``i`` sees ``j <= i`` and, with a ``window``, only
``i - j < window``.

* Equal heads over all positions (latent attention), on one TPU chip: the
  Pallas flash-attention kernel that ships with JAX
  (``jax.experimental.pallas.ops.tpu.flash_attention``: one forward kernel,
  a dq and a dkv kernel behind a ``custom_vjp``), with the block sizes
  measured on the v5e for 20 heads of 256 over 8,192 positions (PERF.md,
  PR 29: 5.4 ms forward and 23.3 ms forward and backward at 1024/512
  against 25.6 and 101.6 ms at the kernel's default of 128).
* Grouped heads or a window, on one TPU chip: JAX's block-sparse kernel
  (``...pallas.ops.tpu.splash_attention``, its multi-query form, one call a
  key/value head by ``vmap``): the mask is laid over the grid of blocks
  when the step is traced, so a key block wholly outside the window is
  neither fetched nor computed, in the forward, the dq and the dkv kernel;
  what it saves between the passes is one log-sum-exp a row, ``(heads,
  seq)`` float32 (the flash kernel's wrapper broadcasts its row statistics
  to lanes: 3.0 GB a layer at 72 heads, PERF.md 7).
* Everywhere else (the CPU, a mesh of several devices, ``use_pallas = 0``,
  a head size the kernels do not take): plain XLA over blocks of queries
  with the same mask and the same grouping, each block recomputed in the
  backward pass, so that what is live is one ``(heads, block, seq)`` slab.

All compute the scores and the softmax in float32.  ``_use_flash`` and
``_use_splash`` choose from what they can observe - the backend, the mesh's
size, the operands' shapes, the window - and ``use_pallas``; nothing else in
the tree selects an attention kernel, and these are the only blocked
attentions a step can reach.
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
#: the block-sparse kernel's tiles (queries, keys fetched, keys a product)
#: over all positions and under a window, measured on the v5e (PERF.md 6,
#: PR 36): a window's blocks are the smaller, so that fewer of the keys a
#: block fetches lie outside it
SPLASH_BLOCKS_FULL = (1024, 1024, 512)
SPLASH_BLOCKS_WINDOW = (512, 512, 512)


def _on_one_tpu_chip(spmd_devices: int) -> bool:
    # neither kernel has an interpret mode worth a step, so the CPU never
    # takes one
    return (pallas_mode() != 'off' and spmd_devices == 1
            and jax.default_backend() == 'tpu')


def _use_flash(q, k, v, spmd_devices: int) -> bool:
    if not _on_one_tpu_chip(spmd_devices):
        return False
    seq, dim = q.shape[2], q.shape[3]
    return (k.shape == q.shape and v.shape == q.shape and dim % 128 == 0
            and seq % FLASH_BLOCK_MINOR == 0)


def _use_splash(q, k, v, spmd_devices: int) -> bool:
    if not _on_one_tpu_chip(spmd_devices):
        return False
    seq, dim = q.shape[2], q.shape[3]
    return (k.shape == v.shape and k.shape[3] == dim and dim % 128 == 0
            and seq % SPLASH_BLOCKS_FULL[0] == 0)


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


@functools.lru_cache(maxsize=None)
def _splash_kernel(seq: int, group: int, window: int):
    """The block-sparse kernel of one key/value head and its ``group``
    query heads: the mask's blocks are worked out here, once a shape, in
    numpy, and ride in the step as small integer tables."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as masks)
    if window:
        mask = masks.LocalMask((seq, seq), (window - 1, 0), 0)
        bq, bkv, bc = SPLASH_BLOCKS_WINDOW
    else:
        mask = masks.CausalMask((seq, seq))
        bq, bkv, bc = SPLASH_BLOCKS_FULL
    blocks = kernel.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bc,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bc,
        block_q_dq=bq, block_kv_dq=bkv)
    with jax.ensure_compile_time_eval():
        return kernel.make_splash_mqa_single_device(
            masks.MultiHeadMask([mask] * group), block_sizes=blocks)


def _splash(q, k, v, scale: float, window: int):
    b, h, s, d = q.shape
    hk = k.shape[1]
    one_kv_head = _splash_kernel(s, h // hk, window)   # (g, s, d), (s, d) x 2
    # the kernel takes no scale: the queries carry it, as its own users do
    q = (q * jnp.asarray(scale, q.dtype)).reshape(b, hk, h // hk, s, d)
    o = jax.vmap(jax.vmap(one_kv_head))(q, k, v)
    return o.reshape(b, h, s, v.shape[3])


def _masked_softmax_block(qb, k, v, scale: float, first_row, window: int):
    """One block of queries against every key: ``first_row`` is the
    position of the block's first query; ``qb`` ``(b, kv_heads, group, q,
    d)``, ``k``, ``v`` ``(b, kv_heads, seq, d)``."""
    scores = jnp.einsum('bngqd,bnkd->bngqk', qb, k,
                        preferred_element_type=jnp.float32) * scale
    rows = first_row + jnp.arange(qb.shape[3])[:, None]
    cols = jnp.arange(k.shape[2])[None, :]
    seen = cols <= rows
    if window:
        seen = seen & (rows - cols < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum('bngqk,bnkd->bngqd', probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def causal_attention_xla(q, k, v, scale: float, block_q: int = XLA_BLOCK_Q,
                         window: int = 0):
    b, h, seq, d = q.shape
    hk = k.shape[1]
    q = q.reshape(b, hk, h // hk, seq, d)
    if seq <= block_q or seq % block_q:
        out = _masked_softmax_block(q, k, v, scale, 0, window)
    else:
        nblk = seq // block_q
        qs = jnp.moveaxis(q.reshape(q.shape[:3] + (nblk, block_q, d)), 3, 0)
        body = jax.checkpoint(functools.partial(
            _masked_softmax_block, scale=scale, window=window))
        out = jax.lax.map(
            lambda a: body(a[0], k, v, first_row=a[1]),
            (qs, jnp.arange(nblk) * block_q))
        out = jnp.moveaxis(out, 0, 3)
    return out.reshape(b, h, seq, v.shape[3])


def causal_attention(q, k, v, scale: float, spmd_devices: int = 1,
                     window: int = 0):
    """softmax(q k^T * scale, causal and windowed) v; ``q``: ``(b, h, s,
    dk)``, ``k``: ``(b, hk, s, dk)``, ``v``: ``(b, hk, s, dv)`` with ``hk``
    a divisor of ``h``; ``window`` 0 is none."""
    if q.shape[1] % k.shape[1]:
        raise ValueError(f'causal_attention: {q.shape[1]} query heads over '
                         f'{k.shape[1]} key/value heads')
    if not window and _use_flash(q, k, v, spmd_devices):
        return _flash(q, k, v, scale)
    if _use_splash(q, k, v, spmd_devices):
        return _splash(q, k, v, scale, window)
    return causal_attention_xla(q, k, v, scale, window=window)
