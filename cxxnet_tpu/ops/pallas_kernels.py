"""Pallas TPU kernels for hot ops.

Per the north-star mapping (BASELINE.json), the reference's hand-written
CUDA/mshadow hot paths become TPU kernels.  Design notes:

* **conv / pooling** stay on XLA's native convolution/reduce-window — on
  TPU those already lower to MXU-optimal programs (the cuDNN analogy);
  a hand-written Pallas conv would have to re-derive XLA's spatial
  partitioning to break even.  Measured, not assumed: see bench notes.
* **LRN** has no kernel here: in the net the (rows, c) layout a custom
  call demands cost more in copies than the kernels saved, and XLA fuses
  the O(local_size) window sum of ``layers/norm.lrn`` in the layout the
  neighbouring convolutions keep (PERF.md 6, PR 28).
* **fullc** gets a tiled-MXU matmul (``pallas_matmul``) used when
  ``CXXNET_PALLAS=1``; XLA's dot is the default.

All kernels run under ``interpret=True`` on CPU, which is how the test
suite validates them without hardware.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def pallas_mode() -> str:
    """Tri-state Pallas switch: ``'on'`` (config ``use_pallas=1`` /
    ``CXXNET_PALLAS=1`` forces every Pallas path), ``'off'`` (explicit 0
    disables even the measured-profitable ones), ``'auto'`` (unset: each
    op consults its own receipts-derived profitability gate — see
    ``fullc_use_pallas`` and receipts/micro_*.json)."""
    v = os.environ.get('CXXNET_PALLAS')
    if v is None or not v.strip():
        return 'auto'
    return ('on' if v.strip().lower() in ('1', 'true', 'yes', 'on')
            else 'off')


def pallas_enabled() -> bool:
    """True only when Pallas paths are explicitly forced on."""
    return pallas_mode() == 'on'




_FLASH_SCORE_BYTES = 4 << 30   # dense-score budget: ~1/4 of v5e HBM


def attn_use_flash(seq_len: int, batch: int = 1, heads: int = 1) -> bool:
    """Whether fused flash attention should replace the dense local path
    for a (local) ``batch x heads x seq x seq`` attention.  ``'on'``
    forces it; in ``'auto'`` it engages only on a real TPU when the
    dense O(seq^2) score
    materialization — ``batch*heads*seq^2`` f32 — would blow a ~4 GiB
    budget (about a quarter of v5e HBM, leaving room for params,
    activations, and the backward's second score pass).  The gate is a
    MEMORY feasibility bound, not a speed claim: at every SPEED-measured
    shape (seq <= 4096 at small b*h, receipts/micro_attn.json) XLA's
    dense path won, so auto stays off while dense still fits."""
    mode = pallas_mode()
    if mode == 'off':
        return False
    if mode == 'on':
        return True
    score_bytes = 4.0 * batch * heads * seq_len * seq_len
    return not _interpret() and score_bytes >= _FLASH_SCORE_BYTES


def fullc_use_pallas(m: int, k: int, n: int, *, is_train: bool,
                     spmd_devices: int = 1) -> bool:
    """Whether fullc's forward matmul should take the Pallas kernel.

    Training keeps XLA everywhere: with honest (scatter-add-perturbed)
    timing the fwd+bwd kernels lose at every production shape
    (receipts/micro_matmul.json).  The exception this gate encodes is
    the EVAL path at fc8's shape class: at 256x4096x1000 the Pallas
    forward measured **4.28x** over XLA — XLA mishandles the
    non-lane-aligned N=1000 (48.7 TF/s) while the padded Pallas tiles
    don't care.  ``auto`` therefore engages only when no backward will
    run (``is_train=False`` — pred/extract/evaluate forwards), on a
    real single-device TPU program, at the measured shape class:
    lane-ragged N (``n % 128 != 0``) big enough to matter
    (m >= 128, k >= 1024, n >= 512).  Anything narrower was never
    measured and stays on XLA; ``use_pallas=1`` still forces the
    kernel everywhere, ``0`` disables it."""
    mode = pallas_mode()
    if mode == 'off':
        return False
    if mode == 'on':
        return True
    if os.environ.get('CXXNET_FULLC_PALLAS', '').strip() == '0':
        # fullc-only kill switch: lets bench.py eval_alexnet A/B THIS
        # gate in isolation, whatever else CXXNET_PALLAS reaches
        return False
    if is_train or _interpret() or spmd_devices != 1:
        return False
    return fullc_pallas_shape_class(m, k, n)


def fullc_pallas_shape_class(m: int, k: int, n: int) -> bool:
    """The measured fc8 shape class (receipts/micro_matmul.json):
    lane-ragged N big enough to matter."""
    return n % 128 != 0 and m >= 128 and k >= 1024 and n >= 512


def decode_use_flash(explicit=None) -> bool:
    """Whether the serve decode step should take the paged flash-decode
    kernel (:func:`paged_flash_decode`) instead of the gather-then-dense
    path.  ``explicit`` is the ``serve.flash_decode`` key: ``1``/``0``
    force it on/off, ``'auto'``/None defer to the tri-state
    ``pallas_mode()`` gate — ``'on'`` forces the kernel everywhere
    (interpret mode included: that is the CPU validation path), anything
    else leaves the gather path on.  ``auto`` never picks the kernels:
    Mosaic refuses both :func:`paged_flash_decode` and
    :func:`paged_flash_verify` on the TPU (doc/serving.md quotes the
    compiler), so only a forced spelling reaches them, and there they
    fail loudly."""
    if explicit is not None:
        text = str(explicit).strip().lower()
        if text in ('1', 'true', 'yes', 'on'):
            return True
        if text in ('0', 'false', 'no', 'off'):
            return False
        # anything else ('auto', '') falls through to the global gate
    return pallas_mode() == 'on'


def _interpret() -> bool:
    return jax.default_backend() != 'tpu'


#: every Pallas kernel's ``name=``.  The compiled program names the custom
#: call after it (``%matmul.1 = ... custom_call_target="tpu_custom_call"``)
#: and the profiler's device event carries that text, so a trace tells the
#: kernels apart and ``utils/profiler.device_time_by_scope`` sums each one
#: (doc/observability.md).  ``[a-z0-9_]`` only: a trace reader that sorts
#: events by words such as ``convolution`` or ``all-reduce`` in their text
#: must keep seeing a Mosaic custom call.  A new ``pallas_call`` adds its
#: name here (tests/test_trace_names.py holds every call site to the table).
KERNEL_NAMES = (
    'matmul', 'matmul_nt', 'matmul_tn', 'int8_matmul',
    'flash_fwd', 'flash_bwd_dq', 'flash_bwd_dkv',
    'paged_decode', 'paged_verify',
    'conv_bias_act',
)


def _block_spec(shape, index_map=None):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _compiler_params(*dimension_semantics):
    """Mark grid dims 'parallel' (independent; Mosaic can pipeline) or
    'arbitrary' (sequential — reduction dims carrying scratch state).
    Interpret mode takes no TPU compiler params."""
    if _interpret():
        return {}
    return {'compiler_params':
            pltpu.CompilerParams(dimension_semantics=dimension_semantics)}


# --- tiled matmul (fullc) -------------------------------------------------

def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref):
    """Grid (m, n, k): K is innermost so the f32 accumulator tile stays in
    VMEM scratch across K steps (keeping whole K per tile VMEM-OOMs at
    AlexNet's 9216-wide fc6)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(a_ref[:], b_ref[:],
                          preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


# Measured-winning forward tile config (r4 tile sweep, TPU v5 lite, bf16,
# earlier harness; BASELINE.md kernel table): at fc6's 256x9216x4096 the
# (256, 1024, 512) tiling ran 172.6 TF/s vs XLA's 151.0 — 1.143x, the
# first Pallas matmul win at a production shape.  Not the default (the
# sweep never covered fc7; the training path's bwd kernels still lose) —
# callers opt in via _matmul_impl(a, b, *MATMUL_TILES_WIDE_N).
MATMUL_TILES_WIDE_N = (256, 1024, 512)


@jax.custom_vjp
def pallas_matmul(a, b):
    """(m, k) @ (k, n) with an MXU-tiled Pallas kernel; differentiable
    (backward runs the same kernel on the transposed operands)."""
    return _matmul_impl(a, b)


def _matmul_vjp_fwd(a, b):
    return _matmul_impl(a, b), (a, b)


def _matmul_vjp_bwd(res, g):
    a, b = res
    # transpose-free backward: da = g @ b^T and db = a^T @ g are computed
    # by kernels that contract directly against the STORED layouts of b
    # and a — a physical .T of the (9216, 4096) fc6 weight costs a ~75 MB
    # HBM round-trip per operand per step, paid before the old
    # reuse-the-forward-kernel approach even started multiplying
    return (_matmul_nt_impl(g, b).astype(a.dtype),
            _matmul_tn_impl(a, g).astype(b.dtype))


pallas_matmul.defvjp(_matmul_vjp_fwd, _matmul_vjp_bwd)


def _matmul_nt_kernel(g_ref, b_ref, o_ref, acc_ref):
    """(bm, bn) x (bk, bn) -> (bm, bk): contract the trailing axis of
    both tiles (da = g @ b^T without transposing b)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        g_ref[:], b_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _matmul_tn_kernel(a_ref, g_ref, o_ref, acc_ref):
    """(bm, bk) x (bm, bn) -> (bk, bn): contract the leading axis of
    both tiles (db = a^T @ g without transposing a)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        a_ref[:], g_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _pad2(x, tr, tc):
    pr, pc = (-x.shape[0]) % tr, (-x.shape[1]) % tc
    return jnp.pad(x, ((0, pr), (0, pc))) if pr or pc else x


def _clamp_tile(tile: int, dim: int, align: int = 128) -> int:
    """Shrink a default tile size to the dimension it will cover (rounded
    up to MXU lane alignment), so a dim smaller than the default tile is
    not padded up to the tile — at fullc's production m=256, the TN
    backward's old fixed tile_m=512 padded the reduction to twice its
    real size and HALVED its throughput (receipts/micro_matmul_bwd.json,
    TN 0.23-0.26x vs NT 0.49-0.54x)."""
    return min(tile, max(align, -(-dim // align) * align))


def _matmul_nt_impl(g, b, tile_m: int = 256, tile_n: int = 512,
                    tile_k: int = 256):
    """g (m, n) @ b (k, n)^T -> (m, k); reduction over n (innermost)."""
    m, n = g.shape
    k = b.shape[0]
    tile_m = _clamp_tile(tile_m, m)
    tile_n = _clamp_tile(tile_n, n)
    tile_k = _clamp_tile(tile_k, k)
    gp, bp = _pad2(g, tile_m, tile_n), _pad2(b, tile_k, tile_n)
    out = pl.pallas_call(
        _matmul_nt_kernel,
        out_shape=jax.ShapeDtypeStruct((gp.shape[0], bp.shape[0]), g.dtype),
        grid=(gp.shape[0] // tile_m, bp.shape[0] // tile_k,
              gp.shape[1] // tile_n),
        in_specs=[_block_spec((tile_m, tile_n), lambda i, j, t: (i, t)),
                  _block_spec((tile_k, tile_n), lambda i, j, t: (j, t))],
        out_specs=_block_spec((tile_m, tile_k), lambda i, j, t: (i, j)),
        scratch_shapes=[_scratch((tile_m, tile_k))],
        interpret=_interpret(),
        name='matmul_nt',
        **_compiler_params('parallel', 'parallel', 'arbitrary'),
    )(gp, bp)
    return out[:m, :k]


def _matmul_tn_impl(a, g, tile_m: int = 512, tile_n: int = 256,
                    tile_k: int = 256):
    """a (m, k)^T @ g (m, n) -> (k, n); reduction over m (innermost)."""
    m, k = a.shape
    n = g.shape[1]
    tile_m = _clamp_tile(tile_m, m)
    tile_n = _clamp_tile(tile_n, n)
    tile_k = _clamp_tile(tile_k, k)
    ap, gp = _pad2(a, tile_m, tile_k), _pad2(g, tile_m, tile_n)
    out = pl.pallas_call(
        _matmul_tn_kernel,
        out_shape=jax.ShapeDtypeStruct((ap.shape[1], gp.shape[1]), a.dtype),
        grid=(ap.shape[1] // tile_k, gp.shape[1] // tile_n,
              ap.shape[0] // tile_m),
        in_specs=[_block_spec((tile_m, tile_k), lambda i, j, t: (t, i)),
                  _block_spec((tile_m, tile_n), lambda i, j, t: (t, j))],
        out_specs=_block_spec((tile_k, tile_n), lambda i, j, t: (i, j)),
        scratch_shapes=[_scratch((tile_k, tile_n))],
        interpret=_interpret(),
        name='matmul_tn',
        **_compiler_params('parallel', 'parallel', 'arbitrary'),
    )(ap, gp)
    return out[:k, :n]


def _matmul_impl(a, b, tile_m: int = 256, tile_n: int = 256,
                 tile_k: int = 512):
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    tile_m = _clamp_tile(tile_m, m)
    tile_n = _clamp_tile(tile_n, n)
    tile_k = _clamp_tile(tile_k, k)
    pm, pn, pk = (-m) % tile_m, (-n) % tile_n, (-k) % tile_k
    ap = jnp.pad(a, ((0, pm), (0, pk))) if pm or pk else a
    bp = jnp.pad(b, ((0, pk), (0, pn))) if pk or pn else b
    mm, nn, kk = ap.shape[0], bp.shape[1], ap.shape[1]
    out = pl.pallas_call(
        _matmul_kernel,
        out_shape=jax.ShapeDtypeStruct((mm, nn), a.dtype),
        grid=(mm // tile_m, nn // tile_n, kk // tile_k),
        in_specs=[_block_spec((tile_m, tile_k), lambda i, j, t: (i, t)),
                  _block_spec((tile_k, tile_n), lambda i, j, t: (t, j))],
        out_specs=_block_spec((tile_m, tile_n), lambda i, j, t: (i, j)),
        scratch_shapes=[_scratch((tile_m, tile_n))],
        interpret=_interpret(),
        name='matmul',
        **_compiler_params('parallel', 'parallel', 'arbitrary'),
    )(ap, bp)
    return out[:m, :n]


# --- flash attention ------------------------------------------------------
#
# Fused online-softmax attention: the (seq_q, seq_k) score matrix never
# leaves VMEM.  Forward and both backward passes (dq; dk/dv) are blockwise
# Pallas kernels wired through jax.custom_vjp, with the standard
# log-sum-exp + delta recomputation scheme.  Layout inside the kernels is
# (batch*heads, seq, head_dim); the public API takes (b, s, h, d).

_NEG_INF = -1e30


def _causal_mask(qi, kj, bq, bk, sk_valid):
    """(bq, bk) bool mask of *allowed* positions for query block qi /
    key block kj, also masking padded keys beyond sk_valid."""
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return (q_pos >= k_pos) & (k_pos < sk_valid)


def _valid_mask(kj, bq, bk, sk_valid):
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return k_pos < sk_valid



def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the varying-manual-axes of ``like`` so
    pallas_call works under shard_map(check_vma=True)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *, scale, causal, sk_valid):
    """Grid (bh, q_blocks, k_blocks): only one (block, d) tile of each
    operand is VMEM-resident at a time; the online-softmax state lives in
    VMEM scratch carried across the innermost (key) grid dimension."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # skip key blocks that are entirely masked: fully above the causal
    # diagonal, or entirely in the padded key range
    run = kj * bk < sk_valid
    if causal:
        run = jnp.logical_and(run, qi * bq + bq - 1 >= kj * bk)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        mask = (_causal_mask(qi, kj, bq, bk, sk_valid) if causal
                else _valid_mask(kj, bq, bk, sk_valid))
        s = jnp.where(mask, s, _NEG_INF)
        m = m_ref[:, 0]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=-1)
        m_ref[:, 0] = m_new
        acc_ref[:] = (acc_ref[:] * corr[:, None]
                      + jnp.dot(p, v_blk, preferred_element_type=jnp.float32))

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[:, 0] + jnp.log(l_safe))[:, None]


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dqacc_ref, *, scale, causal, sk_valid):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        dqacc_ref[:] = jnp.zeros_like(dqacc_ref)

    run = kj * bk < sk_valid
    if causal:
        run = jnp.logical_and(run, qi * bq + bq - 1 >= kj * bk)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
        mask = (_causal_mask(qi, kj, bq, bk, sk_valid) if causal
                else _valid_mask(kj, bq, bk, sk_valid))
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dqacc_ref[:] = dqacc_ref[:] + jnp.dot(
            ds, k_blk, preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = (dqacc_ref[:] * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dkacc_ref, dvacc_ref, *, scale,
                      causal, sq_valid):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    bk = k_ref.shape[1]
    bq = q_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dkacc_ref[:] = jnp.zeros_like(dkacc_ref)
        dvacc_ref[:] = jnp.zeros_like(dvacc_ref)

    # skip query blocks entirely below the valid range or, for causal,
    # entirely above the diagonal (no query in the block sees key block kj)
    run = qi * bq < sq_valid
    if causal:
        run = jnp.logical_and(run, qi * bq + bq - 1 >= kj * bk)

    @pl.when(run)
    def _compute():
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        q_blk = q_ref[0].astype(jnp.float32)
        do_blk = do_ref[0].astype(jnp.float32)
        lse_blk = lse_ref[0, :, 0]
        delta_blk = delta_ref[0, :, 0]
        s = jnp.dot(q_blk, k.T, preferred_element_type=jnp.float32) * scale
        q_pos = (qi * bq
                 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
        k_pos = (kj * bk
                 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
        mask = q_pos < sq_valid
        if causal:
            mask = mask & (q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse_blk[:, None]), 0.0)
        dvacc_ref[:] = dvacc_ref[:] + jnp.dot(
            p.T, do_blk, preferred_element_type=jnp.float32)
        dp = jnp.dot(do_blk, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk[:, None])
        dkacc_ref[:] = dkacc_ref[:] + jnp.dot(
            ds.T, q_blk, preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = (dkacc_ref[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dvacc_ref[:].astype(dv_ref.dtype)


def _pad_seq(x, block):
    pad = (-x.shape[1]) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _flash_blocks(seq, block):
    return max(1, min(block, seq))


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


def _flash_fwd_impl(q, k, v, causal, block_q, block_k):
    """q,k,v: (bh, s, d).  Returns (out, lse) with lse over valid keys."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = _flash_blocks(sq, block_q)
    bk = _flash_blocks(sk, block_k)
    qp, kp, vp = _pad_seq(q, bq), _pad_seq(k, bk), _pad_seq(v, bk)
    sqp, skp = qp.shape[1], kp.shape[1]
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                               sk_valid=sk)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[_sds((bh, sqp, d), q.dtype, qp),
                   _sds((bh, sqp, 1), jnp.float32, qp)],
        grid=(bh, sqp // bq, skp // bk),
        in_specs=[_block_spec((1, bq, d), lambda i, j, t: (i, j, 0)),
                  _block_spec((1, bk, d), lambda i, j, t: (i, t, 0)),
                  _block_spec((1, bk, d), lambda i, j, t: (i, t, 0))],
        out_specs=[_block_spec((1, bq, d), lambda i, j, t: (i, j, 0)),
                   _block_spec((1, bq, 1), lambda i, j, t: (i, j, 0))],
        scratch_shapes=[_scratch((bq, d)), _scratch((bq, 1)),
                        _scratch((bq, 1))],
        interpret=_interpret(),
        name='flash_fwd',
        **_compiler_params('parallel', 'parallel', 'arbitrary'),
    )(qp, kp, vp)
    return out[:, :sq], lse[:, :sq, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bhsd(q, k, v, causal, block_q, block_k):
    out, _ = _flash_fwd_impl(q, k, v, causal, block_q, block_k)
    return out


def _flash_bhsd_fwd(q, k, v, causal, block_q, block_k):
    out, lse = _flash_fwd_impl(q, k, v, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_bhsd_bwd(causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = _flash_blocks(sq, block_q)
    bk = _flash_blocks(sk, block_k)
    scale = 1.0 / math.sqrt(d)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    qp, gp = _pad_seq(q, bq), _pad_seq(g, bq)
    kp, vp = _pad_seq(k, bk), _pad_seq(v, bk)
    sqp, skp = qp.shape[1], kp.shape[1]
    pad_q = sqp - sq
    lse_p = jnp.pad(lse, ((0, 0), (0, pad_q)))[..., None]
    delta_p = jnp.pad(delta, ((0, 0), (0, pad_q)))[..., None]

    dq_kernel = functools.partial(_flash_dq_kernel, scale=scale,
                                  causal=causal, sk_valid=sk)
    dq = pl.pallas_call(
        dq_kernel,
        out_shape=_sds((bh, sqp, d), q.dtype, qp),
        grid=(bh, sqp // bq, skp // bk),
        in_specs=[_block_spec((1, bq, d), lambda i, j, t: (i, j, 0)),
                  _block_spec((1, bk, d), lambda i, j, t: (i, t, 0)),
                  _block_spec((1, bk, d), lambda i, j, t: (i, t, 0)),
                  _block_spec((1, bq, d), lambda i, j, t: (i, j, 0)),
                  _block_spec((1, bq, 1), lambda i, j, t: (i, j, 0)),
                  _block_spec((1, bq, 1), lambda i, j, t: (i, j, 0))],
        out_specs=_block_spec((1, bq, d), lambda i, j, t: (i, j, 0)),
        scratch_shapes=[_scratch((bq, d))],
        interpret=_interpret(),
        name='flash_bwd_dq',
        **_compiler_params('parallel', 'parallel', 'arbitrary'),
    )(qp, kp, vp, gp, lse_p, delta_p)

    dkv_kernel = functools.partial(_flash_dkv_kernel, scale=scale,
                                   causal=causal, sq_valid=sq)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        out_shape=[_sds((bh, skp, d), k.dtype, kp),
                   _sds((bh, skp, d), v.dtype, vp)],
        grid=(bh, skp // bk, sqp // bq),
        in_specs=[_block_spec((1, bq, d), lambda i, t, j: (i, j, 0)),
                  _block_spec((1, bk, d), lambda i, t, j: (i, t, 0)),
                  _block_spec((1, bk, d), lambda i, t, j: (i, t, 0)),
                  _block_spec((1, bq, d), lambda i, t, j: (i, j, 0)),
                  _block_spec((1, bq, 1), lambda i, t, j: (i, j, 0)),
                  _block_spec((1, bq, 1), lambda i, t, j: (i, j, 0))],
        out_specs=[_block_spec((1, bk, d), lambda i, t, j: (i, t, 0)),
                   _block_spec((1, bk, d), lambda i, t, j: (i, t, 0))],
        scratch_shapes=[_scratch((bk, d)), _scratch((bk, d))],
        interpret=_interpret(),
        name='flash_bwd_dkv',
        **_compiler_params('parallel', 'parallel', 'arbitrary'),
    )(qp, kp, vp, gp, lse_p, delta_p)

    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


def flash_attention(q, k, v, causal: bool = False, block_q: int = 128,
                    block_k: int = 128):
    """Fused attention over ``(batch, seq, heads, head_dim)`` arrays.

    Exact (online-softmax) attention; O(seq) memory — the score matrix
    stays in VMEM blocks.  Differentiable via blockwise Pallas backward
    kernels.  Oracle: ``parallel.sequence.attention_reference``.

    ``causal=True`` uses TOP-LEFT mask alignment (position counted from
    0 for both q and k), which only makes sense for ``sq == sk``; the
    bottom-right (decode) convention is not implemented, so mismatched
    lengths with ``causal`` are rejected.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if causal and sq != sk:
        raise ValueError(
            f'causal flash_attention requires q and k of equal length '
            f'(top-left mask alignment); got sq={sq} sk={sk}')

    def to_bhsd(x, s):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[-1])

    out = _flash_bhsd(to_bhsd(q, sq), to_bhsd(k, sk), to_bhsd(v, sk),
                      causal, block_q, block_k)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


# --- paged flash-decode attention (serve/decode.py) ------------------------
#
# The decode engine's step used to GATHER every slot's KV pages into a
# dense (S, T, H, hd) cache in HBM on every token (kpool[:, table] — a
# full-pool materialization per step per stage).  This kernel reads each
# slot's pages IN PLACE: the page table is a scalar-prefetch operand, so
# the (slot, logical-page) grid cell's BlockSpec index map resolves the
# PHYSICAL page to DMA — HBM traffic per step is exactly the slot's live
# pages, once.  Per-slot positions (``pos``) and left-pad widths (``w``)
# drive the same live mask as ``transformer.decode_step``; the final
# masked softmax + weighted sum mirror the dense ops EXACTLY (same
# einsum shapes, same f32 cast points), which is what makes the kernel
# bitwise-equal to the gather-then-dense twin — pinned by
# tests/test_serve_decode.py on the CPU ``interpret=True`` path.

def _paged_decode_kernel(table_ref, pos_ref, w_ref, q_ref, k_ref, v_ref,
                         o_ref, s_scr, v_scr, *, scale, ps, pp):
    """Grid (slots, pages_per_slot): page j of slot s is DMA'd from the
    physical page ``table[s, j]``; its scores land in the score scratch
    (an exact per-page slice write — no cross-page reduction), its V rows
    in the V scratch.  The last page step applies the live mask and runs
    the one full-width softmax + value contraction."""
    s = pl.program_id(0)
    j = pl.program_id(1)
    q = q_ref[0]                                   # (H, hd)
    # per-page scores: same per-element hd-length dots as the dense
    # einsum 'bqhd,bkhd->bhqk' — slice writes are exact, so assembling
    # the (H, T) score row page-by-page loses nothing
    s_scr[:, pl.ds(j * ps, ps)] = jnp.einsum('hd,khd->hk', q, k_ref[0])
    v_scr[pl.ds(j * ps, ps)] = v_ref[0]

    @pl.when(j == pp - 1)
    def _finalize():
        t = pos_ref[s]
        wv = w_ref[s]
        ar = jax.lax.broadcasted_iota(jnp.int32, (1, pp * ps), 1)
        live = (ar <= t) & (ar >= wv)              # (1, T)
        sc = s_scr[:] * scale
        sc = jnp.where(live, sc, -jnp.inf)
        p = jax.nn.softmax(sc.astype(jnp.float32), axis=-1
                           ).astype(v_scr.dtype)
        # keep the singleton q axis: 'hqk,khd->qhd' lowers to the same
        # contraction as the dense 'bhqk,bkhd->bqhd' (dropping it pads
        # the result a ulp apart on CPU — measured, not assumed)
        o_ref[0] = jnp.einsum('hqk,khd->qhd', p[:, None], v_scr[:])[0]


def paged_flash_decode(q, kpool, vpool, table, pos, w, scale):
    """One decode step's attention for every slot, over the paged pool.

    ``q``: (S, H, hd) — each slot's single-token query.  ``kpool`` /
    ``vpool``: (P, ps, H, hd) — ONE stage's physical page pool (the
    current token's K/V must already be scattered in at ``pos``).
    ``table``: (S, pp) int32 page table (physical page 0 = scratch: its
    rows are masked dead by ``pos``/``w``).  ``pos``/``w``: (S,) int32
    per-slot write position and left-pad width.  Returns (S, H, hd)
    attention outputs, bitwise-equal to gathering ``kpool[table]`` into
    a dense cache and running ``transformer.decode_step``'s attention.
    """
    S, H, hd = q.shape
    P, ps = kpool.shape[0], kpool.shape[1]
    pp = table.shape[1]
    kernel = functools.partial(_paged_decode_kernel, scale=scale, ps=ps,
                               pp=pp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, pp),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda s, j, tr, pr, wr: (s, 0, 0)),
            pl.BlockSpec((1, ps, H, hd),
                         lambda s, j, tr, pr, wr: (tr[s, j], 0, 0, 0)),
            pl.BlockSpec((1, ps, H, hd),
                         lambda s, j, tr, pr, wr: (tr[s, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd),
                               lambda s, j, tr, pr, wr: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, pp * ps), q.dtype),
                        pltpu.VMEM((pp * ps, H, hd), vpool.dtype)],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, H, hd), vpool.dtype),
        grid_spec=grid_spec,
        interpret=_interpret(),
        name='paged_decode',
        **_compiler_params('parallel', 'arbitrary'),
    )(table, pos, w, q, kpool, vpool)


def _paged_verify_kernel(table_ref, pos_ref, w_ref, q_ref, k_ref, v_ref,
                         o_ref, s_scr, v_scr, *, scale, ps, pp, K):
    """Grid (slots, pages_per_slot): the K-query window extension of
    :func:`_paged_decode_kernel` (speculative-decode verify / prefix-
    shared tail, serve/decode.py).  Page j of slot s is DMA'd from
    physical page ``table[s, j]``; its per-query scores land in the
    (K, H, T) score scratch as exact slice writes; the last page step
    applies the PER-QUERY live mask — window query k sees cache
    positions ``[w, pos + k]``, its own row and earlier drafts, never a
    later one — and runs one full-width softmax + value contraction per
    query, mirroring the dense ``verify_step`` ops (same einsum shapes,
    same f32 cast points) so the two legs are bitwise-equal."""
    s = pl.program_id(0)
    j = pl.program_id(1)
    q = q_ref[0]                                   # (K, H, hd)
    s_scr[:, :, pl.ds(j * ps, ps)] = jnp.einsum('qhd,khd->qhk', q,
                                                k_ref[0])
    v_scr[pl.ds(j * ps, ps)] = v_ref[0]

    @pl.when(j == pp - 1)
    def _finalize():
        t = pos_ref[s]
        wv = w_ref[s]
        ar = jax.lax.broadcasted_iota(jnp.int32, (K, 1, pp * ps), 2)
        kq = jax.lax.broadcasted_iota(jnp.int32, (K, 1, pp * ps), 0)
        live = (ar <= t + kq) & (ar >= wv)         # (K, 1, T)
        sc = s_scr[:] * scale
        sc = jnp.where(live, sc, -jnp.inf)
        p = jax.nn.softmax(sc.astype(jnp.float32), axis=-1
                           ).astype(v_scr.dtype)
        o_ref[0] = jnp.einsum('qhk,khd->qhd', p, v_scr[:])


def paged_flash_verify(q, kpool, vpool, table, pos, w, scale):
    """A K-token verify window's attention for every slot, in place over
    the paged pool — :func:`paged_flash_decode` widened to multi-query
    (serve/decode.py "Speculative decoding" / prefix-shared tail
    prefill).

    ``q``: (S, K, H, hd) — each slot's K window queries, query k at
    position ``pos[s] + k``.  ``kpool``/``vpool``: (P, ps, H, hd) — ONE
    stage's physical page pool (the window's K/V rows must already be
    scattered in at ``[pos, pos + K)``).  ``table``: (S, pp) int32 page
    table.  ``pos``/``w``: (S,) int32 per-slot window start and left-pad
    width.  Returns (S, K, H, hd), bitwise-equal to gathering
    ``kpool[table]`` dense and running ``transformer.verify_step``'s
    attention (the per-query mask is the verify-step rule:
    ``[w, pos + k]``)."""
    S, K, H, hd = q.shape
    P, ps = kpool.shape[0], kpool.shape[1]
    pp = table.shape[1]
    kernel = functools.partial(_paged_verify_kernel, scale=scale, ps=ps,
                               pp=pp, K=K)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, pp),
        in_specs=[
            pl.BlockSpec((1, K, H, hd),
                         lambda s, j, tr, pr, wr: (s, 0, 0, 0)),
            pl.BlockSpec((1, ps, H, hd),
                         lambda s, j, tr, pr, wr: (tr[s, j], 0, 0, 0)),
            pl.BlockSpec((1, ps, H, hd),
                         lambda s, j, tr, pr, wr: (tr[s, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, H, hd),
                               lambda s, j, tr, pr, wr: (s, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((K, H, pp * ps), q.dtype),
                        pltpu.VMEM((pp * ps, H, hd), vpool.dtype)],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, K, H, hd), vpool.dtype),
        grid_spec=grid_spec,
        interpret=_interpret(),
        name='paged_verify',
        **_compiler_params('parallel', 'arbitrary'),
    )(table, pos, w, q, kpool, vpool)


# --- int8 matmul (quantized inference tier, nnet/quantize.py) --------------

def _int8_matmul_kernel(a_ref, b_ref, o_ref, acc_ref):
    """``pallas_matmul``'s K-innermost tiling with int8 MXU inputs and an
    exact int32 accumulator (integer adds reassociate freely, so the
    K-split accumulation is bitwise-equal to the XLA fallback's one-shot
    dot — the scale application to f32 happens outside)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(a_ref[:], b_ref[:],
                          preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[:] = acc_ref[:]


def pallas_int8_matmul(a, b, tile_m: int = 256, tile_n: int = 256,
                       tile_k: int = 512):
    """(m, k) int8 @ (k, n) int8 -> (m, n) int32, MXU-tiled.

    The quantized-inference matmul leg (doc/serving.md "Quantized
    inference"): int8 operand tiles feed the MXU, the accumulator is
    exact int32, and the caller applies the (row-scale x col-scale) f32
    rescale.  Bitwise-equal to ``lax.dot_general`` on the same int8
    operands (integer accumulation has no rounding), so the
    Pallas-vs-XLA twin is exact, not a tolerance."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    tile_m = _clamp_tile(tile_m, m)
    tile_n = _clamp_tile(tile_n, n)
    tile_k = _clamp_tile(tile_k, k)
    pm, pn, pk = (-m) % tile_m, (-n) % tile_n, (-k) % tile_k
    ap = jnp.pad(a, ((0, pm), (0, pk))) if pm or pk else a
    bp = jnp.pad(b, ((0, pk), (0, pn))) if pk or pn else b
    mm, nn, kk = ap.shape[0], bp.shape[1], ap.shape[1]
    out = pl.pallas_call(
        _int8_matmul_kernel,
        out_shape=jax.ShapeDtypeStruct((mm, nn), jnp.int32),
        grid=(mm // tile_m, nn // tile_n, kk // tile_k),
        in_specs=[_block_spec((tile_m, tile_k), lambda i, j, t: (i, t)),
                  _block_spec((tile_k, tile_n), lambda i, j, t: (t, j))],
        out_specs=_block_spec((tile_m, tile_n), lambda i, j, t: (i, j)),
        scratch_shapes=[_scratch((tile_m, tile_n), jnp.int32)],
        interpret=_interpret(),
        name='int8_matmul',
        **_compiler_params('parallel', 'parallel', 'arbitrary'),
    )(ap, bp)
    return out[:m, :n]
