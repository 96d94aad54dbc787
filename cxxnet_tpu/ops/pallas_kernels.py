"""The Pallas TPU kernels' names, and the tiled matmul behind fullc.

A hand-written kernel stays here only if some run that forces nothing can
reach it on some platform (PR 31): the matmul's forward is picked by
``fullc_use_pallas`` on an evaluation forward on one TPU device at fc8's
shape class.  Design notes:

* **conv / pooling** stay on XLA's native convolution/reduce-window — on
  TPU those already lower to MXU-optimal programs (the cuDNN analogy);
  a hand-written Pallas conv would have to re-derive XLA's spatial
  partitioning to break even.  Measured, not assumed: see bench notes.
* **LRN** has no kernel here: in the net the (rows, c) layout a custom
  call demands cost more in copies than the kernels saved, and XLA fuses
  the O(local_size) window sum of ``layers/norm.lrn`` in the layout the
  neighbouring convolutions keep (PERF.md 6, PR 28).
* **attention** is ``ops/attention.py``: JAX's own flash kernel on one
  TPU chip, XLA over blocks of queries elsewhere.
* **the gated delta rule** of the ``kda`` layers is
  ``ops/delta_rule_kernel.py``: a forward and a backward kernel over the
  chunks on one TPU chip, XLA elsewhere (``ops/delta_rule.py``).
* **fullc** gets a tiled-MXU matmul (``pallas_matmul``) where
  ``fullc_use_pallas`` says so, everywhere under ``CXXNET_PALLAS=1``;
  XLA's dot is the default.

The kernels run under ``interpret=True`` on CPU, which is how the test
suite validates their math without hardware.
"""

from __future__ import annotations

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
KERNEL_NAMES = ('matmul', 'matmul_nt', 'matmul_tn', 'delta_rule_fwd',
                'delta_rule_bwd')


def _block_spec(shape, index_map=None):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


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
