"""Fused Pallas CNN blocks + μ-cuDNN convolution microbatching.

Two training/CNN-tier primitives beyond ``pallas_kernels``:

* **fused conv+bias+activation** (``fused_conv_bias_act``): one Pallas
  forward block computes the convolution as an im2col GEMM *in VMEM*
  (the column tensor never touches HBM — the maxDNN/cuDNN fusion the
  reference hand-wrote in CUDA), adds the bias, and applies the
  activation (relu or identity) before the single HBM write-back.  The
  grid walks batch x output-row tiles; each step holds one padded input
  image and builds its patch matrix with static strided slices over the
  kernel taps, so the MXU contracts ``kh*kw*cin`` deep per pass.  The
  backward is a ``jax.custom_vjp`` that reuses the saved pre-activation
  tensor for the relu mask and hands dx/dw to XLA's conv transpose —
  the measured-loser Pallas backwards stay off the trainer path (the
  ``fullc`` lesson, receipts/micro_matmul.json).  The block is pinned to
  the XLA reference composition by tolerance twins (``_FUSED_RTOL`` /
  ``_FUSED_ATOL``, tests/test_cnn_fused.py): the in-VMEM GEMM reduces in
  a different order than XLA's conv, so the contract is pinned-tolerance,
  never silently looser (the PR 10 quant rule).

* **convolution microbatching** (``microbatched_conv``): μ-cuDNN's
  observation, recast for XLA — splitting a convolution's *batch* axis
  into ``micro_batch`` sequential slices bounds the layer's live
  workspace (im2col patch tensors, wide activation intermediates) at the
  cost of dispatching k smaller convs.  The forward and dx run per-slice
  under ``lax.map``; **dw is computed by the one full-batch transpose
  op**, because a slice-accumulated dw sums in a different order and is
  NOT bitwise-equal to the unsplit step (measured — see
  doc/kernels.md).  Under jit the unused full-batch primal is DCE'd, so
  the anchor costs one conv-transpose, exactly like the unsplit step.
  This makes the microbatched step a **bitwise twin** of the unsplit
  one at every declared split — the property grafttune's LedgerGate
  relies on when it prices ``micro_batch`` from ``memory_analysis``
  peak bytes (tune/space.py, ``mem_inv``).

Both paths run under ``interpret=True`` on CPU — correctness validation
without hardware; speed claims come only from on-TPU receipts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .pallas_kernels import (_block_spec, _compiler_params, _interpret,
                             pallas_mode)

_DN = ('NHWC', 'HWIO', 'NHWC')

#: pinned fused-vs-XLA twin tolerances (f32): the VMEM im2col GEMM and
#: XLA's native conv reduce in different orders, so equality is pinned
#: here, once, and asserted everywhere (tests AND bench) — never loosened
#: at a call site.
_FUSED_RTOL = 1e-5
_FUSED_ATOL = 1e-5

#: rows-per-grid-step target for the output tile: ~512 output pixels per
#: MXU pass (same scale as pallas_kernels._ROW_TILE)
_TILE_PIXELS = 512


def conv_use_fused(explicit=None) -> bool:
    """Whether eligible conv(+bias)+relu pairs take the fused Pallas
    block.  ``explicit`` is the ``fuse=`` net param: ``1``/``0`` force it
    on/off (``1`` engages even in interpret mode — that is the CPU
    validation path), anything else (``'auto'``/None) defers to
    ``pallas_mode()``, whose own ``auto`` never picks the block: Mosaic
    refuses the kernel on the TPU (doc/kernels.md quotes the compiler),
    so only a forced spelling reaches it, and there it fails loudly."""
    if explicit is not None:
        text = str(explicit).strip().lower()
        if text in ('1', 'true', 'yes', 'on'):
            return True
        if text in ('0', 'false', 'no', 'off'):
            return False
        # anything else ('auto', '') falls through to the global gate
    return pallas_mode() == 'on'


def _conv_ref(x, w, strides, pad, groups=1):
    """The XLA reference lowering the fused block's backward (and its
    twin tests) anchor to.  Deliberately a local duplicate of
    ``layers.conv.conv_native`` — ops/ cannot import layers/ (the conv
    layer imports this module), and the 4 lines ARE the contract."""
    return lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad,
        dimension_numbers=_DN, feature_group_count=groups)


# --- fused conv + bias + activation ---------------------------------------

def _conv_act_kernel(x_ref, w_ref, b_ref, y_ref, z_ref, *, kh, kw, sy, sx,
                     tile_oy, ox, groups, act):
    """One (batch image, output-row tile) grid step.

    ``x_ref`` holds the whole zero-padded image (1, Hp, Wp, cin); the
    step slices its input row window, builds the im2col patch matrix
    with static strided slices over the kernel taps (column order
    (u, v, c) — exactly ``w.reshape(kh*kw*cin_g, cout)`` row order), and
    contracts on the MXU in f32.  Grouped convs loop the (static) groups
    with static channel slices.  The pre-activation ``z`` is written as
    a second output: the custom-VJP backward reuses it as the relu mask
    instead of re-deriving it.
    """
    j = pl.program_id(1)
    x = x_ref[0]                                        # (Hp, Wp, cin)
    cin = x.shape[-1]
    iy = (tile_oy - 1) * sy + kh
    xwin = lax.dynamic_slice(
        x, (j * tile_oy * sy, 0, 0), (iy, x.shape[1], cin))
    w2 = w_ref[...]                                 # (kh*kw*cin_g, cout)
    cout = w2.shape[1]
    cin_g = cin // groups
    cout_g = cout // groups
    outs = []
    for gi in range(groups):
        xg = lax.slice_in_dim(xwin, gi * cin_g, (gi + 1) * cin_g, axis=2)
        cols = []
        for u in range(kh):
            for v in range(kw):
                tap = lax.slice(
                    xg, (u, v, 0),
                    (u + (tile_oy - 1) * sy + 1,
                     v + (ox - 1) * sx + 1, cin_g),
                    (sy, sx, 1))                     # (tile_oy, ox, cin_g)
                cols.append(tap.reshape(tile_oy * ox, cin_g))
        patches = jnp.concatenate(cols, axis=1)
        wg = lax.slice_in_dim(w2, gi * cout_g, (gi + 1) * cout_g, axis=1)
        outs.append(jnp.dot(patches, wg,
                            preferred_element_type=jnp.float32))
    z = outs[0] if groups == 1 else jnp.concatenate(outs, axis=1)
    z = z + b_ref[...]                               # (1, cout) broadcast
    y = jnp.maximum(z, 0.0) if act == 'relu' else z
    z_ref[...] = z.reshape(1, tile_oy, ox, cout)
    y_ref[...] = y.reshape(1, tile_oy, ox, cout).astype(y_ref.dtype)


def _fused_call(x, w, b, strides, padding, groups, act):
    """Launch the fused block; returns (activated out, f32 pre-act)."""
    if act not in ('relu', 'identity'):
        raise ValueError(f'fused conv: unknown act {act!r}')
    n, h, win, cin = x.shape
    kh, kw, cin_g, cout = w.shape
    sy, sx = strides
    (py_lo, py_hi), (px_lo, px_hi) = padding
    oy = (h + py_lo + py_hi - kh) // sy + 1
    ox = (win + px_lo + px_hi - kw) // sx + 1
    if oy <= 0 or ox <= 0:
        raise ValueError('fused conv: kernel larger than padded input')
    tile_oy = max(1, min(oy, -(-_TILE_PIXELS // max(1, ox))))
    oy_p = -(-oy // tile_oy) * tile_oy
    # rows padded so every tile's input window is in bounds (the extra
    # zero rows produce garbage output rows sliced off below); the width
    # pad is the conv pad alone — the kernel's static slices never read
    # past (ox-1)*sx + kw
    hp_need = (oy_p - 1) * sy + kh
    extra = max(0, hp_need - (h + py_lo + py_hi))
    xp = jnp.pad(x, ((0, 0), (py_lo, py_hi + extra),
                     (px_lo, px_hi), (0, 0)))
    w2 = w.reshape(kh * kw * cin_g, cout).astype(jnp.float32)
    bvec = (jnp.zeros((cout,), jnp.float32) if b is None
            else b.astype(jnp.float32)).reshape(1, cout)
    xp32 = xp.astype(jnp.float32)
    hp, wp = xp32.shape[1], xp32.shape[2]
    kernel = functools.partial(_conv_act_kernel, kh=kh, kw=kw, sy=sy,
                               sx=sx, tile_oy=tile_oy, ox=ox,
                               groups=groups, act=act)
    y, z = pl.pallas_call(
        kernel,
        grid=(n, oy_p // tile_oy),
        in_specs=[
            _block_spec((1, hp, wp, cin), lambda i, j: (i, 0, 0, 0)),
            _block_spec((kh * kw * cin_g, cout), lambda i, j: (0, 0)),
            _block_spec((1, cout), lambda i, j: (0, 0)),
        ],
        out_specs=[
            _block_spec((1, tile_oy, ox, cout), lambda i, j: (i, j, 0, 0)),
            _block_spec((1, tile_oy, ox, cout), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, oy_p, ox, cout), x.dtype),
            jax.ShapeDtypeStruct((n, oy_p, ox, cout), jnp.float32),
        ],
        interpret=_interpret(),
        name='conv_bias_act',
        **_compiler_params('parallel', 'parallel'),
    )(xp32, w2, bvec)
    return y[:, :oy], z[:, :oy]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def fused_conv_bias_act(x, w, b, strides, padding, groups=1, act='relu'):
    """Fused conv + bias + activation, differentiable.

    ``b`` may be None (no-bias conv; the kernel adds a zero vector,
    which is bitwise-identity in f32, and the backward returns a None
    cotangent).  The forward is the Pallas block; the backward masks the
    upstream cotangent with the SAVED pre-activation (no recompute) and
    takes XLA's conv transposes for dx/dw.
    """
    y, _ = _fused_call(x, w, b, strides, padding, groups, act)
    return y


def _fused_fwd(x, w, b, strides, padding, groups, act):
    y, z = _fused_call(x, w, b, strides, padding, groups, act)
    return y, (x, w, b, z)


def _fused_bwd(strides, padding, groups, act, res, ct):
    x, w, b, z = res
    g = ct.astype(jnp.float32)
    if act == 'relu':
        # the saved pre-activation IS the mask — no recompute.  The
        # reference relu is jnp.maximum(x, 0), whose XLA gradient at an
        # EXACT z==0 tie is 0.5 (lax.max splits equal operands), so the
        # mask mirrors that: ties are measure-zero for continuous
        # inputs, but zero-padded integer images with zero-init bias tie
        # densely at step 0 and the twin must hold there too
        g = jnp.where(z > 0, g, jnp.where(z == 0, 0.5 * g, 0.0))
    gx = g.astype(x.dtype)
    _, vjp = jax.vjp(
        lambda xx, ww: _conv_ref(xx, ww, strides, padding, groups), x, w)
    dx, dw = vjp(gx)
    db = None if b is None else jnp.sum(gx, axis=(0, 1, 2)).astype(b.dtype)
    return dx, dw, db


fused_conv_bias_act.defvjp(_fused_fwd, _fused_bwd)


# --- μ-cuDNN-style convolution microbatching ------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def microbatched_conv(x, w, strides, padding, groups, split, conv_fn):
    """Run ``conv_fn`` over ``split`` sequential batch slices.

    ``conv_fn(x, w, strides, padding, groups)`` is a module-level
    callable (hashable, so the trace caches); the batch must divide
    evenly — callers gate on ``batch % split == 0`` and fall through to
    the unsplit op otherwise.  Bitwise contract: forward and dx are
    per-example-independent, so the slice loop reproduces the unsplit
    values exactly; dw is the one full-batch transpose op (see module
    docstring) — the whole step is a bitwise twin of ``split=1``.
    """
    return _mb_fwd_impl(x, w, strides, padding, groups, split, conv_fn)


def _mb_fwd_impl(x, w, strides, padding, groups, split, conv_fn):
    n = x.shape[0]
    xs = x.reshape((split, n // split) + x.shape[1:])
    ys = lax.map(lambda xt: conv_fn(xt, w, strides, padding, groups), xs)
    return ys.reshape((n,) + ys.shape[2:])


def _mb_fwd(x, w, strides, padding, groups, split, conv_fn):
    y = _mb_fwd_impl(x, w, strides, padding, groups, split, conv_fn)
    return y, (x, w)


def _mb_bwd(strides, padding, groups, split, conv_fn, res, g):
    x, w = res
    n = x.shape[0]
    xs = x.reshape((split, n // split) + x.shape[1:])
    gs = g.reshape((split, n // split) + g.shape[1:])

    def _slice_dx(pair):
        xt, gt = pair
        _, vjp = jax.vjp(
            lambda xx: conv_fn(xx, w, strides, padding, groups), xt)
        return vjp(gt)[0]

    dx = lax.map(_slice_dx, (xs, gs)).reshape(x.shape)
    # dw anchors on the ONE full-batch transpose op: a slice-accumulated
    # dw reduces in a different order and is NOT bitwise-equal to the
    # unsplit step (measured; doc/kernels.md).  Under jit the unused
    # primal recompute is DCE'd away.
    _, vjp_w = jax.vjp(
        lambda ww: conv_fn(x, ww, strides, padding, groups), w)
    dw = vjp_w(g)[0]
    return dx, dw


microbatched_conv.defvjp(_mb_fwd, _mb_bwd)
