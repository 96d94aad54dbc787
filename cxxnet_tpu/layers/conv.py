"""Convolution layer.

TPU-native replacement for the reference's im2col-GEMM convolution
(``src/layer/convolution_layer-inl.hpp:70-155``) and its cuDNN override
(``cudnn_convolution_layer-inl.hpp``): forward and both backward passes
lower to ``lax.conv_general_dilated`` in NHWC/HWIO layout, which XLA tiles
directly onto the MXU — no explicit column buffer, so the reference's
``temp_col_max`` chunking knob is accepted but has no effect on memory.

Grouped convolution (``ngroup``) maps to ``feature_group_count``.
Output spatial size matches the reference exactly:
``(in + 2*pad - k) / stride + 1`` (floor).
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
from jax import lax

from .base import Layer, NodeSpec, kConv, register_layer

_DN = ('NHWC', 'HWIO', 'NHWC')


def conv_native(x, w, strides, pad, groups=1):
    """Plain lax.conv lowering; grouped via feature_group_count."""
    return lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad,
        dimension_numbers=_DN, feature_group_count=groups)


def conv_im2col(x, w, strides, pad):
    """Explicit patches->GEMM lowering: a shallow input (e.g. AlexNet
    conv1's c=3) gives the native conv only a c-deep contraction per MXU
    pass; the patch GEMM contracts kh*kw*c deep (363) at the cost of
    materializing the column tensor — the reference's im2col
    (``convolution_layer-inl.hpp:70-106``) reborn as an XLA-level
    lowering choice.  Backward comes from AD: dW is a GEMM, dx flows
    through the patch-extraction transpose (col2im)."""
    kh, kw, _, cout = w.shape
    pat = lax.conv_general_dilated_patches(
        x, filter_shape=(kh, kw), window_strides=strides,
        padding=pad, dimension_numbers=_DN)
    b, oy, ox, k = pat.shape
    # patches feature order is (c, kh, kw)
    w2 = jnp.transpose(w, (2, 0, 1, 3)).reshape(k, cout)
    return (pat.reshape(b * oy * ox, k) @ w2).reshape(b, oy, ox, cout)


def conv_s2d(x, w, strides, pad):
    """Space-to-depth lowering for strided convs on shallow inputs
    (AlexNet conv1: 11x11 s4 on c=3).  Rearranging each stride-sized
    pixel block into channels turns the stride-s conv into a stride-1
    conv whose contraction is ``s*s*c`` deep (conv1: 48, and the
    ceil(k/s)=3-tap kernel contracts 3*3*48=432 per output) — the MXU
    fill of im2col WITHOUT materializing the patch tensor (the s2d
    input is the same bytes as the input; the kernel rearrangement is
    weight-sized).  The MLPerf-era TPU ResNet entry-conv trick, applied
    as a general lowering.  Math: with the kernel zero-padded to
    ``K = ceil(k/s)*s``, ``y[o] = sum_u x[o*s+u] w[u]`` regroups by
    ``u = a*s + r`` into a stride-1 conv over block index ``a`` with
    ``(r, c)`` as channels — exact, so backward comes from AD through
    the reshapes.  Handles ARBITRARY padding (the pad folds into
    explicit zeros before blocking, so no alignment is required for
    correctness); the ``_lowering`` gate nonetheless only routes
    stride-aligned pads here — a conservative POLICY bound, keeping s2d
    on the shape class the on-chip receipts actually measured, not a
    correctness requirement."""
    sy, sx = strides
    (py_lo, py_hi), (px_lo, px_hi) = pad
    b, _, _, c = x.shape
    kh, kw, cin, cout = w.shape
    x = jnp.pad(x, ((0, 0), (py_lo, py_hi), (px_lo, px_hi), (0, 0)))
    h2, w2 = x.shape[1], x.shape[2]
    out_h = (h2 - kh) // sy + 1
    out_w = (w2 - kw) // sx + 1
    bkh, bkw = -(-kh // sy), -(-kw // sx)       # kernel taps in blocks
    wp = jnp.pad(w, ((0, bkh * sy - kh), (0, bkw * sx - kw),
                     (0, 0), (0, 0)))
    # input must cover block (out-1)+bk-1 on each axis
    hp = max(-(-h2 // sy), out_h - 1 + bkh) * sy
    wpx = max(-(-w2 // sx), out_w - 1 + bkw) * sx
    x = jnp.pad(x, ((0, 0), (0, hp - h2), (0, wpx - w2), (0, 0)))
    xb = x.reshape(b, hp // sy, sy, wpx // sx, sx, c)
    xb = xb.transpose(0, 1, 3, 2, 4, 5).reshape(
        b, hp // sy, wpx // sx, sy * sx * c)
    wb = wp.reshape(bkh, sy, bkw, sx, cin, cout)
    wb = wb.transpose(0, 2, 1, 3, 4, 5).reshape(
        bkh, bkw, sy * sx * cin, cout)
    out = lax.conv_general_dilated(xb, wb, (1, 1), ((0, 0), (0, 0)),
                                   dimension_numbers=_DN)
    return out[:, :out_h, :out_w, :]


def _conv_native_mb(x, w, strides, pad, groups):
    """Module-level adapter handed to ``microbatched_conv`` (a stable,
    hashable nondiff arg — closures would retrace per call)."""
    return conv_native(x, w, strides, pad, groups)


def _conv_im2col_mb(x, w, strides, pad, groups):
    """im2col adapter for microbatching; the routing gate guarantees
    ``groups == 1`` (im2col targets ungrouped convs)."""
    return conv_im2col(x, w, strides, pad)


# --- μ-cuDNN-style convolution microbatching ------------------------------
#
# Splitting a convolution's *batch* axis into ``micro_batch`` sequential
# slices bounds the layer's live workspace (im2col patch tensors, wide
# activation intermediates) at the cost of dispatching k smaller convs.
# The forward and dx run per-slice under ``lax.map``; **dw is computed by
# the one full-batch transpose op**, because a slice-accumulated dw sums in
# a different order and is NOT bitwise-equal to the unsplit step (measured —
# see doc/kernels.md).  Under jit the unused full-batch primal is DCE'd, so
# the anchor costs one conv-transpose, exactly like the unsplit step.  This
# makes the microbatched step a **bitwise twin** of the unsplit one at every
# declared split — the property grafttune's LedgerGate relies on when it
# prices ``micro_batch`` from ``memory_analysis`` peak bytes
# (tune/space.py, ``mem_inv``).

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def microbatched_conv(x, w, strides, padding, groups, split, conv_fn):
    """Run ``conv_fn`` over ``split`` sequential batch slices.

    ``conv_fn(x, w, strides, padding, groups)`` is a module-level
    callable (hashable, so the trace caches); the batch must divide
    evenly — callers gate on ``batch % split == 0`` and fall through to
    the unsplit op otherwise.  Bitwise contract: forward and dx are
    per-example-independent, so the slice loop reproduces the unsplit
    values exactly; dw is the one full-batch transpose op (see the
    comment above) — the whole step is a bitwise twin of ``split=1``.
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


def conv_split(x, w, strides, pad, groups):
    """Per-group convs + concat instead of feature_group_count: lets XLA
    pick each group's layout independently (grouped convs halve the
    contraction depth per pass under fgc)."""
    cin_g = x.shape[-1] // groups
    cout_g = w.shape[-1] // groups
    return jnp.concatenate([
        lax.conv_general_dilated(
            x[..., i * cin_g:(i + 1) * cin_g],
            w[..., i * cout_g:(i + 1) * cout_g],
            window_strides=strides, padding=pad, dimension_numbers=_DN)
        for i in range(groups)], axis=-1)


@register_layer
class ConvolutionLayer(Layer):
    type_name = 'conv'
    type_id = kConv
    param_fields = ('wmat', 'bias')

    def infer_shapes(self, in_specs: List[NodeSpec]) -> List[NodeSpec]:
        assert len(in_specs) == 1, 'conv: only supports 1-1 connection'
        p = self.param
        s = in_specs[0]
        if p.num_channel <= 0:
            raise ValueError('conv: must set nchannel correctly')
        if p.kernel_height <= 0 or p.kernel_width <= 0:
            raise ValueError('conv: must set kernel_size correctly')
        if s.c % p.num_group or p.num_channel % p.num_group:
            raise ValueError('conv: channels must be divisible by ngroup')
        p.num_input_channel = s.c
        oy = (s.y + 2 * p.pad_y - p.kernel_height) // p.stride + 1
        ox = (s.x + 2 * p.pad_x - p.kernel_width) // p.stride + 1
        if oy <= 0 or ox <= 0:
            raise ValueError('conv: kernel larger than padded input')
        return [NodeSpec(p.num_channel, oy, ox)]

    def init_params(self, rng, in_specs, dtype=jnp.float32):
        p = self.param
        cin_g = in_specs[0].c // p.num_group
        # HWIO layout for lax.conv; fan numbers match the reference's
        # (ngroup, nch/g, nin/g*kh*kw) weight: in = nin/g*kh*kw, out = nch/g
        shape = (p.kernel_height, p.kernel_width, cin_g, p.num_channel)
        in_num = cin_g * p.kernel_height * p.kernel_width
        out_num = p.num_channel // p.num_group
        out = {'wmat': p.rand_init_weight(rng, shape, in_num, out_num, dtype)}
        if p.no_bias == 0:
            out['bias'] = jnp.full((p.num_channel,), p.init_bias, dtype)
        return out

    def _lowering(self) -> str:
        """Resolve the conv_lowering knob.  'auto' currently means native
        for every shape — the im2col and split variants exist as measured
        experiments (tools/conv_lowering_bench.py times THESE module
        functions); auto flips per shape class only when an on-chip
        receipt shows a win (same policy as
        ops.pallas_kernels.fullc_use_pallas)."""
        mode = self.param.conv_lowering
        if mode == 'auto':
            return 'native'
        # each variant degrades to native on the shapes it does not
        # target, so the knob is usable as a netconfig GLOBAL (replayed
        # into every layer): im2col targets ungrouped convs, split
        # grouped ones, s2d ungrouped strided convs.  The s2d
        # stride-aligned-padding clause is a conservative POLICY bound,
        # not correctness (conv_s2d handles arbitrary pads — it folds
        # them into explicit zeros first): it pins the lowering to the
        # entry-conv shape class the receipts measured wins on
        if mode == 'split' and self.param.num_group == 1:
            return 'native'
        if mode == 'im2col' and self.param.num_group != 1:
            return 'native'
        if mode == 's2d' and (self.param.num_group != 1
                              or self.param.stride <= 1
                              or self.param.pad_y % self.param.stride
                              or self.param.pad_x % self.param.stride):
            return 'native'
        return mode

    def _micro_split(self, mode: str, batch: int) -> int:
        """Resolve the ``micro_batch`` knob for this dispatch: engage
        only on the per-example-independent lowerings (native/im2col —
        s2d/split reshape the batch themselves) when the split divides
        the batch evenly; anything else falls through to unsplit, which
        is bitwise-identical anyway."""
        split = self.param.micro_batch
        if split <= 1 or mode not in ('native', 'im2col'):
            return 1
        if batch % split:
            return 1
        return split

    def forward(self, params, inputs, ctx):
        p = self.param
        x = inputs[0]  # (b, y, x, c)
        # operands share the activation dtype; the MXU accumulates in f32
        # internally for bf16 inputs, so no preferred_element_type needed
        # (which also trips the conv transpose rule on mixed cotangents)
        w = params['wmat'].astype(x.dtype)
        strides = (p.stride, p.stride)
        pad = ((p.pad_y, p.pad_y), (p.pad_x, p.pad_x))
        mode = self._lowering()
        split = self._micro_split(mode, x.shape[0])
        if split > 1:
            fn = _conv_im2col_mb if mode == 'im2col' else _conv_native_mb
            out = microbatched_conv(x, w, strides, pad, p.num_group,
                                    split, fn)
        elif mode == 'im2col':
            out = conv_im2col(x, w, strides, pad)
        elif mode == 's2d':
            out = conv_s2d(x, w, strides, pad)
        elif mode == 'split':
            out = conv_split(x, w, strides, pad, p.num_group)
        else:
            out = conv_native(x, w, strides, pad, p.num_group)
        if p.no_bias == 0:
            out = out + params['bias'].astype(x.dtype)
        return [out.astype(x.dtype)]
