"""Normalization layers: LRN and BatchNorm.

LRN (``src/layer/lrn_layer-inl.hpp:46-57``): cross-channel response
normalization, ``out = x * (knorm + alpha/n * sum_{window} x^2)^(-beta)``
with a centered channel window of ``local_size``.

BatchNorm (``src/layer/batch_norm_layer-inl.hpp``): per-channel (conv) or
per-feature (fc).  The reference keeps **no running averages — evaluation
also normalizes with current-minibatch statistics** (doc/layer.md:258); we
reproduce that exactly (a parity quirk worth revisiting).  eps default 1e-10;
learnable slope is visited under the 'wmat' tag, bias under 'bias'.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .base import Layer, kBatchNorm, kLRN, register_layer


def _norm(x, nsize: int, alpha: float, knorm: float):
    """``knorm + alpha/nsize * sum(x[..., j-lo : j+hi+1] ** 2)`` in float32,
    zeros past the ends: ``nsize`` shifted slices of the zero-padded ``x``,
    each converted and squared by itself.  The cost an element is the
    window's width whatever the channel count, the compiler sees plain
    elementwise work that it fuses with the tail in the layout the
    neighbouring layers keep, and what the fusion reads is ``x`` in its own
    dtype (squared before the pad, the layer before writes a float32 copy
    of ``x * x`` for it)."""
    c = x.shape[-1]
    lo = (nsize - 1) // 2
    pad = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(lo, nsize - 1 - lo)])
    window = None
    for k in range(nsize):
        term = pad[..., k:k + c].astype(jnp.float32)
        window = term * term if window is None else window + term * term
    return knorm + (alpha / nsize) * window


def _lrn_out(x, nsize, alpha, beta, knorm):
    norm = _norm(x, nsize, alpha, knorm)
    return (x.astype(jnp.float32) * jnp.power(norm, -beta)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def lrn(x, nsize: int, alpha: float, beta: float, knorm: float):
    """Cross-channel LRN over a channels-last ``x``:
    ``x * (knorm + alpha/nsize * sum_window(x^2)) ** -beta``, the window
    centred on each channel (``(nsize-1)//2`` below, the rest above).
    Float32 inside, ``x.dtype`` out.  The one LRN of every channel count,
    batch and mesh: plain XLA, so it shards with the batch and fuses where
    it stands.  The backward keeps ``x`` alone and recomputes the norm.
    Which spelling each pass sums its window with was read from the
    layers' rows of the step's trace on the chip, not from a
    microbenchmark (PERF.md 6, PR 28)."""
    return _lrn_out(x, nsize, alpha, beta, knorm)


def _lrn_fwd(x, nsize, alpha, beta, knorm):
    return _lrn_out(x, nsize, alpha, beta, knorm), x


def _lrn_bwd(nsize, alpha, beta, knorm, x, g):
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    norm = _norm(x, nsize, alpha, knorm)
    npow = jnp.power(norm, -beta)
    t = g32 * x32 * npow / norm
    # dx_j = g_j norm_j^-b - 2 b alpha/n x_j sum_i t_i over the windows i
    # that CONTAIN j: rows j-hi .. j+lo of t, the forward's widths swapped.
    # Summed as a banded (c, c) dot: t costs a pow an element, so shifted
    # slices of it would send it through HBM in float32 and back, while a
    # dot takes it as an operand computed in the same fusion, tail
    # included.  0/1 weights and HIGHEST: the sum is float32's
    lo = (nsize - 1) // 2
    hi = nsize - 1 - lo
    i = np.arange(x.shape[-1])
    band = (i[:, None] >= i[None, :] - hi) & (i[:, None] <= i[None, :] + lo)
    win_t = jnp.einsum('...i,ij->...j', t, jnp.asarray(band, jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    dx = g32 * npow - 2.0 * beta * (alpha / nsize) * x32 * win_t
    return (dx.astype(x.dtype),)


lrn.defvjp(_lrn_fwd, _lrn_bwd)


@register_layer
class LRNLayer(Layer):
    type_name = 'lrn'
    type_id = kLRN

    def __init__(self, name=''):
        super().__init__(name)
        self.knorm = 1.0
        self.nsize = 3
        self.alpha = 0.001
        self.beta = 0.75

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == 'local_size':
            self.nsize = int(val)
        if name == 'alpha':
            self.alpha = float(val)
        if name == 'beta':
            self.beta = float(val)
        if name == 'knorm':
            self.knorm = float(val)

    def infer_shapes(self, in_specs):
        assert len(in_specs) == 1, 'lrn: only supports 1-1 connection'
        return [in_specs[0]]

    def forward(self, params, inputs, ctx):
        return [lrn(inputs[0], self.nsize, self.alpha, self.beta,
                    self.knorm)]


def fold_scale_shift(gamma, beta, mean, var, eps):
    """The conv+BN fold algebra (nnet/fold.py): with frozen statistics
    ``(mean, var)``, BN is the affine map ``y = z*scale + shift`` with
    ``scale = gamma/sqrt(var+eps)`` and ``shift = beta - mean*scale`` —
    which a preceding conv absorbs as ``w*scale`` (output-channel axis)
    and ``b*scale + shift``.  All f32; the sqrt spelling matches
    ``BatchNormLayer.forward`` exactly so the fold's frozen-stats
    normalization is the same float program as the live one."""
    scale = gamma / jnp.sqrt(var + eps)
    return scale, beta - mean * scale


@register_layer
class BatchNormLayer(Layer):
    type_name = 'batch_norm'
    type_id = kBatchNorm
    param_fields = ('wmat', 'bias')   # slope under 'wmat', bias under 'bias'

    def __init__(self, name=''):
        super().__init__(name)
        self.init_slope = 1.0
        self.init_bias = 0.0
        self.eps = 1e-10

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == 'init_slope':
            self.init_slope = float(val)
        if name == 'init_bias':
            self.init_bias = float(val)
        if name == 'eps':
            self.eps = float(val)

    def infer_shapes(self, in_specs):
        assert len(in_specs) == 1, 'batch_norm: only supports 1-1 connection'
        s = in_specs[0]
        self._channels = s.x if s.is_mat else s.c
        return [s]

    def init_params(self, rng, in_specs, dtype=jnp.float32):
        return {'wmat': jnp.full((self._channels,), self.init_slope, dtype),
                'bias': jnp.full((self._channels,), self.init_bias, dtype)}

    def forward(self, params, inputs, ctx):
        x = inputs[0]
        x32 = x.astype(jnp.float32)
        axes = tuple(range(x.ndim - 1))   # all but trailing channel/feature
        mean = jnp.mean(x32, axis=axes)
        var = jnp.mean((x32 - mean) ** 2, axis=axes)
        # batch statistics at train AND eval — the reference quirk
        xhat = (x32 - mean) / jnp.sqrt(var + self.eps)
        return [(xhat * params['wmat'] + params['bias']).astype(x.dtype)]
