"""Sequence layers: what a decoder-only language model with latent or
grouped, windowed and gated attention, routed experts and a
multi-token-prediction head is made of, as conf layer types on the same
``Net`` graph as the CNN zoo (doc/sequence.md).

The node contract.  A *sequence node* is ``NodeSpec(c=d, y=1, x=seq)``,
stored ``(batch, 1, seq, d)`` like any NHWC node.  Token ids travel as a
matrix node ``(batch, n)`` that stays integer from the iterator through
``stage_batch`` to the embedding (``Net.takes_token_ids``).  Labels are
columns of the label matrix, one per token and head (``label_vec[0,2*seq)
= label`` for a main and a multi-token-prediction head).

One layer type a sublayer, so that the scope a conf layer gets in a trace
(``lNN_mla``, ``lNN_moe``) splits the step by sublayer.  The residual layers
(``mla``, ``gqa``, ``swiglu``, ``moe``) hold their pre-norm and their residual
add inside, take and return the residual stream, and are recomputed in the
backward pass (``recompute``): what is saved between them is the residual
stream alone.

Parameters are float32 masters; products run in the context's compute type
with float32 accumulation; norms, softmax, router scores, rotary angles and
the loss are float32 inside.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..ops import delta_rule
from ..ops.attention import causal_attention
from ..parallel import moe as moe_ops
from .base import (Layer, NodeSpec, Params, kEmbedding, kGQA, kKDA,
                   kLMHeadLoss, kMLA, kMoE, kMTPJoin, kRMSNorm, kSeqSlice,
                   kSwiGLU, register_layer)
from .loss import LossLayerBase


def rms_norm(x, gamma, eps: float):
    """``x * rsqrt(mean(x^2) + eps) * gamma`` over the last axis, float32
    inside, ``x.dtype`` out."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * gamma.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta: float):
    """Rotary positions over the last axis of ``x`` (..., seq, dim), all
    ``dim`` of them, half-split layout: the pair of ``x[..., i]`` is
    ``x[..., i + dim/2]``.  Position = index along ``seq``, the axis before
    the last (the head-major arrays' own: nothing is moved to rotate it)."""
    seq, dim = x.shape[-2:]
    half = dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def yarn_frequencies(dims: int, theta: float, factor: float,
                     original_positions: int, beta_fast: float,
                     beta_slow: float):
    """The ``dims / 2`` rotary frequencies of ``rope_type: yarn`` (Hugging
    Face's ``_compute_yarn_parameters``): the plain ``theta ** (-2i /
    dims)`` where a pair turns more than ``beta_fast`` times over the
    original context, the same over ``factor`` where it turns fewer than
    ``beta_slow`` times, and a linear ramp between the two pairs (rounded
    outward) in which those counts fall.  ``factor <= 1`` is plain rotary.
    Worked out once, in float64, from the conf's numbers."""
    import numpy as np
    plain = theta ** (-np.arange(0, dims, 2, dtype=np.float64) / dims)
    if factor <= 1.0:
        return plain

    def pair_turning(turns: float) -> float:
        return dims * math.log(original_positions / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), dims - 1)
    ramp = np.clip((np.arange(dims // 2, dtype=np.float64) - low)
                   / ((high if high != low else high + 0.001) - low), 0, 1)
    return plain / factor * ramp + plain * (1 - ramp)


def rotary(x, inv_freq, attention_factor: float = 1.0):
    """Rotary positions over the first ``2 * len(inv_freq)`` components of
    the last axis of ``x`` (..., seq, dim), the rest passed through
    (``partial_rotary_factor``); half-split pairs inside the rotated part,
    position = index along ``seq``; ``cos`` and ``sin`` times
    ``attention_factor`` (YaRN's)."""
    seq, half = x.shape[-2], len(inv_freq)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = jnp.cos(ang) * attention_factor
    sin = jnp.sin(ang) * attention_factor
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:2 * half]
    parts = [a * cos - b * sin, b * cos + a * sin]
    if 2 * half < x.shape[-1]:
        parts.append(x32[..., 2 * half:])
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    dt = x.dtype
    g = jnp.dot(x, w_gate.astype(dt), preferred_element_type=jnp.float32)
    u = jnp.dot(x, w_up.astype(dt), preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(dt)
    return jnp.dot(h, w_down.astype(dt), preferred_element_type=jnp.float32)


class SequenceLayer(Layer):
    """Shared by the layers below: the model width and ``eps`` keys, the
    initialiser, and the sequence node's shape."""

    def __init__(self, name: str = ''):
        super().__init__(name=name)
        self.eps = 1e-5

    def set_param(self, name: str, val: str) -> None:
        super().set_param(name, val)
        if name == 'eps':
            self.eps = float(val)

    @staticmethod
    def _seq_spec(spec: NodeSpec, what: str) -> NodeSpec:
        if spec.y != 1:
            raise ValueError(f'{what}: input must be a sequence node '
                             f'(c=d, y=1, x=seq), got {spec}')
        return spec

    def _w(self, rng, i: int, shape, dtype):
        fan_in, fan_out = shape[-2], shape[-1]
        return self.param.rand_init_weight(jax.random.fold_in(rng, i), shape,
                                           fan_in, fan_out, dtype)


@register_layer
class SeqSliceLayer(SequenceLayer):
    """``seq_len`` columns of a matrix of token ids from ``offset`` on: the
    model's tokens (offset 0) and the multi-token-prediction module's (offset
    1) are windows of one staged row of ``seq_len + 1`` ids.  ``seq_len`` is
    a global key of the conf, so that one pair changes the sequence length
    (with ``input_shape`` and the ``label_vec`` ranges)."""

    type_name = 'seq_slice'
    type_id = kSeqSlice
    takes_token_ids = True

    def __init__(self, name: str = ''):
        super().__init__(name=name)
        self.offset, self.length = 0, 0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == 'offset':
            self.offset = int(val)
        if name == 'seq_len':
            self.length = int(val)

    def infer_shapes(self, in_specs):
        (spec,) = in_specs
        if not spec.is_mat or self.offset + self.length > spec.x \
                or self.length <= 0:
            raise ValueError(
                f'seq_slice: [{self.offset}, {self.offset + self.length}) '
                f'of a matrix node of {spec.x} ids')
        return [NodeSpec(1, 1, self.length)]

    def forward(self, params, inputs, ctx):
        return [inputs[0][:, self.offset:self.offset + self.length]]


@register_layer
class EmbeddingLayer(SequenceLayer):
    """``(batch, seq)`` ids -> ``(batch, 1, seq, nhidden)``.  The table has
    ``vocab_held`` rows: a sliced vocabulary is a smaller vocabulary, and
    ``vocab_published`` is kept for the record."""

    type_name = 'embedding'
    type_id = kEmbedding
    param_fields = ('wmat',)
    takes_token_ids = True

    def __init__(self, name: str = ''):
        super().__init__(name=name)
        self.vocab_held, self.vocab_published = 0, 0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == 'vocab_held':
            self.vocab_held = int(val)
        if name == 'vocab_published':
            self.vocab_published = int(val)

    def infer_shapes(self, in_specs):
        (spec,) = in_specs
        if not spec.is_mat:
            raise ValueError('embedding: input must be a matrix of ids')
        if self.vocab_held <= 0 or self.param.num_hidden <= 0:
            raise ValueError('embedding: set vocab_held and nhidden')
        return [NodeSpec(self.param.num_hidden, 1, spec.x)]

    def init_params(self, rng, in_specs, dtype=jnp.float32) -> Params:
        return {'wmat': self._w(rng, 0, (self.vocab_held,
                                         self.param.num_hidden), dtype)}

    def forward(self, params, inputs, ctx):
        ids = inputs[0].astype(jnp.int32)
        table = params['wmat'].astype(ctx.compute_dtype)
        return [jnp.take(table, ids, axis=0)[:, None]]


@register_layer
class RMSNormLayer(SequenceLayer):
    type_name = 'rmsnorm'
    type_id = kRMSNorm
    param_fields = ('gamma',)

    def infer_shapes(self, in_specs):
        return [self._seq_spec(in_specs[0], 'rmsnorm')]

    def init_params(self, rng, in_specs, dtype=jnp.float32) -> Params:
        return {'gamma': jnp.ones((in_specs[0].c,), dtype)}

    def forward(self, params, inputs, ctx):
        return [rms_norm(inputs[0], params['gamma'], self.eps)]


@register_layer
class LatentAttentionLayer(SequenceLayer):
    """Multi-head latent attention in the expanded (training) form, with its
    pre-norm and its residual: ``h + MLA(RMSNorm(h))``, no biases.

    ``c_q = RMSNorm(x W_qa)``; ``[q_nope | q_rope] = c_q W_qb``;
    ``[c_kv | k_r] = x W_kva``; ``[k_nope | v] = RMSNorm(c_kv) W_kvb``;
    ``q_rope`` and ``k_r`` (one head, shared by all) rotated; scores
    ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``, causal
    softmax, ``out = concat(P v) W_o``.  No cache, no absorption.

    Between the projections and the kernels every activation is head-major,
    ``(batch, heads, seq, dim)``, written so by the product that makes it
    (``wq_b``, ``wkv_b`` viewed ``(rank, heads, dim)``; ``wo`` contracts
    ``(heads, v_dim)`` of the kernel's output): no transposed copy in
    either pass.  The leaves stay stored as published."""

    type_name = 'mla'
    type_id = kMLA
    param_fields = ('norm', 'wq_a', 'q_norm', 'wq_b', 'wkv_a', 'kv_norm',
                    'wkv_b', 'wo')
    recompute = True

    def __init__(self, name: str = ''):
        super().__init__(name=name)
        self.nhead = 0
        self.q_lora_rank = self.kv_lora_rank = 0
        self.nope = self.rope_dim = self.v_dim = 0
        self.rope_theta = 10000.0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == 'nhead':
            self.nhead = int(val)
        if name == 'q_lora_rank':
            self.q_lora_rank = int(val)
        if name == 'kv_lora_rank':
            self.kv_lora_rank = int(val)
        if name == 'qk_nope_head_dim':
            self.nope = int(val)
        if name == 'qk_rope_head_dim':
            self.rope_dim = int(val)
        if name == 'v_head_dim':
            self.v_dim = int(val)
        if name == 'rope_theta':
            self.rope_theta = float(val)

    def infer_shapes(self, in_specs):
        if min(self.nhead, self.q_lora_rank, self.kv_lora_rank, self.nope,
               self.rope_dim, self.v_dim) <= 0 or self.rope_dim % 2:
            raise ValueError(
                'mla: set nhead, q_lora_rank, kv_lora_rank, '
                'qk_nope_head_dim, qk_rope_head_dim (even) and v_head_dim')
        return [self._seq_spec(in_specs[0], 'mla')]

    def init_params(self, rng, in_specs, dtype=jnp.float32) -> Params:
        d, h = in_specs[0].c, self.nhead
        return {
            'norm': jnp.ones((d,), dtype),
            'wq_a': self._w(rng, 0, (d, self.q_lora_rank), dtype),
            'q_norm': jnp.ones((self.q_lora_rank,), dtype),
            'wq_b': self._w(rng, 1, (self.q_lora_rank,
                                     h * (self.nope + self.rope_dim)), dtype),
            'wkv_a': self._w(rng, 2, (d, self.kv_lora_rank + self.rope_dim),
                             dtype),
            'kv_norm': jnp.ones((self.kv_lora_rank,), dtype),
            'wkv_b': self._w(rng, 3, (self.kv_lora_rank,
                                      h * (self.nope + self.v_dim)), dtype),
            'wo': self._w(rng, 4, (h * self.v_dim, d), dtype),
        }

    def forward(self, params, inputs, ctx):
        h = inputs[0][:, 0]                                  # (b, s, d)
        b, s, d = h.shape
        dt, nh = h.dtype, self.nhead
        dot = lambda a, w: jnp.dot(                          # noqa: E731
            a, w.astype(dt), preferred_element_type=jnp.float32).astype(dt)

        def heads(a, w):
            """``a`` through ``w``'s columns, a head at a time, written
            head-major by the product: ``(b, s, r) -> (b, nh, s, dim)``."""
            w = w.astype(dt).reshape(w.shape[0], nh, -1)
            return jnp.einsum('bsr,rhd->bhsd', a, w,
                              preferred_element_type=jnp.float32).astype(dt)

        x = rms_norm(h, params['norm'], self.eps)
        c_q = rms_norm(dot(x, params['wq_a']), params['q_norm'], self.eps)
        q = heads(c_q, params['wq_b'])                       # [nope | rope]
        q = jnp.concatenate(
            [q[..., :self.nope], rope(q[..., self.nope:], self.rope_theta)],
            axis=-1)
        ckv = dot(x, params['wkv_a'])                        # [c_kv | k_r]
        c_kv = rms_norm(ckv[..., :self.kv_lora_rank], params['kv_norm'],
                        self.eps)
        kv = heads(c_kv, params['wkv_b'])                    # [k_nope | v]
        # one head of rotated keys, shared: broadcast inside k's concat
        k_rope = rope(ckv[..., self.kv_lora_rank:], self.rope_theta)[:, None]
        k = jnp.concatenate(
            [kv[..., :self.nope],
             jnp.broadcast_to(k_rope, (b, nh, s, self.rope_dim))], axis=-1)
        v = kv[..., self.nope:]
        o = causal_attention(q, k, v,
                             1.0 / math.sqrt(self.nope + self.rope_dim),
                             ctx.spmd_devices)               # (b, nh, s, v)
        out = jnp.einsum('bhsv,hvd->bsd', o,
                         params['wo'].astype(dt).reshape(nh, self.v_dim, d),
                         preferred_element_type=jnp.float32)
        return [(h.astype(jnp.float32) + out).astype(dt)[:, None]]


class HeadShare:
    """A layer of heads that holds ``nhead`` of ``nhead_published`` from
    ``head_first`` on (``nhead_published`` 0: all of them), as ``moe`` holds
    its share of the experts: the chip's share of a layer whose heads are
    split over chips.  It computes its heads' part of ``... W_o``; what the
    other heads would add is left out, and that partial result goes on to
    the next layer.  Nothing in a head's arithmetic depends on where it
    sits, so the share is the layer with fewer heads, and the two keys are
    metadata: checked, and the step is the same program without them
    (doc/sequence.md)."""

    nhead_published = head_first = 0

    def _set_head_share(self, name, val) -> None:
        if name in ('nhead_published', 'head_first'):
            setattr(self, name, int(val))

    def _head_share_error(self, group: int = 1) -> str:
        """Why the share is no share of whole groups of ``group`` heads
        (query heads a key/value head), or ``''``."""
        published = self.nhead_published or self.nhead
        if not (0 < self.nhead <= published
                and 0 <= self.head_first <= published - self.nhead
                and not (published % group or self.head_first % group)):
            return (f'{self.nhead} heads from head {self.head_first} of '
                    f'{published} in groups of {group}')
        return ''


@register_layer
class GroupedAttentionLayer(HeadShare, SequenceLayer):
    """Grouped-query attention with a causal window, rotary positions by the
    layer's kind and a gate, with its pre-norm and its residual: ``h + (g *
    Attn(RMSNorm(h))) W_o``, no biases.

    ``x = RMSNorm(h)``; ``q = x W_q`` as ``nhead`` heads of ``head_dim``,
    ``k = x W_k`` and ``v = x W_v`` as ``nkvhead`` heads: query head ``i``
    reads key/value head ``i // (nhead / nkvhead)``.  The first
    ``rotary_dims`` components of every ``q`` and ``k`` head are rotated
    (``rope_theta``; with ``rope_factor > 1`` the frequencies and the
    ``rope_attention_factor`` of YaRN, :func:`yarn_frequencies`); with
    ``use_rope = 0`` none is (NoPE).  Scores ``q . k / sqrt(head_dim)``;
    position ``i`` sees ``j <= i`` and, with ``window > 0``, only ``i - j <
    window``.  ``g = sigmoid(x W_g)`` scales the head's output before
    ``W_o``: one number a head and position (``gate = head``, the default),
    or one a channel of each head (``gate = elementwise``).

    A layer may hold ``nhead`` of ``nhead_published`` query heads, from
    ``head_first`` on, and the key/value heads they read (:class:`HeadShare`).

    ``nhead``, ``window`` and the rotary keys differ by layer; ``nkvhead``,
    ``head_dim`` and ``eps`` are global pairs of the conf.  Head-major from
    the products to the kernels and back, as ``mla``: no transposed copy."""

    type_name = 'gqa'
    type_id = kGQA
    param_fields = ('norm', 'wq', 'wk', 'wv', 'wgate', 'wo')
    recompute = True

    def __init__(self, name: str = ''):
        super().__init__(name=name)
        self.nhead = self.nkvhead = self.head_dim = 0
        self.window = 0
        self.rope_theta = 10000.0
        self.rotary_dims = 0                 # 0: all of head_dim
        self.rope_factor = 1.0               # YaRN's; 1 is plain rotary
        self.rope_original_positions = 0
        self.rope_beta_fast, self.rope_beta_slow = 32.0, 1.0
        self.rope_attention_factor = 1.0
        self.use_rope = 1
        self.gate = 'head'

    def set_param(self, name, val):
        super().set_param(name, val)
        self._set_head_share(name, val)
        if name in ('nhead', 'nkvhead', 'head_dim', 'window', 'rotary_dims',
                    'rope_original_positions', 'use_rope'):
            setattr(self, name, int(val))
        if name in ('rope_theta', 'rope_factor', 'rope_beta_fast',
                    'rope_beta_slow', 'rope_attention_factor'):
            setattr(self, name, float(val))
        if name == 'gate':
            if val not in ('head', 'elementwise'):
                raise ValueError(f'gqa: gate = {val!r}, not head or '
                                 f'elementwise')
            self.gate = val

    def infer_shapes(self, in_specs):
        rot = self.rotary_dims or self.head_dim
        if min(self.nhead, self.nkvhead, self.head_dim) <= 0 \
                or self.nhead % self.nkvhead or self.window < 0 \
                or rot % 2 or rot > self.head_dim \
                or (self.rope_factor > 1
                    and self.rope_original_positions <= 0):
            raise ValueError(
                'gqa: set nhead (a multiple of nkvhead), nkvhead, head_dim, '
                'window >= 0, rotary_dims (even, at most head_dim) and, with '
                'rope_factor > 1, rope_original_positions')
        share = self._head_share_error(self.nhead // self.nkvhead)
        if share:
            raise ValueError(f'gqa: {share}: hold whole key/value heads')
        return [self._seq_spec(in_specs[0], 'gqa')]

    def init_params(self, rng, in_specs, dtype=jnp.float32) -> Params:
        d, hd = in_specs[0].c, self.head_dim
        gates = self.nhead * (hd if self.gate == 'elementwise' else 1)
        return {'norm': jnp.ones((d,), dtype),
                'wq': self._w(rng, 0, (d, self.nhead * hd), dtype),
                'wk': self._w(rng, 1, (d, self.nkvhead * hd), dtype),
                'wv': self._w(rng, 2, (d, self.nkvhead * hd), dtype),
                'wgate': self._w(rng, 3, (d, gates), dtype),
                'wo': self._w(rng, 4, (self.nhead * hd, d), dtype)}

    def forward(self, params, inputs, ctx):
        h = inputs[0][:, 0]                                  # (b, s, d)
        d, dt, hd = h.shape[2], h.dtype, self.head_dim

        def heads(a, w, n):
            """``a`` through ``w``'s columns, a head at a time, written
            head-major by the product: ``(b, s, d) -> (b, n, s, hd)``."""
            return jnp.einsum('bsr,rhd->bhsd', a,
                              w.astype(dt).reshape(d, n, hd),
                              preferred_element_type=jnp.float32).astype(dt)

        def turned(a):
            """Rotary positions, or none (``use_rope = 0``)."""
            if not self.use_rope:
                return a
            inv_freq = yarn_frequencies(
                self.rotary_dims or hd, self.rope_theta, self.rope_factor,
                self.rope_original_positions, self.rope_beta_fast,
                self.rope_beta_slow)
            return rotary(a, inv_freq, self.rope_attention_factor)

        elementwise = self.gate == 'elementwise'
        x = rms_norm(h, params['norm'], self.eps)
        q = turned(heads(x, params['wq'], self.nhead))
        k = turned(heads(x, params['wk'], self.nkvhead))
        v = heads(x, params['wv'], self.nkvhead)
        o = causal_attention(q, k, v, 1.0 / math.sqrt(hd), ctx.spmd_devices,
                             window=self.window)             # (b, nh, s, hd)
        if elementwise:                                      # (b, nh, s, hd)
            gate = jax.nn.sigmoid(jnp.einsum(
                'bsr,rhd->bhsd', x,
                params['wgate'].astype(dt).reshape(d, self.nhead, hd),
                preferred_element_type=jnp.float32))
        else:                                                # (b, s, nh)
            gate = jax.nn.sigmoid(jnp.dot(x, params['wgate'].astype(dt),
                                          preferred_element_type=jnp.float32))
        o = (o.astype(jnp.float32)
             * (gate if elementwise
                else jnp.moveaxis(gate, 2, 1)[..., None])).astype(dt)
        out = jnp.einsum('bhsv,hvd->bsd', o,
                         params['wo'].astype(dt).reshape(self.nhead, hd, d),
                         preferred_element_type=jnp.float32)
        return [(h.astype(jnp.float32) + out).astype(dt)[:, None]]


def l2_norm(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def short_conv(x, w):
    """Causal depthwise convolution along the axis before the last: ``x``
    ``(..., seq, c)``, ``w`` ``(taps, c)``, ``y_t = sum_j w_j x_{t - taps + 1
    + j}`` with zeros before the first position; float32, no bias."""
    taps, s = w.shape[0], x.shape[-2]
    x = jnp.pad(x.astype(jnp.float32),
                [(0, 0)] * (x.ndim - 2) + [(taps - 1, 0), (0, 0)])
    w = w.astype(jnp.float32)
    return sum(x[..., j:j + s, :] * w[j] for j in range(taps))


@register_layer
class DeltaAttentionLayer(HeadShare, SequenceLayer):
    """Kimi Delta Attention (Kimi Linear, arXiv 2510.26692, section 3): a
    short convolution and a per-channel gated delta rule over ``nhead``
    heads of ``head_dim``, with its pre-norm and its residual.

    ``x = RMSNorm(h)``; for each head, ``q = L2Norm(SiLU(Conv(x W_q)))``,
    ``k`` the same with ``W_k``, ``v = SiLU(Conv(x W_v))`` (``Conv``: causal,
    depthwise, ``CONV_TAPS`` taps, no bias); one decay a channel of ``k``,
    ``log alpha = -exp(A_log) * softplus(x W_a_down W_a_up + dt_bias)``
    (``A_log`` one a head; ``W_a_down`` ``(d, head_dim)``, the low-rank
    form), ``beta = BETA_MAX * sigmoid(x W_beta)`` one a head
    (``BETA_MAX`` 2 lets ``I - beta k k^T`` take a negative eigenvalue);
    ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t
    v_t^T`` from ``S_0 = 0`` and ``o_t = S_t^T q_t / sqrt(head_dim)``
    (``ops/delta_rule``: chunks of 64, float32 at the highest precision,
    Pallas kernels on one TPU chip);
    out ``h + sum_heads (sigmoid(x W_g_down W_g_up + b_g) * RMSNorm(o))
    W_o``, the output norm's gain shared by the heads.

    Products in the context's compute type with float32 accumulation; the
    convolution, the norms, the decays, ``beta``, the gate and the
    recurrence in float32.  Head-major from the products on, as ``gqa``.
    Besides its output the layer counts the most negative summed
    ``log alpha`` of any chunk of 64 tokens (``kda.chunk_log_decay_min``):
    how near the chunked form runs to float32's ``exp`` range."""

    type_name = 'kda'
    type_id = kKDA
    param_fields = ('norm', 'wq', 'wk', 'wv', 'conv_q', 'conv_k', 'conv_v',
                    'wa_down', 'wa_up', 'dt_bias', 'a_log', 'wbeta',
                    'wg_down', 'wg_up', 'g_bias', 'o_norm', 'wo')
    recompute = True
    has_stats = True
    #: Kimi Linear's ``short_conv_kernel_size`` 4 and
    #: ``kda_allow_neg_eigval`` (``beta`` in (0, 2))
    CONV_TAPS = 4
    BETA_MAX = 2.0

    def __init__(self, name: str = ''):
        super().__init__(name=name)
        self.nhead = self.head_dim = 0

    def set_param(self, name, val):
        super().set_param(name, val)
        self._set_head_share(name, val)
        if name in ('nhead', 'head_dim'):
            setattr(self, name, int(val))

    def infer_shapes(self, in_specs):
        if min(self.nhead, self.head_dim) <= 0 or self._head_share_error():
            raise ValueError('kda: set nhead and head_dim; hold nhead of '
                             'nhead_published heads from head_first')
        return [self._seq_spec(in_specs[0], 'kda')]

    def init_params(self, rng, in_specs, dtype=jnp.float32) -> Params:
        d, hd, nh = in_specs[0].c, self.head_dim, self.nhead
        width = nh * hd
        ka, kt = jax.random.split(jax.random.fold_in(rng, 100))
        # Mamba's convention: A from U(1, 16), the decay's step (softplus of
        # dt_bias) log-uniform over [1e-3, 0.1]
        dt = jnp.exp(jax.random.uniform(kt, (width,), jnp.float32,
                                        math.log(1e-3), math.log(0.1)))
        return {'norm': jnp.ones((d,), dtype),
                'wq': self._w(rng, 0, (d, width), dtype),
                'wk': self._w(rng, 1, (d, width), dtype),
                'wv': self._w(rng, 2, (d, width), dtype),
                'conv_q': self._w(rng, 3, (self.CONV_TAPS, width), dtype),
                'conv_k': self._w(rng, 4, (self.CONV_TAPS, width), dtype),
                'conv_v': self._w(rng, 5, (self.CONV_TAPS, width), dtype),
                'wa_down': self._w(rng, 6, (d, hd), dtype),
                'wa_up': self._w(rng, 7, (hd, width), dtype),
                'dt_bias': (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                'a_log': jnp.log(jax.random.uniform(
                    ka, (nh,), jnp.float32, 1.0, 16.0)).astype(dtype),
                'wbeta': self._w(rng, 8, (d, nh), dtype),
                'wg_down': self._w(rng, 9, (d, hd), dtype),
                'wg_up': self._w(rng, 10, (hd, width), dtype),
                'g_bias': jnp.zeros((width,), dtype),
                'o_norm': jnp.ones((hd,), dtype),
                'wo': self._w(rng, 11, (width, d), dtype)}

    @staticmethod
    def recurrence(q, k, v, log_decay, beta, spmd_devices: int = 1):
        """The delta rule as the layer runs it, from its float32 inputs:
        ``q``, ``k``, ``log_decay`` ``(b, nh, s, head_dim)``, ``v`` the same,
        ``beta`` ``(b, nh, s)`` -> ``o`` ``(b, nh, s, head_dim)``; the
        Pallas kernels on one TPU chip, XLA elsewhere
        (``delta_rule.gated_delta_rule``).  The benchmark's comparison calls
        it on the plain reference's inputs at the cell's size."""
        return delta_rule.gated_delta_rule(
            q, k, v, log_decay, beta, 1.0 / math.sqrt(q.shape[-1]),
            spmd_devices)

    def forward_with_stats(self, params, inputs, ctx):
        h = inputs[0][:, 0]                                  # (b, s, d)
        d, dt, hd, nh = h.shape[2], h.dtype, self.head_dim, self.nhead
        f32 = jnp.float32

        def heads(a, w, bias=None):
            """``a`` through ``w``'s columns, head-major and float32:
            ``(b, s, r) -> (b, nh, s, hd)``."""
            out = jnp.einsum('bsr,rhd->bhsd', a,
                             w.astype(dt).reshape(w.shape[0], nh, hd),
                             preferred_element_type=f32)
            if bias is not None:
                out = out + bias.astype(f32).reshape(nh, 1, hd)
            return out

        def low_rank(down, up, bias):
            r = jnp.dot(x, params[down].astype(dt),
                        preferred_element_type=f32).astype(dt)
            return heads(r, params[up], params[bias])

        def conv(w):
            return short_conv(heads(x, params['w' + w]),
                              params['conv_' + w].astype(f32).reshape(
                                  -1, nh, 1, hd))

        x = rms_norm(h, params['norm'], self.eps)
        q = l2_norm(jax.nn.silu(conv('q')))
        k = l2_norm(jax.nn.silu(conv('k')))
        v = jax.nn.silu(conv('v'))
        log_decay = -jnp.exp(params['a_log'].astype(f32)).reshape(nh, 1, 1) \
            * jax.nn.softplus(low_rank('wa_down', 'wa_up', 'dt_bias'))
        beta = self.BETA_MAX * jax.nn.sigmoid(jnp.moveaxis(jnp.dot(
            x, params['wbeta'].astype(dt), preferred_element_type=f32), 2, 1))
        o = self.recurrence(q, k, v, log_decay, beta, ctx.spmd_devices)
        gate = jax.nn.sigmoid(low_rank('wg_down', 'wg_up', 'g_bias'))
        o = (rms_norm(o, params['o_norm'], self.eps) * gate).astype(dt)
        out = jnp.einsum('bhsv,hvd->bsd', o,
                         params['wo'].astype(dt).reshape(nh, hd, d),
                         preferred_element_type=f32)
        stats = {'kda.chunk_log_decay_min': jnp.min(
            delta_rule.chunk_log_decay_sums(log_decay))}
        return [(h.astype(f32) + out).astype(dt)[:, None]], stats

    def forward(self, params, inputs, ctx):
        return self.forward_with_stats(params, inputs, ctx)[0]


@register_layer
class SwiGLULayer(SequenceLayer):
    """The dense gated FFN with its pre-norm and residual:
    ``h + W_down(silu(W_gate x) * (W_up x))``, ``x = RMSNorm(h)``."""

    type_name = 'swiglu'
    type_id = kSwiGLU
    param_fields = ('norm', 'wgate', 'wup', 'wdown')
    recompute = True

    def infer_shapes(self, in_specs):
        if self.param.num_hidden <= 0:
            raise ValueError('swiglu: set nhidden (the FFN width)')
        return [self._seq_spec(in_specs[0], 'swiglu')]

    def init_params(self, rng, in_specs, dtype=jnp.float32) -> Params:
        d, f = in_specs[0].c, self.param.num_hidden
        return {'norm': jnp.ones((d,), dtype),
                'wgate': self._w(rng, 0, (d, f), dtype),
                'wup': self._w(rng, 1, (d, f), dtype),
                'wdown': self._w(rng, 2, (f, d), dtype)}

    def forward(self, params, inputs, ctx):
        h = inputs[0]
        x = rms_norm(h, params['norm'], self.eps)
        y = swiglu(x, params['wgate'], params['wup'], params['wdown'])
        return [(h.astype(jnp.float32) + y).astype(h.dtype)]


@register_layer
class MoELayer(SequenceLayer):
    """Routed experts of which this chip holds ``experts_held`` from
    ``expert_first`` on, a shared expert, pre-norm and residual inside.

    ``s = sigmoid(x W_r)`` (``router_score = sigmoid``, the default) or
    ``softmax(x W_r)`` (``router_score = softmax``) over all
    ``experts_published`` experts, float32; chosen =
    top-``experts_per_token`` of ``s + b``; ``w_e =
    routed_scaling_factor * s_e / sum_chosen s`` (the sum over all chosen,
    held here or not); ``y = sum_{e chosen and held} w_e SwiGLU_e(x) +
    SwiGLU_shared(x)``.  What the experts held elsewhere would add is left
    out; with ``experts_held = experts_published`` this is the whole layer.
    No capacity: nothing is dropped at any imbalance
    (``parallel/moe.held_experts_ffn``).

    Besides its output the layer counts, for the step's statistics, the
    assignments that landed on held experts and the largest held expert's
    load over the mean."""

    type_name = 'moe'
    type_id = kMoE
    param_fields = ('norm', 'router', 'router_bias', 'wgate', 'wup', 'wdown',
                    'sgate', 'sup', 'sdown')
    recompute = True
    has_stats = True

    def __init__(self, name: str = ''):
        super().__init__(name=name)
        self.experts_published = self.experts_held = 0
        self.expert_first = 0
        self.experts_per_token = 1
        self.routed_scaling_factor = 1.0
        self.shared_experts = 1
        self.router_score = 'sigmoid'

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == 'experts_published':
            self.experts_published = int(val)
        if name == 'experts_held':
            self.experts_held = int(val)
        if name == 'expert_first':
            self.expert_first = int(val)
        if name == 'experts_per_token':
            self.experts_per_token = int(val)
        if name == 'routed_scaling_factor':
            self.routed_scaling_factor = float(val)
        if name == 'shared_experts':
            self.shared_experts = int(val)
        if name == 'router_score':
            if val not in moe_ops.ROUTER_SCORES:
                raise ValueError(f'moe: router_score = {val!r}, not one of '
                                 f'{sorted(moe_ops.ROUTER_SCORES)}')
            self.router_score = val

    def infer_shapes(self, in_specs):
        pub, held = self.experts_published, self.experts_held
        if self.param.num_hidden <= 0 or pub <= 0 or not 0 < held <= pub \
                or not 0 <= self.expert_first <= pub - held \
                or not 0 < self.experts_per_token <= pub:
            raise ValueError(
                'moe: set nhidden (the expert width), experts_published, '
                'experts_held <= experts_published, expert_first and '
                'experts_per_token')
        return [self._seq_spec(in_specs[0], 'moe')]

    def init_params(self, rng, in_specs, dtype=jnp.float32) -> Params:
        d, f = in_specs[0].c, self.param.num_hidden
        e, sf = self.experts_held, f * self.shared_experts
        p = {'norm': jnp.ones((d,), dtype),
             'router': self._w(rng, 0, (d, self.experts_published), dtype),
             # the choice's correction bias: zero, and nothing updates it
             # (it reaches the choice alone, so its gradient is zero)
             'router_bias': jnp.zeros((self.experts_published,), dtype),
             'wgate': self._w(rng, 1, (e, d, f), dtype),
             'wup': self._w(rng, 2, (e, d, f), dtype),
             'wdown': self._w(rng, 3, (e, f, d), dtype)}
        if sf:
            p.update(sgate=self._w(rng, 4, (d, sf), dtype),
                     sup=self._w(rng, 5, (d, sf), dtype),
                     sdown=self._w(rng, 6, (sf, d), dtype))
        return p

    def forward_with_stats(self, params, inputs, ctx):
        h = inputs[0]
        b, _, s, d = h.shape
        x = rms_norm(h, params['norm'], self.eps).reshape(b * s, d)
        idx, weights = moe_ops.topk_route(
            x, params['router'], params['router_bias'],
            self.experts_per_token, self.routed_scaling_factor,
            self.router_score)
        y, sizes, full = moe_ops.held_experts_ffn(
            x, idx, weights, params['wgate'], params['wup'], params['wdown'],
            self.expert_first, self.experts_published)
        if 'sgate' in params:
            y = y + swiglu(x, params['sgate'], params['sup'], params['sdown'])
        out = (h.astype(jnp.float32) + y.reshape(h.shape)).astype(h.dtype)
        sizes = sizes.astype(jnp.float32)
        local = jnp.sum(sizes)
        stats = {
            'moe.local_assignment_share':
                local / float(b * s * self.experts_per_token),
            'moe.load_max_over_mean':
                jnp.max(sizes) * self.experts_held / jnp.maximum(local, 1.0),
            'moe.full_buffer_share': full}
        return [out], stats

    def forward(self, params, inputs, ctx):
        return self.forward_with_stats(params, inputs, ctx)[0]


@register_layer
class MTPJoinLayer(SequenceLayer):
    """The joint of a multi-token-prediction module (DeepSeek-V3 report,
    2.2): ``[RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] W_eh``, inputs the
    next tokens' embeddings and the main stack's output before its final
    norm, in that order."""

    type_name = 'mtp_join'
    type_id = kMTPJoin
    param_fields = ('enorm', 'hnorm', 'wmat')

    def infer_shapes(self, in_specs):
        if len(in_specs) != 2 or in_specs[0] != in_specs[1]:
            raise ValueError('mtp_join: two sequence nodes of one shape '
                             '(embeddings of the next tokens, hidden)')
        return [self._seq_spec(in_specs[0], 'mtp_join')]

    def init_params(self, rng, in_specs, dtype=jnp.float32) -> Params:
        d = in_specs[0].c
        return {'enorm': jnp.ones((d,), dtype),
                'hnorm': jnp.ones((d,), dtype),
                'wmat': self._w(rng, 0, (2 * d, d), dtype)}

    def forward(self, params, inputs, ctx):
        emb, h = inputs
        x = jnp.concatenate([rms_norm(emb, params['enorm'], self.eps),
                             rms_norm(h, params['hnorm'], self.eps)],
                            axis=-1)
        return [jnp.dot(x, params['wmat'].astype(x.dtype),
                        preferred_element_type=jnp.float32).astype(x.dtype)]


def _to_chunks(a, chunk: int):
    """``(batch, seq, ...)`` -> ``(seq / chunk, batch, chunk, ...)``."""
    b, s = a.shape[:2]
    return a.reshape(b, s // chunk, chunk, *a.shape[2:]).swapaxes(0, 1)


def _from_chunks(a):
    """``(n, batch, chunk, ...)`` -> ``(batch, n * chunk, ...)``."""
    n, b, chunk = a.shape[:3]
    return a.swapaxes(0, 1).reshape(b, n * chunk, *a.shape[3:])


def _is_label(logits, labels):
    """Where ``logits``' last index is the row's label: a compare against an
    iota, which fuses into whatever reads it (a gather of one scalar a row
    and its scatter do not)."""
    return jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1) == labels[..., None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunked_nll_mean(hidden, wmat, labels, chunk: int):
    """``(batch,)`` mean over a sequence's tokens of the softmax
    cross-entropy of ``hidden . wmat`` against ``labels``, ``chunk`` tokens
    at a time: ``hidden`` ``(batch, 1, seq, d)``, ``wmat`` ``(d, vocab)``,
    ``labels`` ``(batch, seq)`` int32, ``chunk`` a divisor of ``seq``.

    Products in ``hidden``'s dtype with float32 accumulation, softmax in
    float32.  Forward and backward are written out (no autodiff through a
    log-softmax and a gather): what is saved beside the three arguments is
    each token's log-sum-exp ``(batch, seq)`` float32; the backward pass
    recomputes a chunk's logits by the one product, writes ``(softmax -
    onehot) * g / seq`` once in ``hidden``'s dtype for the two products that
    read it, and sums the weight's gradient over chunks in float32."""
    return _chunked_nll_fwd(hidden, wmat, labels, chunk)[0]


def _chunked_nll_fwd(hidden, wmat, labels, chunk):
    w = wmat.astype(hidden.dtype)

    def one(args):                       # (b, chunk, d), (b, chunk)
        xc, yc = args
        logits = jnp.dot(xc, w, preferred_element_type=jnp.float32)
        top = jnp.max(logits, axis=-1)
        lse = top + jnp.log(jnp.sum(jnp.exp(logits - top[..., None]),
                                    axis=-1))
        target = jnp.sum(jnp.where(_is_label(logits, yc), logits, 0.0),
                         axis=-1)
        return lse, jnp.sum(lse - target, axis=-1)

    lse, nll = jax.lax.map(one, (_to_chunks(hidden[:, 0], chunk),
                                 _to_chunks(labels, chunk)))
    return (jnp.sum(nll, axis=0) / hidden.shape[2],
            (hidden, wmat, labels, _from_chunks(lse)))


def _chunked_nll_bwd(chunk, saved, g):
    hidden, wmat, labels, lse = saved
    w = wmat.astype(hidden.dtype)
    scale = (g / hidden.shape[2]).astype(jnp.float32)[:, None, None]

    def one(dw, args):
        xc, yc, lc = args
        logits = jnp.dot(xc, w, preferred_element_type=jnp.float32)
        p = jnp.exp(logits - lc[..., None])
        dlogits = (jnp.where(_is_label(logits, yc), p - 1.0, p)
                   * scale).astype(xc.dtype)
        dx = jax.lax.dot_general(dlogits, w, (((2,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dw = dw + jax.lax.dot_general(xc, dlogits,
                                      (((0, 1), (0, 1)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dw, dx.astype(xc.dtype)

    dw, dx = jax.lax.scan(
        one, jnp.zeros(wmat.shape, jnp.float32),
        (_to_chunks(hidden[:, 0], chunk), _to_chunks(labels, chunk),
         _to_chunks(lse, chunk)))
    return (_from_chunks(dx)[:, None], dw.astype(wmat.dtype), None)


chunked_nll_mean.defvjp(_chunked_nll_fwd, _chunked_nll_bwd)


@register_layer
class LMHeadLossLayer(LossLayerBase):
    """The output head and its loss in one layer: ``(batch, 1, seq, d)`` ->
    probabilities ``(batch, 1, seq, vocab_held)`` through one untied,
    bias-free ``wmat``, and softmax cross-entropy a token, mean over the
    sequence's tokens, then the loss layers' common ``grad_scale /
    (batch_size * update_period)`` over the batch.

    The loss never forms a sequence's whole logits
    (:func:`chunked_nll_mean`): ``gcd(seq, chunk_tokens)`` tokens at a
    time, forward and backward written by hand.  Saved between the passes: the layer's input,
    the labels, ``wmat`` and each token's float32 log-sum-exp.  Recomputed
    in the backward pass: a chunk's logits, by the one product.  The
    label's logit is picked by a compare against the vocabulary's index,
    forward and backward (no gather, no scatter), and a chunk's gradient
    ``(softmax - onehot) * g / seq`` is written once, in the activations'
    dtype, for the two products that read it; ``wmat``'s gradient is summed
    over chunks in float32.  The probabilities of :meth:`forward` are
    whole, and exist only where somebody reads the node.

    One input a head, all through the same ``wmat``: the main stack's
    output, then each multi-token-prediction module's in depth order.
    Input ``k`` is scored against columns ``[k * seq, (k + 1) * seq)`` of
    the ``target`` label field and weighs ``head_weight[k]`` in the loss."""

    type_name = 'lm_head_loss'
    type_id = kLMHeadLoss
    param_fields = ('wmat',)

    def __init__(self, name: str = ''):
        super().__init__(name=name)
        self.vocab_held, self.vocab_published = 0, 0
        self.head_weight = []
        self.chunk_tokens = 1024

    def set_param(self, name, val):
        super().set_param(name, val)
        if name in ('vocab_held', 'vocab_published', 'chunk_tokens'):
            setattr(self, name, int(val))
        if name == 'head_weight':
            self.head_weight = [float(t) for t in val.split(',')]

    def infer_shapes(self, in_specs):
        if self.vocab_held <= 0:
            raise ValueError('lm_head_loss: set vocab_held')
        if any(s != in_specs[0] or s.y != 1 for s in in_specs):
            raise ValueError('lm_head_loss: sequence nodes of one shape')
        self.head_weight = self.head_weight or [1.0] * len(in_specs)
        if len(self.head_weight) != len(in_specs):
            raise ValueError('lm_head_loss: one head_weight an input')
        return [NodeSpec(self.vocab_held, 1, s.x) for s in in_specs]

    def init_params(self, rng, in_specs, dtype=jnp.float32) -> Params:
        d = in_specs[0].c
        return {'wmat': self.param.rand_init_weight(
            jax.random.fold_in(rng, 0), (d, self.vocab_held), d,
            self.vocab_held, dtype)}

    def forward(self, params, inputs, ctx):
        return [jax.nn.softmax(
            jnp.dot(x, params['wmat'].astype(x.dtype),
                    preferred_element_type=jnp.float32), axis=-1)
            for x in inputs]

    def loss(self, params, inputs, labels, ctx, mask=None):
        s = inputs[0].shape[2]
        per_inst = sum(
            weight * self._nll_mean(x, params['wmat'],
                                    labels[:, k * s:(k + 1) * s])
            for k, (x, weight) in enumerate(zip(inputs, self.head_weight)))
        if mask is not None:
            per_inst = per_inst * mask
        return jnp.sum(per_inst) * self.scale

    def _nll_mean(self, hidden, w_head, labels):
        """(batch,) mean cross-entropy over a sequence's tokens, a chunk of
        tokens at a time."""
        chunk = math.gcd(hidden.shape[2], self.chunk_tokens)
        return chunked_nll_mean(hidden, w_head, labels.astype(jnp.int32),
                                chunk)
