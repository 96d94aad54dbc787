"""Quantized inference parameters: int8 / bf16 storage tiers.

The serving tier this module implements (doc/serving.md "Quantized
inference") trades a bounded accuracy delta for device memory: a model
loaded at ``serve.dtype=int8`` keeps roughly 1/4 the resident bytes of
its f32 twin, so the ``MemoryBudgeter`` fits ~4x more models per chip
before evicting.  Quantization happens ONCE, at load/swap time (the
engines call :func:`quantize_tree` inside ``place_params``) — the hot
path never re-quantizes weights.

Two tiers:

* **bf16** — every float leaf cast to bfloat16 (2x).  Pure storage/
  compute dtype change; no extra machinery.
* **int8** — symmetric per-channel weight-only quantization of matmul
  weights: ``q = round(x / scale)`` with ``scale = max|x| / 127`` taken
  over the contraction axis (``axis=-2``), so each output channel keeps
  its own dynamic range; leading stack axes (the transformer's stage
  axis) are preserved, which is what lets ``jax.tree.map(lambda a: a[i])``
  slice a stacked :class:`QuantLeaf` per stage exactly like a plain
  array.  Non-matmul leaves (layernorm scales, biases) stay in the
  compute dtype — quantizing them saves nothing and costs accuracy.

:class:`QuantLeaf` is a registered pytree node (children: ``q`` int8 +
``scale`` f32), so quantized trees flow through ``jit`` / ``device_put``
/ ``tree.leaves`` unchanged — ``sum(l.nbytes for l in leaves)`` is the
TRUE quantized footprint the budgeter sees.

Execution: consumers route matmuls through :func:`qdot` and embedding
gathers through :func:`qtake` — ``models/transformer.py`` does at every
inference matmul site (``_stage_attn``, ``_gen_ffn``,
``_nodrop_moe_ffn``'s gate, ``prefill_kv``'s head, and the block walks
of ``decode_step`` and ``verify_step``).  For a plain array ``qdot(x, w)`` IS
``x @ w`` (the
training path is bitwise untouched); for a :class:`QuantLeaf` it runs
W8A8: dynamic per-row symmetric activation quantization, an int8 x int8
``lax.dot_general`` with exact int32 accumulation (integer adds carry no
rounding) and one f32 rescale.  Determinism is the point: a quantized
model's outputs are a pure function of its int8 weights, identical
across join orders, so the decode
engine's streams still have an EXACT offline twin
(``transformer.generate`` over the same quantized tree); the accuracy
delta vs f32 is policed separately by the tolerance twins
(tests/test_quantize.py) whose thresholds are pinned, never silently
loosened.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ['QuantLeaf', 'quantize_leaf', 'quantize_tree',
           'dequantize_tree', 'qdot', 'qtake', 'tree_nbytes',
           'parse_serve_dtype', 'SERVE_DTYPES', 'LM_MATMUL_KEYS',
           'quantize_lm_tree', 'shard_put']

SERVE_DTYPES = ('f32', 'bf16', 'int8')

#: transformer-tree leaf names consumed through ``qdot``/``qtake`` —
#: the int8 tier quantizes exactly these (MoE expert stacks ``w1``/``w2``
#: at ndim 4 are einsum-consumed and stay unquantized)
LM_MATMUL_KEYS = ('embed', 'head', 'wq', 'wk', 'wv', 'wo',
                  'w1', 'w2', 'gate')


def parse_serve_dtype(value: str) -> str:
    """Validate a ``serve.dtype`` key value ('f32' aliases 'float32')."""
    text = str(value).strip().lower()
    if text in ('', 'f32', 'float32', 'fp32'):
        return 'f32'
    if text in ('bf16', 'bfloat16'):
        return 'bf16'
    if text == 'int8':
        return 'int8'
    raise ValueError(
        f'serve.dtype must be one of {SERVE_DTYPES}, got {value!r}')


@jax.tree_util.register_pytree_node_class
class QuantLeaf:
    """A symmetric per-channel int8 tensor: ``x ~= q * scale`` with
    ``scale`` broadcast along the contraction axis (``axis=-2``).
    ``out_dtype`` is the compute dtype dequantized values take."""

    __slots__ = ('q', 'scale', 'out_dtype')

    def __init__(self, q, scale, out_dtype=jnp.float32):
        self.q = q
        self.scale = scale
        self.out_dtype = jnp.dtype(out_dtype)

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.q, self.scale), (self.out_dtype,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        return cls(q, scale, aux[0])

    # -- array-ish surface -------------------------------------------------
    @property
    def shape(self):
        return tuple(self.q.shape)

    @property
    def nbytes(self) -> int:
        return int(self.q.nbytes) + int(self.scale.nbytes)

    def dequantize(self, dtype=None):
        """Exact ``q * scale`` expansion (deterministic: the only float
        op is one multiply per element)."""
        s = jnp.expand_dims(self.scale, -2)
        return (self.q.astype(jnp.float32) * s).astype(
            self.out_dtype if dtype is None else dtype)

    def __repr__(self):
        return (f'QuantLeaf(int8 {self.shape}, scale '
                f'{tuple(self.scale.shape)}, out={self.out_dtype})')


def quantize_leaf(x, out_dtype=jnp.float32) -> QuantLeaf:
    """Symmetric per-channel int8 quantization over ``axis=-2`` (the
    contraction axis of ``x @ w``): every output channel — and every
    entry of any leading stack axis — gets its own ``max|x|/127``
    scale.  Dead channels (all-zero) take scale 1 so ``q`` stays 0."""
    xf = np.asarray(jax.device_get(x), np.float32)
    if xf.ndim < 2:
        raise ValueError(f'quantize_leaf needs ndim >= 2, got {xf.shape}')
    amax = np.max(np.abs(xf), axis=-2)
    scale = np.where(amax == 0.0, 1.0, amax / 127.0).astype(np.float32)
    q = np.clip(np.round(xf / np.expand_dims(scale, -2)),
                -127, 127).astype(np.int8)
    return QuantLeaf(q, scale, out_dtype)


def _map_named(fn, tree, name=''):
    """Depth-first map over a nested-dict tree with the leaf's own key
    (both the trainer's layer->field dicts and the transformer tree are
    nested dicts of arrays)."""
    if isinstance(tree, dict):
        return {k: _map_named(fn, v, k) for k, v in tree.items()}
    return fn(name, tree)


def _default_quant_key(name: str, leaf) -> bool:
    """The generic (netconfig/CNN) int8 rule: weight-shaped leaves
    (ndim >= 2) quantize; vectors (biases, norm scales) stay float."""
    return getattr(leaf, 'ndim', 0) >= 2


def lm_quant_key(name: str, leaf) -> bool:
    """The transformer rule: exactly the ``qdot``/``qtake``-consumed
    matmul leaves (MoE 4D expert stacks excluded — einsum-consumed)."""
    return (name in LM_MATMUL_KEYS
            and 2 <= getattr(leaf, 'ndim', 0) <= 3)


def quantize_tree(tree, mode: str, *, out_dtype=None, quant_key=None):
    """Quantize a HOST param tree into its serving storage tier.

    ``mode``: ``'f32'`` (identity), ``'bf16'`` (float leaves cast), or
    ``'int8'`` (leaves passing ``quant_key`` become :class:`QuantLeaf`;
    the rest cast to ``out_dtype``).  ``out_dtype`` defaults to f32 for
    the generic rule and is the compute dtype quantized consumers
    produce."""
    mode = parse_serve_dtype(mode)
    if mode == 'f32':
        return tree
    out_dtype = jnp.dtype(jnp.float32 if out_dtype is None else out_dtype)
    key = _default_quant_key if quant_key is None else quant_key

    def one(name, leaf):
        # jnp.issubdtype, not np: bfloat16 is outside numpy's hierarchy
        if not jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            return leaf
        if mode == 'bf16':
            return jnp.asarray(leaf, jnp.bfloat16)
        if key(name, leaf):
            return quantize_leaf(leaf, out_dtype)
        return jnp.asarray(leaf, out_dtype)

    return _map_named(one, tree)


def quantize_lm_tree(tree, mode: str, *, out_dtype=None):
    """Quantize a transformer param tree into its serving tier under the
    LM matmul-leaf rule — the one call the decode engine makes for BOTH
    its target and its speculative-decode draft tree (serve/decode.py),
    so the two models always land on the same storage tier and the
    greedy verify math consumes them through the identical ``qdot``
    dispatch."""
    return quantize_tree(tree, mode, out_dtype=out_dtype,
                         quant_key=lm_quant_key)


def dequantize_tree(tree, dtype=None):
    """Expand every :class:`QuantLeaf` (and optionally cast every float
    leaf to ``dtype``) — the weight-only execution path's per-forward
    step, and the host-side reference for exact twins."""

    def one(leaf):
        if isinstance(leaf, QuantLeaf):
            return leaf.dequantize(dtype)
        if dtype is not None and jnp.issubdtype(
                jnp.asarray(leaf).dtype, jnp.floating):
            return jnp.asarray(leaf, dtype)
        return leaf

    return jax.tree.map(one, tree,
                        is_leaf=lambda n: isinstance(n, QuantLeaf))


def shard_put(leaf, mesh, spec):
    """Device-put one param leaf with a full-rank ``PartitionSpec``
    over ``mesh`` (the graftshard tensor-parallel placement,
    doc/serving.md "Sharded serving").

    A plain array takes ``spec`` directly.  A :class:`QuantLeaf` must
    keep its two children CO-SHARDED: ``q`` takes ``spec``, and
    ``scale`` — whose shape is ``q``'s with the contraction axis
    (``-2``) dropped — takes ``spec`` with that same entry dropped, so
    every per-output-channel scale lives on the device that owns its
    channels and ``qdot``'s rescale multiply never crosses devices."""
    from jax.sharding import NamedSharding, PartitionSpec

    def put(arr, parts):
        return jax.device_put(arr, NamedSharding(mesh,
                                                 PartitionSpec(*parts)))

    if isinstance(leaf, QuantLeaf):
        parts = tuple(spec) + (None,) * (leaf.q.ndim - len(tuple(spec)))
        return QuantLeaf(put(leaf.q, parts),
                         put(leaf.scale, parts[:-2] + parts[-1:]),
                         leaf.out_dtype)
    return put(leaf, tuple(spec))


def tree_nbytes(tree) -> int:
    """True storage bytes of a (possibly quantized) tree — QuantLeaf
    flattens to its int8 payload + scales, so plain leaf summation IS
    the quantized footprint."""
    return int(sum(l.nbytes for l in jax.tree.leaves(tree)))


def _int8_mm(aq, bq):
    """int8 x int8 -> int32, exact integer accumulation."""
    return jax.lax.dot_general(aq, bq, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def qdot(x, w):
    """``x @ w`` through the quantized-leaf dispatcher.

    Plain array ``w``: returns ``x @ w`` — the native op, bitwise
    untouched (this is why the training/reference paths can share the
    call site).  :class:`QuantLeaf` ``w`` (2D, post-stage-slice): W8A8 —
    per-row symmetric activation quantization, exact-int32 int8 matmul,
    one f32 rescale, result in ``w.out_dtype``."""
    if not isinstance(w, QuantLeaf):
        return x @ w
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    xs = jnp.where(amax == 0.0, jnp.float32(1.0), amax / 127.0)
    xq = jnp.clip(jnp.round(xf / xs), -127, 127).astype(jnp.int8)
    lead = xq.shape[:-1]
    acc = _int8_mm(xq.reshape(-1, xq.shape[-1]), w.q)
    out = (acc.astype(jnp.float32) * xs.reshape(-1, 1)
           * w.scale[None, :])
    return out.reshape(*lead, w.q.shape[-1]).astype(w.out_dtype)


def qtake(emb, idx):
    """Embedding-row gather through the dispatcher: plain arrays take
    ``jnp.take``; an int8 embedding gathers its rows and dequantizes
    just those (``scale`` is per-channel over the embedding dim, so it
    broadcasts across gathered rows)."""
    if not isinstance(emb, QuantLeaf):
        return jnp.take(emb, idx, axis=0)
    rows = jnp.take(emb.q, idx, axis=0).astype(jnp.float32)
    return (rows * emb.scale).astype(emb.out_dtype)
