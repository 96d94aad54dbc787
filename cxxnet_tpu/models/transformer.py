"""TransformerLM — the long-context / multi-dimensional-parallelism
flagship.

The reference framework tops out at data parallelism over GPUs
(SURVEY.md §2.5); this model family is where the TPU build goes past it:
one ``shard_map`` over a ``(pipe, data, seq, model)`` mesh runs the FULL
training step with every collective explicit and riding ICI:

* **dp**   — batch sharded over ``data``; gradient pmean over data+seq,
* **pp**   — transformer blocks stacked on a leading stage axis sharded
  over ``pipe``; GPipe microbatch schedule (parallel/pipeline.py),
* **sp**   — sequence sharded over ``seq``; exact ring attention
  (parallel/sequence.py) rotates K/V blocks with ``ppermute``,
* **tp**   — attention heads and FFN hidden sharded over ``model``;
  row-parallel output projections finish with ``psum``,
* **ep**   — switch-MoE FFN, experts sharded over ``data`` with
  all_to_all dispatch/combine (parallel/moe.py).

Because everything lives in one shard_map body, the strategies compose:
ring attention runs inside a pipeline stage inside the microbatch scan.
Backward is ``jax.value_and_grad`` straight through (collectives
transpose to collectives); the SGD update runs sharded in the same body,
so optimizer state never leaves the device that owns the shard.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import math
import os
import threading
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nnet.quantize import qdot, qtake
from ..parallel.moe import moe_ffn_local
from ..parallel.pipeline import pipeline_stage_loop, split_microbatches
from ..parallel.sequence import _local_attention, _ring_attention_local


AXES = ('pipe', 'data', 'seq', 'model')

# --- serving-side tensor parallelism (graftshard, doc/serving.md
# "Sharded serving") -------------------------------------------------------
#
# The decode engine serves a COLUMN-sharded param tree over a 1xN
# ('data', 'model') mesh: every matmul weight's last (output-feature)
# axis is split over 'model' — wq/wk/wv along attention heads, wo/w2
# along d_model, w1 along d_ff, head along vocab, embed along d_model —
# and the residual stream is pulled back to replicated with an explicit
# sharding constraint BEFORE any op that would contract over a sharded
# axis.  That constraint lowers to an all-gather: pure data movement, no
# arithmetic.  The payoff is the stream-twin contract — every float
# reduction (matmul K-loops, layernorm moments, softmax sums) runs over
# fully-replicated operands in the exact operand order of the
# single-device program, so sharded logits are BITWISE-equal to
# unsharded ones at any shard count (tests/test_serve_shard.py).  The
# training path (`_stage_fn`) keeps its psum-based row-parallel layout:
# training tolerates reduction-order drift, serving twins do not.
#
# The active serve mesh rides a thread-local rather than the config:
# `TransformerConfig` must stay `dataclasses.astuple`-able (generate()'s
# program-cache key), and tracing happens on whichever thread first
# calls the jitted program — the engine wraps each traced body in
# :func:`shard_scope`, so concurrent prefill workers tracing different
# programs cannot see each other's mesh.
_SHARD_TLS = threading.local()


@contextlib.contextmanager
def shard_scope(mesh):
    """Activate ``mesh`` as the serve-shard mesh for ops traced inside
    this scope (``None`` = single-device: every hook is an identity)."""
    prev = getattr(_SHARD_TLS, 'mesh', None)
    _SHARD_TLS.mesh = mesh
    try:
        yield
    finally:
        _SHARD_TLS.mesh = prev


def serve_shard_mesh():
    """The serve-shard mesh active on this thread (None = off)."""
    return getattr(_SHARD_TLS, 'mesh', None)


def _rep(x):
    """Constrain a (possibly model-sharded) activation to fully
    replicated — the all-gather boundary of the column-parallel serving
    layout.  Identity when no serve-shard mesh is active, so training,
    ``generate`` and the single-device engines compile byte-identical
    programs."""
    mesh = serve_shard_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


@dataclass
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 64
    num_heads: int = 4
    d_ff: int = 128
    num_stages: int = 2          # pipeline stages == transformer blocks
    seq_len: int = 64
    num_experts: int = 0         # 0 = dense FFN; >0 = switch-MoE FFN
    capacity_factor: float = 2.0
    balance_loss_weight: float = 0.01   # Switch aux-loss weight (MoE only)
    attn: str = 'ring'           # 'ring' | 'local'
    causal: bool = True
    num_microbatches: int = 4
    dtype: object = jnp.float32
    remat: bool = False          # rematerialize each block in backward:
    # activations of a stage are recomputed instead of stored, cutting
    # per-block activation HBM to O(1) blocks — the lever that lets long
    # sequences fit (pairs with ring attention's O(s) memory)


def init_params(rng: np.random.RandomState, cfg: TransformerConfig):
    """Stage params stacked on axis 0 (the ``pipe``-sharded axis)."""
    s, d, f, v = cfg.num_stages, cfg.d_model, cfg.d_ff, cfg.vocab_size

    def init(*shape, scale=None):
        scale = scale or 1.0 / math.sqrt(shape[-2] if len(shape) > 1
                                         else shape[-1])
        return jnp.asarray(rng.randn(*shape) * scale, cfg.dtype)

    stages = {
        'ln1_scale': jnp.ones((s, d), cfg.dtype),
        'ln1_bias': jnp.zeros((s, d), cfg.dtype),
        'wq': init(s, d, d), 'wk': init(s, d, d), 'wv': init(s, d, d),
        'wo': init(s, d, d),
        'ln2_scale': jnp.ones((s, d), cfg.dtype),
        'ln2_bias': jnp.zeros((s, d), cfg.dtype),
    }
    if cfg.num_experts:
        e = cfg.num_experts
        stages['gate'] = init(s, d, e)
        stages['w1'] = init(s, e, d, f)
        stages['w2'] = init(s, e, f, d, scale=1.0 / math.sqrt(f))
    else:
        stages['w1'] = init(s, d, f)
        stages['w2'] = init(s, f, d, scale=1.0 / math.sqrt(f))
    return {
        'embed': init(v, d, scale=0.02),
        'head': init(d, v),
        'stages': stages,
    }


def param_specs(cfg: TransformerConfig):
    """PartitionSpecs over AXES for every leaf."""
    col = P('pipe', None, 'model')       # qkv: heads sharded over model
    stages = {
        'ln1_scale': P('pipe', None), 'ln1_bias': P('pipe', None),
        'wq': col, 'wk': col, 'wv': col,
        'wo': P('pipe', 'model', None),  # row-parallel out-proj -> psum
        'ln2_scale': P('pipe', None), 'ln2_bias': P('pipe', None),
    }
    if cfg.num_experts:
        stages['gate'] = P('pipe', None, None)
        stages['w1'] = P('pipe', 'data', None, None)   # ep over data axis
        stages['w2'] = P('pipe', 'data', None, None)
    else:
        stages['w1'] = P('pipe', None, 'model')        # col-parallel
        stages['w2'] = P('pipe', 'model', None)        # row-parallel
    return {'embed': P(None, None), 'head': P(None, None),
            'stages': stages}


def _map_with_specs(fn, tree, specs):
    """Apply ``fn(leaf, spec)`` over parallel nested dicts (PartitionSpec
    is a tuple subclass, so jax.tree.map would descend into it)."""
    if isinstance(tree, dict):
        return {k: _map_with_specs(fn, v, specs[k]) for k, v in tree.items()}
    return fn(tree, specs)


def _layer_norm(x, scale, bias):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + 1e-6) * scale + bias).astype(x.dtype)


def _stage_fn(p, x, *, cfg: TransformerConfig, tp: int, sp: int):
    """One transformer block on the local activation shard.
    x: (mb_local, s_local, D).  p: this stage's params (leading dim
    squeezed).  Collectives: 'seq' (ring attention), 'model' (psum for
    row-parallel projections), 'data' (MoE all_to_all).
    Returns (y, aux): aux carries the MoE balance loss / drop fraction
    (zeros for dense FFN) and is accumulated by the pipeline loop."""
    mb, s_loc, d = x.shape
    h_local = cfg.num_heads // tp        # heads owned by this model rank
    hd = d // cfg.num_heads

    # --- attention ---------------------------------------------------------
    y = _layer_norm(x, p['ln1_scale'], p['ln1_bias'])
    q = (y @ p['wq']).reshape(mb, s_loc, h_local, hd)
    k = (y @ p['wk']).reshape(mb, s_loc, h_local, hd)
    v = (y @ p['wv']).reshape(mb, s_loc, h_local, hd)
    if cfg.attn == 'ring' and sp > 1:
        attn = _ring_attention_local(q, k, v, axis_name='seq',
                                     causal=cfg.causal)
    else:
        mask = None
        if cfg.causal:
            mask = jnp.tril(jnp.ones((s_loc, s_loc), bool))[None, None]
        attn = _local_attention(q, k, v, 1.0 / math.sqrt(hd), mask)
    attn = attn.reshape(mb, s_loc, h_local * hd)
    out = attn @ p['wo']                  # row-parallel: partial sums
    if tp > 1:
        out = lax.psum(out, 'model')
    x = x + out

    # --- ffn ---------------------------------------------------------------
    y = _layer_norm(x, p['ln2_scale'], p['ln2_bias'])
    if cfg.num_experts:
        yf = y.reshape(mb * s_loc, d)
        ff, aux = moe_ffn_local(yf, p['gate'], p['w1'], p['w2'],
                                axis_name='data',
                                capacity_factor=cfg.capacity_factor)
        ff = ff.reshape(mb, s_loc, d)
    else:
        ff = jax.nn.relu(y @ p['w1']) @ p['w2']
        if tp > 1:
            ff = lax.psum(ff, 'model')
        aux = {'balance_loss': jnp.float32(0.0),
               'drop_frac': jnp.float32(0.0)}
    return x + ff, aux


def _loss_local(params, tokens, labels, *, cfg, tp, sp):
    """Local shard loss: embed -> pipelined blocks -> head -> mean NLL
    (+ weighted MoE balance loss).  Returns (loss, aux)."""
    h = jnp.take(params['embed'], tokens, axis=0)        # (b, s, D)
    xs = split_microbatches(h, cfg.num_microbatches)
    stage = functools.partial(_stage_fn, cfg=cfg, tp=tp, sp=sp)
    if cfg.remat:
        # recompute the block in backward instead of storing its
        # activations; collectives (ring ppermute, psum, all_to_all)
        # replay under remat, so this composes with all four axes
        stage = jax.checkpoint(stage)
    hs, aux = pipeline_stage_loop(stage, params['stages'], xs,
                                  axis_name='pipe',
                                  num_stages=cfg.num_stages, has_aux=True)
    h = hs.reshape(h.shape)
    logits = (h @ params['head']).astype(jnp.float32)     # (b, s, V)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
    loss = nll.mean()
    if cfg.num_experts:
        loss = loss + cfg.balance_loss_weight * aux['balance_loss']
    return loss, aux


def _make_step_body(cfg: TransformerConfig, mesh: Mesh, lr: float):
    """The per-rank train-step body of :func:`make_train_step`:
    (params, tokens, labels) -> (new_params, loss, aux), all local
    shards.  NOTE: :func:`make_multi_train_step` does NOT use this — it
    scans :func:`reference_loss` (see its docstring for why); optimizer
    changes here must be mirrored there."""
    tp = mesh.shape['model']
    sp = mesh.shape['seq']
    if cfg.num_heads % tp:
        raise ValueError('num_heads must divide model axis')
    if sp > 1 and cfg.attn != 'ring':
        raise ValueError(
            f"attn='{cfg.attn}' on a seq-sharded mesh (seq={sp}) would "
            "attend block-diagonally; use attn='ring'")
    specs = param_specs(cfg)

    n_ranks = (mesh.shape['pipe'] * mesh.shape['data']
               * mesh.shape['seq'] * mesh.shape['model'])

    def _replicated_axes(spec: P) -> Tuple[str, ...]:
        used = {a for part in spec if part is not None
                for a in ((part,) if isinstance(part, str) else part)}
        return tuple(a for a in AXES if a not in used)

    def body(params, tokens, labels):
        (loss, aux), grads = jax.value_and_grad(
            functools.partial(_loss_local, cfg=cfg, tp=tp, sp=sp),
            has_aux=True)(params, tokens, labels)
        # Per-rank autodiff yields d(sum of every rank's local loss)/
        # d(local shard) — collective transposes already crossed ranks.
        # Tie replicas back together: sum each leaf's gradient over the
        # axes it is replicated on, then normalize by the total rank
        # count so the result is the gradient of the *mean* loss.
        # Validated against the single-device oracle in
        # tests/test_transformer_parallel.py.
        def tie(g, spec):
            rep = _replicated_axes(spec)
            if rep:
                g = lax.psum(g, rep)
            return g / n_ranks
        grads = _map_with_specs(tie, grads, specs)
        new_params = jax.tree.map(
            lambda w, g: (w - lr * g).astype(w.dtype), params, grads)
        aux = jax.tree.map(lambda v: lax.pmean(v, AXES), aux)
        return new_params, lax.pmean(loss, AXES), aux

    return body, specs


def make_train_step(cfg: TransformerConfig, mesh: Mesh, lr: float = 0.1):
    """Jitted full train step: (params, tokens, labels) ->
    (new_params, loss, aux).  tokens/labels are global (B, seq_len) int32;
    aux reports ``balance_loss`` (unweighted) and ``drop_frac`` summed over
    MoE blocks (zeros for dense FFN)."""
    body, specs = _make_step_body(cfg, mesh, lr)
    tok_spec = P('data', 'seq')
    fn = shard_map(body, mesh=mesh,
                   in_specs=(specs, tok_spec, tok_spec),
                   out_specs=(specs, P(), {'balance_loss': P(),
                                           'drop_frac': P()}),
                   check_vma=False)
    return jax.jit(fn)


def make_multi_train_step(cfg: TransformerConfig, n_steps: int,
                          lr: float = 0.1):
    """Single-device jitted ``n_steps``-step training loop in ONE
    dispatch: (params, tok_stack, lab_stack) -> (new_params, last_loss),
    the stacks (nstack, B, seq_len) int32 cycled round-robin — the
    transformer counterpart of ``NetTrainer.compile_multi_step``, used by
    bench.py (whose K-vs-1 quotient cancels the per-dispatch cost) and by
    single-chip pre-staged pipelines.  Built
    on :func:`reference_loss` (the oracle the mesh step is tested
    against): a ``lax.scan`` whose body contains a shard_map does not
    lower on this jax version (internally-jitted jnp helpers become
    closed_calls the lowering cache misses), and a single chip needs no
    mesh anyway."""

    def multi(params, tok_stack, lab_stack):
        nstack = tok_stack.shape[0]

        def sbody(p, t):
            tok = lax.dynamic_index_in_dim(tok_stack, t % nstack,
                                           keepdims=False)
            lab = lax.dynamic_index_in_dim(lab_stack, t % nstack,
                                           keepdims=False)
            loss, grads = jax.value_and_grad(reference_loss)(p, tok, lab,
                                                             cfg)
            p = jax.tree.map(
                lambda w, g: (w - lr * g).astype(w.dtype), p, grads)
            return p, loss

        params, losses = lax.scan(sbody, params, jnp.arange(n_steps))
        return params, losses[-1]

    jitted = jax.jit(multi, donate_argnums=(0,))
    jitted.n_steps = n_steps
    return jitted


def build_transformer_mesh(n_devices: int,
                           pp: int, dp: int, sp: int, tp: int,
                           devices=None) -> Mesh:
    if pp * dp * sp * tp != n_devices:
        raise ValueError(f'pp*dp*sp*tp = {pp * dp * sp * tp} '
                         f'!= {n_devices} devices')
    devs = np.asarray(devices if devices is not None
                      else jax.devices()[:n_devices])
    return Mesh(devs.reshape(pp, dp, sp, tp), AXES)


def param_shapes(cfg: TransformerConfig):
    """ShapeDtypeStructs mirroring ``init_params`` — shapes without
    allocating anything (test-pinned against init_params)."""
    s, d, f, v = cfg.num_stages, cfg.d_model, cfg.d_ff, cfg.vocab_size

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, cfg.dtype)

    stages = {
        'ln1_scale': sds(s, d), 'ln1_bias': sds(s, d),
        'wq': sds(s, d, d), 'wk': sds(s, d, d), 'wv': sds(s, d, d),
        'wo': sds(s, d, d),
        'ln2_scale': sds(s, d), 'ln2_bias': sds(s, d),
    }
    if cfg.num_experts:
        e = cfg.num_experts
        stages['gate'] = sds(s, d, e)
        stages['w1'] = sds(s, e, d, f)
        stages['w2'] = sds(s, e, f, d)
    else:
        stages['w1'] = sds(s, d, f)
        stages['w2'] = sds(s, f, d)
    return {'embed': sds(v, d), 'head': sds(d, v), 'stages': stages}


def abstract_params(params, cfg: TransformerConfig, mesh: Mesh):
    """Sharding-annotated ShapeDtypeStructs for ``params`` — the restore
    target for sharded checkpoints (nnet/sharded_ckpt.py): orbax lays each
    shard straight onto its mesh position, no full-replica host copy.
    ``params=None`` derives shapes from the config (``param_shapes``), so
    resume never materializes a throwaway replica."""
    from jax.sharding import NamedSharding
    if params is None:
        params = param_shapes(cfg)
    return _map_with_specs(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        params, param_specs(cfg))


def _stage_attn(p, h, cfg: TransformerConfig, mask):
    """One block's attention half on a single device: ln1 -> qkv ->
    attention -> residual out-proj -> ln2.  THE single copy of the
    block math — :func:`reference_loss` and :func:`generate`'s prefill
    both run through here, so they cannot drift.  Returns
    ``(h, y2, k, v)`` (k/v for the decode cache)."""
    mb, s, d = h.shape
    hd = d // cfg.num_heads
    y = _layer_norm(h, p['ln1_scale'], p['ln1_bias'])
    # matmuls route through the quantized-leaf dispatcher: a plain
    # array takes the native ``x @ w`` (bitwise-identical — training and
    # reference paths are untouched); an int8 QuantLeaf (serve.dtype,
    # nnet/quantize.py) runs the W8A8 leg
    q = qdot(y, p['wq']).reshape(mb, s, cfg.num_heads, hd)
    k = qdot(y, p['wk']).reshape(mb, s, cfg.num_heads, hd)
    v = qdot(y, p['wv']).reshape(mb, s, cfg.num_heads, hd)
    attn = _local_attention(q, k, v, 1.0 / math.sqrt(hd), mask)
    # serve-shard boundary: gather the head-sharded attention output
    # before contracting over d_model, re-replicate wo's column-sharded
    # output before the residual add (no-ops off-mesh)
    h = h + _rep(qdot(_rep(attn.reshape(mb, s, d)), p['wo']))
    y2 = _layer_norm(h, p['ln2_scale'], p['ln2_bias'])
    return h, y2, k, v


def _nodrop_moe_ffn(y2, p, gather: bool):
    """No-drop top-1 switch routing: gate-probability-scaled expert
    output (the same per-token math as ``switch_gate``'s
    ``combine = dispatch * gate_prob``, parallel/moe.py) WITHOUT the
    capacity bound — at inference the capacity bucket is a training-time
    load-balancing artifact (a handful of live tokens makes
    ``capacity = ceil(cf*N/E)`` round to 0-1 and drop arbitrarily).

    ``gather=True`` gathers each token's expert weights directly —
    O(tokens) weight copies, right for the decode step's single live
    token.  ``gather=False`` uses a one-hot dispatch einsum (no weight
    duplication, E-way activation buffer like ``moe_ffn_local``) —
    right for the prefill's b*s0 tokens."""
    probs = jax.nn.softmax(qdot(y2, p['gate']).astype(jnp.float32),
                           axis=-1)
    ex = jnp.argmax(probs, axis=-1)                        # (n,)
    pg = jnp.take_along_axis(probs, ex[:, None], axis=-1)  # (n, 1)
    if gather:
        w1 = jnp.take(p['w1'], ex, axis=0)                 # (n, d, f)
        w2 = jnp.take(p['w2'], ex, axis=0)                 # (n, f, d)
        hmid = jax.nn.relu(jnp.einsum('nd,ndf->nf', y2, w1))
        out = jnp.einsum('nf,nfd->nd', hmid, w2)
    else:
        oh = jax.nn.one_hot(ex, p['w1'].shape[0], dtype=y2.dtype)
        buf = jnp.einsum('ne,nd->end', oh, y2)             # (E, n, d)
        hmid = jax.nn.relu(jnp.einsum('end,edf->enf', buf, p['w1']))
        out = jnp.einsum('enf,efd,ne->nd', hmid, p['w2'], oh)
    return (pg * out.astype(jnp.float32)).astype(y2.dtype)


# compiled decode programs keyed by (cfg, bucketed shapes, sampling):
# generate() is called repeatedly (sampling loops, tests) and must not
# re-trace — and the jitted fn takes params as an ARGUMENT so weights are
# inputs, not baked-in XLA constants.  Two guards keep the cache from
# retaining one compiled program per distinct request shape forever:
# prompt/new-token lengths are BUCKETED into power-of-two size classes
# before keying (below), and the cache itself is a small LRU
# (``CXXNET_GEN_CACHE_MAX``, default 8) — a varying-prompt sampling loop
# touches a handful of entries, evicting cold programs instead of
# growing without bound.
_GEN_CACHE: 'collections.OrderedDict' = collections.OrderedDict()

# hit/miss tallies for the program cache — serving telemetry
# (serve stats / bench receipts) reads these through gen_cache_stats()
# so a retrace storm under live traffic is visible, not silent
_GEN_STATS = {'hit': 0, 'miss': 0}


def gen_cache_stats(reset: bool = False) -> dict:
    """Snapshot (optionally reset) the ``generate`` program-cache
    hit/miss counters; serving surfaces export them onto a
    ``utils.metric.StatSet`` (``gen_cache.hit`` / ``gen_cache.miss``)."""
    out = dict(_GEN_STATS)
    if reset:
        _GEN_STATS['hit'] = _GEN_STATS['miss'] = 0
    return out


def _gen_cache_max() -> int:
    return max(1, int(os.environ.get('CXXNET_GEN_CACHE_MAX', '8')))


def _size_class(n: int, floor: int = 1) -> int:
    """Bucket a length into its size class: the next power of two (the
    prompt axis floors at 8; ``max_new`` uses the full {1,2,4,8,...}
    ladder — a 1-token request must not pay 8 decode steps).  EXACT
    under bucketing (see ``generate``): extra decode steps are computed
    and trimmed (decode is sequential — token t never depends on later
    steps), and a bucketed prompt is LEFT-padded with masked-out slots
    (the model has no positional encoding, so a uniform slot shift with
    pads excluded from every attention is the identical computation on
    the real tokens).  ``CXXNET_GEN_BUCKETS=0`` disables bucketing
    (exact shapes — e.g. bench.py's K-vs-1 decode quotient)."""
    b = max(1, floor)
    while b < n:
        b <<= 1
    return b


def generate(params, prompt, max_new: int, cfg: TransformerConfig,
             temperature: float = 0.0, rng=None, eos_id: int = None):
    """KV-cached autoregressive decode (single device) — the LM family's
    ``task=pred`` analog (the reference predicts with ``TransformPred``
    argmax, ``nnet_impl:286-298``; an LM predicts by decoding).

    Two phases under one jit: a vectorized prefill runs the whole prompt
    through :func:`_stage_attn` (the same block math as
    :func:`reference_loss`) capturing each stage's K/V, then
    ``lax.scan`` emits ``max_new`` tokens, each step attending over the
    cache — O(total) work per token instead of re-running the full
    forward.  Dense configs match the training forward exactly; MoE
    configs route through :func:`_nodrop_moe_ffn` (gate-prob-scaled
    top-1, NO capacity drops), which equals the training math except at
    tokens training's capacity bound would have dropped.
    ``temperature=0`` is greedy argmax; ``>0`` samples
    ``jax.random.categorical(logits/T, rng)``.  Requires
    ``cfg.causal`` (autoregressive decode is meaningless for a
    bidirectional model).

    ``prompt``: (batch, s0) int32; returns (batch, max_new) int32.
    ``eos_id``: per-row early stop — every position after a row's first
    emitted eos is eos (shapes stay static under jit; trim host-side).
    """
    if not cfg.causal:
        raise ValueError('generate() requires a causal config')
    if temperature > 0 and rng is None:
        raise ValueError('temperature>0 sampling needs an rng key')
    prompt = jnp.asarray(prompt, jnp.int32)
    b, s0 = prompt.shape
    if os.environ.get('CXXNET_GEN_BUCKETS', '1') != '0':
        s0b, mnb = _size_class(s0, floor=8), _size_class(max_new)
    else:
        s0b, mnb = s0, max_new
    w = s0b - s0                    # left-pad width (0 = exact shape)
    if w:
        prompt = jnp.pad(prompt, ((0, 0), (w, 0)))
    key = (dataclasses.astuple(cfg), b, s0b, mnb, float(temperature),
           eos_id)
    run = _GEN_CACHE.get(key)
    if run is None:
        _GEN_STATS['miss'] += 1
        run = _GEN_CACHE[key] = _build_generate(
            cfg, b, s0b, mnb, temperature, eos_id)
    else:
        _GEN_STATS['hit'] += 1
        _GEN_CACHE.move_to_end(key)     # LRU touch
    # enforce the bound on EVERY call (hits included): an env value that
    # shrinks mid-process takes effect on the next call, not the next miss
    while len(_GEN_CACHE) > _gen_cache_max():
        _GEN_CACHE.popitem(last=False)
    # the pad width is a traced VALUE, not a shape: every w for the same
    # bucket reuses one compiled program.  Sampling keys are split for
    # the REQUESTED horizon and zero-padded to the bucket (split(rng, n)
    # prefixes are not stable across n), so the first max_new draws
    # match the unbucketed schedule exactly; the padded tail's draws are
    # trimmed with the extra tokens.
    if temperature > 0:
        keys = jax.random.split(rng, max_new + 1)
        if mnb > max_new:
            keys = jnp.concatenate(
                [keys, jnp.zeros((mnb - max_new,) + keys.shape[1:],
                                 keys.dtype)])
    else:
        keys = jnp.zeros((mnb + 1, 2), jnp.uint32)
    return run(params, prompt, keys, jnp.int32(w))[:, :max_new]


def _gen_ffn(cfg: TransformerConfig, p, y2, gather: bool):
    """Inference-path FFN for one stage: dense nets run the training
    math; MoE nets route through the no-drop top-1 gate."""
    mb, s, d = y2.shape
    if cfg.num_experts:
        return _nodrop_moe_ffn(y2.reshape(mb * s, d), p,
                               gather).reshape(mb, s, d)
    # serve-shard boundaries around the d_ff contraction (see _rep)
    return _rep(qdot(_rep(jax.nn.relu(qdot(y2, p['w1']))), p['w2']))


def prefill_kv(params, prompt, w, cfg: TransformerConfig):
    """Vectorized prompt prefill — the whole (possibly left-padded)
    prompt through :func:`_stage_attn` in one pass, capturing each
    stage's K/V.  THE single copy of the prefill math: ``generate``'s
    compiled program and the serve decode engine's per-request prefill
    (serve/decode.py) both run through here.

    ``prompt``: (b, s0) int32 with the first ``w`` slots bucket padding
    (``w`` is a traced value — every pad width shares one program).
    Returns ``(ks, vs, logits0)``: ks/vs (num_stages, b, s0, heads, hd)
    cache rows for positions [0, s0), logits0 (b, vocab) float32 for the
    last position (the first generated token's distribution)."""
    b, s0 = prompt.shape
    h = _rep(qtake(params['embed'], prompt))
    # causal over the real tokens only: the first ``w`` slots are
    # bucket padding (generate() left-pads), excluded from every
    # real query.  Each PAD query attends just its own slot — an
    # all-masked softmax row is NaN, and 0 * NaN cached-V rows would
    # poison real outputs downstream.  ``w`` is traced, so w=0
    # reduces to the plain tril without a separate program.
    ar = jnp.arange(s0)
    mask = ((ar[None, :] <= ar[:, None]) & (ar[None, :] >= w)
            | (ar[None, :] == ar[:, None]) & (ar[:, None] < w)
            )[None, None]
    ks, vs = [], []
    for i in range(cfg.num_stages):
        p = jax.tree.map(lambda a, i=i: a[i], params['stages'])
        h, y2, k, v = _stage_attn(p, h, cfg, mask)
        ks.append(k)
        vs.append(v)
        h = h + _gen_ffn(cfg, p, y2, gather=False)
    logits0 = _rep(qdot(h[:, -1], params['head'])).astype(jnp.float32)
    return jnp.stack(ks), jnp.stack(vs), logits0


def prefill_tail_kv(params, prefix_ks, prefix_vs, tail, w,
                    cfg: TransformerConfig):
    """Prefix-shared prompt prefill: run ONLY the prompt's tail through
    the block walk, attending over the already-cached prefix K/V
    (serve/decode.py "Prefix sharing" — the prefix rows came out of an
    earlier request's :func:`prefill_kv` over the identical token span,
    so recomputing them would be pure waste).

    ``prefix_ks``/``prefix_vs``: (num_stages, b, t0, heads, hd) cache
    rows for positions ``[0, t0)``.  ``tail``: (b, tt) int32 tokens at
    positions ``[t0, t0 + tt)`` — every tail position must be a REAL
    token (the caller only shares prefixes that cover all bucket-pad
    slots, so ``t0 >= w``).  ``w`` is the traced left-pad width.

    Deliberately mirrors :func:`prefill_kv`'s math — ``_local_attention``
    in the operand dtype, ``_gen_ffn(gather=False)``, the same mask rule
    for real queries — so the tail rows and last-position logits are the
    ones the full prefill would have produced (row-for-row: each tail
    query's softmax sees exactly the positions ``[w, pos]``).  Returns
    ``(ks_tail, vs_tail, logits0)``: the (num_stages, b, tt, heads, hd)
    cache rows for the tail positions and the (b, vocab) f32 logits of
    the last position."""
    b, tt = tail.shape
    t0 = prefix_ks.shape[2]
    hd = cfg.d_model // cfg.num_heads
    h = _rep(qtake(params['embed'], tail))
    # query i sits at global position t0 + i; it attends cache positions
    # [w, t0 + i] — the same set full prefill's mask grants a real query
    gq = t0 + jnp.arange(tt)
    ar = jnp.arange(t0 + tt)
    mask = ((ar[None, :] <= gq[:, None])
            & (ar[None, :] >= w))[None, None]
    ks, vs = [], []
    for i in range(cfg.num_stages):
        p = jax.tree.map(lambda a, i=i: a[i], params['stages'])
        y = _layer_norm(h, p['ln1_scale'], p['ln1_bias'])
        q = qdot(y, p['wq']).reshape(b, tt, cfg.num_heads, hd)
        k = qdot(y, p['wk']).reshape(b, tt, cfg.num_heads, hd)
        v = qdot(y, p['wv']).reshape(b, tt, cfg.num_heads, hd)
        kf = jnp.concatenate([prefix_ks[i], k], axis=1)
        vf = jnp.concatenate([prefix_vs[i], v], axis=1)
        attn = _local_attention(q, kf, vf, 1.0 / math.sqrt(hd), mask)
        h = h + _rep(qdot(_rep(attn.reshape(b, tt, cfg.d_model)),
                          p['wo']))
        y2 = _layer_norm(h, p['ln2_scale'], p['ln2_bias'])
        ks.append(k)
        vs.append(v)
        h = h + _gen_ffn(cfg, p, y2, gather=False)
    logits0 = _rep(qdot(h[:, -1], params['head'])).astype(jnp.float32)
    return jnp.stack(ks), jnp.stack(vs), logits0


def verify_step(params, cfg: TransformerConfig, toks, kc, vc, t, w):
    """A (b, K)-token WINDOW through the decode block walk in one pass —
    the speculative-decoding verify entry (serve/decode.py "Speculative
    decoding") and the multi-token generalization of :func:`decode_step`
    (K=1 reduces to the same shapes and cast points).

    ``toks``: (b, K) int32, the tokens consumed at positions
    ``[t, t + K)`` per row (window slot k consumes ``toks[:, k]`` at
    position ``t + k``).  ``kc``/``vc``: dense (num_stages, b, total,
    heads, hd) caches; all K rows are written before attending, and
    window query ``k`` masks the cache to ``[w, t + k]`` — its own row
    and earlier, never a later draft's — so each window position
    computes exactly what a sequential :func:`decode_step` at that
    position would (the greedy spec-decode token-equality hinges on
    this; the masking rule is the same ``(ar <= t) & (ar >= w)`` with
    ``t`` per query).  ``t``/``w`` are (b,) int32 per-row vectors.

    Returns ``(logits, kc, vc, knew, vnew)``: logits (b, K, vocab) f32 —
    row k is the next-token distribution after consuming window slots
    ``0..k`` — and knew/vnew (num_stages, b, K, heads, hd), the rows
    written at ``[t, t + K)`` (the paged engine scatters those into its
    page pool)."""
    total = kc.shape[2]
    b, K = toks.shape
    hd = cfg.d_model // cfg.num_heads
    scale = 1.0 / math.sqrt(hd)
    ar = jnp.arange(total)
    tq = t[:, None] + jnp.arange(K)[None, :]               # (b, K)
    live = ((ar[None, None, :] <= tq[:, :, None])
            & (ar[None, None, :] >= w[:, None, None]))[:, None]  # (b,1,K,T)
    knews, vnews = [], []
    bi = jnp.arange(b)[:, None]
    # decode_step's block walk widened to K tokens: the same projection,
    # FFN and head call sites, the head applied to EVERY window position
    h = _rep(qtake(params['embed'], toks))
    for i in range(cfg.num_stages):
        p = jax.tree.map(lambda a, i=i: a[i], params['stages'])
        y = _layer_norm(h, p['ln1_scale'], p['ln1_bias'])
        q = qdot(y, p['wq']).reshape(b, K, cfg.num_heads, hd)
        k = qdot(y, p['wk']).reshape(b, K, cfg.num_heads, hd)
        v = qdot(y, p['wv']).reshape(b, K, cfg.num_heads, hd)
        kc = kc.at[i, bi, tq].set(k)
        vc = vc.at[i, bi, tq].set(v)
        ki, vi = kc[i], vc[i]
        s_ = jnp.einsum('bqhd,bkhd->bhqk', q, ki) * scale
        s_ = jnp.where(live, s_, -jnp.inf)
        knews.append(k)
        vnews.append(v)
        attn = jnp.einsum(
            'bhqk,bkhd->bqhd',
            jax.nn.softmax(s_.astype(jnp.float32),
                           axis=-1).astype(ki.dtype), vi)
        h = h + _rep(qdot(_rep(attn.reshape(b, K, cfg.d_model)),
                          p['wo']))
        y2 = _layer_norm(h, p['ln2_scale'], p['ln2_bias'])
        h = h + _gen_ffn(cfg, p, y2, gather=True)
    logits = _rep(qdot(h, params['head'])).astype(jnp.float32)
    return logits, kc, vc, jnp.stack(knews), jnp.stack(vnews)


def decode_step(params, cfg: TransformerConfig, tok, kc, vc, t, w):
    """One KV-cached decode step over a DENSE cache — the
    single-token-step entry the serve decode engine drives
    (serve/decode.py) and the body of ``generate``'s scan: one copy of
    the per-token block math, so the two cannot drift.

    ``tok``: (b,) int32, the token consumed this step.  ``kc``/``vc``:
    (num_stages, b, total, heads, hd) caches; this step's K/V is written
    at position ``t`` before attending.  ``t``/``w`` are traced values —
    scalars (every row at the same position: ``generate``) or (b,)
    vectors (per-row positions and pad widths: the decode engine's
    slots, each mid-stream at its own offset).  Cache positions outside
    ``[w, t]`` are masked out of the attention (the paged-attention
    masking rule: a slot's unwritten/bucket-pad positions never
    contribute).

    Returns ``(logits, kc, vc, knew, vnew)``: logits (b, vocab) float32
    for the next token, the updated caches, and knew/vnew
    (num_stages, b, heads, hd) — just the rows written at ``t`` (the
    paged engine scatters those into its page pool; ``generate`` keeps
    the dense caches and ignores them)."""
    total = kc.shape[2]
    b = tok.shape[0]
    hd = cfg.d_model // cfg.num_heads
    scale = 1.0 / math.sqrt(hd)
    per_row = jnp.ndim(t) > 0
    ar = jnp.arange(total)
    if per_row:
        live = ((ar[None, :] <= t[:, None])
                & (ar[None, :] >= w[:, None]))[:, None, None, :]
    else:
        # cache slots [0, w) hold bucket-pad K/V: never attended
        live = ((ar <= t) & (ar >= w))[None, None, None, :]
    knews, vnews = [], []
    # THE per-token block walk: embed -> [ln1 -> qkv -> cache write ->
    # attend -> out proj -> ln2 -> ffn] per stage -> head
    h = _rep(qtake(params['embed'], tok[:, None]))
    for i in range(cfg.num_stages):
        p = jax.tree.map(lambda a, i=i: a[i], params['stages'])
        y = _layer_norm(h, p['ln1_scale'], p['ln1_bias'])
        q = qdot(y, p['wq']).reshape(b, 1, cfg.num_heads, hd)
        k = qdot(y, p['wk']).reshape(b, 1, cfg.num_heads, hd)
        v = qdot(y, p['wv']).reshape(b, 1, cfg.num_heads, hd)
        if per_row:
            kc = kc.at[i, jnp.arange(b), t].set(k[:, 0])
            vc = vc.at[i, jnp.arange(b), t].set(v[:, 0])
        else:
            kc = jax.lax.dynamic_update_slice(
                kc, k[None], (i, 0, t, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, v[None], (i, 0, t, 0, 0))
        ki, vi = kc[i], vc[i]
        # (b, heads, 1, total) scores over the cache
        s_ = jnp.einsum('bqhd,bkhd->bhqk', q, ki) * scale
        s_ = jnp.where(live, s_, -jnp.inf)
        knews.append(k[:, 0])
        vnews.append(v[:, 0])
        attn = jnp.einsum(
            'bhqk,bkhd->bqhd',
            jax.nn.softmax(s_.astype(jnp.float32),
                           axis=-1).astype(ki.dtype), vi)
        h = h + _rep(qdot(_rep(attn.reshape(b, 1, cfg.d_model)),
                          p['wo']))
        y2 = _layer_norm(h, p['ln2_scale'], p['ln2_bias'])
        h = h + _gen_ffn(cfg, p, y2, gather=True)
    logits = _rep(qdot(h[:, -1], params['head'])).astype(jnp.float32)
    return logits, kc, vc, jnp.stack(knews), jnp.stack(vnews)


def _build_generate(cfg: TransformerConfig, b: int, s0: int,
                    max_new: int, temperature: float, eos_id=None):
    total = s0 + max_new
    hd = cfg.d_model // cfg.num_heads

    def pick(logits, r):
        if temperature > 0:
            return jax.random.categorical(r, logits / temperature,
                                          axis=-1)
        return jnp.argmax(logits, axis=-1)

    @jax.jit
    def run(params, prompt, keys, w):
        # --- prefill: full prompt in one pass, K/V captured per stage
        ks, vs, logits0 = prefill_kv(params, prompt, w, cfg)
        kc = jnp.zeros((cfg.num_stages, b, total, cfg.num_heads, hd),
                       ks.dtype)
        vc = jnp.zeros_like(kc)
        kc = kc.at[:, :, :s0].set(ks)
        vc = vc.at[:, :, :s0].set(vs)

        tok0 = pick(logits0, keys[0] if temperature > 0 else None)
        rngs = keys[1:]
        done0 = (tok0 == eos_id if eos_id is not None
                 else jnp.zeros((b,), bool))

        # --- decode: one token per scan step, attending over the cache
        def step(carry, inp):
            tok, done, kc, vc = carry
            t, r = inp
            logits, kc, vc, _, _ = decode_step(params, cfg, tok, kc, vc,
                                               t, w)
            nxt = pick(logits, r if temperature > 0 else None)
            if eos_id is not None:
                # a finished row keeps emitting eos (static shapes under
                # jit: the scan always runs max_new steps)
                nxt = jnp.where(done, eos_id, nxt)
                done = done | (nxt == eos_id)
            return (nxt, done, kc, vc), tok

        ts = jnp.arange(s0, total)
        _, toks = jax.lax.scan(step, (tok0, done0, kc, vc), (ts, rngs))
        # step j consumes generated token j and emits it; the carry's
        # final pick (token max_new) is past the requested horizon
        return toks.T

    return run


def reference_loss(params, tokens, labels, cfg: TransformerConfig):
    """Single-device oracle: same math, no mesh, sequential stages —
    including the weighted MoE balance loss the distributed step adds."""
    h = jnp.take(params['embed'], tokens, axis=0)
    balance = jnp.float32(0.0)
    for i in range(cfg.num_stages):
        p = jax.tree.map(lambda a: a[i], params['stages'])
        mb, s, d = h.shape
        mask = None
        if cfg.causal:
            mask = jnp.tril(jnp.ones((s, s), bool))[None, None]
        h, y, _, _ = _stage_attn(p, h, cfg, mask)
        if cfg.num_experts:
            from ..parallel.moe import moe_ffn_reference
            ff, aux = moe_ffn_reference(y.reshape(mb * s, d), p['gate'],
                                        p['w1'], p['w2'],
                                        capacity_factor=cfg.capacity_factor)
            h = h + ff.reshape(mb, s, d)
            balance = balance + aux['balance_loss']
        else:
            h = h + jax.nn.relu(y @ p['w1']) @ p['w2']
    logits = (h @ params['head']).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()
    if cfg.num_experts:
        nll = nll + cfg.balance_loss_weight * balance
    return nll
